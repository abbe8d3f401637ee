"""Unified overlap scheduler: FSDP param-prefetch / grad-scatter hiding
composed with the TP rings (``tpusystem/parallel/schedule.py``).

Parity harness on the virtual CPU mesh, mirroring ``test_overlap.py``:
the scheduled FFN must match the GSPMD reference in forward AND
gradients — and the FSDP-prefetch-only forward must match **bitwise**
(the ring gather is a copy, so every matmul sees identical operands).
Plan helpers pin exactly which path each leaf takes; the tie-break of
the placement policy's FSDP dimension choice is a regression contract
(a silent reshard would invalidate every checkpoint); model-level, the
``schedule=`` knob never changes a param tree, and a checkpoint written
before the knob existed restores under it unchanged. The compile guard
pins that a scheduled train step traces and compiles exactly once
across steps (the pipeline.py per-step-retrace bug class from PR 1).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from tpusystem.checkpoint import Checkpointer
from tpusystem.models import GPT2
from tpusystem.models.llama import llama_tiny
from tpusystem.parallel import (MeshSpec, OverlapSchedule, ShardingPolicy,
                                batch_sharding, fsdp_plan, resolve_schedule,
                                schedule_applicable, scheduled_ffn)
from tpusystem.parallel.collectives import (ring_allgather,
                                            ring_reducescatter)
from tpusystem.parallel.mesh import FSDP, MODEL
from tpusystem.parallel.sharding import fsdp_shard_dim

RING = 4           # >= 4-device virtual mesh (conftest forces 8 devices)


def fsdp_mesh():
    return MeshSpec(fsdp=RING).build(jax.devices()[:RING])


def composed_mesh():
    return MeshSpec(fsdp=2, model=2).build(jax.devices()[:4])


# ---------------------------------------------------------------------------
# the ring collectives the prefetch custom_vjp is built from
# ---------------------------------------------------------------------------


@pytest.mark.parametrize('dimension,chunks', [(0, 1), (0, 2), (1, 1)])
def test_ring_allgather_is_bitwise_identical_to_lax(dimension, chunks):
    """The decomposed gather is a pure copy: every row-block lands
    exactly where ``lax.all_gather(tiled=True)`` puts it, bit for bit."""
    mesh = fsdp_mesh()
    value = jnp.asarray(
        np.random.default_rng(0).normal(size=(16, 24)), jnp.float32)
    in_spec = P(FSDP, None) if dimension == 0 else P(None, FSDP)

    @functools.partial(jax.shard_map, mesh=mesh, check_vma=False,
                       in_specs=in_spec, out_specs=P(None, None))
    def ring(shard):
        return ring_allgather(shard, FSDP, dimension=dimension,
                              chunks=chunks)

    @functools.partial(jax.shard_map, mesh=mesh, check_vma=False,
                       in_specs=in_spec, out_specs=P(None, None))
    def monolithic(shard):
        return lax.all_gather(shard, FSDP, axis=dimension, tiled=True)

    np.testing.assert_array_equal(np.asarray(jax.jit(ring)(value)),
                                  np.asarray(jax.jit(monolithic)(value)))


@pytest.mark.parametrize('dimension,chunks', [(0, 1), (0, 2), (1, 1)])
def test_ring_reducescatter_matches_psum_scatter(dimension, chunks):
    """The decomposed scatter sums all ring contributions into the home
    block — ``lax.psum_scatter`` semantics, f32 carry, tight tolerance
    (only the summation order differs)."""
    mesh = fsdp_mesh()
    # distinct full-size value per device, stacked on the fsdp axis
    values = jnp.asarray(
        np.random.default_rng(1).normal(size=(RING, 16, 24)), jnp.float32)
    out_spec = P(FSDP, None) if dimension == 0 else P(None, FSDP)

    @functools.partial(jax.shard_map, mesh=mesh, check_vma=False,
                       in_specs=P(FSDP, None, None), out_specs=out_spec)
    def ring(stacked):
        return ring_reducescatter(stacked[0], FSDP, dimension=dimension,
                                  chunks=chunks)

    out = jax.jit(ring)(values)
    reference = values.sum(axis=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(reference),
                               rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# plan pinning: every leaf's path is decided by the pure helper
# ---------------------------------------------------------------------------


def test_fsdp_plan_pins_skip_paths():
    # trivial axis: the leaf was never sharded
    plan = fsdp_plan((256, 1024), 1)
    assert plan.path == 'skip' and 'axis_size' in plan.reason
    # tiny leaf below fsdp_min_size stays replicated by the policy
    plan = fsdp_plan((8, 8), RING, min_size=4096)
    assert plan.path == 'skip' and 'fsdp_min_size' in plan.reason
    # no dimension divides the fsdp axis -> policy left it unsharded
    plan = fsdp_plan((5001, 3), RING, min_size=64)
    assert plan.path == 'skip' and 'divisible' in plan.reason
    # dimensions claimed by rule axes are not FSDP candidates
    plan = fsdp_plan((256, 1024), RING, taken=(0, 1))
    assert plan.path == 'skip'


def test_fsdp_plan_pins_one_shot_when_chunks_cannot_tile():
    plan = fsdp_plan((256, 1024), RING, chunks=3)
    assert plan.path == 'one-shot' and 'chunks' in plan.reason
    assert plan.dim == 1                   # the gather dim is still chosen
    plan = fsdp_plan((256, 1024), RING, chunks=2)
    assert plan.path == 'ring' and plan.chunks == 2


def test_fsdp_plan_dim_agrees_with_the_placement_policy():
    """The plan's gather dim IS fsdp_shard_dim's choice — the manual
    collectives and the placement policy can never disagree."""
    for shape, taken in [((256, 1024), ()), ((256, 1024), (1,)),
                         ((64, 64), ()), ((4, 256, 256), (0,))]:
        plan = fsdp_plan(shape, RING, taken=taken, min_size=64)
        assert plan.dim == fsdp_shard_dim(shape, RING, taken)


# ---------------------------------------------------------------------------
# satellite: deterministic FSDP dimension tie-breaking
# ---------------------------------------------------------------------------


def test_fsdp_shard_dim_tie_breaks_to_the_lowest_index():
    """Several equally-largest divisible dims: the LOWEST index wins,
    deterministically — a checkpoint placed under this choice must
    never silently reshard across jax/python versions."""
    assert fsdp_shard_dim((64, 64), 4) == 0
    assert fsdp_shard_dim((4, 64, 64), 4) == 1          # dim 0 smaller
    assert fsdp_shard_dim((64, 64, 64), 4, taken=(0,)) == 1
    # largest still wins over lower index when sizes differ
    assert fsdp_shard_dim((64, 128), 4) == 1
    # non-divisible largest dim loses to a smaller divisible one
    assert fsdp_shard_dim((129, 64), 4) == 1
    assert fsdp_shard_dim((5, 3), 4) is None


def test_policy_fsdp_placement_tie_break_is_deterministic():
    """Policy-level regression: a square kernel's FSDP axis lands on
    dim 0 (the tie-break), not wherever enumeration order wandered."""
    mesh = fsdp_mesh()
    policy = ShardingPolicy(rules=(), fsdp=True, fsdp_min_size=64)
    assert policy.spec('dense/kernel', (64, 64), mesh) == P(FSDP)
    # a rule-claimed dim 0 pushes the tie-winner to dim 1
    ruled = ShardingPolicy(rules=((r'kernel', P(MODEL)),), fsdp=True,
                           fsdp_min_size=64)
    assert ruled.spec('dense/kernel', (64, 64), mesh) == P(MODEL, FSDP)


# ---------------------------------------------------------------------------
# the schedule object and the models' seam
# ---------------------------------------------------------------------------


def test_resolve_schedule_defaults_and_passes_through():
    assert resolve_schedule(None) == OverlapSchedule()
    passed = OverlapSchedule(tp='overlap', fsdp='prefetch', chunks=4)
    assert resolve_schedule(passed) is passed


def test_resolve_schedule_rejects_another_type():
    with pytest.raises(TypeError, match='OverlapSchedule'):
        resolve_schedule('overlap')


def test_overlap_schedule_validates_knobs():
    with pytest.raises(ValueError, match='tp'):
        OverlapSchedule(tp='magic')
    with pytest.raises(ValueError, match='fsdp'):
        OverlapSchedule(fsdp='magic')
    with pytest.raises(ValueError, match='chunks'):
        OverlapSchedule(chunks=0)


def test_for_policy_matches_the_policy_min_size():
    """The schedule's fsdp_min_size must equal the placement policy's or
    jit reshards at the manual boundary — for_policy pins the pairing."""
    policy = ShardingPolicy(rules=(), fsdp=True, fsdp_min_size=64)
    schedule = OverlapSchedule.for_policy(policy, tp='overlap', chunks=2)
    assert schedule.fsdp_min_size == 64
    assert (schedule.tp, schedule.fsdp) == ('overlap', 'prefetch')


def test_schedule_applicable_gates_per_shape():
    mesh = composed_mesh()
    schedule = OverlapSchedule(tp='overlap', fsdp='prefetch', chunks=1)
    # seq 16 shards over model=2; batch 4 over fsdp=2
    assert schedule_applicable(schedule, mesh, (4, 16, 64), 256)
    # odd sequence cannot ride the TP ring nor shard rows
    assert not schedule_applicable(schedule, mesh, (4, 15, 64), 256)
    # no mesh -> GSPMD path
    assert not schedule_applicable(schedule, None, (4, 16, 64), 256)
    # all-gspmd schedule never takes the manual path
    assert not schedule_applicable(OverlapSchedule(), mesh, (4, 16, 64), 256)
    # prefetch-only schedule applies without a model axis
    pure = MeshSpec(fsdp=RING).build(jax.devices()[:RING])
    assert schedule_applicable(
        OverlapSchedule(fsdp='prefetch'), pure, (4, 16, 64), 256)
    # ... but not when the batch cannot shard over (data, fsdp): the
    # manual gradient scatter assumes distinct batch slices per device
    assert not schedule_applicable(
        OverlapSchedule(fsdp='prefetch'), pure, (3, 16, 64), 256)


# ---------------------------------------------------------------------------
# scheduled FFN vs the GSPMD reference
# ---------------------------------------------------------------------------


def _ffn_operands(dtype, batch=4, seq=16, dim=64, grown=256, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch, seq, dim)) * 0.5, dtype)
    w_up = jnp.asarray(rng.normal(size=(dim, grown)) * 0.1, dtype)
    b_up = jnp.asarray(rng.normal(size=(grown,)) * 0.1, dtype)
    w_down = jnp.asarray(rng.normal(size=(grown, dim)) * 0.1, dtype)
    b_down = jnp.asarray(rng.normal(size=(dim,)) * 0.1, dtype)
    return x, w_up, b_up, w_down, b_down


def _reference_ffn(x, w_up, b_up, w_down, b_down):
    grown = jax.nn.gelu(jnp.matmul(x, w_up) + b_up)
    return jnp.matmul(grown, w_down) + b_down


def _loss(fn):
    def loss(*operands):
        out = fn(*operands)
        return jnp.sum(jnp.square(out.astype(jnp.float32))) * 1e-3
    return loss


@pytest.mark.parametrize('chunks', [1, 2])
def test_prefetch_forward_is_bitwise_vs_gspmd_f32(chunks):
    """fsdp='prefetch' alone (tp left to GSPMD on a model-free mesh):
    the ring gather is a copy, so every device's matmuls see identical
    operands — the scheduled forward is BITWISE-equal in f32 to the
    same FFN with every collective left monolithic (the all-gspmd
    schedule), and tight against the unsharded reference (only
    operand-shape-dependent fusion differs there)."""
    mesh = fsdp_mesh()
    operands = _ffn_operands(jnp.float32)
    schedule = OverlapSchedule(fsdp='prefetch', chunks=chunks,
                               fsdp_min_size=64)
    out = jax.jit(lambda *a: scheduled_ffn(
        *a, mesh, schedule=schedule))(*operands)
    monolithic = OverlapSchedule(fsdp_min_size=64)
    baseline = jax.jit(lambda *a: scheduled_ffn(
        *a, mesh, schedule=monolithic))(*operands)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(baseline))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference_ffn(*operands)),
                               rtol=2e-6, atol=2e-6)


def test_prefetch_grads_match_gspmd_f32():
    """The backward's deferred grad reduce-scatter reproduces the
    reference cotangents (tight f32: only the ring sum's order
    differs from the partitioner's reduction)."""
    mesh = fsdp_mesh()
    operands = _ffn_operands(jnp.float32)
    schedule = OverlapSchedule(fsdp='prefetch', chunks=2, fsdp_min_size=64)
    scheduled = lambda *a: scheduled_ffn(*a, mesh, schedule=schedule)
    grads = jax.jit(jax.grad(_loss(scheduled), argnums=(0, 1, 2, 3, 4)))(
        *operands)
    reference = jax.grad(_loss(_reference_ffn), argnums=(0, 1, 2, 3, 4))(
        *operands)
    for got, want in zip(grads, reference):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('chunks', [1, 2])
def test_composed_tp_ring_plus_prefetch_matches_gspmd_f32(chunks):
    """The composition the three-knob world could not express: TP rings
    AND FSDP prefetch in ONE manual region, on a fsdp=2 x model=2 mesh,
    matching the reference in forward and all gradients."""
    mesh = composed_mesh()
    operands = _ffn_operands(jnp.float32)
    schedule = OverlapSchedule(tp='overlap', fsdp='prefetch', chunks=chunks,
                               fsdp_min_size=64)
    scheduled = lambda *a: scheduled_ffn(*a, mesh, schedule=schedule)
    out = jax.jit(scheduled)(*operands)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference_ffn(*operands)),
                               rtol=2e-5, atol=2e-5)
    grads = jax.jit(jax.grad(_loss(scheduled), argnums=(0, 1, 2, 3, 4)))(
        *operands)
    reference = jax.grad(_loss(_reference_ffn), argnums=(0, 1, 2, 3, 4))(
        *operands)
    for got, want in zip(grads, reference):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_scheduled_ffn_bf16_bounded():
    """bf16 operands with f32 accumulation: bounded tolerance against
    the reference computed the GSPMD way (bf16 matmuls), the
    test_overlap bf16 discipline."""
    mesh = composed_mesh()
    operands = _ffn_operands(jnp.bfloat16)
    schedule = OverlapSchedule(tp='overlap', fsdp='prefetch', chunks=1,
                               fsdp_min_size=64)
    scheduled = lambda *a: scheduled_ffn(*a, mesh, schedule=schedule)
    out = jax.jit(scheduled)(*operands)
    reference = _reference_ffn(*operands)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(reference, np.float32),
                               rtol=0.05, atol=0.1)
    grads = jax.jit(jax.grad(_loss(scheduled), argnums=(0, 1)))(*operands)
    want = jax.grad(_loss(_reference_ffn), argnums=(0, 1))(*operands)
    for got, ref in zip(grads, want):
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=0.1, atol=0.5)


def test_one_shot_fallback_still_matches_reference():
    """chunks=3 cannot tile the per-device kernel shards (pinned by the
    plan) -> the monolithic lax.all_gather path runs and stays correct,
    grads (its native psum_scatter transpose) included."""
    mesh = fsdp_mesh()
    operands = _ffn_operands(jnp.float32)
    assert fsdp_plan((64, 256), RING, chunks=3, min_size=64).path == 'one-shot'
    schedule = OverlapSchedule(fsdp='prefetch', chunks=3, fsdp_min_size=64)
    scheduled = lambda *a: scheduled_ffn(*a, mesh, schedule=schedule)
    out = jax.jit(scheduled)(*operands)
    monolithic = OverlapSchedule(fsdp_min_size=64)
    baseline = jax.jit(lambda *a: scheduled_ffn(
        *a, mesh, schedule=monolithic))(*operands)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(baseline))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference_ffn(*operands)),
                               rtol=2e-6, atol=2e-6)
    grads = jax.jit(jax.grad(_loss(scheduled), argnums=(1, 3)))(*operands)
    reference = jax.grad(_loss(_reference_ffn), argnums=(1, 3))(*operands)
    for got, want in zip(grads, reference):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_down_kernel_row_split_falls_back_to_one_shot():
    """Regression: the down kernel's rows are TP-sharded INSIDE the
    manual region, so the plan's chunk-tiling check must see the LOCAL
    row count — chunks=32 tiles the full 96 rows but not the 48 a
    model=2 shard holds, and without ``row_split`` the plan said
    ``'ring'`` for a shard ``ring_shift_chunked`` then refused to split
    at trace time. It must fall back to one-shot and stay correct."""
    plan = fsdp_plan((96, 64), 2, taken=(0,), chunks=32, row_split=2,
                     min_size=64)
    assert plan.path == 'one-shot' and 'chunks' in plan.reason
    # the bug's exact shape: without the row split the leaf planned 'ring'
    assert fsdp_plan((96, 64), 2, taken=(0,), chunks=32,
                     min_size=64).path == 'ring'
    mesh = composed_mesh()
    operands = _ffn_operands(jnp.float32, grown=96)
    schedule = OverlapSchedule(tp='overlap', fsdp='prefetch', chunks=32,
                               fsdp_min_size=64)
    scheduled = lambda *a: scheduled_ffn(*a, mesh, schedule=schedule)
    out = jax.jit(scheduled)(*operands)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference_ffn(*operands)),
                               rtol=2e-5, atol=2e-5)
    grads = jax.jit(jax.grad(_loss(scheduled), argnums=(1, 3)))(*operands)
    reference = jax.grad(_loss(_reference_ffn), argnums=(1, 3))(*operands)
    for got, want in zip(grads, reference):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# model-level: the schedule= knob on GPT-2 and Llama
# ---------------------------------------------------------------------------


def _run_model(model, rules, tokens, mesh, min_size=64):
    variables = model.init(jax.random.PRNGKey(0), tokens[:1, :8])
    params = ShardingPolicy(rules=rules, fsdp=True,
                            fsdp_min_size=min_size).place(
        variables['params'], mesh)
    placed_tokens = jax.device_put(tokens, batch_sharding(mesh))
    out = jax.jit(lambda p, t: model.apply({'params': p}, t))(
        params, placed_tokens)

    def loss(p):
        logits = model.apply({'params': p}, placed_tokens)
        return jnp.sum(jnp.square(logits.astype(jnp.float32))) * 1e-3

    grads = jax.jit(jax.grad(loss))(params)
    return variables, out, grads


@pytest.mark.parametrize('family', ['gpt2', 'llama'])
def test_schedule_knob_matches_gspmd_model_level(family):
    """schedule=OverlapSchedule(tp='overlap', fsdp='prefetch') is purely
    an implementation schedule: identical param trees (bitwise — the
    checkpoint contract), matching logits and grads, on the composed
    fsdp=2 x model=2 mesh with FSDP-placed params."""
    mesh = composed_mesh()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (4, 16)), jnp.int32)
    schedule = OverlapSchedule(tp='overlap', fsdp='prefetch', chunks=2,
                               fsdp_min_size=64)

    def build(schedule):
        if family == 'gpt2':
            model = GPT2(vocab_size=256, layers=2, dim=64, heads=4,
                         max_seq=128, dropout=0.0, dtype='float32',
                         mesh=mesh, schedule=schedule)
            return model, GPT2.partition_rules()
        model = llama_tiny(dtype='float32', mesh=mesh, schedule=schedule)
        return model, type(model).partition_rules()

    v_ref, out_ref, grads_ref = _run_model(*build(None),
                                           tokens=tokens, mesh=mesh)
    v_sch, out_sch, grads_sch = _run_model(*build(schedule),
                                           tokens=tokens, mesh=mesh)
    # the knob never changes the checkpoint: identical trees, identical init
    assert (jax.tree_util.tree_structure(v_ref)
            == jax.tree_util.tree_structure(v_sch))
    for ref, sch in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_sch)):
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(sch))
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_sch),
                               rtol=2e-5, atol=2e-5)
    for ref, sch in zip(jax.tree.leaves(grads_ref),
                        jax.tree.leaves(grads_sch)):
        np.testing.assert_allclose(np.asarray(ref), np.asarray(sch),
                                   rtol=2e-4, atol=3e-5)


def test_scan_path_accepts_the_schedule():
    """The BlockSpan scan path (scan_layers=True) threads the schedule
    through the scanned block and still matches the GSPMD scan —
    including BITWISE-identical init draws. Regression: the legacy
    threefry's bits depend on the sharding the manual region imposes
    inside the scanned init program, so on a composed fsdp x model mesh
    a schedule-on init that ran the scheduled branch drew different
    kernels than schedule-off; init must always take the nn.Dense path."""
    mesh = composed_mesh()
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, 256, (4, 16)), jnp.int32)
    schedule = OverlapSchedule(tp='overlap', fsdp='prefetch', chunks=2,
                               fsdp_min_size=64)
    common = dict(vocab_size=256, layers=2, dim=64, heads=4, max_seq=128,
                  dropout=0.0, dtype='float32', mesh=mesh, scan_layers=True)
    v_ref, out_ref, _ = _run_model(GPT2(**common), GPT2.partition_rules(),
                                   tokens, mesh)
    v_sch, out_sch, _ = _run_model(GPT2(**common, schedule=schedule),
                                   GPT2.partition_rules(), tokens, mesh)
    assert (jax.tree_util.tree_structure(v_ref)
            == jax.tree_util.tree_structure(v_sch))
    for ref, sch in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_sch)):
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(sch))
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_sch),
                               rtol=2e-5, atol=2e-5)


def test_schedule_rejects_unknown_values_at_model_level():
    with pytest.raises(ValueError, match='schedule fsdp'):
        OverlapSchedule(fsdp='sometimes')
    model = GPT2(vocab_size=64, layers=1, dim=32, heads=4, max_seq=32,
                 dropout=0.0, dtype='float32', schedule='overlap')
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(TypeError, match='OverlapSchedule'):
        model.init(jax.random.PRNGKey(0), tokens)


# ---------------------------------------------------------------------------
# checkpoint invariance: a pre-schedule-era checkpoint restores unchanged
# ---------------------------------------------------------------------------


def test_pre_schedule_checkpoint_restores_under_the_new_knob(tmp_path):
    """Regression for the PR-5-era fleet: a checkpoint written by a
    model with NO schedule knob (the old tree) restores bitwise into a
    schedule-on run and produces matching logits — the knob is invisible
    to every existing checkpoint."""
    from tpusystem.train import AdamW, init_state

    mesh = composed_mesh()
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 256, (4, 16)), jnp.int32)
    common = dict(vocab_size=256, layers=2, dim=64, heads=4, max_seq=128,
                  dropout=0.0, dtype='float32', mesh=mesh)
    old_era = GPT2(**common)                        # exactly the PR-5 model
    state = init_state(old_era, AdamW(lr=1e-3), tokens[:1, :8], rng=0)
    with Checkpointer(tmp_path, async_save=False) as checkpointer:
        checkpointer.save('pre-schedule', 0, state)
        blank = jax.tree.map(jnp.zeros_like, state)
        restored = checkpointer.restore('pre-schedule', blank)
    for original, loaded in zip(jax.tree.leaves(state),
                                jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(original),
                                      np.asarray(loaded))
    scheduled = GPT2(**common, schedule=OverlapSchedule(
        tp='overlap', fsdp='prefetch', chunks=2, fsdp_min_size=64))
    placed = ShardingPolicy(rules=GPT2.partition_rules(), fsdp=True,
                            fsdp_min_size=64).place(restored.params, mesh)
    placed_tokens = jax.device_put(tokens, batch_sharding(mesh))
    out_old = jax.jit(lambda p, t: old_era.apply({'params': p}, t))(
        placed, placed_tokens)
    out_new = jax.jit(lambda p, t: scheduled.apply({'params': p}, t))(
        placed, placed_tokens)
    np.testing.assert_allclose(np.asarray(out_old), np.asarray(out_new),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# compile guard: schedule-on traces and compiles ONCE across steps
# ---------------------------------------------------------------------------


def test_compile_guard_scheduled_step_never_retraces():
    """The pipeline.py bug class from PR 1, guarded permanently: a
    scheduled train step must trace exactly once and hit the jit cache
    on every subsequent step — a per-step retrace/recompile would eat
    the overlap win thousands of times over."""
    from tpusystem.train import (AdamW, NextTokenLoss, build_train_step,
                                 flax_apply, init_state)

    mesh = composed_mesh()
    schedule = OverlapSchedule(tp='overlap', fsdp='prefetch', chunks=2,
                               fsdp_min_size=64)
    module = GPT2(vocab_size=256, layers=2, dim=64, heads=4, max_seq=128,
                  dropout=0.0, dtype='float32', mesh=mesh,
                  scan_layers=True, schedule=schedule)
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(0, 256, (4, 16)), jnp.int32)
    optimizer = AdamW(lr=1e-3)
    state = init_state(module, optimizer, tokens[:1, :8])
    state = ShardingPolicy(rules=GPT2.partition_rules(), fsdp=True,
                           fsdp_min_size=64).place(state, mesh)
    placed = jax.device_put(tokens, batch_sharding(mesh))
    step = build_train_step(flax_apply(module), NextTokenLoss(), optimizer,
                            jit=False)

    traces = []

    def counting_step(state, inputs, targets):
        traces.append(1)          # runs at trace time only
        return step(state, inputs, targets)

    runner = jax.jit(counting_step)
    for _ in range(3):
        state, _ = runner(state, placed, placed)
    assert len(traces) == 1, (
        f'scheduled train step retraced: {len(traces)} traces for 3 steps')
    if hasattr(runner, '_cache_size'):    # recompile guard, where exposed
        assert runner._cache_size() == 1


# ---------------------------------------------------------------------------
# the pp= and moe= arms: pipeline p2p + expert all-to-all under the schedule
# ---------------------------------------------------------------------------


from tpusystem.models import GPT2Pipelined, gpt2_tiny  # noqa: E402
from tpusystem.parallel import (PipelineParallel, moe_plan,  # noqa: E402
                                pipeline_apply, pp_plan)
from tpusystem.train import (AdamW, NextTokenLoss, WithAuxLoss,  # noqa: E402
                             build_train_step, flax_apply, init_state)

def test_overlap_schedule_validates_the_new_arms():
    with pytest.raises(ValueError, match='schedule pp'):
        OverlapSchedule(pp='sometimes')
    with pytest.raises(ValueError, match='schedule moe'):
        OverlapSchedule(moe='magic')
    # the new arms participate in identity and equality like the old ones
    a = OverlapSchedule(pp='overlap', moe='overlap')
    assert a != OverlapSchedule() and hash(a) != hash(OverlapSchedule())
    assert 'pp=' in repr(a) and 'moe=' in repr(a)
    # for_policy threads them through the policy pairing
    policy = ShardingPolicy(rules=(), fsdp=True, fsdp_min_size=64)
    paired = OverlapSchedule.for_policy(policy, tp='overlap', pp='overlap',
                                        moe='overlap')
    assert (paired.pp, paired.moe) == ('overlap', 'overlap')
    assert paired.fsdp_min_size == 64
    # the models' default keeps both new arms on gspmd
    default = resolve_schedule(None)
    assert (default.pp, default.moe) == ('gspmd', 'gspmd')


def test_pp_plan_pins_paths():
    # no stage axis: nothing to hide
    plan = pp_plan(4, 1)
    assert plan.path == 'skip' and 'axis_size' in plan.reason
    # chunks that cannot tile the microbatch rows: classic ticks
    plan = pp_plan(3, 4, chunks=2)
    assert plan.path == 'one-shot' and 'chunks' in plan.reason
    # the interleaved schedule owns its ticks
    plan = pp_plan(4, 4, chunks=1, interleave=2)
    assert plan.path == 'one-shot' and 'interleaved' in plan.reason
    # plain GPipe with tiling rows: the skewed overlap schedule
    plan = pp_plan(4, 4, chunks=2)
    assert plan == pp_plan(4, 4, chunks=2)
    assert plan.path == 'overlap' and plan.chunks == 2


def test_moe_plan_pins_paths():
    plan = moe_plan(8, 1)
    assert plan.path == 'skip' and 'axis_size' in plan.reason
    # ragged exchanges seat at the receiver: not pipelined today
    for exchange in ('ragged', 'ragged-emulated'):
        plan = moe_plan(8, 2, exchange=exchange)
        assert plan.path == 'one-shot' and 'receiver' in plan.reason
    # rows that won't split into pieces
    plan = moe_plan(5, 2)
    assert plan.path == 'one-shot' and 'split' in plan.reason
    plan = moe_plan(8, 2)
    assert plan.path == 'overlap' and plan.pieces == 2


def _pp_stack():
    layers, batch, dim = 8, 8, 16
    keys = jax.random.split(jax.random.PRNGKey(0), layers)
    weights = jax.vmap(lambda key: jax.random.normal(key, (dim, dim)) / dim)(
        keys)
    inputs = jax.random.normal(jax.random.PRNGKey(1), (batch, dim))
    block_fn = lambda lp, x: jnp.tanh(x @ lp['w'])
    return weights, inputs, block_fn


@pytest.mark.parametrize('chunks', [1, 2])
def test_pp_overlap_gpipe_is_bitwise_vs_classic(chunks):
    """The skewed schedule computes identical math on identical operands
    (the hops are pure copies), so outputs AND gradients are bitwise-
    equal to the classic GPipe tick — in any dtype, the strongest form
    of the f32-bitwise parity contract."""
    mesh = MeshSpec(stage=4, data=2).build()
    weights, inputs, block_fn = _pp_stack()
    schedule = OverlapSchedule(pp='overlap', chunks=chunks)
    assert pp_plan(2, 4, chunks=chunks).path == 'overlap'

    classic = pipeline_apply(block_fn, {'w': weights}, inputs, mesh,
                             microbatches=2)
    skewed = pipeline_apply(block_fn, {'w': weights}, inputs, mesh,
                            microbatches=2, schedule=schedule)
    np.testing.assert_array_equal(np.asarray(classic), np.asarray(skewed))

    def loss(sched):
        def inner(w):
            out = pipeline_apply(block_fn, {'w': w}, inputs, mesh,
                                 microbatches=2, schedule=sched)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return inner

    g_classic = jax.jit(jax.grad(loss(None)))(weights)
    g_skewed = jax.jit(jax.grad(loss(schedule)))(weights)
    np.testing.assert_array_equal(np.asarray(g_classic),
                                  np.asarray(g_skewed))


def test_pp_overlap_fallback_when_chunks_cannot_tile():
    """Microbatch rows that won't split into the requested chunks pin the
    classic schedule (pp_plan) — and the run stays correct."""
    mesh = MeshSpec(stage=4, data=2).build()
    weights, inputs, block_fn = _pp_stack()
    # local batch 4 over 2 microbatches = 2 rows; chunks=3 cannot tile
    assert pp_plan(2, 4, chunks=3).path == 'one-shot'
    schedule = OverlapSchedule(pp='overlap', chunks=3)
    out = pipeline_apply(block_fn, {'w': weights}, inputs, mesh,
                         microbatches=2, schedule=schedule)
    reference = pipeline_apply(block_fn, {'w': weights}, inputs, mesh,
                               microbatches=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(reference))


def _moe_mesh():
    return MeshSpec(data=2, expert=2).build(jax.devices()[:4])


def _moe_tokens(seed=0, batch=8, seq=32):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, 256, (batch, seq)), jnp.int32)


def _moe_loss_and_grads(schedule, mesh, tokens, **overrides):
    config = dict(dim=64, heads=4, mesh=mesh, moe_experts=2, moe_every=2,
                  moe_capacity_factor=2.0, dtype='float32',
                  schedule=schedule)
    config.update(overrides)
    module = gpt2_tiny(**config)
    optimizer = AdamW(lr=1e-3)
    state = init_state(module, optimizer, tokens[:1], rng=0)
    state = ShardingPolicy(rules=module.partition_rules()).place(state, mesh)
    placed = jax.device_put(tokens, batch_sharding(mesh))
    criterion = WithAuxLoss(NextTokenLoss())
    apply_fn = flax_apply(module)

    def loss(params):
        return criterion(apply_fn(params, placed, None, True), placed)

    value, grads = jax.jit(jax.value_and_grad(loss))(state.params)
    return state.params, float(value), grads


@pytest.mark.slow
def test_moe_overlap_dispatch_matches_gspmd_model_level():
    """moe='overlap' on the sharded quota path: the pipelined dispatch
    (piece k+1's all_to_all under the expert matmuls of k) reproduces
    the one-shot exchange — loss BITWISE in f32 at ample capacity
    (routing runs unsplit; the FFN and combine are row-independent),
    grads f32-tight (only backward summation order differs), identical
    param trees."""
    mesh = _moe_mesh()
    tokens = _moe_tokens()
    p_ref, l_ref, g_ref = _moe_loss_and_grads(None, mesh, tokens)
    p_ovl, l_ovl, g_ovl = _moe_loss_and_grads(
        OverlapSchedule(moe='overlap'), mesh, tokens)
    assert (jax.tree_util.tree_structure(p_ref)
            == jax.tree_util.tree_structure(p_ovl))
    for ref, ovl in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_ovl)):
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ovl))
    assert l_ref == l_ovl, (l_ref, l_ovl)
    for ref, ovl in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_ovl)):
        np.testing.assert_allclose(np.asarray(ref), np.asarray(ovl),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_moe_overlap_ragged_exchange_falls_back_one_shot():
    """The ragged exchange keeps its single whole-batch exchange under
    moe='overlap' (pinned by moe_plan) — the knob degrades to the
    documented fallback instead of changing semantics or crashing."""
    assert moe_plan(8, 2, exchange='ragged-emulated').path == 'one-shot'
    mesh = _moe_mesh()
    tokens = _moe_tokens(seed=1)
    _, l_ref, _ = _moe_loss_and_grads(None, mesh, tokens,
                                      moe_exchange='ragged-emulated')
    _, l_ovl, _ = _moe_loss_and_grads(OverlapSchedule(moe='overlap'), mesh,
                                      tokens,
                                      moe_exchange='ragged-emulated')
    assert l_ref == l_ovl, (l_ref, l_ovl)


def _pipelined_moe_losses(schedule, mesh, tokens, steps=3, **overrides):
    config = dict(vocab_size=256, layers=4, dim=48, heads=4, max_seq=64,
                  dtype='float32', microbatches=2, mesh=mesh,
                  moe_experts=2, moe_every=2, moe_capacity_factor=2.0,
                  schedule=schedule)
    config.update(overrides)
    model = GPT2Pipelined(**config)
    optimizer = AdamW(lr=1e-3)
    state = init_state(model, optimizer, tokens[:1], rng=0)
    state = PipelineParallel(
        stacked_rules=GPT2Pipelined.block_partition_rules(),
        fsdp=True, fsdp_min_size=64).place(state, mesh)
    placed = jax.device_put(tokens, batch_sharding(mesh))
    step = build_train_step(flax_apply(model), WithAuxLoss(NextTokenLoss()),
                            optimizer)
    losses = []
    for _ in range(steps):
        state, (_, loss) = step(state, placed, placed)
        losses.append(float(loss))
    return state, losses


def test_composed_pp_fsdp_moe_pipelined_step_is_bitwise_vs_gspmd():
    """The composed arms on a dp x fsdp x stage mesh (fully-manual
    pipeline — runs on every jaxlib): a pipelined MoE GPT-2 under
    OverlapSchedule(pp='overlap', fsdp='prefetch', moe='overlap') trains
    BITWISE-equal losses and params to the all-GSPMD reference across 3
    steps — pp reschedules pure copies; fsdp/moe arms degrade per their
    plans inside the pipe (the blocks see mesh=None) and bite on the
    non-pipelined meshes their own tests cover."""
    mesh = MeshSpec(data=2, fsdp=2, stage=2).build()
    tokens = _moe_tokens(seed=2, batch=16)
    schedule = OverlapSchedule(tp='overlap', fsdp='prefetch', pp='overlap',
                               moe='overlap', fsdp_min_size=64)
    s_ref, l_ref = _pipelined_moe_losses(None, mesh, tokens)
    s_ovl, l_ovl = _pipelined_moe_losses(schedule, mesh, tokens)
    assert l_ref == l_ovl, (l_ref, l_ovl)
    assert (jax.tree_util.tree_structure(s_ref.params)
            == jax.tree_util.tree_structure(s_ovl.params))
    for ref, ovl in zip(jax.tree.leaves(s_ref.params),
                        jax.tree.leaves(s_ovl.params)):
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ovl))


def test_composed_pp_tp_fsdp_moe_pipelined_step_matches_gspmd():
    """The full four-axis composition (dp-free fsdp x model x stage mesh,
    partial-manual pipeline: GSPMD partitions the stage bodies over
    `model`): losses bitwise vs the all-GSPMD reference."""
    mesh = MeshSpec(fsdp=2, model=2, stage=2).build()
    tokens = _moe_tokens(seed=3, batch=16)
    schedule = OverlapSchedule(tp='overlap', fsdp='prefetch', pp='overlap',
                               moe='overlap', fsdp_min_size=64)
    s_ref, l_ref = _pipelined_moe_losses(None, mesh, tokens)
    s_ovl, l_ovl = _pipelined_moe_losses(schedule, mesh, tokens)
    assert l_ref == l_ovl, (l_ref, l_ovl)
    for ref, ovl in zip(jax.tree.leaves(s_ref.params),
                        jax.tree.leaves(s_ovl.params)):
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ovl))


def test_compile_guard_composed_pipelined_step_never_retraces():
    """The PR-1 pipeline retrace bug class, guarded for the new arms: the
    composed pp/moe-scheduled train step traces exactly once across
    steps."""
    mesh = MeshSpec(data=2, fsdp=2, stage=2).build()
    tokens = _moe_tokens(seed=4, batch=16)
    schedule = OverlapSchedule(pp='overlap', moe='overlap',
                               fsdp='prefetch', fsdp_min_size=64)
    model = GPT2Pipelined(vocab_size=256, layers=4, dim=48, heads=4,
                          max_seq=64, dtype='float32', microbatches=2,
                          mesh=mesh, moe_experts=2, moe_every=2,
                          moe_capacity_factor=2.0, schedule=schedule)
    optimizer = AdamW(lr=1e-3)
    state = init_state(model, optimizer, tokens[:1], rng=0)
    state = PipelineParallel(fsdp=True, fsdp_min_size=64).place(state, mesh)
    placed = jax.device_put(tokens, batch_sharding(mesh))
    raw = build_train_step(flax_apply(model), WithAuxLoss(NextTokenLoss()),
                           optimizer, jit=False)

    traces = []

    def counting_step(state, inputs, targets):
        traces.append(1)          # runs at trace time only
        return raw(state, inputs, targets)

    runner = jax.jit(counting_step)
    loss = None
    for _ in range(3):
        state, (_, loss) = runner(state, placed, placed)
    assert np.isfinite(float(loss)), float(loss)
    assert len(traces) == 1, (
        f'composed pipelined step retraced: {len(traces)} traces for 3 steps')


def test_pipelined_moe_rejects_1f1b_and_interleave():
    from tpusystem.train import build_1f1b_train_step
    mesh = MeshSpec(data=2, stage=2).build(jax.devices()[:4])
    with pytest.raises(ValueError, match='interleave'):
        GPT2Pipelined(vocab_size=64, layers=4, dim=32, heads=2, max_seq=32,
                      mesh=mesh, moe_experts=2, interleave=2)
    model = GPT2Pipelined(vocab_size=64, layers=4, dim=32, heads=2,
                          max_seq=32, dtype='float32', microbatches=2,
                          mesh=mesh, moe_experts=2)
    with pytest.raises(ValueError, match='MoE spans'):
        build_1f1b_train_step(model, NextTokenLoss(), AdamW(lr=1e-3))
