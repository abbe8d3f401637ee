"""Latency-hiding TP collectives: decomposed all-gather/reduce-scatter
matmuls (``tpusystem/parallel/overlap.py``).

Parity harness on the virtual CPU mesh: the decomposed ring kernels must
match the GSPMD reference (a plain global matmul — what the partitioner
computes via its monolithic collectives) in forward AND gradients, f32 at
tight tolerance and bf16 bounded (f32 accumulation, different summation
order), with the one-shot fallback taken exactly where chunk shapes
cannot tile. Model-level: ``OverlapSchedule(tp='overlap')`` is a pure
implementation knob for GPT-2 and Llama — identical param trees, matching
logits/grads.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpusystem.models import GPT2
from tpusystem.models.llama import llama_tiny
from tpusystem.parallel import (MeshSpec, OverlapSchedule, ShardingPolicy,
                                batch_sharding, allgather_matmul,
                                allgather_plan, matmul_reducescatter,
                                reducescatter_plan)
from tpusystem.parallel.mesh import MODEL

RING = 4           # >= 4-device virtual mesh (conftest forces 8 devices)


def tp_mesh():
    return MeshSpec(model=RING).build(jax.devices()[:RING])


def _operands(dtype, rows=16, inner=12, cols=24, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, inner)) * 0.5, dtype)
    w = jnp.asarray(rng.normal(size=(inner, cols)) * 0.5, dtype)
    return x, w


def _mapped_allgather(mesh, chunks):
    # x row-sharded over model (the sequence-sharded activation), w
    # column-sharded (Megatron up-projection): the gathered matmul
    @functools.partial(jax.shard_map, mesh=mesh, check_vma=False,
                       in_specs=(P(MODEL, None), P(None, MODEL)),
                       out_specs=P(None, MODEL))
    def mapped(x, w):
        return allgather_matmul(x, w, MODEL, chunks=chunks)
    return mapped


def _mapped_reducescatter(mesh, chunks):
    # x column-sharded (the grown activation), w row-sharded (Megatron
    # down-projection): partial products sum + scatter rows
    @functools.partial(jax.shard_map, mesh=mesh, check_vma=False,
                       in_specs=(P(None, MODEL), P(MODEL, None)),
                       out_specs=P(MODEL, None))
    def mapped(x, w):
        return matmul_reducescatter(x, w, MODEL, chunks=chunks)
    return mapped


@pytest.mark.parametrize('chunks', [1, 2])
def test_allgather_matmul_forward_matches_gspmd_reference(chunks):
    mesh = tp_mesh()
    x, w = _operands(jnp.float32)
    out = jax.jit(_mapped_allgather(mesh, chunks))(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize('chunks', [1, 2])
def test_matmul_reducescatter_forward_matches_gspmd_reference(chunks):
    mesh = tp_mesh()
    x, w = _operands(jnp.float32)
    out = jax.jit(_mapped_reducescatter(mesh, chunks))(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize('mapped_builder', [_mapped_allgather,
                                            _mapped_reducescatter])
def test_overlap_grads_match_gspmd_reference_f32(mapped_builder):
    """The custom_vjp (each decomposition's transpose is its dual with
    swapped operands) reproduces the reference cotangents."""
    mesh = tp_mesh()
    x, w = _operands(jnp.float32)
    mapped = mapped_builder(mesh, 2)

    def loss(x, w):
        return jnp.sum(jnp.square(mapped(x, w)))

    def reference(x, w):
        return jnp.sum(jnp.square(x @ w))

    dx, dw = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)
    rx, rw = jax.grad(reference, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(rx),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(rw),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('mapped_builder', [_mapped_allgather,
                                            _mapped_reducescatter])
def test_overlap_grads_match_gspmd_reference_bf16(mapped_builder):
    """bf16 compute with f32 accumulation: bounded tolerance against the
    reference computed the GSPMD way (bf16 matmul), mirroring the MoE
    three-impl bf16 grad-parity case."""
    mesh = tp_mesh()
    x, w = _operands(jnp.bfloat16)
    mapped = mapped_builder(mesh, 1)

    def loss(x, w):
        return jnp.sum(jnp.square(mapped(x, w).astype(jnp.float32)))

    def reference(x, w):
        return jnp.sum(jnp.square(jnp.matmul(x, w).astype(jnp.float32)))

    out = jax.jit(mapped)(x, w)
    ref = jnp.matmul(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0.05, atol=0.1)
    dx, dw = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)
    rx, rw = jax.grad(reference, argnums=(0, 1))(x, w)
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(rx, np.float32),
                               rtol=0.1, atol=0.5)
    np.testing.assert_allclose(np.asarray(dw, np.float32),
                               np.asarray(rw, np.float32),
                               rtol=0.1, atol=0.5)


# ---------------------------------------------------------------------------
# fallback planning
# ---------------------------------------------------------------------------


def test_plans_pick_one_shot_when_chunks_cannot_tile():
    # trivial ring: nothing to decompose
    assert allgather_plan(16, 1).path == 'one-shot'
    assert reducescatter_plan(16, 1).path == 'one-shot'
    # 16 shard rows cannot split into 3 ppermute chunks
    plan = allgather_plan(16, RING, chunks=3)
    assert plan.path == 'one-shot' and 'chunks' in plan.reason
    # scatter block 16/4 = 4 rows cannot split into 3
    plan = reducescatter_plan(16, RING, chunks=3)
    assert plan.path == 'one-shot' and 'chunks' in plan.reason
    # tiling shapes decompose
    assert allgather_plan(16, RING, chunks=2).path == 'overlap'
    assert reducescatter_plan(16, RING, chunks=2).path == 'overlap'
    # rows that cannot scatter at all have no semantics on either path
    with pytest.raises(ValueError):
        reducescatter_plan(18, RING)


def test_one_shot_fallback_still_matches_reference():
    """chunks=3 cannot tile the 4-row shards -> the one-shot collective
    path runs (pinned by the plan above) and stays correct, grads too."""
    mesh = tp_mesh()
    x, w = _operands(jnp.float32)
    assert allgather_plan(x.shape[0] // RING, RING, 3).path == 'one-shot'
    mapped = _mapped_allgather(mesh, 3)
    out = jax.jit(mapped)(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=2e-6, atol=2e-6)
    dx = jax.jit(jax.grad(lambda x, w: jnp.sum(jnp.square(mapped(x, w)))))(x, w)
    rx = jax.grad(lambda x, w: jnp.sum(jnp.square(x @ w)))(x, w)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(rx),
                               rtol=2e-5, atol=2e-5)

    assert reducescatter_plan(x.shape[0], RING, 3).path == 'one-shot'
    mapped = _mapped_reducescatter(mesh, 3)
    out = jax.jit(mapped)(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# model-level: the schedule's tp= knob
# ---------------------------------------------------------------------------


def _model_mesh():
    return MeshSpec(data=2, model=2).build(jax.devices()[:4])


def _run_model(model, rules, tokens, mesh):
    variables = model.init(jax.random.PRNGKey(0), tokens[:1, :8])
    params = ShardingPolicy(rules=rules).place(variables['params'], mesh)
    placed_tokens = jax.device_put(tokens, batch_sharding(mesh))
    out = jax.jit(lambda p, t: model.apply({'params': p}, t))(
        params, placed_tokens)

    def loss(p):
        logits = model.apply({'params': p}, placed_tokens)
        return jnp.sum(jnp.square(logits.astype(jnp.float32))) * 1e-3

    grads = jax.jit(jax.grad(loss))(params)
    return variables, out, grads


@pytest.mark.parametrize('family', ['gpt2', 'llama'])
def test_tp_overlap_matches_gspmd_model_level(family):
    """Same params, logits and grads either way: 'overlap' is purely an
    implementation knob for the TP FFN projections."""
    mesh = _model_mesh()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (4, 16)), jnp.int32)

    def build(impl):
        if family == 'gpt2':
            model = GPT2(vocab_size=256, layers=2, dim=64, heads=4,
                         max_seq=128, dropout=0.0, dtype='float32',
                         mesh=mesh,
                         schedule=OverlapSchedule(tp=impl, chunks=2))
            return model, GPT2.partition_rules()
        model = llama_tiny(dtype='float32', mesh=mesh,
                           schedule=OverlapSchedule(tp=impl, chunks=2))
        return model, type(model).partition_rules()

    v_ref, out_ref, grads_ref = _run_model(*build('gspmd'),
                                           tokens=tokens, mesh=mesh)
    v_ovl, out_ovl, grads_ovl = _run_model(*build('overlap'),
                                           tokens=tokens, mesh=mesh)
    # the knob never changes the checkpoint: identical trees, identical init
    assert (jax.tree_util.tree_structure(v_ref)
            == jax.tree_util.tree_structure(v_ovl))
    for ref, ovl in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_ovl)):
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ovl))
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_ovl),
                               rtol=2e-5, atol=2e-5)
    for ref, ovl in zip(jax.tree.leaves(grads_ref),
                        jax.tree.leaves(grads_ovl)):
        np.testing.assert_allclose(np.asarray(ref), np.asarray(ovl),
                                   rtol=2e-4, atol=2e-5)


def test_tp_overlap_falls_back_on_non_tiling_sequence():
    """seq=15 cannot shard over the model axis -> the Dense/GSPMD path
    runs under the same params and the forward still matches."""
    mesh = _model_mesh()
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 256, (4, 15)), jnp.int32)
    common = dict(vocab_size=256, layers=2, dim=64, heads=4, max_seq=128,
                  dropout=0.0, dtype='float32', mesh=mesh)
    reference = GPT2(**common, schedule=OverlapSchedule(tp='gspmd'))
    model = GPT2(**common, schedule=OverlapSchedule(tp='overlap'))
    variables = reference.init(jax.random.PRNGKey(0), tokens[:1, :8])
    out_ref = jax.jit(lambda v, t: reference.apply(v, t))(variables, tokens)
    out_ovl = jax.jit(lambda v, t: model.apply(v, t))(variables, tokens)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_ovl),
                               rtol=1e-6, atol=1e-6)
