"""Pipeline parallelism: GPipe schedule over the ``stage`` mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.4); these tests are
the simulated-multi-device coverage the TPU build adds: numerical parity of
the pipelined forward/backward against a sequential reference, and a full
sharded train step on a (data x stage) mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusystem.models import GPT2Pipelined
from tpusystem.parallel import (MeshSpec, PipelineParallel, ShardingPolicy,
                               batch_sharding, pipeline_apply)
from tpusystem.train import AdamW, NextTokenLoss, build_train_step, flax_apply, init_state

def make_model(stages=4, data=2, microbatches=2, model=1, **overrides):
    mesh = MeshSpec(data=data, stage=stages, model=model).build()
    config = dict(vocab_size=64, layers=4, dim=32, heads=4, max_seq=32,
                  dtype='float32', microbatches=microbatches, mesh=mesh)
    config.update(overrides)
    return GPT2Pipelined(**config), mesh


def test_pipelined_forward_matches_sequential():
    model, mesh = make_model()
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (4, 16)))
    variables = model.init(jax.random.PRNGKey(0), tokens)
    pipelined = jax.jit(model.apply)(variables, tokens)
    sequential = jax.jit(model.sequential_apply)(variables, tokens)
    np.testing.assert_allclose(np.asarray(pipelined), np.asarray(sequential),
                               rtol=1e-4, atol=1e-4)


def test_pipelined_gradients_match_sequential():
    model, mesh = make_model()
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 64, (4, 16)))
    variables = model.init(jax.random.PRNGKey(1), tokens)

    def loss_pipe(params):
        logits = model.apply({'params': params}, tokens)
        return jnp.mean((logits.astype(jnp.float32)) ** 2)

    def loss_seq(params):
        logits = model.sequential_apply({'params': params}, tokens)
        return jnp.mean((logits.astype(jnp.float32)) ** 2)

    grads_pipe = jax.jit(jax.grad(loss_pipe))(variables['params'])
    grads_seq = jax.jit(jax.grad(loss_seq))(variables['params'])
    flat_pipe = jax.tree.leaves(grads_pipe)
    flat_seq = jax.tree.leaves(grads_seq)
    for a, b in zip(flat_pipe, flat_seq):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_pipeline_train_step_on_stage_mesh():
    model, mesh = make_model()
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 64, (8, 16)))
    optimizer = AdamW(lr=1e-2)
    state = init_state(model, optimizer, tokens[:4])
    policy = PipelineParallel(fsdp=False)
    state = policy.place(state, mesh)
    tokens = jax.device_put(tokens, batch_sharding(mesh))

    step = build_train_step(flax_apply(model), NextTokenLoss(), optimizer)
    losses = []
    for _ in range(4):
        state, (_, loss) = step(state, tokens, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_stage_sharding_placement():
    model, mesh = make_model(stages=4, data=2)
    tokens = jnp.zeros((2, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    placed = PipelineParallel().place(variables['params'], mesh)
    spec = placed['h']['attn']['qkv']['kernel'].sharding.spec
    assert spec[0] == 'stage', spec
    assert placed['wte']['embedding'].sharding.spec == ()


@pytest.mark.slow
def test_layers_not_divisible_by_stages_raises():
    model, mesh = make_model(stages=4, layers=6)
    tokens = jnp.zeros((2, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match='divisible'):
        model.apply(variables, tokens)


def test_pipeline_apply_plain_stack():
    """pipeline_apply works on any stacked layer fn, not just transformers."""
    mesh = MeshSpec(stage=4, data=2).build()
    layers, batch, dim = 8, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(0), layers)
    weights = jax.vmap(lambda k: jax.random.normal(k, (dim, dim)) / dim)(keys)
    inputs = jax.random.normal(jax.random.PRNGKey(1), (batch, dim))

    def block_fn(layer_params, x):
        return jnp.tanh(x @ layer_params['w'])

    out = pipeline_apply(block_fn, {'w': weights}, inputs, mesh, microbatches=2)

    reference = inputs
    for index in range(layers):
        reference = jnp.tanh(reference @ weights[index])
    np.testing.assert_allclose(np.asarray(out), np.asarray(reference),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_1f1b_matches_gpipe_autodiff_step():
    """The 1F1B interleaved schedule produces the same loss and updated
    parameters as autodiffing the GPipe pipeline_apply path — including
    the tied embedding whose gradient merges head and tail contributions."""
    from tpusystem.models import GPT2Pipelined
    from tpusystem.train import (NextTokenLoss, SGD, build_1f1b_train_step,
                                 build_train_step, flax_apply, init_state)
    mesh = MeshSpec(data=2, stage=4).build()
    model = GPT2Pipelined(vocab_size=256, layers=4, dim=64, heads=4,
                          max_seq=64, dtype='float32', microbatches=8,
                          mesh=mesh)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (16, 32)), jnp.int32)

    def one_step(build):
        state = init_state(model, SGD(lr=0.1), tokens[:1], rng=0)
        step = build()
        state, (_, loss) = step(state, tokens, tokens)
        return float(loss), state.params

    gpipe_loss, gpipe_params = one_step(lambda: build_train_step(
        flax_apply(model), NextTokenLoss(), SGD(lr=0.1)))
    f1b_loss, f1b_params = one_step(lambda: build_1f1b_train_step(
        model, NextTokenLoss(), SGD(lr=0.1)))

    np.testing.assert_allclose(gpipe_loss, f1b_loss, rtol=1e-5)
    flat_a = jax.tree.leaves(gpipe_params)
    flat_b = jax.tree.leaves(f1b_params)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


@pytest.mark.slow
def test_1f1b_single_stage_degenerates_to_microbatch_loop():
    from tpusystem.models import GPT2Pipelined
    from tpusystem.train import (NextTokenLoss, SGD, build_1f1b_train_step,
                                 build_train_step, flax_apply, init_state)
    mesh = MeshSpec(data=2).build(jax.devices()[:2])
    model = GPT2Pipelined(vocab_size=128, layers=2, dim=32, heads=2,
                          max_seq=32, dtype='float32', microbatches=2,
                          mesh=mesh)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 128, (4, 16)), jnp.int32)
    state = init_state(model, SGD(lr=0.1), tokens[:1], rng=0)
    step = build_1f1b_train_step(model, NextTokenLoss(), SGD(lr=0.1))
    state, (_, loss) = step(state, tokens, tokens)
    reference = build_train_step(flax_apply(model), NextTokenLoss(), SGD(lr=0.1))
    ref_state = init_state(model, SGD(lr=0.1), tokens[:1], rng=0)
    ref_state, (_, ref_loss) = reference(ref_state, tokens, tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    # params too: loss alone cannot catch dropped embedding gradients
    for a, b in zip(jax.tree.leaves(ref_state.params),
                    jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


@pytest.mark.slow
def test_interleaved_1f1b_matches_gpipe_autodiff_step():
    """interleave=2: each device owns two non-contiguous layer chunks
    (virtual stages), microbatches ride the ring twice — loss and updated
    params still match the GPipe autodiff reference exactly."""
    from tpusystem.models import GPT2Pipelined
    from tpusystem.train import (NextTokenLoss, SGD, build_1f1b_train_step,
                                 build_train_step, flax_apply, init_state)
    mesh = MeshSpec(data=2, stage=4).build()
    model = GPT2Pipelined(vocab_size=256, layers=8, dim=64, heads=4,
                          max_seq=64, dtype='float32', microbatches=8,
                          mesh=mesh, interleave=2)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 256, (16, 32)), jnp.int32)

    def one_step(build):
        state = init_state(model, SGD(lr=0.1), tokens[:1], rng=0)
        step = build()
        state, (_, loss) = step(state, tokens, tokens)
        return float(loss), state.params

    gpipe_loss, gpipe_params = one_step(lambda: build_train_step(
        flax_apply(model), NextTokenLoss(), SGD(lr=0.1)))
    f1b_loss, f1b_params = one_step(lambda: build_1f1b_train_step(
        model, NextTokenLoss(), SGD(lr=0.1)))

    np.testing.assert_allclose(gpipe_loss, f1b_loss, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gpipe_params),
                    jax.tree.leaves(f1b_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


@pytest.mark.slow
def test_interleaved_1f1b_partial_last_group():
    """microbatches not a multiple of stages: the schedule pads the last
    chunk sweep with idle units instead of clipping onto real microbatches
    (which would silently duplicate some and skip others) — parity with
    the GPipe autodiff reference must still hold exactly."""
    from tpusystem.models import GPT2Pipelined
    from tpusystem.train import (NextTokenLoss, SGD, build_1f1b_train_step,
                                 build_train_step, flax_apply, init_state)
    mesh = MeshSpec(stage=4).build(jax.devices()[:4])
    model = GPT2Pipelined(vocab_size=128, layers=8, dim=32, heads=2,
                          max_seq=32, dtype='float32', microbatches=6,
                          mesh=mesh, interleave=2)
    tokens = jnp.asarray(
        np.random.default_rng(8).integers(0, 128, (6, 16)), jnp.int32)

    def one_step(build):
        state = init_state(model, SGD(lr=0.1), tokens[:1], rng=0)
        state, (_, loss) = build()(state, tokens, tokens)
        return float(loss), state.params

    gpipe_loss, gpipe_params = one_step(lambda: build_train_step(
        flax_apply(model), NextTokenLoss(), SGD(lr=0.1)))
    f1b_loss, f1b_params = one_step(lambda: build_1f1b_train_step(
        model, NextTokenLoss(), SGD(lr=0.1)))
    np.testing.assert_allclose(gpipe_loss, f1b_loss, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gpipe_params),
                    jax.tree.leaves(f1b_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


def test_interleaved_schedule_units_and_bubble():
    """Round-unit accounting for the interleaved schedule: every (chunk,
    microbatch) unit executes exactly once per device at a
    dependency-consistent tick, and the fill/drain bubble shrinks with the
    interleave factor instead of growing with stage count alone."""
    from tpusystem.parallel.pipeline import _stash_slots

    def fwd_tick(S, v, s, c, m):
        g, pos = divmod(m, S)
        return s + g * v * S + c * S + pos

    def bwd_tick(S, v, s, c, m):
        g, pos = divmod(m, S)
        return (v * S + S - 2 - s) + g * v * S + (v - 1 - c) * S + pos

    for S, v, M in [(4, 1, 8), (4, 2, 8), (4, 4, 16), (2, 3, 6), (8, 2, 16)]:
        rounds = v * M + v * S + S - 2
        for s in range(S):
            fwd = [(c, m, fwd_tick(S, v, s, c, m))
                   for c in range(v) for m in range(M)]
            bwd = [(c, m, bwd_tick(S, v, s, c, m))
                   for c in range(v) for m in range(M)]
            # one unit per slot per tick, all within the round budget
            assert len({t for _, _, t in fwd}) == v * M
            assert len({t for _, _, t in bwd}) == v * M
            assert all(0 <= t < rounds for _, _, t in fwd + bwd)
            for c in range(v):
                for m in range(M):
                    # virtual-stage dependency: stage q consumes what q-1
                    # produced one tick earlier (ring latency 1)
                    q = c * S + s
                    if q > 0:
                        prev_s, prev_c = (s - 1, c) if s else (S - 1, c - 1)
                        assert (fwd_tick(S, v, prev_s, prev_c, m)
                                == fwd_tick(S, v, s, c, m) - 1)
                    # backward runs at/after the forward, and the stash
                    # slot m % slots is never clobbered while live
                    assert bwd_tick(S, v, s, c, m) >= fwd_tick(S, v, s, c, m)
            slots = _stash_slots(S, v, M)
            for c in range(v):
                for m in range(M - slots):
                    assert (fwd_tick(S, v, s, c, m + slots)
                            > bwd_tick(S, v, s, c, m))
    # v=1 recovers the classic 1F1B accounting
    assert _stash_slots(4, 1, 8) <= 2 * 4 - 1
    # bubble (idle chunk-ticks per fwd slot) = rounds - busy units:
    # interleave 2 at S=4, M=8 idles 10 chunk-ticks where plain 1F1B
    # idles 6 *stage*-ticks = 12 chunk-ticks of real compute
    plain = (8 + 2 * 4 - 2) - 8          # rounds - busy, stage units
    inter = (2 * 8 + 2 * 4 + 4 - 2) - 2 * 8  # chunk units
    assert inter < plain * 2             # chunk units vs v * stage units


def test_interleaved_placement_shards_chunk_stack():
    """PipelineParallel(interleave=v) shards the chunk-major stack's second
    dim over stage, so each device holds v non-contiguous chunks."""
    model, mesh = make_model(stages=4, layers=8, interleave=2)
    tokens = jnp.zeros((2, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    kernel = variables['params']['h']['attn']['qkv']['kernel']
    assert kernel.shape[:2] == (2, 4), kernel.shape
    placed = PipelineParallel(interleave=2).place(variables['params'], mesh)
    spec = placed['h']['attn']['qkv']['kernel'].sharding.spec
    assert spec[:2] == (None, 'stage'), spec
    # sequential reference still runs on the chunk-major storage
    out = jax.jit(model.sequential_apply)(variables, tokens)
    assert out.shape == (2, 8, 64)


@pytest.mark.slow
def test_1f1b_token_weighted_under_padding():
    """With a masked LM loss and pad-heavy microbatches, the 1F1B step
    weights microbatches by unmasked-token count like
    build_train_step(accumulate=...) — the full-batch reference and the
    pipelined step still agree."""
    from tpusystem.models import GPT2Pipelined
    from tpusystem.train import (NextTokenLoss, SGD, build_1f1b_train_step,
                                 build_train_step, flax_apply, init_state)
    mesh = MeshSpec(stage=4).build(jax.devices()[:4])
    model = GPT2Pipelined(vocab_size=128, layers=4, dim=32, heads=2,
                          max_seq=32, dtype='float32', microbatches=4,
                          mesh=mesh)
    tokens = np.random.default_rng(2).integers(0, 128, (8, 16)).astype(np.int32)
    tokens[:3, 4:] = -1                  # uneven padding across microbatches
    tokens = jnp.asarray(tokens)

    state = init_state(model, SGD(lr=0.1), jnp.abs(tokens[:1]), rng=0)
    step = build_1f1b_train_step(model, NextTokenLoss(), SGD(lr=0.1))
    state, (_, loss) = step(state, jnp.abs(tokens), tokens)

    reference = build_train_step(flax_apply(model), NextTokenLoss(), SGD(lr=0.1))
    ref_state = init_state(model, SGD(lr=0.1), jnp.abs(tokens[:1]), rng=0)
    ref_state, (_, ref_loss) = reference(ref_state, jnp.abs(tokens), tokens)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ref_state.params),
                    jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize('microbatches', [8, 6])
def test_interleaved_gpipe_forward_matches_sequential(microbatches):
    """pipeline_apply(interleave=2): the chunk-major stack rides the ring
    twice through chunk-sized units (pipeline_train's forward slot) —
    outputs must match the sequential reference, including a microbatch
    count that does not divide the stage count (padded last group)."""
    model, mesh = make_model(stages=4, data=2, layers=8,
                             microbatches=microbatches, interleave=2)
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, (2 * microbatches, 16)))
    variables = model.init(jax.random.PRNGKey(2), tokens)
    pipelined = jax.jit(model.apply)(variables, tokens)
    sequential = jax.jit(model.sequential_apply)(variables, tokens)
    np.testing.assert_allclose(np.asarray(pipelined), np.asarray(sequential),
                               rtol=1e-4, atol=1e-4)


def test_interleaved_gpipe_gradients_match_sequential():
    """Autodiff through the interleaved GPipe forward (cond-gated idle
    units, gathered emission ticks) matches the sequential reference."""
    model, mesh = make_model(stages=4, data=2, layers=8, microbatches=8,
                             interleave=2)
    tokens = jnp.asarray(np.random.default_rng(6).integers(0, 64, (16, 16)))
    variables = model.init(jax.random.PRNGKey(3), tokens)

    def loss_pipe(params):
        logits = model.apply({'params': params}, tokens)
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    def loss_seq(params):
        logits = model.sequential_apply({'params': params}, tokens)
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    grads_pipe = jax.jit(jax.grad(loss_pipe))(variables['params'])
    grads_seq = jax.jit(jax.grad(loss_seq))(variables['params'])
    for a, b in zip(jax.tree.leaves(grads_pipe), jax.tree.leaves(grads_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_interleaved_gpipe_fill_drain_units():
    """Forward-schedule unit accounting for pipeline_apply(interleave=v):
    every (chunk, microbatch) unit runs exactly once per device, emission
    ticks are where the gather expects them, and the fill/drain bubble is
    S-1 chunk-units (vs S-1 *stage*-units = v(S-1) chunk-units
    contiguous)."""
    def fwd_tick(S, v, s, c, m):
        g, pos = divmod(m, S)
        return s + g * v * S + c * S + pos

    for S, v, M in [(4, 2, 8), (4, 2, 6), (2, 3, 6), (8, 2, 16)]:
        padded = -(-M // S) * S
        ticks = v * padded + S - 1
        for s in range(S):
            units = [(c, m, fwd_tick(S, v, s, c, m))
                     for c in range(v) for m in range(M)]
            assert len({t for *_, t in units}) == v * M   # one unit per tick
            assert all(0 <= t < ticks for *_, t in units)
        # last stage emits microbatch m's final chunk at the gathered tick
        for m in range(M):
            expected = ((m // S) * v * S + (v - 1) * S + (m % S) + S - 1)
            assert fwd_tick(S, v, S - 1, v - 1, m) == expected
        # fill/drain bubble: idle ticks on the last stage's final chunk
        # slot shrink from v*(S-1) contiguous chunk-units to (S-1) + the
        # partial-group padding v*(padded-M)
        busy = v * M
        assert ticks - busy == (S - 1) + v * (padded - M)


def test_pp_tp_placement_shards_stage_and_model():
    """stacked_rules compose: a qkv kernel lands P(stage, None, model)."""
    model, mesh = make_model(stages=2, data=2, model=2)
    tokens = jnp.zeros((2, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    policy = PipelineParallel(
        stacked_rules=GPT2Pipelined.block_partition_rules())
    placed = policy.place(variables['params'], mesh)
    qkv = placed['h']['attn']['qkv']['kernel'].sharding.spec
    assert tuple(qkv) == ('stage', None, 'model'), qkv
    out = placed['h']['attn']['out']['kernel'].sharding.spec
    assert tuple(out) == ('stage', 'model'), out
    # the model's own partition_rules build the same composition
    own = ShardingPolicy(rules=model.partition_rules()).place(
        variables['params'], mesh)
    assert tuple(own['h']['fc']['kernel'].sharding.spec) == \
        ('stage', None, 'model')


def test_pp_tp_forward_matches_sequential():
    """PP x TP: with the model axis live (stage=2 x model=2) and stacked
    params model-sharded, the pipelined forward still matches the
    sequential reference — the partial-manual shard_map lets GSPMD
    partition the stage bodies over `model`."""
    model, mesh = make_model(stages=2, data=2, model=2)
    tokens = jnp.asarray(np.random.default_rng(4).integers(0, 64, (4, 16)))
    variables = model.init(jax.random.PRNGKey(2), tokens)
    params = ShardingPolicy(rules=model.partition_rules()).place(
        variables['params'], mesh)
    pipelined = jax.jit(model.apply)({'params': params}, tokens)
    sequential = jax.jit(model.sequential_apply)(variables, tokens)
    np.testing.assert_allclose(np.asarray(pipelined), np.asarray(sequential),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_pp_tp_1f1b_matches_gpipe_autodiff_step():
    """The 1F1B schedule composes with within-stage TP: loss and updated
    params on a stage=2 x model=2 mesh match the GPipe autodiff path."""
    from tpusystem.train import (SGD, build_1f1b_train_step,
                                 build_train_step)
    mesh = MeshSpec(data=2, stage=2, model=2).build()
    model = GPT2Pipelined(vocab_size=256, layers=4, dim=64, heads=4,
                          max_seq=64, dtype='float32', microbatches=4,
                          mesh=mesh)
    tokens = jnp.asarray(
        np.random.default_rng(6).integers(0, 256, (8, 32)), jnp.int32)
    policy = PipelineParallel(
        stacked_rules=GPT2Pipelined.block_partition_rules())

    def one_step(build):
        state = init_state(model, SGD(lr=0.1), tokens[:1], rng=0)
        state = policy.place(state, mesh)
        step = build()
        state, (_, loss) = step(state, tokens, tokens)
        return float(loss), state.params

    gpipe_loss, gpipe_params = one_step(lambda: build_train_step(
        flax_apply(model), NextTokenLoss(), SGD(lr=0.1)))
    f1b_loss, f1b_params = one_step(lambda: build_1f1b_train_step(
        model, NextTokenLoss(), SGD(lr=0.1)))

    np.testing.assert_allclose(gpipe_loss, f1b_loss, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gpipe_params),
                    jax.tree.leaves(f1b_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)
