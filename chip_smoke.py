"""The standing proof that the system starts on the chip.

One process: checks the Pallas kernels on the main path against their
references at GPT-2 125M shapes, trains ``examples/lm`` ``--full`` for two
epochs from a fresh store (FSDP over every chip the process sees), serves
mixed-length requests through ``InferenceService``'s own defaults, and
prints one JSON object as the last line of stdout. Any phase that fails
raises; nothing is caught.

Run it on the chip (``chiprun -- python chip_smoke.py``). Without a TPU it
refuses. The seconds it prints are wall-clock of a smoke, compile
included where it says so — they are not benchmark metrics.
"""

from __future__ import annotations

import importlib.metadata
import importlib.util
import json
import pathlib
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
OUT = HERE / '.chip_smoke'          # the smoke's own output directory
MOSAIC = 'tpu_custom_call'          # how a compiled Pallas kernel lowers
PAGED_ATTENTION = 'kernel_name = "paged_decode_attention"'

# the tolerances tests/test_attention.py and tests/test_decode_fused.py
# already hold the same kernels to, by compute dtype
TOLERANCE = {'float32': dict(atol=5e-4, rtol=0.0),
             'bfloat16': dict(atol=2e-2, rtol=2e-2)}

# prompt lengths at max_seq 1024 (scaled down for smaller modules): short
# ones for the einsum prefill, >= 512 for the flash prefill branch
PROMPTS = (16, 24, 100, 120, 300, 500, 600, 640)
BUDGETS = (16, 64, 32, 48, 24, 64, 16, 32)
EPOCH_STEPS = 64    # examples/lm draws 64 * batch synthetic samples an epoch


def environment() -> dict:
    device = jax.devices()[0]
    versions = {name: importlib.metadata.version(name)
                for name in ('jax', 'jaxlib', 'libtpu')}
    return {'platform': device.platform, 'kind': device.device_kind,
            'count': len(jax.devices()), **versions}


def require(condition, message) -> None:
    """A check that survives ``python -O``."""
    if not condition:
        raise AssertionError(message)


def close(got, want, dtype) -> None:
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               **TOLERANCE[jnp.dtype(dtype).name])


def check_flash(*, batch: int, seq: int, heads: int, head_dim: int,
                dtype) -> None:
    """Flash forward and backward in ``dtype`` against
    ``dot_product_attention`` in float32 on the same seeded normals (a
    bf16 reference is itself off by more than the tolerance in places)."""
    from tpusystem.ops.attention import dot_product_attention
    from tpusystem.ops.pallas.flash import _block_sizes, flash_attention

    require(_block_sizes(seq, seq, 1024, 1024), f'flash cannot tile {seq}')
    rng = np.random.default_rng(0)
    narrow = [jnp.asarray(rng.normal(size=(batch, seq, heads, head_dim)),
                          dtype) for _ in range(3)]
    exact = [tensor.astype(jnp.float32) for tensor in narrow]

    def outputs(attend):
        loss = lambda q, k, v: jnp.sum(
            attend(q, k, v, causal=True).astype(jnp.float32) ** 2)
        return jax.jit(lambda q, k, v: (attend(q, k, v, causal=True),
                                        *jax.grad(loss, (0, 1, 2))(q, k, v)))

    with jax.default_matmul_precision('highest'):
        wanted = outputs(dot_product_attention)(*exact)
    for got, want in zip(outputs(flash_attention)(*narrow), wanted):
        close(got, want, dtype)


def check_decode(*, rows: int, dim: int, dtype) -> None:
    """``decode_matmul`` and ``decode_ffn`` with int8 weights against
    ``qdot`` at one block's shapes."""
    from tpusystem.ops.pallas import auto_interpret
    from tpusystem.ops.pallas.decode_matmul import (decode_ffn, decode_matmul,
                                                    decode_plan)
    from tpusystem.ops.precision import qdot, quantize_leaf

    for cols in (3 * dim, dim, 4 * dim):
        require(decode_plan(dim, cols, auto_interpret(None)),
                f'decode_matmul cannot tile {dim}x{cols}')
    rng = np.random.default_rng(0)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x = normal(rows, dim).astype(dtype)
    w1 = quantize_leaf(normal(dim, 4 * dim) * 0.02, 'int8')
    w2 = quantize_leaf(normal(4 * dim, dim) * 0.02, 'int8')
    b1, b2 = normal(4 * dim), normal(dim)
    close(jax.jit(decode_matmul)(x, w1, b1),
          (qdot(x, w1) + b1).astype(dtype), dtype)
    middle = jax.nn.gelu(qdot(x, w1) + b1).astype(dtype)
    close(jax.jit(decode_ffn)(x, w1, b1, w2, b2),
          (qdot(middle, w2) + b2).astype(dtype), dtype)


def load_lm():
    """``examples/lm/main.py`` as a module (examples are not a package)."""
    spec = importlib.util.spec_from_file_location(
        'lm_main', HERE / 'examples' / 'lm' / 'main.py')
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def train(lm, store: pathlib.Path, *, full: bool, epochs: int = 2) -> dict:
    """``lm.main(epochs, full=full)`` against ``store``, then what the run
    left behind: steps taken, stored losses, the committed checkpoint,
    where the parameters live, and the lowered train program."""
    from tpusystem.checkpoint import Repository
    from tpusystem.storage import (DocumentMetrics, DocumentModels,
                                   DocumentStore)

    # a store that already records the model makes lm.main resume at its
    # epoch and take no step at all, so count from where this run started
    before = max((row.epoch for row in DocumentModels(DocumentStore(
        store / 'experiments.json')).list(lm.experiment())), default=0)
    started = time.perf_counter()
    model = lm.main(epochs=epochs, full=full, root=store)
    wall = time.perf_counter() - started

    steps = int(model.state.step) - before * EPOCH_STEPS
    require(steps == epochs * EPOCH_STEPS,
            f'{steps} train steps taken, expected {epochs * EPOCH_STEPS}: '
            f'the store at {store} held this experiment at epoch {before}')

    rows = DocumentMetrics(DocumentStore(
        store / 'experiments.json')).list(str(model.id))
    losses = [row.value for row in rows
              if row.name == 'loss' and row.phase == 'train']
    require(len(losses) == epochs and np.isfinite(losses).all(), losses)
    require(losses[-1] < losses[0], f'train loss did not fall: {losses}')

    weights = Repository(store / 'weights')
    saved = weights.latest(model)
    verified = weights.checkpointer.verify(str(model.id), saved)
    weights.close()
    require(saved == epochs and verified, f'checkpoint {saved} not committed')

    devices = list(model.mesh.devices.flat)
    leaves = jax.tree.leaves(model.state.params)
    require(all(leaf.sharding.device_set == set(devices) for leaf in leaves),
            'a parameter leaf does not span the mesh')
    split = [leaf.sharding.shard_shape(leaf.shape) != leaf.shape
             for leaf in leaves]
    require(len(devices) == 1 or all(
        parted for leaf, parted in zip(leaves, split)
        if leaf.size >= 2 ** 20), 'a large matrix is replicated')
    in_use = [stats['bytes_in_use'] for stats in
              (device.memory_stats() for device in devices) if stats]
    require(not in_use or max(in_use) <= 2 * min(in_use),
            f'device memory is lopsided: {in_use}')

    # one more dispatch, fenced two ways: if block_until_ready returned
    # early the host read after it would carry the step's time
    stack = model.shard_batches(np.random.default_rng(0).integers(
        0, 256, (8, 16, model.network.max_seq)).astype(np.int32))
    mosaic = model.lowered(stack).count(MOSAIC)
    started = time.perf_counter()
    tail = jax.block_until_ready(model.fit_many(stack))
    fenced = time.perf_counter() - started
    started = time.perf_counter()
    last = float(np.asarray(tail)[-1])
    read = time.perf_counter() - started
    require(np.isfinite(last), f'loss {last} after the probe dispatch')
    return {'steps': steps, 'losses': losses, 'checkpoint': saved,
            'devices': len(devices),
            'sharded_leaves': f'{sum(split)}/{len(leaves)}',
            'bytes_in_use': in_use, 'mosaic_calls': mosaic,
            'wall_seconds_with_compile': round(wall, 2),
            'steady_8_step_dispatch_seconds': round(fenced, 4),
            'host_read_after_fence_seconds': round(read, 6)}


def serve(module) -> dict:
    """Seeded weights behind ``InferenceService``'s own defaults,
    ``len(PROMPTS)`` requests submitted by name, twice: the first pass
    compiles, the second must repeat it token for token."""
    from tpusystem.serve import InferenceService, Request

    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))['params']
    service = InferenceService(module, params)
    engine = service.engine
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, module.vocab_size,
                            max(1, length * module.max_seq // 1024)).tolist()
               for length in PROMPTS]

    def one_pass(tag: str) -> tuple[dict, float]:
        started = time.perf_counter()
        for index, (prompt, budget) in enumerate(zip(prompts, BUDGETS)):
            service.service.handle('submit',
                                   Request(f'{tag}{index}', prompt, budget))
        results = service.run_until_idle()
        return ({index: results[f'{tag}{index}'] for index in
                 range(len(prompts))}, time.perf_counter() - started)

    cold, cold_seconds = one_pass('cold')
    warm, warm_seconds = one_pass('warm')
    for index, budget in enumerate(BUDGETS):
        tokens = cold[index].tokens
        require(cold[index].reason == 'length' and len(tokens) == budget,
                f'request {index}: {cold[index].reason}, {len(tokens)} '
                f'tokens of {budget}')
        require(all(0 <= token < module.vocab_size for token in tokens),
                f'request {index} left the vocabulary')
        require(warm[index].tokens == tokens,
                f'request {index} did not repeat')
    require(engine.trace_count == 1,
            f'the decode step traced {engine.trace_count} times')
    lowered = engine.lowered_step()
    return {'requests': len(prompts),
            'prompt_lengths': [len(prompt) for prompt in prompts],
            'tokens': sum(BUDGETS), 'trace_count': engine.trace_count,
            'decode_impl': engine.decode_impl,
            'stream_dtype': engine.stream_dtype,
            'mosaic_calls': lowered.count(MOSAIC),
            'paged_attention_calls': lowered.count(PAGED_ATTENTION),
            'first_pass_seconds_with_compile': round(cold_seconds, 2),
            'second_pass_seconds': round(warm_seconds, 2)}


def dispatch_seconds(calls: int = 200) -> float:
    """Host cost of one trivial jitted dispatch, fenced — the floor under
    every per-token serving tick."""
    bump = jax.jit(lambda x: x + 1)
    x = jax.block_until_ready(bump(jnp.zeros((), jnp.int32)))
    started = time.perf_counter()
    for _ in range(calls):
        x = jax.block_until_ready(bump(x))
    return (time.perf_counter() - started) / calls


def main() -> None:
    started = time.perf_counter()
    found = environment()
    if found['platform'] != 'tpu':
        sys.exit(f'chip_smoke needs a TPU; jax.devices() found {found}')

    from tpusystem.data import native
    from tpusystem.models import GPT2
    from tpusystem.ops.precision import fp8_unsupported_reason
    from tpusystem.runtime import compile_cache

    report = {'environment': found, 'compile_cache': compile_cache(),
              'native_batcher': native.available()}
    print('environment', json.dumps(found), flush=True)
    print('compile cache', report['compile_cache'],
          '| native batcher', report['native_batcher'], flush=True)

    network = GPT2(dropout=0.0, vocab_size=50304)
    shape = dict(heads=network.heads, head_dim=network.dim // network.heads,
                 dtype=network.dtype)
    check_flash(batch=2, seq=network.max_seq, **shape)
    # the resident-dq backward only exists past one kv block: its 96 MB
    # VMEM request has to be one this toolchain grants
    check_flash(batch=1, seq=2 * network.max_seq, **shape)
    check_decode(rows=4, dim=network.dim, dtype=network.dtype)
    report['fp8_probe'] = fp8_unsupported_reason() or 'lowers'
    print('kernels ok: flash fwd+bwd, decode_matmul, decode_ffn | fp8 probe',
          report['fp8_probe'], flush=True)

    if OUT.exists():
        shutil.rmtree(OUT)
    report['train'] = train(load_lm(), OUT / 'store', full=True)
    shutil.rmtree(OUT)              # two 1.5 GB checkpoints: checked, not kept
    require(report['train']['devices'] == found['count'], report['train'])
    require(report['train']['mosaic_calls'] >= 2 * network.layers,
            'the train step took an XLA attention path')
    print('train', json.dumps(report['train']), flush=True)

    report['serve'] = serve(network)
    require((report['serve']['decode_impl'], report['serve']['stream_dtype'])
            == ('fused', 'int8'), report['serve'])
    require(report['serve']['mosaic_calls'] >= 4 * network.layers,
            'the decode step took an einsum path')
    require(report['serve']['paged_attention_calls'] == network.layers,
            'the decode step does not read the pool through the '
            'paged-attention kernel')
    print('serve', json.dumps(report['serve']), flush=True)

    report['trivial_dispatch_seconds'] = round(dispatch_seconds(), 6)
    report['wall_seconds'] = round(time.perf_counter() - started, 1)
    print('trivial dispatch', report['trivial_dispatch_seconds'], 's | wall',
          report['wall_seconds'], 's', flush=True)
    results = HERE / 'chiprun_out'
    results.mkdir(exist_ok=True)
    (results / 'chip_smoke.json').write_text(json.dumps(report, indent=1))
    print(json.dumps({'ok': True, 'device': {
        key: found[key] for key in ('platform', 'kind', 'count')}}))


if __name__ == '__main__':
    main()
