"""MFU for workload-ladder rows 2 (classifier) and 3 (ResNet-50) — the
same unit as the ladder-4 headline (`bench.py`), same anti-hoisting
methodology (steps chained through the carried TrainState inside one jit,
completion forced by materializing a value).

FLOPs per step come from XLA's own cost model on the compiled single-step
program (`compile().cost_analysis()['flops']`): it counts the executed
fwd+bwd+optimizer HLO, so the number is an *executed*-FLOPs utilization —
marginally above a hand-counted model-FLOPs MFU (optimizer/elementwise
included).
"""
import sys, time, json, pathlib
sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))
from functools import partial

import jax, jax.numpy as jnp, numpy as np

from bench import peak_flops
from tpusystem.models import MLP, ResNet
from tpusystem.train import (AdamW, CrossEntropyLoss, build_train_step,
                             flax_apply, init_state)


def measure(tag, module, inputs, targets, steps):
    optimizer = AdamW(lr=1e-3)
    state = init_state(module, optimizer, inputs[:1])
    step = build_train_step(flax_apply(module), CrossEntropyLoss(),
                            optimizer, jit=False)

    single = jax.jit(lambda st: step(st, inputs, targets)[0])
    flops = float(single.lower(state).compile().cost_analysis()['flops'])

    @partial(jax.jit, donate_argnums=0)
    def run(state):
        return jax.lax.fori_loop(
            0, steps, lambda i, st: step(st, inputs, targets)[0], state)

    state = run(state)
    float(jax.tree.leaves(state.params)[0].sum())     # force completion
    start = time.perf_counter()
    state = run(state)
    float(jax.tree.leaves(state.params)[0].sum())
    elapsed = time.perf_counter() - start

    steps_per_sec = steps / elapsed
    peak = peak_flops(jax.devices()[0])
    result = {
        'workload': tag, 'steps_per_sec': round(steps_per_sec, 2),
        'flops_per_step': float(flops),
        'examples_per_sec': round(steps_per_sec * inputs.shape[0], 1),
        'mfu': round(flops * steps_per_sec / peak, 4),
    }
    print(json.dumps(result))


def composed_row(steps: int = 20):
    """The composed-mesh ladder row: dp x fsdp x tp x stage with ALL four
    overlap arms on (`OverlapSchedule(tp='overlap', fsdp='prefetch',
    pp='overlap', moe='overlap')`) — the measurable row behind ROADMAP
    item 3's >= 0.60-MFU target. A pipelined MoE GPT-2 trains on the
    first 8 devices; needs 8+ chips and a jaxlib that lowers the
    pipeline's partial-manual shard_map (PP x TP) — prints a skip row
    otherwise so single-chip/CPU ladder runs stay green."""
    devices = jax.devices()
    if len(devices) < 8:
        print(json.dumps({'workload': 'composed_gpt2_pp_tp_fsdp_moe',
                          'mfu': None,
                          'note': f'skipped: needs 8 devices, have '
                                  f'{len(devices)}'}))
        return
    from tpusystem.parallel import (MeshSpec, OverlapSchedule,
                                    PipelineParallel, batch_sharding)
    from tpusystem.models import GPT2Pipelined
    from tpusystem.train import (NextTokenLoss, WithAuxLoss,
                                 build_train_step, flax_apply)
    mesh = MeshSpec(data=len(devices) // 8, fsdp=2, model=2,
                    stage=2).build(devices)
    schedule = OverlapSchedule(tp='overlap', fsdp='prefetch', pp='overlap',
                               moe='overlap', chunks=2, fsdp_min_size=4096)
    # layers/moe_every = 4 stacked spans must divide the stage axis (2);
    # pipeline_apply validates this at apply time
    module = GPT2Pipelined(vocab_size=50304, layers=16, dim=768, heads=12,
                           max_seq=1024, microbatches=8, mesh=mesh,
                           moe_experts=4, moe_every=4, schedule=schedule)
    batch = 16 * mesh.shape['data'] * mesh.shape['fsdp']
    tokens = jnp.asarray(rng.integers(0, 50257, (batch, 1024)), jnp.int32)
    optimizer = AdamW(lr=3e-4)
    state = init_state(module, optimizer, tokens[:1])
    state = PipelineParallel(
        stacked_rules=GPT2Pipelined.block_partition_rules(),
        fsdp=True).place(state, mesh)
    placed = jax.device_put(tokens, batch_sharding(mesh))
    step = build_train_step(flax_apply(module), WithAuxLoss(NextTokenLoss()),
                            optimizer, jit=False)

    single = jax.jit(lambda st: step(st, placed, placed)[0])
    flops = float(single.lower(state).compile().cost_analysis()['flops'])

    @partial(jax.jit, donate_argnums=0)
    def run(state):
        return jax.lax.fori_loop(
            0, steps, lambda i, st: step(st, placed, placed)[0], state)

    state = run(state)
    float(jax.tree.leaves(state.params)[0].sum())
    start = time.perf_counter()
    state = run(state)
    float(jax.tree.leaves(state.params)[0].sum())
    elapsed = time.perf_counter() - start

    steps_per_sec = steps / elapsed
    peak = peak_flops(devices[0])
    result = {'workload': 'composed_gpt2_pp_tp_fsdp_moe',
              'mesh': {axis: size for axis, size in mesh.shape.items()
                       if size > 1},
              'steps_per_sec': round(steps_per_sec, 3),
              'flops_per_step': float(flops),
              # per-chip MFU: executed FLOPs over every chip's peak
              'mfu': round(flops * steps_per_sec / (peak * len(devices)), 4)}
    print(json.dumps(result))


rng = np.random.default_rng(0)

# ladder row 2: the tinysys-equivalent MNIST classifier (MLP 256/128)
images = jnp.asarray(rng.normal(size=(64, 28, 28)), jnp.float32)
labels = jnp.asarray(rng.integers(0, 10, (64,)), jnp.int32)
measure('classifier_mlp_bs64', MLP(features=(256, 128), classes=10),
        images, labels, steps=200)

# ladder row 3: ResNet-50 at 224^2, bf16 NHWC, bs 64
images = jnp.asarray(rng.normal(size=(64, 224, 224, 3)), jnp.bfloat16)
labels = jnp.asarray(rng.integers(0, 1000, (64,)), jnp.int32)
measure('resnet50_224_bs64', ResNet(), images, labels, steps=30)

# composed-mesh row: dp x fsdp x tp x stage, all four overlap arms on
# (the >= 0.60-MFU target row — skips cleanly off-pod)
composed_row()
