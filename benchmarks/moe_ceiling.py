"""MoE whole-model ceiling: decompose the 0.416 active-MFU row.

BASELINE.md's MoE whole-model row (GPT-2 125M body, 8 experts / top-2
every second block, b16 s1024, sparse dispatch) is the one measured row
under the 0.50 north-star without a ceiling argument. This benchmark
times every phase of the MoE layer *at the whole-model row's shapes*
(dim 768, hidden 3072, tokens 16384, capacity 5120), fwd+bwd, with the
conv_ceiling data-chained discipline (each rep folds a scalar of the
phase's gradient back into the carried input, so neither the forward nor
any gradient is hoisted or dead-code-eliminated):

  router     f32 logits matmul + softmax + top_k + renormalize
  seating    the integer sort/seat machinery of route_top_k_sparse
  dispatch   token-row gather + scatter into the [E*C, D] expert buffer
  expert_ffn the per-expert ecd,edh/ech,ehd einsum pair (the MXU work)
  combine    buffer gather + weighted scatter-add back to token order
  fused      the Pallas grouped gather-matmul pair: dispatch riding the
             up-projection's loads, the weighted combine riding the
             down-projection's epilogue (no standalone row movement)
  moe_layer  the full MoEMLP, all three sparse impls
             (scatter / gather / fused — the three-way table)
  dense_ffn  the fc/gelu/proj block at the same token count (reference)

`python benchmarks/moe_ceiling.py [whole [scatter|gather|fused]]` —
`whole` additionally re-measures the end-to-end 323M-param train step
(the BASELINE row) with the chosen single-shard row movement.

Accounting note: active-MFU charges k=2 experts' FLOPs per token, but
the capacity-factor buffer executes k*cf = 2.5 experts' worth — the FFN
phase alone cannot exceed k/(k*cf) = 0.80 of the matmul rate in
active-FLOPs terms. That structural factor plus the measured routing /
dispatch / combine time IS the ceiling this file pins.
"""

from __future__ import annotations

import sys
sys.path.insert(0, str(__import__('pathlib').Path(__file__).parent.parent))

import json
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from bench import materialize as _materialize, peak_flops

DIM, RATIO, EXPERTS, K, CF = 768, 4, 8, 2, 1.25
TOKENS = 16 * 1024                       # b16 s1024
HIDDEN = RATIO * DIM
REPS = 50


def _chain_scalar(tree):
    """One element of every leaf, summed — the data-dependency probe."""
    total = jnp.float32(0)
    for leaf in jax.tree.leaves(tree):
        total = total + leaf.reshape(-1)[0].astype(jnp.float32)
    return total


def _has_float(tree) -> bool:
    return any(jnp.issubdtype(leaf.dtype, jnp.inexact)
               for leaf in jax.tree.leaves(tree))


def _fold(tree, feedback):
    return jax.tree.map(
        lambda leaf: leaf + feedback.astype(leaf.dtype)
        if jnp.issubdtype(leaf.dtype, jnp.inexact) else leaf, tree)


def time_fwd_bwd(fn, *args) -> float:
    """Seconds per fwd+bwd of ``fn(*args) -> array`` over REPS chained
    iterations. Every float arg (pytrees allowed) gets its gradient
    computed and folded into the carry (no DCE), the loss feeds the next
    iteration's inputs (no hoisting), and the loss is a *sum of squares*
    so the output cotangent is data-dependent — a constant cotangent
    lets XLA collapse backward matmuls of broadcast rows to O(D*H)
    (measured: 'impossible' >1 MFU on the FFN phase with a linear
    loss)."""
    grad_argnums = tuple(i for i, a in enumerate(args) if _has_float(a))

    def loss_fn(*a):
        out = fn(*a)
        return jnp.sum(jnp.square(out.astype(jnp.float32))) * 1e-9

    vg = jax.value_and_grad(loss_fn, argnums=grad_argnums)

    def body(_, carry):
        loss, grads = vg(*carry)
        feedback = ((loss + _chain_scalar(grads)) * 1e-7)
        return tuple(
            _fold(a, feedback) if i in grad_argnums else a
            for i, a in enumerate(carry))

    run = jax.jit(lambda *a: jax.lax.fori_loop(0, REPS, body, a))
    out = run(*args)
    _materialize(out)
    t0 = time.perf_counter()
    out = run(*args)
    _materialize(out)
    return (time.perf_counter() - t0) / REPS


def time_fwd(fn, *args) -> float:
    """Forward-only variant (integer phases have no gradient)."""
    def body(_, carry):
        out = fn(*carry)
        feedback = _chain_scalar(out) * 1e-7
        return tuple(a + feedback.astype(a.dtype)
                     if jnp.issubdtype(a.dtype, jnp.inexact) else a
                     for a in carry)
    run = jax.jit(lambda *a: jax.lax.fori_loop(0, REPS, body, a))
    out = run(*args)
    _materialize(out)
    t0 = time.perf_counter()
    out = run(*args)
    _materialize(out)
    return (time.perf_counter() - t0) / REPS


def phases() -> None:
    from tpusystem.ops.moe import (MoEMLP, expert_capacity,
                                   route_top_k_sparse)

    peak = peak_flops(jax.devices()[0])
    rng = np.random.default_rng(0)
    capacity = expert_capacity(TOKENS, EXPERTS, K, CF)
    flat = jnp.asarray(rng.normal(size=(TOKENS, DIM)) * 0.1, jnp.bfloat16)
    router = jnp.asarray(rng.normal(size=(DIM, EXPERTS)) * 0.02, jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(EXPERTS, DIM, HIDDEN)) * 0.02,
                     jnp.float32)
    b1 = jnp.zeros((EXPERTS, HIDDEN), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(EXPERTS, HIDDEN, DIM)) * 0.02,
                     jnp.float32)
    b2 = jnp.zeros((EXPERTS, DIM), jnp.float32)

    def report(tag, seconds, flops=None, note=None):
        entry = {'phase': tag, 'us': round(seconds * 1e6, 1)}
        if flops:
            entry['mfu'] = round(flops / seconds / peak, 3)
        if note:
            entry['note'] = note
        print(json.dumps(entry))
        return seconds

    # --- router: f32 matmul + softmax + top_k + renorm ------------------
    def router_phase(flat, router):
        logits = flat.astype(jnp.float32) @ router
        gates = jax.nn.softmax(logits)
        top_gates, _ = jax.lax.top_k(gates, K)
        return top_gates / (jnp.sum(top_gates, -1, keepdims=True) + 1e-9)

    t_router = report('router', time_fwd_bwd(router_phase, flat, router),
                      flops=3 * 2 * TOKENS * DIM * EXPERTS)

    # --- seating: integer sort/rank machinery ---------------------------
    gates = jax.nn.softmax(flat.astype(jnp.float32) @ router)

    def seating_phase(gates):
        token_ids, slots, weights, fraction = route_top_k_sparse(
            gates, K, capacity)
        # fold ints through float so the chain probe has a float leaf
        return (weights + slots.astype(jnp.float32) * 1e-12,)

    t_seating = report('seating', time_fwd(seating_phase, gates))

    token_ids, slots, weights, _ = route_top_k_sparse(gates, K, capacity)

    # --- dispatch: gather rows + scatter into the expert buffer ---------
    def dispatch_phase(flat):
        rows = flat[token_ids]
        buffer = jnp.zeros((EXPERTS * capacity, DIM), flat.dtype)
        return buffer.at[slots].set(rows, mode='drop')

    t_dispatch = report('dispatch[scatter]', time_fwd_bwd(dispatch_phase, flat),
                        note='gather[kN,D] + row scatter into [E*C,D]')

    # --- the scatter-free custom_vjp alternative ------------------------
    from tpusystem.ops.moe import (_gather_combine, _gather_dispatch,
                                   _invert_seating)
    slot_asg, slot_token, slots_by_choice = _invert_seating(
        slots, K, TOKENS, EXPERTS * capacity)

    t_dispatch_g = report(
        'dispatch[gather]',
        time_fwd_bwd(lambda f: _gather_dispatch(f, slot_token,
                                                slots_by_choice), flat),
        note='inverse-map gather; bwd = k gathers + sum')

    # --- expert FFN: the MXU phase --------------------------------------
    expert_in = dispatch_phase(flat).reshape(EXPERTS, capacity, DIM)

    def ffn_phase(expert_in, w1, b1, w2, b2):
        compute = jnp.bfloat16
        grown = jnp.einsum('ecd,edh->ech', expert_in, w1.astype(compute))
        grown = nn.gelu(grown + b1[:, None].astype(compute))
        return (jnp.einsum('ech,ehd->ecd', grown, w2.astype(compute))
                + b2[:, None].astype(compute))

    ffn_flops = 3 * 2 * 2 * EXPERTS * capacity * DIM * HIDDEN  # fwd+bwd
    t_ffn = report('expert_ffn',
                   time_fwd_bwd(ffn_phase, expert_in, w1, b1, w2, b2),
                   flops=ffn_flops,
                   note=f'[{EXPERTS},{capacity},{DIM}]x[{EXPERTS},{DIM},'
                        f'{HIDDEN}] pair')

    # --- combine: buffer gather + weighted scatter-add ------------------
    buffer = ffn_phase(expert_in, w1, b1, w2, b2).reshape(
        EXPERTS * capacity, DIM)

    def combine_phase(buffer, weights):
        gathered = buffer.at[slots].get(mode='fill', fill_value=0)
        return jnp.zeros((TOKENS, DIM), buffer.dtype).at[token_ids].add(
            gathered * weights[:, None].astype(buffer.dtype))

    t_combine = report('combine[scatter]',
                       time_fwd_bwd(combine_phase, buffer, weights),
                       note='gather[kN,D] + scatter-add to token order')

    t_combine_g = report(
        'combine[gather]',
        time_fwd_bwd(lambda b, w: _gather_combine(b, w, slots_by_choice,
                                                  slot_token, slot_asg),
                     buffer, weights),
        note='k gathers + weighted sum; bwd gathers only')

    # --- fused kernel phases: the data movement rides the matmuls -------
    # (forward-only rows: the kernels' backwards ARE the same kernels with
    # swapped operands, measured through moe_layer[fused] below. MFU here
    # charges the executed matmul FLOPs — compare dispatch[gather] +
    # half of expert_ffn against dispatch+up[fused]. Seating arrays are
    # the slot_asg/slot_token/slots_by_choice computed above, so the
    # fused rows measure exactly the seating the gather rows measure.)
    from tpusystem.ops.pallas.grouped_matmul import (gather_rows_matmul,
                                                     matmul_scatter_rows)

    clamped = jnp.minimum(slot_token, TOKENS - 1)
    valid = (slot_token < TOKENS).astype(jnp.float32)
    w_slot = weights.at[slot_asg].get(mode='fill', fill_value=0)
    w1c, b1c = w1.astype(jnp.bfloat16), b1.astype(jnp.bfloat16)
    w2c, b2c = w2.astype(jnp.bfloat16), b2.astype(jnp.bfloat16)

    up_flops = 2 * EXPERTS * capacity * DIM * HIDDEN
    t_fused_up = report(
        'dispatch+up_mm[fused]',
        time_fwd(lambda f: gather_rows_matmul(f, w1c, clamped, valid,
                                              rows_per_group=capacity),
                 flat),
        flops=up_flops,
        note='rows DMA from unpermuted tokens into the MXU tiles')

    grown = nn.gelu(dispatch_phase(flat).reshape(EXPERTS, capacity, DIM)
                    @ w1c + b1c[:, None]).reshape(EXPERTS * capacity, HIDDEN)

    t_fused_down = report(
        'down_mm+combine[fused]',
        time_fwd(lambda g: matmul_scatter_rows(
            g, w2c, b2c, slot_token, w_slot, TOKENS,
            rows_per_group=capacity)[0], grown),
        flops=up_flops,
        note='k-way weighted combine in the matmul epilogue (RMW rows)')

    # --- whole MoE layer, all three impls -------------------------------
    t_by_impl = {}
    for impl in ('scatter', 'gather', 'fused'):
        layer = MoEMLP(EXPERTS, k=K, mlp_ratio=RATIO, capacity_factor=CF,
                       dispatch='sparse', sparse_impl=impl)
        variables = layer.init(jax.random.PRNGKey(0), flat[:64])

        def layer_phase(flat, params, layer=layer):
            out, aux = layer.apply({'params': params}, flat)
            return out.astype(jnp.float32) + aux

        t_by_impl[impl] = report(
            f'moe_layer[{impl}]',
            time_fwd_bwd(layer_phase, flat, variables['params']))
    t_layer = min(t_by_impl.values())

    # --- dense FFN reference at the same token count --------------------
    wf = jnp.asarray(rng.normal(size=(DIM, HIDDEN)) * 0.02, jnp.float32)
    wp = jnp.asarray(rng.normal(size=(HIDDEN, DIM)) * 0.02, jnp.float32)

    def dense_phase(flat, wf, wp):
        compute = jnp.bfloat16
        grown = nn.gelu(flat @ wf.astype(compute))
        return grown @ wp.astype(compute)

    dense_flops = 3 * 2 * 2 * TOKENS * DIM * HIDDEN
    t_dense = report('dense_ffn', time_fwd_bwd(dense_phase, flat, wf, wp),
                     flops=dense_flops)

    overhead = t_layer - t_ffn
    active_ffn_flops = 3 * 2 * 2 * K * TOKENS * DIM * HIDDEN  # what MFU charges
    print(json.dumps({
        'summary': {
            'phase_sum_us': round((t_router + t_seating + t_dispatch
                                   + t_ffn + t_combine) * 1e6, 1),
            'layer_us_by_impl': {impl: round(t * 1e6, 1)
                                 for impl, t in t_by_impl.items()},
            'fused_up_us': round(t_fused_up * 1e6, 1),
            'fused_down_us': round(t_fused_down * 1e6, 1),
            'moe_layer_us': round(t_layer * 1e6, 1),
            'dense_ffn_us': round(t_dense * 1e6, 1),
            'layer_vs_dense': round(t_layer / t_dense, 2),
            'routing_overhead_pct': round(100 * overhead / t_layer, 1),
            'structural_cap': round(K / (K * CF), 3),
            'active_mfu_ceiling_ffn_only': round(
                active_ffn_flops / t_layer / peak_flops(jax.devices()[0]), 3),
        }}))


def whole_model(sparse_impl: str = 'gather') -> None:
    """Re-measure the BASELINE whole-model MoE row (323M / 153M active).

    ``python benchmarks/moe_ceiling.py whole [scatter|gather|fused]``
    selects the single-shard row movement (BASELINE.md compares the
    gather row against the fused grouped gather-matmul row)."""
    from tpusystem.models import GPT2
    from tpusystem.train import (AdamW, ChunkedNextTokenLoss, WithAuxLoss,
                                 build_train_step, flax_apply, init_state)

    batch, seq, steps = 16, 1024, 30
    module = GPT2(dropout=0.0, attention='flash', vocab_size=50304,
                  return_features=True, moe_experts=EXPERTS, moe_every=2,
                  moe_sparse_impl=sparse_impl)
    optimizer = AdamW(lr=3e-4, grad_clip=1.0)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 50257, (batch, seq)), jnp.int32)
    state = init_state(module, optimizer, tokens[:1, :8])
    step = build_train_step(flax_apply(module),
                            WithAuxLoss(ChunkedNextTokenLoss(chunks=8)),
                            optimizer, jit=False)

    @partial(jax.jit, donate_argnums=0)
    def run(state, tokens):
        return jax.lax.fori_loop(
            0, steps, lambda i, st: step(st, tokens, tokens)[0], state)

    state = run(state, tokens)
    float(jax.tree.leaves(state.params)[0].sum())
    t0 = time.perf_counter()
    state = run(state, tokens)
    float(jax.tree.leaves(state.params)[0].sum())
    elapsed = time.perf_counter() - t0

    params_count = sum(leaf.size for leaf in jax.tree.leaves(state.params))
    # active params: total minus the (experts - k) inactive experts' FFNs
    per_expert = DIM * HIDDEN * 2 + HIDDEN + DIM
    moe_layers = module.layers // 2
    active = params_count - moe_layers * (EXPERTS - K) * per_expert
    head_dim = module.dim // module.heads
    attention_flops = (12 * module.layers * module.heads * seq * seq
                       * head_dim * batch)
    step_flops = 6 * active * batch * seq + attention_flops
    mfu = step_flops * steps / elapsed / peak_flops(jax.devices()[0])
    print(json.dumps({
        'whole_model': {'sparse_impl': sparse_impl,
                        'params_m': round(params_count / 1e6, 1),
                        'active_m': round(active / 1e6, 1),
                        'steps_per_s': round(steps / elapsed, 2),
                        'tok_per_s': round(batch * seq * steps / elapsed),
                        'active_mfu': round(mfu, 4)}}))


if __name__ == '__main__':
    if 'whole' in sys.argv[1:]:
        impls = [a for a in sys.argv[1:]
                 if a in ('scatter', 'gather', 'fused')]
        whole_model(*impls[:1])
    else:
        phases()
