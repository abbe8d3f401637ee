"""The serving engine's fused decode step over the paged KV pool.

The flax decode path dispatches ~6 XLA ops per matrix param per token
step and streams every weight at the tree's storage width.
:func:`build_fused_paged_step` is the alternative
:class:`tpusystem.serve.Engine` runs under ``decode_impl='fused'``: one
hand-rolled GPT-2 ``[rows, 1]`` token-step whose four per-layer matmuls
run through the Pallas decode kernels
(:mod:`tpusystem.ops.pallas.decode_matmul`) — the ``[rows, dim]``
activation resident in VMEM, weights streamed tile-by-tile, int8/fp8
tiles dequantized in-kernel against their per-channel scales (so
``stream_dtype='int8'|'fp8'`` keeps its narrow HBM traffic inside the
step instead of being hoisted into a wide copy), and the fc→gelu→proj
pair fused into ONE kernel whose hidden activation never exists in HBM.
Its cache read is one Pallas kernel
(:func:`tpusystem.ops.pallas.paged_attention.paged_decode_attention`)
that walks each row's own block-table columns up to its cursor and
attends over the pool where it lies — no window gather, no
``lax.switch`` — and the step's K/V scatter updates the donated pool in
place.

Contract: **the same tokens as the engine's flax paged step.** The step
math mirrors ``GPT2.__call__`` in decode mode op for op — f32 layernorms
(flax fast-variance form), f32-accumulated matmuls, the tied f32-logit
head — and prefill runs through the flax module itself, so the cache
layout and prompt logits are the flax path's own. Greedy decode is
token-exact against ``Engine(decode_impl='flax')`` in window-invariant
arithmetic (CPU f32; TPU at ``jax_default_matmul_precision='highest'``)
and matches within the platform's near-tie argmax tolerance at default
MXU precision — the speculative-verify caveat, same cause.

Scopes: the XLA work between the kernels runs under ``embed``, ``ln``,
``kv_write``, ``kv_read`` and ``head`` (``jax.named_scope``), the names
a device trace is read by.

Gate: the unrolled dense GPT-2 family on one device, with a pool the
paged-attention kernel can tile (:func:`fused_paged_reason` names the
exact refusal). Llama/MoE/scanned stacks and TP meshes serve through
the flax paged step under ``Engine(decode_impl='auto')`` and raise under
an explicit ``'fused'``.

Sampling: the fused step's contract ends at the logits it exposes —
:class:`tpusystem.serve.Engine` applies
:func:`tpusystem.train.generate.select_tokens` (seeded counter-based
sampling, temperature/top-k/top-p, grammar masks) to those logits
inside the SAME jitted program, so sampled decode through the fused
chain needs no gate here and stays bitwise-identical to the flax step's
sampled stream wherever greedy is token-exact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpusystem.ops.pallas import auto_interpret
from tpusystem.ops.pallas.decode_matmul import decode_ffn, decode_matmul
from tpusystem.ops.pallas.paged_attention import (paged_decode_attention,
                                                  paged_plan)
from tpusystem.ops.precision import head_logits


def fused_paged_reason(decoder) -> str | None:
    """Why the serving engine's fused PAGED token-step
    (:func:`build_fused_paged_step`) cannot run this decode clone, or
    ``None`` when it can. The paged step owns per-row cursors and the
    block-table scatter write — the gates are the step-math ones (GPT-2
    dense, unrolled: Llama, MoE stacks and the latent-attention DeepSeekV2
    decoder take the flax paged step, and the message says why), the TP
    mesh (no ring arms yet) and the pool shapes the paged-attention kernel
    can tile."""
    from tpusystem.models.gpt2 import GPT2
    mesh = getattr(decoder, 'mesh', None)
    if mesh is not None and dict(getattr(mesh, 'shape', {})).get(
            'model', 1) > 1:
        return ('the fused paged step has no ring arms — its Pallas '
                'matmuls are single-device; under a TP mesh '
                "decode_impl='auto' serves through the sharded flax "
                'paged step (token-exact vs single-device)')
    if not isinstance(decoder, GPT2):
        reason = ('the fused paged step implements the GPT2 family only '
                  f'(got {type(decoder).__name__})')
        if hasattr(decoder, 'kv_rank'):
            # a latent-attention decoder (DeepSeekV2): nothing of the
            # chain fits, so 'auto' serves it through the flax paged step
            reason += (": a latent-attention decoder's pool row is one "
                       'latent [kv_rank + rope], not a key and a value per '
                       'head (the paged-attention kernel reads those), its '
                       'attention is absorbed into the query and the '
                       'output, and its FFN is a gated grouped expert '
                       "product — the engine's flax paged step serves it")
        return reason
    if decoder.scan_layers:
        return ('scan_layers stacks params under a leading layer dim the '
                'fused per-layer sweep does not walk')
    if decoder.moe_experts:
        return ('MoE blocks route through expert dispatch, not the FFN '
                "chain — the engine's flax paged step serves MoE (full-"
                'capacity decode dispatch), this fused chain does not')
    if not decoder.decode_pages:
        return ('no decode_pages on this clone — the paged step needs the '
                "serving engine's block-pool cache layout")
    block = decoder.decode_pages[1]
    head_dim = decoder.dim // decoder.heads
    if paged_plan(decoder.heads, head_dim, block, decoder.max_seq // block,
                  decoder.dtype, auto_interpret(None)) is None:
        return (f'the paged-attention kernel cannot tile heads='
                f'{decoder.heads} head_dim={head_dim} block_size={block} '
                f'{jnp.dtype(decoder.dtype).name} on the TPU (the pool\'s '
                'minor dim must fill 128 lanes and a block whole sublane '
                'tiles: 16 positions of bf16, 8 of f32)')
    return None


def _layernorm(x, scale, bias):
    """flax ``nn.LayerNorm(dtype=float32)`` numerics: f32, fast variance
    (``E[x^2] - E[x]^2``), epsilon 1e-6."""
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True) - jnp.square(mean)
    inv = jax.lax.rsqrt(var + 1e-6)
    return (x - mean) * inv * scale + bias


def build_fused_paged_step(decoder):
    """The serving engine's fused ``[rows, 1]`` token-step over the
    paged KV pool: the GPT-2 layer chain (Pallas
    ``decode_matmul``/``decode_ffn``, in-kernel int8/fp8 dequant, f32
    layernorms, tied f32-logit head) with per-row cursors, the
    block-table scatter write (in place on the donated pool), and the
    Pallas paged-attention kernel
    (:func:`tpusystem.ops.pallas.paged_attention.paged_decode_attention`)
    that walks each row's own table columns ``0 … cursor // block`` over
    the pool where it lies: no window is gathered, no bucket is switched
    on, and a row pays for the positions it holds, not for the deepest
    row's. Returns ``step(params, cache, tokens) ->
    (logits, new_cache)`` where ``cache`` is the engine's paged cache
    tree (per-layer ``key``/``value`` pools + ``table``/``index``,
    model-level ``position``); cursor leaves in the returned cache are
    the input's — the engine's post-step ``rewind`` owns advancement.
    Token-exact vs the flax paged step in window-length-invariant
    arithmetic.

    The XLA work between the kernels carries ``jax.named_scope`` names a
    device trace is read by (``embed``, ``ln``, ``kv_write``, ``head``),
    and ``kv_read`` holds the paged-attention kernel, so the time the step
    spends reading keys and values stays under the scope that always read
    it. The TPU compiler names a Mosaic call after its innermost scope:
    the kernel brings its own (``paged_decode_attention
    [tpu_custom_call]`` in a trace), and no scope encloses
    ``decode_matmul`` or ``decode_ffn`` — the weight chain's trace name
    is the jitted step's."""
    reason = fused_paged_reason(decoder)
    if reason is not None:
        raise ValueError(f'fused paged step unsupported: {reason}')
    layers, heads = decoder.layers, decoder.heads
    dim, max_seq = decoder.dim, decoder.max_seq
    head_dim = dim // heads
    compute = jnp.dtype(decoder.dtype)
    num_blocks, block = decoder.decode_pages
    max_blocks = max_seq // block

    def step(params, cache, tokens):
        rows = tokens.shape[0]
        cursor = cache['h_0']['attn']['index']               # [rows]
        wte = params['wte']['embedding']
        wpe = params['wpe']['embedding']
        with jax.named_scope('embed'):
            embedded = (jnp.asarray(wte)[tokens].astype(jnp.float32)
                        + jnp.asarray(wpe)[cache['position']].astype(
                            jnp.float32))
            hidden = embedded.astype(compute)
        # physical token slot of this step's position through each row's
        # table — past-capacity clamps onto the last (trash) column,
        # exactly paged_attention's write discipline
        logical = jnp.minimum(cursor // block, max_blocks - 1)
        pools = {}                       # ('h_i', 'key'|'value') -> pool
        for index in range(layers):
            layer = params[f'h_{index}']
            with jax.named_scope('ln'):
                normed = _layernorm(hidden, layer['ln_1']['scale'],
                                    layer['ln_1']['bias']).astype(compute)
            attn = layer['attn']
            qkv = decode_matmul(normed, attn['qkv']['kernel'],
                                attn['qkv']['bias'])
            query, key, value = jnp.split(qkv, 3, axis=-1)
            entry = cache[f'h_{index}']['attn']
            table = entry['table']
            with jax.named_scope('kv_write'):
                physical = jnp.take_along_axis(table, logical[:, None],
                                               axis=1)[:, 0]
                slots = physical * block + cursor % block    # [rows]
                key_pool = entry['key'].at[slots].set(
                    key.astype(entry['key'].dtype))
                value_pool = entry['value'].at[slots].set(
                    value.astype(entry['value'].dtype))
            pools[(f'h_{index}', 'key')] = key_pool
            pools[(f'h_{index}', 'value')] = value_pool
            with jax.named_scope('kv_read'):
                context = paged_decode_attention(
                    query.reshape(rows, heads, head_dim), key_pool,
                    value_pool, table, cursor, block=block)
            attended = decode_matmul(context.reshape(rows, dim),
                                     attn['out']['kernel'],
                                     attn['out']['bias'])
            hidden = hidden + attended
            with jax.named_scope('ln'):
                normed = _layernorm(hidden, layer['ln_2']['scale'],
                                    layer['ln_2']['bias']).astype(compute)
            hidden = hidden + decode_ffn(
                normed, layer['fc']['kernel'], layer['fc']['bias'],
                layer['proj']['kernel'], layer['proj']['bias'],
                activation=jax.nn.gelu)
        with jax.named_scope('ln'):
            final = _layernorm(hidden, params['ln_f']['scale'],
                               params['ln_f']['bias'])
        with jax.named_scope('head'):
            table = jnp.asarray(wte).astype(compute)
            logits = head_logits(final.astype(compute), table, tied=True)

        def fix(path, leaf):
            if path[-1] in (jax.tree_util.DictKey('key'),
                            jax.tree_util.DictKey('value')):
                return pools[(path[0].key, path[-1].key)]
            return leaf
        return logits, jax.tree_util.tree_map_with_path(fix, cache)

    return step
