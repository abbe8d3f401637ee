"""Plain DeepSeek-V2: the forward pass in ``jax.numpy``, float32, every
matrix product under ``jax.default_matmul_precision('highest')``.

The yardstick ``correct`` is decided against for the ``deepseek_v2`` family.
No kernels, no cache, no batching, no sort and no grouping; it imports
nothing of ``tpusystem/`` and is handed only leaves the benchmark made from
the seed (the bfloat16 values, widened). It follows the published model
(https://huggingface.co/deepseek-ai/DeepSeek-V2: ``config.json`` and
``modeling_deepseek.py``):

* pre-RMSNorm blocks (weight only), residual around the mixer and the FFN,
  a final RMSNorm, an untied head, no biases;
* **MLA, expanded**: ``c_q = norm(h W_qa)``, ``q = c_q W_qb`` per head
  ``[q_nope ; q_rope]``; ``[c_kv ; k_r] = h W_kva``, ``c_kv = norm(c_kv)``,
  ``[k_nope ; v]`` per head ``= c_kv W_kvb``; one rotated ``k_rope`` shared
  by all heads; scores ``(q_nope·k_nope + q_rope·k_rope)·s``, causal softmax,
  ``·v``, ``W_o``; ``s = (nope + rope)^-1/2 · m²``, ``m = 0.1 ·
  mscale_all_dim · ln(factor) + 1``;
* **YaRN** on the rope dims: ``f_i = theta^(-2i/rope)``, ``g_i = f_i /
  factor``, ``inv_freq = g·ramp + f·(1 - ramp)`` with the ramp between
  ``low = ⌊c(beta_fast)⌋`` and ``high = ⌈c(beta_slow)⌉``;
* **expert layers**: ``p = softmax(h W_r)`` over all experts, the best
  ``topk_group`` of ``n_group`` groups by their largest member, the others'
  scores zeroed, the ``k`` largest left, weights ``routed_scaling_factor ·
  p`` not renormalised, plus the shared experts; layer 0 the same gated MLP
  at the dense width.

Departures, each also in the configuration's ``assumed`` or ``deployment``:

* **The share.** ``held = (start, count)``: the router scores every expert,
  and the sum runs over the chosen experts in ``start .. start + count - 1``
  only (a loop over those experts with a mask); what the others would have
  added is left out, as in the program. ``held=None`` is the uncut layer.
* **The rope pairing** is the published one, dims ``(2j, 2j+1)`` turning at
  ``inv_freq[j]``; the rotated vector is left interleaved where the
  published code moves the pairs to the two halves first. Scores are sums
  over the pairs and do not see the order.
* **Given routing.** A pass may be handed the experts a served program
  gave every position (``routing``): it then goes through those experts, at
  weights from its own scores, and counts where its own choice was another.
  A choice of expert that flips on a rounding of the router's input is then
  in neither side of a comparison; everything else still is. Without
  ``routing`` the choice is the reference's own everywhere.
* ``seq_aux`` and the balance losses are training's and are left out.
* The vocabulary is the slice the table and the head hold.

One layer's leaves are made, used for every sequence and freed before the
next (``leaves_of``): an expert layer at the published widths is 4.6 GB in
float32. Queries go through attention in blocks so that the scores fit.

``precision`` and ``bits`` are the control's levers: ``'bfloat16'`` rounds
both operands of every matrix product to bfloat16 first (the product still
accumulates in float32); ``narrow`` rounds the layers' matrices to ``bits``
per weight with one scale per output channel.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

PRECISIONS = ('float32', 'bfloat16')
QUERY_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes the arithmetic needs, under the published names."""
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed: int                  # the router's width
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    rope_factor: float
    rope_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    held: tuple | None = None      # (first expert held, how many)


def _rounded(x, precision: str):
    if precision == 'float32':
        return x
    if precision == 'bfloat16':
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(f'unknown precision {precision!r}; one of {PRECISIONS}')


def _product(spec: str, a, b, precision: str):
    with jax.default_matmul_precision('highest'):
        return jnp.einsum(spec, _rounded(a, precision), _rounded(b, precision))


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * weight


def silu(x):
    return x * jax.nn.sigmoid(x)


# ------------------------------------------------------------------- YaRN

def yarn_range(model: Model) -> tuple[int, int]:
    """``(low, high)``: 10 and 23 at the published values."""
    dim = model.qk_rope_head_dim

    def pair(turns: float) -> float:
        return (dim * math.log(model.rope_original / (turns * 2 * math.pi))
                / (2 * math.log(model.rope_theta)))
    return (max(math.floor(pair(model.beta_fast)), 0),
            min(math.ceil(pair(model.beta_slow)), dim - 1))


def yarn_inv_freq(model: Model):
    dim = model.qk_rope_head_dim
    f = model.rope_theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    low, high = yarn_range(model)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (f / model.rope_factor) * ramp + f * (1.0 - ramp)


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(model: Model) -> float:
    """0.11472 at the published values."""
    scale = (model.qk_nope_head_dim + model.qk_rope_head_dim) ** -0.5
    if model.mscale_all_dim:
        scale *= _mscale(model.rope_factor, model.mscale_all_dim) ** 2
    return scale


def rotate(x, positions, model: Model):
    """``x [..., seq, rope]`` with pairs ``(2j, 2j+1)`` turned by
    ``positions · inv_freq[j]``; ``positions [seq]``."""
    angles = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(model)
    spread = (_mscale(model.rope_factor, model.mscale)
              / _mscale(model.rope_factor, model.mscale_all_dim))
    cos, sin = jnp.cos(angles) * spread, jnp.sin(angles) * spread
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


# -------------------------------------------------------------- attention

def latent_attention(h, p, model: Model, precision: str):
    """Expanded multi-head latent attention over one sequence ``[seq, d]``."""
    seq = h.shape[0]
    heads, nope, rope = (model.num_attention_heads, model.qk_nope_head_dim,
                         model.qk_rope_head_dim)
    rank, v_dim = model.kv_lora_rank, model.v_head_dim
    positions = jnp.arange(seq)
    c_q = rms_norm(_product('sd,dr->sr', h, p['q_a'], precision),
                   p['q_norm'], model.rms_norm_eps)
    q = _product('sr,re->se', c_q, p['q_b'], precision).reshape(
        seq, heads, nope + rope)
    down = _product('sd,dr->sr', h, p['kv_a'], precision)
    c_kv = rms_norm(down[:, :rank], p['kv_norm'], model.rms_norm_eps)
    k_rope = rotate(down[:, rank:], positions, model)            # [seq, rope]
    q_rope = jnp.swapaxes(rotate(jnp.swapaxes(q[..., nope:], 0, 1),
                                 positions, model), 0, 1)
    up = _product('sr,re->se', c_kv, p['kv_b'], precision).reshape(
        seq, heads, nope + v_dim)
    k_nope, value = up[..., :nope], up[..., nope:]
    scale = softmax_scale(model)
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    def attend(start):
        qn = jax.lax.dynamic_slice_in_dim(q[..., :nope], start, block)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, block)
        scores = (_product('qhd,khd->hqk', qn, k_nope, precision)
                  + _product('qhd,kd->hqk', qr, k_rope, precision)) * scale
        causal = (start + jnp.arange(block))[:, None] >= positions[None, :]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _product('hqk,khd->qhd', weights, value, precision)

    mixed = jax.lax.map(attend, jnp.arange(0, seq, block))
    return _product('se,ed->sd', mixed.reshape(seq, heads * v_dim), p['out'],
                    precision)


# ---------------------------------------------------------------- the FFNs

def gated_mlp(h, gate, up, down, precision: str):
    return _product('sw,wd->sd',
                    silu(_product('sd,dw->sw', h, gate, precision))
                    * _product('sd,dw->sw', h, up, precision),
                    down, precision)


def _first_k(scores, k: int):
    """``[seq, n] -> bool``: the ``k`` largest of each row, ties to the
    lower index (an entry is taken if fewer than ``k`` entries beat it)."""
    n = scores.shape[-1]
    earlier = jnp.arange(n)[None, None, :] < jnp.arange(n)[None, :, None]
    beaten_by = jnp.sum(
        (scores[:, None, :] > scores[:, :, None])
        | ((scores[:, None, :] == scores[:, :, None]) & earlier), axis=-1)
    return beaten_by < k


def route(h, router, model: Model, precision: str):
    """Group-limited greedy top-k over **all** experts: ``(chosen [seq,
    experts] bool, scores [seq, experts])``."""
    seq = h.shape[0]
    scores = jax.nn.softmax(_product('sd,de->se', h, router, precision),
                            axis=-1)
    per_group = model.n_routed // model.n_group
    group_score = jnp.max(scores.reshape(seq, model.n_group, per_group),
                          axis=-1)
    keep = _first_k(group_score, model.topk_group)
    limited = jnp.where(jnp.repeat(keep, per_group, axis=1), scores, 0.0)
    return _first_k(limited, model.num_experts_per_tok), scores


def expert_layer(h, p, model: Model, precision: str, given=None):
    """``Σ_e w_e E_e(h)`` over the chosen experts that are held, plus the
    shared experts; and at which positions the choice was the reference's
    own. ``given [seq, k]`` (experts by index, -1 where nothing is given)
    puts a served program's choice in the place of the reference's own at
    the positions it covers: the scores, and so the weights, stay the
    reference's."""
    chosen, scores = route(h, p['router'], model, precision)
    own = jnp.ones(h.shape[0], bool)
    if given is not None:
        forced = jnp.any(given[:, :, None] == jnp.arange(model.n_routed),
                         axis=1)
        covered = given[:, 0] >= 0
        own = ~covered | jnp.all(forced == chosen, axis=-1)
        chosen = jnp.where(covered[:, None], forced, chosen)
    start, count = model.held if model.held is not None \
        else (0, model.n_routed)
    weights = jnp.where(chosen, model.routed_scaling_factor * scores, 0.0)
    weights = jax.lax.dynamic_slice_in_dim(weights, start, count, axis=1)

    def one(total, expert):
        gate, up, down, weight = expert
        return total + weight[:, None] * gated_mlp(h, gate, up, down,
                                                  precision), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (p['gate'], p['up'], p['down'], weights.T))
    return routed + gated_mlp(h, p['shared_gate'], p['shared_up'],
                              p['shared_down'], precision), own


@functools.partial(jax.jit, static_argnames=('model', 'precision'))
def layer(x, p, given=None, *, model: Model, precision: str = 'float32'):
    """One block on one sequence ``[seq, d]``: ``(x, own [seq])``; ``own``
    says where an expert layer's choice was the reference's own (everywhere
    in a dense layer, and with nothing ``given``)."""
    x = x + latent_attention(rms_norm(x, p['attn_norm'], model.rms_norm_eps),
                             p, model, precision)
    h = rms_norm(x, p['ffn_norm'], model.rms_norm_eps)
    if 'router' in p:
        out, own = expert_layer(h, p, model, precision, given)
        return x + out, own
    return (x + gated_mlp(h, p['gate'], p['up'], p['down'], precision),
            jnp.ones(x.shape[0], bool))


@functools.partial(jax.jit, static_argnames=('eps', 'precision'))
def head(x, final_norm, table, *, eps: float, precision: str = 'float32'):
    return _product('sd,dv->sv', rms_norm(x, final_norm, eps), table,
                    precision)


def narrow(leaves: dict, bits: int) -> dict:
    """A layer's leaves with every matrix but the router rounded to ``bits``
    per weight, symmetric, one scale per output channel; norms untouched
    (what a weight-streaming server narrows)."""
    qmax = float(2 ** (bits - 1) - 1)

    def rounded(name, leaf):
        if leaf.ndim < 2 or name == 'router':
            return leaf
        absmax = jnp.max(jnp.abs(leaf), axis=-2, keepdims=True)
        scale = jnp.where(absmax > 0, absmax, qmax) / qmax
        return jnp.round(jnp.clip(leaf / scale, -qmax, qmax)) * scale

    return {name: rounded(name, leaf) for name, leaf in leaves.items()}


# ------------------------------------------------------------ whole passes

def forward(sequences: list, leaves_of, layers: int, model: Model, *,
            precision: str = 'float32', bits: int | None = None,
            routing: list | None = None):
    """The hidden states after the last block for every sequence of
    ``sequences`` (``[seq]`` int32 each), and how many expert-layer choices
    of each differed from the reference's own. ``leaves_of('top')`` gives
    ``{'embedding', 'final_norm', 'lm_head'}``, ``leaves_of(i)`` layer
    ``i``'s leaves; one layer's are alive at a time. ``routing[row]`` is
    ``[seq, expert layers, k]``: the experts a served program gave each
    position in each expert layer, first expert layer first, -1 where it
    served nothing (padding); the pass then goes through those experts
    (:func:`expert_layer`)."""
    table = leaves_of('top')['embedding']
    hidden = [table[tokens] for tokens in sequences]
    del table
    differed = [0] * len(sequences)
    expert_layers = 0
    for index in range(layers):
        leaves = leaves_of(index)
        if bits:
            leaves = narrow(leaves, bits)
        for row, x in enumerate(hidden):
            given = None
            if routing is not None and 'router' in leaves:
                given = jnp.asarray(routing[row][:, expert_layers],
                                    jnp.int32)
            hidden[row], own = layer(x, leaves, given, model=model,
                                     precision=precision)
            differed[row] += int(jnp.sum(~own))
        expert_layers += 'router' in leaves
        del leaves
    return hidden, differed


def logits(sequences: list, leaves_of, layers: int, model: Model, **levers):
    """``[seq, vocabulary rows]`` for every sequence (tests, small sizes)."""
    hidden, _ = forward(sequences, leaves_of, layers, model, **levers)
    top = leaves_of('top')
    return [head(x, top['final_norm'], top['lm_head'],
                 eps=model.rms_norm_eps,
                 precision=levers.get('precision', 'float32'))
            for x in hidden]


@functools.partial(jax.jit, static_argnames=('eps',))
def _gaps(x, lowered, tokens, final_norm, table, *, eps: float):
    scores = head(x, final_norm, table, eps=eps)[:-1]
    if lowered is None:
        first = tokens[1:]
    else:
        first = jnp.argmax(head(lowered, final_norm, table, eps=eps,
                                precision='bfloat16')[:-1], axis=-1)
    chosen = jnp.take_along_axis(scores, first[:, None], axis=-1)[:, 0]
    return jnp.max(scores, axis=-1) - chosen


def served_gaps(sequences: list, leaves_of, layers: int, model: Model, *,
                routing: list | None = None,
                control_bits: int | None = None):
    """For every sequence: at each position but the last, how far the logit
    of the token that follows lies below the reference's best (``[seq -
    1]``), and how many expert-layer choices ``routing`` changed. With
    ``control_bits`` the gap is the control's instead: that of the token
    which the reference with its layers' matrices at that many bits, under
    bfloat16 products, puts first. Both passes go through the experts
    ``routing`` gives (:func:`forward`), so that neither reading holds a
    choice of expert that flipped."""
    hidden, differed = forward(sequences, leaves_of, layers, model,
                               routing=routing)
    lowered = [None] * len(sequences)
    if control_bits:
        lowered, _ = forward(sequences, leaves_of, layers, model,
                             precision='bfloat16', bits=control_bits,
                             routing=routing)
    top = leaves_of('top')
    return [(_gaps(x, low, tokens, top['final_norm'], top['lm_head'],
                   eps=model.rms_norm_eps), changed)
            for x, low, tokens, changed
            in zip(hidden, lowered, sequences, differed)]
