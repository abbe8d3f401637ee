"""Plain dots3: the forward pass in ``jax.numpy``, float32, every matrix
product under ``jax.default_matmul_precision('highest')``.

The yardstick ``correct`` is decided against for the ``dots3`` family. No
kernels, no cache, no batching; it imports nothing of ``tpusystem/`` and is
handed only leaves the benchmark made from the seed (the bfloat16 values,
widened). It follows the equations of the published configuration
(https://huggingface.co/dots-studio/dots3-note-prev, ``config.json``), with
the readings the configuration file lists under ``assumed``:

* pre-RMSNorm blocks (weight only), residual around the mixer and the FFN,
  a final RMSNorm, an untied head, no biases;
* **latent attention, expanded**, of two kinds by ``layer_types``: ``c_q =
  norm(y W_qa)``, ``q = (c_q W_qb) · sqrt(d / q_rank)`` per head ``[nope ;
  rope]``; ``[c ; k_r] = y W_kva``, ``c = norm(c) · sqrt(d / kv_rank)``,
  ``[k_nope ; v]`` per head ``= c W_kvb``; one rotated ``k_rope`` shared by
  every head; scores ``(q_nope·k_nope + q_rope·k_rope) · (nope +
  rope)^-1/2``; a sigmoid gate a head, ``g = sigmoid(y W_g)``, on each
  head's output before ``W_o``;
* **full layers**: the query at ``t`` attends only the ``min(topk, t + 1)``
  positions ``s <= t`` of largest index score ``I(t, s) = sum_j w_j
  relu(qI_j(t) · kI_s)``, ``qI_j = rope(c_q W_IQ)_j`` (the unscaled
  ``c_q``), ``kI = rope(LayerNorm(y W_IK))``, ``w = y W_w · (heads_I ·
  dim_I)^-1/2``, rope on the first ``rope`` of ``dim_I`` dims; the top-k is
  exact (``lax.top_k``'s k-th value, every score at or above it kept);
* **sliding layers**: the query at ``t`` attends ``t - window < s <= t``;
* rope: pairs ``(2i, 2i+1)`` turned by ``position · theta^(-2i / rope)``,
  each kind at its own ``theta`` (scores are sums over the pairs and do
  not see which dims are paired, so any pairing taken alike by query and
  key gives the same scores);
* **expert layers**: ``s = sigmoid(y W_r)`` over all experts, the ``k``
  largest of ``s + b`` chosen (``b``: the learned correction), weights
  ``scale · s_e / sum_chosen s``; SwiGLU experts; plus the shared expert;
  layer 0 the same gated MLP at the dense width.

Departures, each also in the configuration's ``assumed`` or ``deployment``:

* **The share.** ``held = (start, count)``: the router scores every expert,
  and the sum runs over the chosen experts in ``start .. start + count - 1``
  only; what the others would have added is left out, as in the program.
  ``held=None`` is the uncut layer.
* **Given routing.** A pass may be handed the experts a served program gave
  every position (``routing``): it then goes through those experts, at
  weights from its own scores renormalised over them, and counts where its
  own choice was another.
* **Given selections.** A pass may be handed the positions a served
  program's full layers selected at every position (``selections``): it
  then attends those, and counts the positions where its own selection
  (made as above) was another, either way (the count ``correct`` compares,
  ``chipbench/drivers/serve_sparse.py``). A near-tie of index scores at the
  ``topk``-th place flips on a rounding (and the seeded weights make the
  keys of one sequence alike, so such ties are many); one flipped position
  moves the attention's output as much as a rounding of every matrix.

One layer's leaves are made, used for every sequence and freed before the
next (``leaves_of``). Queries go through attention in blocks of
``QUERY_BLOCK`` (scores of ``[heads, block, seq]`` at most).

``precision`` and ``bits`` are the control's levers: ``'bfloat16'`` rounds
both operands of every matrix product to bfloat16 first (the product still
accumulates in float32); ``narrow`` rounds the layers' matrices to ``bits``
per weight with one scale per output channel.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

PRECISIONS = ('float32', 'bfloat16')
QUERY_BLOCK = 128
FULL = 'full_attention'


@dataclasses.dataclass(frozen=True)
class Attention:
    """One kind of attention layer's sizes (``index`` for a full layer:
    ``(heads, dim, topk, eps)``; ``window`` for a sliding one)."""
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    index: tuple | None = None
    window: int | None = None


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes the arithmetic needs."""
    layer_types: tuple
    full: Attention
    sliding: Attention
    hidden_size: int
    n_routed: int                  # the router's width
    num_experts_per_tok: int
    routed_scaling_factor: float
    rms_norm_eps: float
    lora_rescale: bool
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


def layer_norm(x, weight, bias, eps: float):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) \
        * weight + bias


def silu(x):
    return x * jax.nn.sigmoid(x)


def rotate(x, positions, theta: float):
    """``x [..., seq, rope]`` with pairs ``(2i, 2i+1)`` turned by
    ``positions · theta^(-2i / rope)``; ``positions [seq]``."""
    dim = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _heads_first(x, positions, theta):
    """Rotate ``[seq, heads, rope]`` (each head alike)."""
    return jnp.swapaxes(rotate(jnp.swapaxes(x, 0, 1), positions, theta), 0, 1)


# -------------------------------------------------------------- attention

def unpacked(words, positions: int):
    """``[..., positions / 32]`` int32 words -> ``[..., positions]`` bool:
    position ``p`` is bit ``p % 32`` of word ``p // 32``."""
    bits = jax.lax.bitcast_convert_type(words, jnp.uint32)[..., None] \
        >> jnp.arange(32, dtype=jnp.uint32)
    return (bits & 1).astype(bool).reshape(*words.shape[:-1], positions)


def attention(y, p, kind: Attention, model: Model, precision: str,
              selected=None):
    """One attention layer of either kind over one sequence ``[seq, d]``,
    and in a full layer how many of the given selections' positions the
    reference's own selection disagrees on. ``selected`` (a full layer: ``[seq,
    seq / 32]`` words, :func:`unpacked`) the selection a served program
    made at each position, which the attention then goes through."""
    seq, d = y.shape
    heads, nope, rope = (kind.heads, kind.qk_nope_head_dim,
                         kind.qk_rope_head_dim)
    rank, v_dim = kind.kv_lora_rank, kind.v_head_dim
    eps = model.rms_norm_eps
    positions = jnp.arange(seq)
    c_q = rms_norm(_product('sd,dr->sr', y, p['q_a'], precision),
                   p['q_norm'], eps)
    q = _product('sr,re->se', c_q, p['q_b'], precision)
    down = _product('sd,dr->sr', y, p['kv_a'], precision)
    c_kv = rms_norm(down[:, :rank], p['kv_norm'], eps)
    if model.lora_rescale:
        q = q * (d / kind.q_lora_rank) ** 0.5
        c_kv = c_kv * (d / rank) ** 0.5
    q = q.reshape(seq, heads, nope + rope)
    k_rope = rotate(down[:, rank:], positions, kind.rope_theta)
    q_rope = _heads_first(q[..., nope:], positions, kind.rope_theta)
    up = _product('sr,re->se', c_kv, p['kv_b'], precision).reshape(
        seq, heads, nope + v_dim)
    k_nope, value = up[..., :nope], up[..., nope:]
    scale = (nope + rope) ** -0.5
    gate = jax.nn.sigmoid(_product('sd,dh->sh', y, p['attn_gate'], precision))
    if kind.index is not None:
        index_heads, index_dim, topk, index_eps = kind.index
        iq = _product('sr,re->se', c_q, p['index_q'], precision).reshape(
            seq, index_heads, index_dim)
        iq = jnp.concatenate(
            [_heads_first(iq[..., :rope], positions, kind.rope_theta),
             iq[..., rope:]], axis=-1)
        ik = layer_norm(_product('sd,de->se', y, p['index_k'], precision),
                        p['index_k_norm'], p['index_k_bias'], index_eps)
        ik = jnp.concatenate([rotate(ik[:, :rope], positions,
                                     kind.rope_theta), ik[:, rope:]], axis=-1)
        iw = _product('sd,dh->sh', y, p['index_weights'], precision) \
            * (index_heads * index_dim) ** -0.5
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    causal_of = lambda rows: rows[:, None] >= positions[None, :]

    def attend(start):
        rows = start + jnp.arange(block)
        allowed = causal_of(rows)
        flips = jnp.zeros((), jnp.int32)
        if kind.index is not None:
            scores = jnp.einsum(
                'qj,jqk->qk', jax.lax.dynamic_slice_in_dim(iw, start, block),
                jax.nn.relu(_product(
                    'qjd,kd->jqk',
                    jax.lax.dynamic_slice_in_dim(iq, start, block), ik,
                    precision)))
            scores = jnp.where(allowed, scores, -jnp.inf)
            if topk < seq:
                kth = jax.lax.top_k(scores, topk)[0][:, -1:]
                allowed = allowed & (scores >= kth)
            if selected is not None:
                words = jax.lax.dynamic_slice_in_dim(selected, start, block)
                given = causal_of(rows) & unpacked(words, seq)
                served = ~jnp.all(words == -1, axis=-1)    # -1: padding
                flips = jnp.sum((given != allowed) & served[:, None],
                                dtype=jnp.int32)
                allowed = given
        else:
            allowed = allowed & (rows[:, None] - positions[None, :]
                                 < kind.window)
        qn = jax.lax.dynamic_slice_in_dim(q[..., :nope], start, block)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, block)
        scores = (_product('qhd,khd->hqk', qn, k_nope, precision)
                  + _product('qhd,kd->hqk', qr, k_rope, precision)) * scale
        weights = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf),
                                 axis=-1)
        return _product('hqk,khd->qhd', weights, value, precision), flips

    mixed, flips = jax.lax.map(attend, jnp.arange(0, seq, block))
    mixed = mixed.reshape(seq, heads, v_dim) * gate[..., None]
    return _product('se,ed->sd', mixed.reshape(seq, heads * v_dim), p['out'],
                    precision), jnp.sum(flips)


# ---------------------------------------------------------------- the FFNs

def gated_mlp(h, gate, up, down, precision: str):
    return _product('sw,wd->sd',
                    silu(_product('sd,dw->sw', h, gate, precision))
                    * _product('sd,dw->sw', h, up, precision),
                    down, precision)


def expert_layer(h, p, model: Model, precision: str, given=None):
    """``sum_e w_e E_e(h)`` over the chosen experts that are held, plus the
    shared expert; and at which positions the choice was the reference's
    own. ``given [seq, k]`` (experts by index, -1 where nothing is given)
    puts a served program's choice in the place of the reference's own at
    the positions it covers: the scores stay the reference's, and the
    weights are those scores renormalised over the experts gone through."""
    scores = jax.nn.sigmoid(_product('sd,de->se', h, p['router'], precision))
    _, ids = jax.lax.top_k(scores + p['correction'],
                           model.num_experts_per_tok)
    chosen = jnp.any(ids[:, :, None] == jnp.arange(model.n_routed), axis=1)
    own = jnp.ones(h.shape[0], bool)
    if given is not None:
        forced = jnp.any(given[:, :, None] == jnp.arange(model.n_routed),
                         axis=1)
        covered = given[:, 0] >= 0
        own = ~covered | jnp.all(forced == chosen, axis=-1)
        chosen = jnp.where(covered[:, None], forced, chosen)
    picked = jnp.where(chosen, scores, 0.0)
    weights = model.routed_scaling_factor * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    start, count = model.held if model.held is not None \
        else (0, model.n_routed)
    weights = jax.lax.dynamic_slice_in_dim(weights, start, count, axis=1)

    def one(total, expert):
        gate, up, down, weight = expert
        return total + weight[:, None] * gated_mlp(h, gate, up, down,
                                                  precision), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (p['gate'], p['up'], p['down'], weights.T))
    return routed + gated_mlp(h, p['shared_gate'], p['shared_up'],
                              p['shared_down'], precision), own


@functools.partial(jax.jit, static_argnames=('kind', 'model', 'precision'))
def layer(x, p, given=None, selected=None, *, kind: str, model: Model,
          precision: str = 'float32'):
    """One block on one sequence ``[seq, d]``: ``(x, own [seq], flips)``;
    ``own`` says where an expert layer's choice was the reference's own,
    ``flips`` how many positions of the given selections (``selected``)
    its own selection disagrees on."""
    eps = model.rms_norm_eps
    shape = model.full if kind == FULL else model.sliding
    mixed, flips = attention(rms_norm(x, p['attn_norm'], eps), p, shape,
                             model, precision, selected)
    x = x + mixed
    h = rms_norm(x, p['ffn_norm'], eps)
    if 'router' in p:
        out, own = expert_layer(h, p, model, precision, given)
        return x + out, own, flips
    return (x + gated_mlp(h, p['gate'], p['up'], p['down'], precision),
            jnp.ones(x.shape[0], bool), flips)


@functools.partial(jax.jit, static_argnames=('eps', 'precision'))
def head(x, final_norm, table, *, eps: float, precision: str = 'float32'):
    return _product('sd,dv->sv', rms_norm(x, final_norm, eps), table,
                    precision)


def narrow(leaves: dict, bits: int) -> dict:
    """A layer's leaves with every matrix but the router rounded to ``bits``
    per weight, symmetric, one scale per output channel; norms untouched."""
    qmax = float(2 ** (bits - 1) - 1)

    def rounded(name, leaf):
        if leaf.ndim < 2 or name == 'router':
            return leaf
        absmax = jnp.max(jnp.abs(leaf), axis=-2, keepdims=True)
        scale = jnp.where(absmax > 0, absmax, qmax) / qmax
        return jnp.round(jnp.clip(leaf / scale, -qmax, qmax)) * scale

    return {name: rounded(name, leaf) for name, leaf in leaves.items()}


# ------------------------------------------------------------ whole passes

def forward(sequences: list, leaves_of, model: Model, *,
            precision: str = 'float32', bits: int | None = None,
            routing: list | None = None, selections: list | None = None):
    """The hidden states after the last block for every sequence of
    ``sequences`` (``[seq]`` int32 each), and how many expert-layer choices
    of each differed from the reference's own. ``leaves_of('top')`` gives
    ``{'embedding', 'final_norm', 'lm_head'}``, ``leaves_of(i)`` layer
    ``i``'s leaves; one layer's are alive at a time. ``routing[row]`` is
    ``[seq, expert layers, k]`` (-1 where nothing was served);
    ``selections[row]`` ``[seq, full layers, seq / 32]``: the selections a
    served program made (every word -1 where it served nothing: causal),
    which the full layers then go through (``None``: the reference's own). Returns the hidden states, the
    expert-layer choices that differed and the selected positions that
    differed, per sequence."""
    table = leaves_of('top')['embedding']
    hidden = [table[tokens] for tokens in sequences]
    del table
    differed = [0] * len(sequences)
    flipped = [0] * len(sequences)
    expert_layers = full_layers = 0
    for index, kind in enumerate(model.layer_types):
        leaves = leaves_of(index)
        if bits:
            leaves = narrow(leaves, bits)
        for row, x in enumerate(hidden):
            given = selected = None
            if routing is not None and 'router' in leaves:
                given = jnp.asarray(routing[row][:, expert_layers],
                                    jnp.int32)
            if (selections is not None and selections[row] is not None
                    and kind == FULL):
                selected = jnp.asarray(selections[row][:, full_layers],
                                       jnp.int32)
            hidden[row], own, flips = layer(
                x, leaves, given, selected, kind=kind, model=model,
                precision=precision)
            differed[row] += int(jnp.sum(~own))
            flipped[row] += int(flips)
        expert_layers += 'router' in leaves
        full_layers += kind == FULL
        del leaves
    return hidden, differed, flipped


def logits(sequences: list, leaves_of, model: Model, **levers):
    """``[seq, vocabulary rows]`` for every sequence (tests, small sizes)."""
    hidden, _, _ = forward(sequences, leaves_of, model, **levers)
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


def served_gaps(sequences: list, leaves_of, model: Model, *,
                routing: list | None = None, selections: list | None = None,
                control_bits: int | None = None):
    """For every sequence: at each position but the last, how far the logit
    of the token that follows lies below the reference's best (``[seq -
    1]``), how many expert-layer choices ``routing`` changed and how many
    selected positions ``selections`` changed. With ``control_bits`` the
    reading is the control's instead: the gap of the token which the
    reference with its layers' matrices at that many bits, under bfloat16
    products, puts first, and where the control's own choices and
    selections differ from those given. Both passes go through the experts
    ``routing`` gives and the selections ``selections`` gives
    (:func:`forward`)."""
    hidden, differed, flipped = forward(sequences, leaves_of, model,
                                        routing=routing,
                                        selections=selections)
    lowered = [None] * len(sequences)
    if control_bits:
        lowered, differed, flipped = forward(
            sequences, leaves_of, model, precision='bfloat16',
            bits=control_bits, routing=routing, selections=selections)
    top = leaves_of('top')
    return [(_gaps(x, low, tokens, top['final_norm'], top['lm_head'],
                   eps=model.rms_norm_eps), changed, flips)
            for x, low, tokens, changed, flips
            in zip(hidden, lowered, sequences, differed, flipped)]
