"""Plain Nemotron-H: the forward pass in ``jax.numpy``, float32, every
matrix product under ``jax.default_matmul_precision('highest')``.

The yardstick ``correct`` is decided against for the ``nemotron_h`` family.
No kernels, no cache, no batching, no chunks, no sort and no grouping; it
imports nothing of ``tpusystem/`` and is handed only leaves the benchmark
made from the seed (the bfloat16 values, widened). It follows the published
model (https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16:
``config.json`` and the ``nemotron_h`` modelling code). Residual stream
``x``; **every layer** is ``x <- x + mixer(rms_norm(x))`` with the mixer its
character of ``hybrid_override_pattern`` names; then a final RMSNorm and an
untied head; no bias but the convolution's.

* ``M``, **Mamba-2, as the recurrence, token by token** (a ``lax.scan`` over
  positions; the program's prefill is the chunked form, so the two share
  nothing but the equations): ``[z ; xBC ; dt] = u W_in``; ``xBC_t <-
  silu(b_c + sum_j w_c[:, j] xBC_{t-K+1+j})`` (inputs before the sequence
  are zero); ``x [H, P], B [G, N], C [G, N] = split(xBC)``, head ``h`` reads
  group ``h // (H/G)``; ``D_t = softplus(dt_t + dt_bias)`` (no clamp), ``a_t
  = exp(D_t A)``, ``A = -exp(A_log)``; ``S_t = a_t S_{t-1} + D_t x_t (x)
  B_t``, ``S_{-1} = 0``; ``y_t = S_t . C_t + D x_t``; ``y <- y * silu(z)``,
  RMS-normalised within each of the ``G`` groups and scaled by ``w_n``;
  ``out = y W_out``.
* ``*``, **attention**: ``heads`` query heads and ``kv`` key/value heads of
  ``head_dim``, query head ``i`` attends key/value head ``i // (heads /
  kv)``, causal, scale ``head_dim^-1/2``, no positional term.
* ``E``, **experts**: ``s = sigmoid(h W_r)`` over all experts; the ``k``
  chosen are the largest of ``s + b`` (``b``: the learned correction);
  weights ``routed_scaling_factor · s_e / (sum_chosen s + 1e-20)``; ``E_e(h)
  = relu(h W_up,e)² W_down,e``; plus the shared expert of the same form.

Departures, each also in the configuration's ``assumed`` or ``deployment``:

* **The share.** ``held = (start, count)``: the router scores every expert,
  and the sum runs over the chosen experts in ``start .. start + count - 1``
  only (a loop over those experts with a mask); what the others would have
  added is left out, as in the program. ``held=None`` is the uncut layer.
* **Given routing.** A pass may be handed the experts a served program gave
  every position (``routing``): it then goes through those experts, at
  weights from its own scores (renormalised over the given ones), and counts
  where its own choice was another.
* The vocabulary is the slice the table and the head hold.

One layer's leaves are made, used for every sequence and freed before the
next (``leaves_of``). ``precision`` and ``bits`` are the control's levers, as
``chipbench/reference/deepseek_v2.py`` has them (whose plain helpers this
file shares: the rounded product, the norm, the first-k mask, the head and
the gap of a served token).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench.reference.deepseek_v2 import (_first_k, _gaps, _product, head,
                                             rms_norm, silu)

QUERY_BLOCK = 256
# what a weight-streaming server narrows: the layers' matrices, never the
# router, the convolution or a vector
MATRICES = ('in_proj', 'out_proj', 'q', 'k', 'v', 'out', 'up', 'down',
            'shared_up', 'shared_down')


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes the arithmetic needs, under the published names."""
    hybrid_override_pattern: str
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    n_routed: int                  # the router's width
    num_experts_per_tok: int
    routed_scaling_factor: float
    layer_norm_epsilon: float
    held: tuple | None = None      # (first expert held, how many)


# ------------------------------------------------------------- the mixers

def mamba(u, p, model: Model, precision: str):
    """One Mamba-2 layer over one sequence ``[seq, d]``, as the recurrence."""
    seq = u.shape[0]
    heads, width = model.mamba_num_heads, model.mamba_head_dim
    groups, size, taps = model.n_groups, model.ssm_state_size, model.conv_kernel
    inner = heads * width
    channels = inner + 2 * groups * size
    projected = _product('sd,de->se', u, p['in_proj'], precision)
    z, mixed, dt = (projected[:, :inner], projected[:, inner:inner + channels],
                    projected[:, inner + channels:])
    window = jnp.concatenate([jnp.zeros((taps - 1, channels)), mixed])
    mixed = silu(p['conv_bias'] + sum(window[tap:tap + seq]
                                      * p['conv_weight'][:, tap]
                                      for tap in range(taps)))
    x = mixed[:, :inner].reshape(seq, heads, width)
    per_head = lambda grouped: jnp.repeat(
        grouped.reshape(seq, groups, size), heads // groups, axis=1)
    B = per_head(mixed[:, inner:inner + groups * size])
    C = per_head(mixed[:, inner + groups * size:])
    dt = jax.nn.softplus(dt + p['dt_bias'])                       # [seq, H]
    A = -jnp.exp(p['A_log'])

    def step(state, at):
        x_t, b_t, c_t, dt_t = at
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, width, size)), (x, B, C, dt))
    y = (y + p['D'][:, None] * x).reshape(seq, inner) * silu(z)
    grouped = y.reshape(seq, groups, inner // groups)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        + model.layer_norm_epsilon)
    return _product('se,ed->sd', grouped.reshape(seq, inner) * p['norm_scale'],
                    p['out_proj'], precision)


def attention(h, p, model: Model, precision: str):
    """Full causal grouped-query attention over one sequence ``[seq, d]``."""
    seq = h.shape[0]
    heads, kv, size = (model.num_attention_heads, model.num_key_value_heads,
                       model.head_dim)
    q = _product('sd,de->se', h, p['q'], precision).reshape(
        seq, kv, heads // kv, size)
    k = _product('sd,de->se', h, p['k'], precision).reshape(seq, kv, size)
    v = _product('sd,de->se', h, p['v'], precision).reshape(seq, kv, size)
    positions = jnp.arange(seq)
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    def attend(start):
        queries = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = _product('qgrd,kgd->grqk', queries, k, precision) \
            * size ** -0.5
        causal = (start + jnp.arange(block))[:, None] >= positions[None, :]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _product('grqk,kgd->qgrd', weights, v, precision)

    mixed = jax.lax.map(attend, jnp.arange(0, seq, block))
    return _product('se,ed->sd', mixed.reshape(seq, heads * size), p['out'],
                    precision)


def relu2_mlp(h, up, down, precision: str):
    return _product('sw,wd->sd', jnp.square(jax.nn.relu(
        _product('sd,dw->sw', h, up, precision))), down, precision)


def route(h, router, correction, model: Model, precision: str):
    """``(chosen [seq, experts] bool, scores [seq, experts])``: sigmoid
    scores over all experts, the ``k`` largest of score + correction."""
    scores = jax.nn.sigmoid(_product('sd,de->se', h, router, precision))
    return _first_k(scores + correction, model.num_experts_per_tok), scores


def expert_layer(h, p, model: Model, precision: str, given=None):
    """``sum_e w_e E_e(h)`` over the chosen experts that are held, plus the
    shared expert; and at which positions the choice was the reference's
    own. ``given [seq, k]`` (experts by index, -1 where nothing is given)
    puts a served program's choice in the place of the reference's own at
    the positions it covers: the scores stay the reference's, and the
    weights are those scores renormalised over the experts gone through."""
    chosen, scores = route(h, p['router'], p['correction'], model, precision)
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
        up, down, weight = expert
        return total + weight[:, None] * relu2_mlp(h, up, down,
                                                  precision), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (p['up'], p['down'], weights.T))
    return routed + relu2_mlp(h, p['shared_up'], p['shared_down'],
                              precision), own


@functools.partial(jax.jit, static_argnames=('kind', 'model', 'precision'))
def layer(x, p, given=None, *, kind: str, model: Model,
          precision: str = 'float32'):
    """One layer of ``kind`` on one sequence ``[seq, d]``: ``(x, own
    [seq])``; ``own`` says where an expert layer's choice was the
    reference's own (everywhere in the other kinds)."""
    h = rms_norm(x, p['norm'], model.layer_norm_epsilon)
    own = jnp.ones(x.shape[0], bool)
    if kind == 'M':
        out = mamba(h, p, model, precision)
    elif kind == '*':
        out = attention(h, p, model, precision)
    elif kind == 'E':
        out, own = expert_layer(h, p, model, precision, given)
    else:
        raise ValueError(f'layer kind {kind!r}')
    return x + out, own


def narrow(leaves: dict, bits: int) -> dict:
    """A layer's leaves with every matrix of :data:`MATRICES` rounded to
    ``bits`` per weight, symmetric, one scale per output channel."""
    qmax = float(2 ** (bits - 1) - 1)

    def rounded(name, leaf):
        if name not in MATRICES:
            return leaf
        absmax = jnp.max(jnp.abs(leaf), axis=-2, keepdims=True)
        scale = jnp.where(absmax > 0, absmax, qmax) / qmax
        return jnp.round(jnp.clip(leaf / scale, -qmax, qmax)) * scale

    return {name: rounded(name, leaf) for name, leaf in leaves.items()}


# ------------------------------------------------------------ whole passes

def forward(sequences: list, leaves_of, model: Model, *,
            precision: str = 'float32', bits: int | None = None,
            routing: list | None = None):
    """The hidden states after the last layer for every sequence of
    ``sequences`` (``[seq]`` int32 each), and how many expert-layer choices
    of each differed from the reference's own. ``leaves_of('top')`` gives
    ``{'embedding', 'final_norm', 'lm_head'}``, ``leaves_of(i)`` layer
    ``i``'s leaves; one layer's are alive at a time. ``routing[row]`` is
    ``[seq, expert layers, k]``: the experts a served program gave each
    position in each expert layer, first expert layer first, -1 where it
    served nothing (padding)."""
    table = leaves_of('top')['embedding']
    hidden = [table[tokens] for tokens in sequences]
    del table
    differed = [0] * len(sequences)
    expert_layers = 0
    for index, kind in enumerate(model.hybrid_override_pattern):
        leaves = leaves_of(index)
        if bits:
            leaves = narrow(leaves, bits)
        for row, x in enumerate(hidden):
            given = None
            if routing is not None and kind == 'E':
                given = jnp.asarray(routing[row][:, expert_layers], jnp.int32)
            hidden[row], own = layer(x, leaves, given, kind=kind, model=model,
                                     precision=precision)
            differed[row] += int(jnp.sum(~own))
        expert_layers += kind == 'E'
        del leaves
    return hidden, differed


def logits(sequences: list, leaves_of, model: Model, **levers):
    """``[seq, vocabulary rows]`` for every sequence (tests, small sizes)."""
    hidden, _ = forward(sequences, leaves_of, model, **levers)
    top = leaves_of('top')
    return [head(x, top['final_norm'], top['lm_head'],
                 eps=model.layer_norm_epsilon,
                 precision=levers.get('precision', 'float32'))
            for x in hidden]


def served_gaps(sequences: list, leaves_of, model: Model, *,
                routing: list | None = None,
                control_bits: int | None = None):
    """For every sequence: at each position but the last, how far the logit
    of the token that follows lies below the reference's best (``[seq -
    1]``), and how many expert-layer choices ``routing`` changed. With
    ``control_bits`` the gap is the control's instead: that of the token
    which the reference with its layers' matrices at that many bits, under
    bfloat16 products, puts first. Both passes go through the experts
    ``routing`` gives."""
    hidden, differed = forward(sequences, leaves_of, model, routing=routing)
    lowered = [None] * len(sequences)
    if control_bits:
        lowered, _ = forward(sequences, leaves_of, model,
                             precision='bfloat16', bits=control_bits,
                             routing=routing)
    top = leaves_of('top')
    return [(_gaps(x, low, tokens, top['final_norm'], top['lm_head'],
                   eps=model.layer_norm_epsilon), changed)
            for x, low, tokens, changed
            in zip(hidden, lowered, sequences, differed)]
