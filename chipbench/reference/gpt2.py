"""Plain GPT-2: forward, loss, gradients and AdamW in ``jax.numpy``.

The yardstick ``correct`` is decided against. Float32 with every matrix
product at ``Precision.HIGHEST``; no kernels, no cache, no batching tricks;
independent of ``tpusystem/`` (it imports nothing from it and is handed
only weights the benchmark made from the seed). It follows the published
GPT-2 (Radford et al. 2019; ``transformers`` ``GPT2LMHeadModel``):
learned positions, pre-norm blocks, tanh-GELU, tied head. Departures are
the configuration's own ``as_run`` entries (layer-norm epsilon, the padded
table). Layers are a ``lax.scan`` over stacked leaves with the block
rematerialised, and rows go through in blocks, only so that it fits
beside nothing else on one chip.

``precision`` is the control's lever: ``'float32'`` is the reference;
``'bfloat16'`` and ``'fp8'`` round both operands of every matrix product
to that type first (the product itself still accumulates in float32).
``quantize_matrices`` is the serving control: the block matrices rounded
to ``bits`` per weight with one scale per output channel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ('float32', 'bfloat16', 'fp8')
FP8_MAX = 448.0


def _rounded(x, precision: str):
    if precision == 'float32':
        return x
    if precision == 'bfloat16':
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == 'fp8':
        clipped = jnp.clip(x, -FP8_MAX, FP8_MAX)
        return clipped.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f'unknown precision {precision!r}; one of {PRECISIONS}')


def _product(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _rounded(a, precision), _rounded(b, precision),
                      precision=HIGHEST)


def layer_norm(x, scale, bias, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, *, heads: int, eps: float, precision: str):
    """One pre-norm transformer block on ``[rows, seq, dim]``."""
    rows, seq, dim = x.shape
    head_dim = dim // heads
    h = layer_norm(x, p['ln_1']['scale'], p['ln_1']['bias'], eps)
    qkv = _product('rsd,de->rse', h, p['attn']['qkv']['kernel'],
                   precision) + p['attn']['qkv']['bias']
    q, k, v = (part.reshape(rows, seq, heads, head_dim)
               for part in jnp.split(qkv, 3, axis=-1))
    scores = _product('rqhd,rkhd->rhqk', q, k, precision) / math.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    context = _product('rhqk,rkhd->rqhd', weights, v, precision)
    x = x + _product('rsd,de->rse', context.reshape(rows, seq, dim),
                     p['attn']['out']['kernel'],
                     precision) + p['attn']['out']['bias']
    h = layer_norm(x, p['ln_2']['scale'], p['ln_2']['bias'], eps)
    grown = gelu(_product('rsd,de->rse', h, p['fc']['kernel'], precision)
                 + p['fc']['bias'])
    return x + _product('rse,ed->rsd', grown, p['proj']['kernel'],
                        precision) + p['proj']['bias']


def logits(params, tokens, *, heads: int, eps: float,
           precision: str = 'float32'):
    """``[rows, seq] -> [rows, seq, table rows]`` over stacked ``params``."""
    seq = tokens.shape[-1]
    x = (params['wte']['embedding'][tokens]
         + params['wpe']['embedding'][:seq])

    @jax.checkpoint
    def layer(x, p):
        return block(x, p, heads=heads, eps=eps, precision=precision), None

    x, _ = jax.lax.scan(layer, x, params['h'])
    x = layer_norm(x, params['ln_f']['scale'], params['ln_f']['bias'], eps)
    return _product('rsd,vd->rsv', x, params['wte']['embedding'], precision)


def loss_sum(params, tokens, **model):
    """Summed next-token cross-entropy of ``tokens`` and the token count."""
    scores = logits(params, tokens, **model)[:, :-1]
    targets = tokens[:, 1:]
    chosen = jnp.take_along_axis(scores, targets[..., None], axis=-1)[..., 0]
    losses = jax.nn.logsumexp(scores, axis=-1) - chosen
    return jnp.sum(losses), targets.size


# --------------------------------------------------------------- training

def _tree_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(leaf))
                        for leaf in jax.tree.leaves(tree)))


@functools.partial(jax.jit, static_argnames=(
    'heads', 'eps', 'precision', 'block_rows', 'lr', 'b1', 'b2', 'adam_eps',
    'weight_decay', 'grad_clip'), donate_argnums=(0, 1, 2))
def train_step(params, mu, nu, count, batch, *, heads, eps, precision,
               block_rows, lr, b1, b2, adam_eps, weight_decay, grad_clip):
    """One AdamW step on ``batch [rows, seq]``: mean loss over the batch's
    tokens, gradient clipped by its global norm, decoupled weight decay on
    every leaf. Returns ``(params, mu, nu, count, loss)``."""
    rows = batch.shape[0]
    blocks = batch.reshape(rows // block_rows, block_rows, batch.shape[1])
    model = dict(heads=heads, eps=eps, precision=precision)

    def one(carry, block_tokens):
        total, grads = carry
        (loss, _), grad = jax.value_and_grad(
            lambda p: loss_sum(p, block_tokens, **model), has_aux=True)(params)
        return (total + loss, jax.tree.map(jnp.add, grads, grad)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (total, grads), _ = jax.lax.scan(one, (jnp.float32(0), zeros), blocks)
    tokens = rows * (batch.shape[1] - 1)
    loss = total / tokens
    grads = jax.tree.map(lambda g: g / tokens, grads)
    if grad_clip:
        norm = _tree_norm(grads)
        grads = jax.tree.map(
            lambda g: jnp.where(norm < grad_clip, g, g / norm * grad_clip),
            grads)
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * jnp.square(g), nu,
                      grads)
    step = count.astype(jnp.float32)
    mu_hat = 1 - b1 ** step
    nu_hat = 1 - b2 ** step

    def update(p, m, n):
        direction = (m / mu_hat) / (jnp.sqrt(n / nu_hat) + adam_eps)
        return p - lr * (direction + weight_decay * p)

    return jax.tree.map(update, params, mu, nu), mu, nu, count, loss


# ---------------------------------------------------------------- serving

def quantize_matrices(params, bits: int):
    """Stacked ``params`` with every block matrix rounded to ``bits`` per
    weight, symmetric, one scale per output channel; embeddings, biases
    and norms untouched (what a weight-streaming server narrows)."""
    qmax = float(2 ** (bits - 1) - 1)

    def narrow(leaf):
        if leaf.ndim != 3:              # [layers, in, out] matrices only
            return leaf
        absmax = jnp.max(jnp.abs(leaf), axis=-2, keepdims=True)
        scale = jnp.where(absmax > 0, absmax, qmax) / qmax
        return jnp.round(jnp.clip(leaf / scale, -qmax, qmax)) * scale

    return dict(params, h=jax.tree.map(narrow, params['h']))


@functools.partial(jax.jit, static_argnames=('heads', 'eps'))
def served_gaps(params, tokens, *, heads, eps):
    """For one sequence ``[seq]``: at each position, how far the logit of
    the token that follows lies below the reference's best. ``[seq - 1]``."""
    scores = logits(params, tokens[None], heads=heads, eps=eps)[0, :-1]
    chosen = jnp.take_along_axis(scores, tokens[1:, None], axis=-1)[:, 0]
    return jnp.max(scores, axis=-1) - chosen


@functools.partial(jax.jit, static_argnames=('heads', 'eps', 'precision'))
def control_gaps(params, control_params, tokens, *, heads, eps,
                 precision='float32'):
    """The control's reading on the same sequence: at each position, the
    gap of the token that ``control_params`` (or ``precision``) puts first."""
    scores = logits(params, tokens[None], heads=heads, eps=eps)[0, :-1]
    lowered = logits(control_params, tokens[None], heads=heads, eps=eps,
                     precision=precision)[0, :-1]
    first = jnp.argmax(lowered, axis=-1)
    chosen = jnp.take_along_axis(scores, first[:, None], axis=-1)[:, 0]
    return jnp.max(scores, axis=-1) - chosen
