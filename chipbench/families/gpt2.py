"""The GPT-2 family: everything the benchmark knows about ``models/gpt2.py::
GPT2`` and its published configuration keys (``n_layer``, ``n_embd``,
``n_head``, ``n_positions``, ``vocab_size``, ``initializer_range``).

Four parts, under the names ``chipbench/README.md`` fixes for every family:
the program's module for training and for serving; the seeded weights; the
plain reference's readings (``chipbench/reference/gpt2.py`` does the
arithmetic and imports nothing of ``tpusystem/``); the operation and byte
counts ``flops.py`` hands out.

The benchmark owns the weights: the program under test and the plain
reference are both handed what this file makes from ``--seed``, so neither
takes anything the other has made. Every leaf is ``N(0, initializer_range)``
(layer-norm scales are that plus one), drawn per *kind* of leaf with the
layer as the leading axis, on the device in one jitted call. The unrolled
tree is the one the program's ``GPT2`` module uses (``h_0`` .. ``h_{L-1}``);
``stacked`` gives the same numbers with the layer axis kept, which the
reference scans over.

The counts' convention (fixed; a PR that claims a gain never changes it):
matrix-product parameters are ``12·L·d² + V·d`` (the tied head counted once
over the published vocabulary, embedding look-ups and biases not counted);
causal attention adds ``2·L·S·d`` per token forward (half of the full
``4·L·S·d`` square) and three times that with the backward; a decoded token
at cache depth ``p`` attends ``p`` keys: ``4·L·p·d``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check
from chipbench.reference import gpt2 as reference
from chipbench.weights import flatten, nest, seed_key


# ------------------------------------------------------------- the program

def _module(config: dict, **as_built):
    from tpusystem.models import GPT2
    as_run = config['as_run']
    return GPT2(vocab_size=as_run['vocab_rows'], layers=config['n_layer'],
                dim=config['n_embd'], heads=config['n_head'],
                max_seq=config['n_positions'], dropout=as_run['dropout'],
                **as_built)


def train_module(config: dict):
    """The module the training step is compiled from."""
    return _module(config, return_features=True,
                   attention=config['as_run']['attention'])


def serve_module(config: dict):
    """The module ``InferenceService`` is handed."""
    return _module(config)


def vocab_size(config: dict) -> int:
    """Traffic draws its ids below this (the published vocabulary, not the
    padded table)."""
    return config['vocab_size']


def positions(config: dict) -> int:
    """How many positions a sequence may hold."""
    return config['n_positions']


# ------------------------------------------------------ the seeded weights

# one transformer block: leaf path -> shape as a function of the width
BLOCK_LEAVES = {
    'ln_1/scale': lambda d: (d,),
    'ln_1/bias': lambda d: (d,),
    'attn/qkv/kernel': lambda d: (d, 3 * d),
    'attn/qkv/bias': lambda d: (3 * d,),
    'attn/out/kernel': lambda d: (d, d),
    'attn/out/bias': lambda d: (d,),
    'ln_2/scale': lambda d: (d,),
    'ln_2/bias': lambda d: (d,),
    'fc/kernel': lambda d: (d, 4 * d),
    'fc/bias': lambda d: (4 * d,),
    'proj/kernel': lambda d: (4 * d, d),
    'proj/bias': lambda d: (d,),
}
TOP_LEAVES = {
    'wte/embedding': lambda c: (c['as_run']['vocab_rows'], c['n_embd']),
    'wpe/embedding': lambda c: (c['n_positions'], c['n_embd']),
    'ln_f/scale': lambda c: (c['n_embd'],),
    'ln_f/bias': lambda c: (c['n_embd'],),
}


def _draw(key, index: int, shape, std: float, path: str):
    leaf = std * jax.random.normal(jax.random.fold_in(key, index), shape,
                                   jnp.float32)
    return leaf + 1.0 if path.endswith('scale') else leaf


def _stacked_flat(key, layers: int, dim: int, tops: tuple, std: float):
    flat = {}
    for index, (path, shape) in enumerate(tops):
        flat[path] = _draw(key, index, shape, std, path)
    for index, (path, shape_of) in enumerate(BLOCK_LEAVES.items()):
        flat[f'h/{path}'] = _draw(key, 100 + index, (layers,) + shape_of(dim),
                                  std, path)
    return flat


@functools.partial(jax.jit, static_argnames=('layers', 'dim', 'tops', 'std',
                                             'stacked'))
def _make(key, *, layers, dim, tops, std, stacked):
    flat = _stacked_flat(key, layers, dim, tops, std)
    if stacked:
        return nest(flat)
    out = {path: leaf for path, leaf in flat.items()
           if not path.startswith('h/')}
    for path in BLOCK_LEAVES:
        for layer in range(layers):
            out[f'h_{layer}/{path}'] = flat[f'h/{path}'][layer]
    return nest(out)


def from_key(config: dict, key, *, stacked: bool = False) -> dict:
    """Float32 parameters for ``config`` from a key (jit-traceable)."""
    tops = tuple((path, shape_of(config)) for path, shape_of
                 in TOP_LEAVES.items())
    return _make(key, layers=config['n_layer'], dim=config['n_embd'],
                 tops=tops, std=float(config['initializer_range']),
                 stacked=stacked)


def make(config: dict, seed: int, *, stacked: bool = False) -> dict:
    """Float32 parameters for ``config`` from ``seed``, on the default
    device."""
    return from_key(config, seed_key(seed), stacked=stacked)


def _parts(path: str, leaf):
    """A leaf as the pieces norms are taken over. The fused query, key and
    value projection is three: the key's bias has no gradient under
    softmax, and inside one fused leaf it would hide in the other two."""
    if '/attn/qkv/' in path:
        return {f'{path}.{name}': part for name, part
                in zip('qkv', jnp.split(leaf, 3, axis=-1))}
    return {path: leaf}


def stacked_norms(tree: dict) -> dict:
    """Per-leaf L2 norms of a stacked tree, keyed by the unrolled names
    (jit-traceable: values are scalars of the traced computation)."""
    out = {}
    for whole, leaf in flatten(tree).items():
        for path, part in _parts(whole, leaf.astype(jnp.float32)).items():
            if path.startswith('h/'):
                per_layer = jnp.sqrt(jnp.sum(
                    jnp.square(part), axis=tuple(range(1, part.ndim))))
                for layer in range(part.shape[0]):
                    out[f'h_{layer}/{path[2:]}'] = per_layer[layer]
            else:
                out[path] = jnp.sqrt(jnp.sum(jnp.square(part)))
    return out


def norms(tree: dict) -> dict:
    """Per-leaf L2 norms of an unrolled tree (the program's layout)."""
    return {path: jnp.sqrt(jnp.sum(jnp.square(part)))
            for whole, leaf in flatten(tree).items()
            for path, part in _parts(whole, leaf.astype(jnp.float32)).items()}


# ------------------------------------------------- the reference's readings

def _model(config: dict) -> dict:
    return dict(heads=config['n_head'],
                eps=float(config['as_run']['layer_norm_epsilon']))


def reference_training(config: dict, seed: int, batches, *,
                       precision: str = 'float32') -> dict:
    """The reference's reading of the same first steps: weights from the
    seed, then one AdamW step per batch of ``batches`` (``[rows, seq]``
    each), as ``config['as_run']['optimizer']`` states it."""
    optimizer = config['as_run']['optimizer']
    params = make(config, seed, stacked=True)       # donated, step by step
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    rows = batches[0].shape[0]
    block_rows = min(config['reference']['block_rows'], rows)
    while rows % block_rows:
        block_rows -= 1
    losses = []
    for batch in batches:
        params, mu, nu, count, loss = reference.train_step(
            params, mu, nu, count, jnp.asarray(batch), precision=precision,
            block_rows=block_rows, lr=optimizer['lr'], b1=optimizer['b1'],
            b2=optimizer['b2'], adam_eps=optimizer['eps'],
            weight_decay=optimizer['weight_decay'],
            grad_clip=optimizer['grad_clip'], **_model(config))
        losses.append(loss)
    moved = jax.jit(lambda new, old: stacked_norms(
        jax.tree.map(jnp.subtract, new, old)))(
            params, make(config, seed, stacked=True))
    moment = jax.jit(stacked_norms)(mu)
    host = jax.device_get({'losses': losses, 'moment': moment,
                           'moved': moved})
    return {'losses': [float(x) for x in host['losses']],
            'moment': {k: float(v) for k, v in host['moment'].items()},
            'moved': {k: float(v) for k, v in host['moved'].items()}}


def served_gap(config: dict, seed: int, sample: list,
               control_bits: int | None = None) -> tuple[float, int]:
    """The widest gap over ``sample`` and how many served tokens it
    covers. With ``control_bits`` the reading is the control's instead:
    the gap of the token that the reference with its matrices at that many
    bits puts first, at the same positions."""
    params = make(config, seed, stacked=True)
    lowered = (reference.quantize_matrices(params, control_bits)
               if control_bits else None)
    widest, covered = 0.0, 0
    for prompt, tokens in sample:
        padded, span = check.sequence(prompt, tokens, positions(config))
        if lowered is None:
            gaps = reference.served_gaps(params, jnp.asarray(padded),
                                         **_model(config))
        else:
            gaps = reference.control_gaps(params, lowered,
                                          jnp.asarray(padded),
                                          **_model(config))
        gaps = np.asarray(gaps)[span]
        widest = max(widest, float(gaps.max()))
        covered += gaps.size
    return widest, covered


# ------------------------------------------- the operation and byte counts

def matmul_params(config: dict) -> int:
    layers, dim = config['n_layer'], config['n_embd']
    return 12 * layers * dim * dim + config['vocab_size'] * dim


def train_ops_per_token(config: dict, seq: int) -> float:
    attention = 2 * config['n_layer'] * seq * config['n_embd']
    return 6 * matmul_params(config) + 3 * attention


def prefill_ops(config: dict, length: int) -> float:
    attention = 2 * config['n_layer'] * length * config['n_embd']
    return length * (2 * matmul_params(config) + attention)


def decode_ops(config: dict, depth: int) -> float:
    return (2 * matmul_params(config)
            + 4 * config['n_layer'] * depth * config['n_embd'])


def flash_layers(config: dict) -> int:
    """Every layer runs the flash kernel once forward, once backward."""
    return config['n_layer']


def flash_ops_and_bytes(config: dict, rows: int, seq: int,
                        backward: bool) -> tuple[float, float]:
    """Forward is the two products over the causal half; backward the four
    products a backward pass needs (the scores' recomputation does not
    count). Bytes: q, k, v read and the output written forward; q, k, v,
    o, do read and dq, dk, dv written backward."""
    dim = config['n_embd']
    forward_ops = 2.0 * rows * seq * seq * dim
    tensor = rows * seq * dim * 2.0
    if backward:
        return 2 * forward_ops, 8 * tensor
    return forward_ops, 4 * tensor


def decode_chain_ops_and_bytes(config: dict, rows: int,
                               weight_bytes: float) -> tuple[float, float]:
    """Four matrix products per layer. Bytes are the streamed weights at
    ``weight_bytes`` each with one float32 scale per output channel, plus
    the bf16 activations in and out of each of the three kernels."""
    layers, dim = config['n_layer'], config['n_embd']
    weights = 12 * layers * dim * dim
    ops = 2.0 * rows * weights
    scales = layers * (3 * dim + dim + 4 * dim + dim) * 4.0
    activations = layers * rows * (dim + 3 * dim + dim + dim + dim + dim) * 2.0
    return ops, weights * weight_bytes + scales + activations


def kv_bytes_per_position(config: dict) -> float:
    """One cached position: K and V of every layer, ``n_embd`` wide, in
    the pool's bf16 (``as_run.kv_cache_dtype``)."""
    return config['n_layer'] * 2 * config['n_embd'] * 2.0
