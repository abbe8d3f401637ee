"""Seeded GPT-2 weights, made on the device in one jitted call.

The benchmark owns the weights: the program under test and the plain
reference are both handed what this file makes from ``--seed``, so neither
takes anything the other has made. Every leaf is ``N(0, initializer_range)``
(layer-norm scales are that plus one), drawn per *kind* of leaf with the
layer as the leading axis. ``unrolled`` gives the tree the program's
``GPT2`` module uses (``h_0`` .. ``h_{L-1}``); ``stacked`` gives the same
numbers with the layer axis kept, which the reference scans over.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (``--seed`` may pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split('/')
        for name in parents:
            node = node.setdefault(name, {})
        node[last] = leaf
    return tree


def flatten(tree: dict, prefix: str = '') -> dict:
    """``{'a': {'b': x}} -> {'a/b': x}`` for plain nested dicts."""
    flat = {}
    for name, node in tree.items():
        path = f'{prefix}{name}'
        if isinstance(node, dict):
            flat.update(flatten(node, path + '/'))
        else:
            flat[path] = node
    return flat


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
        return _nest(flat)
    out = {path: leaf for path, leaf in flat.items()
           if not path.startswith('h/')}
    for path in BLOCK_LEAVES:
        for layer in range(layers):
            out[f'h_{layer}/{path}'] = flat[f'h/{path}'][layer]
    return _nest(out)


def from_key(config: dict, key, *, stacked: bool = False) -> dict:
    """Float32 parameters for ``config`` from a key (jit-traceable)."""
    tops = tuple((path, shape_of(config)) for path, shape_of
                 in TOP_LEAVES.items())
    return _make(key, layers=config['n_layer'], dim=config['n_embd'],
                 tops=tops, std=float(config['initializer_range']),
                 stacked=stacked)


def make(config: dict, seed: int, *, stacked: bool = False) -> dict:
    """Float32 parameters for ``config`` from ``seed``, on the default device."""
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
    norms = {}
    for whole, leaf in flatten(tree).items():
        for path, part in _parts(whole, leaf.astype(jnp.float32)).items():
            if path.startswith('h/'):
                per_layer = jnp.sqrt(jnp.sum(
                    jnp.square(part), axis=tuple(range(1, part.ndim))))
                for layer in range(part.shape[0]):
                    norms[f'h_{layer}/{path[2:]}'] = per_layer[layer]
            else:
                norms[path] = jnp.sqrt(jnp.sum(jnp.square(part)))
    return norms


def unrolled_norms(tree: dict) -> dict:
    """Per-leaf L2 norms of an unrolled tree (the program's layout)."""
    return {path: jnp.sqrt(jnp.sum(jnp.square(part)))
            for whole, leaf in flatten(tree).items()
            for path, part in _parts(whole, leaf.astype(jnp.float32)).items()}
