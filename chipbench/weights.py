"""What seeded weights share whatever the family: the key a seed becomes and
plain nested trees by leaf path. The leaf tables and the draws themselves
are a family's (``chipbench/families/<family>.py``).
"""

from __future__ import annotations

import jax


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (``--seed`` may pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)


def nest(flat: dict) -> dict:
    """``{'a/b': x} -> {'a': {'b': x}}``."""
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
