"""Per-row KV-cache cursor authority.

Every path that manages decode-cache rows independently — speculative
decoding's rewind-to-accepted-prefix (:mod:`tpusystem.train.generate`),
token-tree verify's winner-row copy, and the serving engine's
admit/evict row recycling (:mod:`tpusystem.serve.engine`) — edits the
same two kinds of cache leaves: the per-layer ``index`` cursor that
:func:`tpusystem.ops.attention.cached_attention` writes and masks at
(and that Llama's rotary reads), and GPT-2's model-level ``position``
offset. This module is the single implementation of those edits, so the
speculative path and the engine cannot drift on which leaves count as
cursors or how scanned stacks broadcast.

A recurrent layer (:class:`tpusystem.ops.ssm.Mamba2`) keeps a third kind of
leaf beside keys/values and cursors: **per-row state** (``state``, the
float32 recurrent state, and ``conv``, the convolution's last inputs),
addressed by row, with no positions a cursor could mask. :func:`is_row_state`
names them for every path that edits a cache: a row is written whole
(admission), gathered whole (:func:`gather_rows`), and never rewound
(:func:`rewind`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# The cache-collection leaf names that hold per-row cursor state: the
# per-layer KV cursor (``index`` — also what Llama's rotary reads) and
# GPT-2's learned-position offset (``position``).
CURSOR_KEYS = (jax.tree_util.DictKey('index'),
               jax.tree_util.DictKey('position'))


# The per-row state leaves of a recurrent layer, by name -> how many
# trailing dims follow the batch axis (``state [batch, H, P, N]``, ``conv
# [batch, taps - 1, channels]``).
ROW_STATE_DIMS = {jax.tree_util.DictKey('state'): 3,
                  jax.tree_util.DictKey('conv'): 2}


def is_cursor(path) -> bool:
    """True when a cache tree path addresses a cursor leaf."""
    return path[-1] in CURSOR_KEYS


def is_row_state(path) -> bool:
    """True when a cache tree path addresses a recurrent layer's per-row
    state leaf (``state`` or ``conv``)."""
    return path[-1] in ROW_STATE_DIMS


def holds_row_state(cache) -> bool:
    """Whether a cache tree (arrays or shapes) has any per-row state leaf:
    whether the module that declared it is recurrent."""
    return any(is_row_state(path) for path, _
               in jax.tree_util.tree_leaves_with_path(cache))


def rewind(cache, cursor, *, state_stands: bool = False):
    """Set every cache cursor to ``cursor`` (``[batch]`` int, or a
    scalar broadcast over rows) — rows beyond it are garbage from
    rejected speculation or a retired serving row, masked out by the
    cursor-based attention mask and overwritten by the next accepted
    tokens. Scanned stacks carry cursors at a leading layer dim; the
    ``[batch]`` cursor broadcasts into whatever shape the leaf has.

    A per-row state leaf cannot be rewound: the state after the rejected
    tokens has no masked positions to fall back behind. A cache that holds
    one is **refused** (``ValueError`` at trace time) unless the caller
    says the state already stands where the cursors are put
    (``state_stands=True``: the serving engine's tick, which moves an active
    row's cursor past the one token its state took in and parks a retired
    row's, whose state nobody reads again)."""
    if not state_stands and holds_row_state(cache):
        raise ValueError(
            'this cache holds a recurrent state (state/conv leaves): a '
            'state cannot be rewound to an earlier position — speculative '
            'decoding over a recurrent layer needs a state snapshot to '
            'fall back to')

    def fix(path, leaf):
        if is_cursor(path):
            return jnp.broadcast_to(jnp.asarray(cursor, leaf.dtype),
                                    leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


def read_cursor(cache):
    """The per-row ``[batch]`` cursor of a decode cache — the first
    ``index`` leaf found (every layer's agrees under the :func:`rewind`
    discipline; scanned stacks return layer 0's slice)."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if path[-1] == jax.tree_util.DictKey('index'):
            return leaf.reshape(-1, leaf.shape[-1])[0] if leaf.ndim > 1 \
                else leaf
    raise ValueError('no index cursor leaf in this cache tree — was the '
                     'cache created by a decode-mode apply?')


def gather_rows(cache, rows):
    """Overwrite every row's cache with row ``rows[i]``'s (token-tree
    verify's winner-copy): KV leaves gather on their batch axis — always
    ``ndim - 4`` for the contiguous ``[..., batch, max_seq, heads,
    head_dim]`` cache layout, which also covers scanned stacks' leading
    layer dim — and cursor leaves (``index``/``position``) on their last
    axis; a per-row state leaf (``state``/``conv``) is gathered whole by
    row, on its own batch axis. Contiguous caches only: a paged cache's
    pool has no batch axis (rows alias blocks through the table), so row
    copies there are block copies, owned by
    :class:`tpusystem.serve.PagedKVCache`."""
    def fix(path, leaf):
        if is_cursor(path):
            axis = leaf.ndim - 1
        elif is_row_state(path):
            axis = leaf.ndim - 1 - ROW_STATE_DIMS[path[-1]]
        else:
            axis = leaf.ndim - 4
        return jnp.take(leaf, rows, axis=axis)
    return jax.tree_util.tree_map_with_path(fix, cache)
