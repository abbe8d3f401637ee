"""Paged KV-cache management: the free-list, block tables, and the
device-side admission writes.

The device layout lives in :func:`tpusystem.ops.attention.paged_attention`
(one shared pool of ``num_blocks * block_size`` token slots per layer,
per-row block tables mapping logical blocks to physical ones). This module
is the **host-side authority** over that layout: which physical blocks are
free, which row owns which blocks, and what every row's table says —
:class:`PagedKVCache` — plus the two jitted cache edits the engine uses to
change batch membership without retracing its decode step:

* :func:`adopt_prefill` scatters a prefilled contiguous KV strip into a
  row's allocated blocks (one program total — admission is a pair of
  device calls, never a reshape of the pool);
* :func:`write_tables` replaces every layer's ``table`` cache leaf with
  the host authority's current map (evictions and admissions both reduce
  to this table edit).

Physical block 0 is the reserved **trash block**: unmapped table entries
point there, so a retired row's dead writes (the fixed-shape step keeps
computing every row) land in trash instead of a live row's blocks.

``share_prefix=True`` adds SGLang/RadixAttention-style **prefix
sharing** on top of the same pool: every allocated block carries a
refcount, and each block whose span is fully covered by its row's
prompt registers in a radix index keyed by the exact token prefix it
caches. A later admission whose prompt starts with the same tokens
adopts those blocks by reference (refcount increment, no copy, no
recompute) and allocates fresh blocks only for its uncached suffix.
Retirement decrements refcounts; a block leaves the live set only at
refcount zero — and a zero-ref block that still holds registered prefix
content parks in a warm LRU cache (it counts as free capacity and is
reclaimed, content dropped, when the free list runs dry) so back-to-back
traffic on one system prompt keeps hitting. The aliasing contract
tightens rather than weakens: two rows may share a physical block ONLY
when their prompts agree on every token the block caches, shared blocks
are never written (prompt KV is write-once; decode writes always start
past the shared region because sharing stops at whole prompt-covered
blocks), and the trash-block discipline is unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TRASH_BLOCK = 0


class PagedKVCache:
    """Host-side free-list + block-table authority for the paged pool.

    Pure bookkeeping (numpy only — unit-testable without a device):
    ``admit`` allocates the blocks covering a sequence's whole token
    budget up front (prompt + generation, so decode never stalls on a
    mid-stream allocation), ``evict`` returns them to the free list and
    resets the row's table to trash. The device copies of the tables are
    refreshed from :attr:`table` via :func:`write_tables`.

    ``share_prefix=True`` enables the refcounted radix index (module
    docstring): ``admit`` then takes the row's prompt tokens, reuses
    every cached whole-block prefix match by reference, and
    :meth:`shared_tokens` tells the engine how many leading positions
    arrived pre-filled (so it can skip recomputing them and must NOT
    scatter over them). Default off — the unshared accounting below is a
    pinned contract of its own.
    """

    def __init__(self, rows: int, blocks: int, block_size: int,
                 max_seq: int, share_prefix: bool = False) -> None:
        if max_seq % block_size:
            raise ValueError(f'max_seq ({max_seq}) must be a multiple of '
                             f'block_size ({block_size})')
        if blocks < 2:
            raise ValueError('need at least 2 blocks (block 0 is the '
                             'reserved trash block)')
        self.rows, self.blocks, self.block_size = rows, blocks, block_size
        self.max_blocks = max_seq // block_size
        self.max_seq = max_seq
        self.share_prefix = share_prefix
        # LIFO free list over blocks 1..blocks-1 (0 is trash)
        self._free = list(range(blocks - 1, 0, -1))
        self._owned: dict[int, list[int]] = {}
        self.table = np.full((rows, self.max_blocks), TRASH_BLOCK, np.int32)
        # --- sharing state (unused when share_prefix is False) ---
        self._refs: dict[int, int] = {}        # live block -> refcount >= 1
        self._cached: dict[int, tuple] = {}    # zero-ref warm block -> key
        #                                        (insertion order = LRU)
        self._keys: dict[tuple, int] = {}      # prefix tokens -> block
        self._block_key: dict[int, tuple] = {} # block -> its registered key
        self._shared_len: dict[int, int] = {}  # row -> adopted prefix tokens

    @property
    def free_blocks(self) -> int:
        """Allocatable blocks: the free list plus (under sharing) warm
        zero-ref prefix blocks, which are reclaimed on demand."""
        return len(self._free) + len(self._cached)

    @property
    def live_blocks(self) -> int:
        """Blocks currently referenced by at least one seated row."""
        if self.share_prefix:
            return len(self._refs)
        return sum(len(ids) for ids in self._owned.values())

    def blocks_for(self, tokens: int) -> int:
        """Physical blocks covering ``tokens`` cache slots."""
        return -(-tokens // self.block_size)

    def can_admit(self, tokens: int, prompt=None) -> bool:
        needed = self.blocks_for(tokens)
        if needed > self.max_blocks:
            return False
        if self.share_prefix and prompt is not None:
            cached, _ = self.adoptable_prefix(prompt)
            needed -= cached // self.block_size
        return needed <= self.free_blocks

    # ------------------------------------------------------- radix index

    def match_prefix(self, prompt) -> tuple[int, list[int]]:
        """Longest cached whole-block prefix of ``prompt``:
        ``(cached_tokens, block_ids)``. A block matches only when the
        index holds its EXACT token prefix (the radix key is the tokens
        themselves — no hash collisions, no partial blocks), so two rows
        can alias a block only through identical prompt prefixes."""
        if not self.share_prefix:
            return 0, []
        prompt = [int(t) for t in prompt]
        ids = []
        for k in range(min(len(prompt) // self.block_size, self.max_blocks)):
            key = tuple(prompt[:(k + 1) * self.block_size])
            block = self._keys.get(key)
            if block is None:
                break
            ids.append(block)
        return len(ids) * self.block_size, ids

    def adoptable_prefix(self, prompt) -> tuple[int, list[int]]:
        """:meth:`match_prefix` capped so at least ONE prompt token stays
        uncached: admission always prefills a non-empty suffix (its
        last-token logits are the request's first emitted token, and that
        token's KV write must land in a private block, never a shared
        one), so the match runs against ``prompt[:-1]``."""
        prompt = list(prompt)
        if len(prompt) < 2:
            return 0, []
        return self.match_prefix(prompt[:len(prompt) - 1])

    def shared_tokens(self, row: int) -> int:
        """How many leading positions of ``row`` were adopted from the
        radix index at admission (0 without sharing)."""
        return self._shared_len.get(row, 0)

    def _allocate(self) -> int:
        """One fresh block: the free list first, else reclaim the
        least-recently-parked warm prefix block (its content — and its
        radix key — are dropped; refcounted LIVE blocks are never
        touched)."""
        if self._free:
            return self._free.pop()
        block, key = next(iter(self._cached.items()))
        del self._cached[block]
        del self._keys[key]
        del self._block_key[block]
        return block

    def _acquire(self, block: int) -> None:
        """Take one reference on a matched block (reviving it from the
        warm cache if it sat at refcount zero)."""
        if block in self._cached:
            del self._cached[block]
        self._refs[block] = self._refs.get(block, 0) + 1

    def _register(self, ids: list, prompt) -> None:
        """Index every block whose span the prompt fully covers. Those
        blocks are write-once by construction: decode writes start at
        ``len(prompt)``, which lies past every fully-covered block. A
        chunk whose key is already indexed keeps the existing holder
        (one canonical copy per prefix)."""
        prompt = [int(t) for t in prompt]
        for k, block in enumerate(ids):
            if (k + 1) * self.block_size > len(prompt):
                break
            key = tuple(prompt[:(k + 1) * self.block_size])
            if key not in self._keys and block not in self._block_key:
                self._keys[key] = block
                self._block_key[block] = key

    # --------------------------------------------------------- admission

    def admit(self, row: int, tokens: int, prompt=None) -> np.ndarray:
        """Allocate ``tokens`` worth of blocks to ``row`` and return the
        ``[max_seq]`` physical token-slot map of the row (positions past
        the allocation map to trash) — the scatter index
        :func:`adopt_prefill` writes the prefilled KV through.

        With ``share_prefix`` and a ``prompt``, the leading blocks come
        from the radix index where it matches (refcount increment — the
        caller must then mask its adoption scatter below
        :meth:`shared_tokens` so shared blocks stay write-once) and the
        prompt's own fully-covered blocks are registered for future
        admissions."""
        if row in self._owned:
            raise ValueError(f'row {row} already owns blocks — evict first')
        needed = self.blocks_for(tokens)
        if needed > self.max_blocks:
            raise ValueError(f'{tokens} tokens need {needed} blocks, over '
                             f'the per-row table width {self.max_blocks}')
        shared_ids: list[int] = []
        if self.share_prefix and prompt is not None:
            _, shared_ids = self.adoptable_prefix(prompt)
            shared_ids = shared_ids[:needed]
        if needed - len(shared_ids) > self.free_blocks:
            raise ValueError(
                f'{needed - len(shared_ids)} blocks needed, '
                f'{self.free_blocks} free — admission must wait (queue, '
                f'do not crash)')
        for block in shared_ids:
            self._acquire(block)
        fresh = [self._allocate() for _ in range(needed - len(shared_ids))]
        if self.share_prefix:
            for block in fresh:
                self._refs[block] = 1
        ids = shared_ids + fresh
        self._owned[row] = ids
        self.table[row, :needed] = ids
        self.table[row, needed:] = TRASH_BLOCK
        if self.share_prefix and prompt is not None:
            self._shared_len[row] = len(shared_ids) * self.block_size
            self._register(ids, prompt)
        return self.slots(row)

    def slots(self, row: int) -> np.ndarray:
        """``[max_seq]`` physical token slot of each logical position of
        ``row`` under its current table (trash wherever unmapped)."""
        positions = np.arange(self.max_seq)
        physical = self.table[row, positions // self.block_size]
        return (physical * self.block_size
                + positions % self.block_size).astype(np.int32)

    def adoption_slots(self, row: int) -> np.ndarray:
        """:meth:`slots` with the shared prefix redirected to trash: the
        adoption scatter's index for a row admitted through the radix
        index. Shared blocks already hold the prefix KV and are
        write-once, so the positions they cache must scatter their
        (identical, or resume-zeroed) strip values into the trash block
        instead."""
        slots = self.slots(row)
        shared = self.shared_tokens(row)
        if shared:
            positions = np.arange(self.max_seq)
            slots = np.where(
                positions < shared,
                (positions % self.block_size).astype(np.int32), slots)
        return slots

    def evict(self, row: int) -> int:
        """Retire ``row``: every owned block drops one reference, and a
        block leaves the live set only at refcount zero — then to the
        warm cache if it still holds registered prefix content, else to
        the free list. Returns how many blocks the row released."""
        freed = self._owned.pop(row, [])
        self._shared_len.pop(row, None)
        if not self.share_prefix:
            self._free.extend(reversed(freed))
        else:
            for block in reversed(freed):
                self._refs[block] -= 1
                if self._refs[block]:
                    continue
                del self._refs[block]
                key = self._block_key.get(block)
                if key is not None:
                    self._cached[block] = key      # warm, LRU-ordered
                else:
                    self._free.append(block)
        self.table[row] = TRASH_BLOCK
        return len(freed)

    def audit(self) -> dict:
        """Invariant check for the churn tests: every non-trash block is
        in exactly one of {free, warm-cached, live}; refcounts equal the
        number of owning rows; tables agree with ownership; the radix
        index is consistent. Returns summary counts."""
        if self.share_prefix:
            owners: dict[int, int] = {}
            for ids in self._owned.values():
                for block in ids:
                    owners[block] = owners.get(block, 0) + 1
            assert owners == self._refs, (owners, self._refs)
            states = [set(self._free), set(self._cached), set(self._refs)]
            everything: set[int] = set()
            for state in states:
                assert not (state & everything), 'block in two states'
                everything |= state
            assert everything == set(range(1, self.blocks))
            assert set(self._keys.values()) == set(self._block_key)
            for block, key in self._block_key.items():
                assert self._keys[key] == block
        else:
            live = [b for ids in self._owned.values() for b in ids]
            assert len(live) == len(set(live)), 'unshared pool aliased a block'
            assert sorted(live + self._free) == list(range(1, self.blocks))
        for row in range(self.rows):
            ids = self._owned.get(row, [])
            mapped = [int(b) for b in self.table[row] if b != TRASH_BLOCK]
            assert mapped == ids, (row, mapped, ids)
        return {'free': len(self._free), 'cached': len(self._cached),
                'live': self.live_blocks}


def _is_kv(path) -> bool:
    return path[-1] in (jax.tree_util.DictKey('key'),
                        jax.tree_util.DictKey('value'))


@functools.partial(jax.jit, donate_argnums=(0,))
def adopt_prefill(cache, prefill_cache, slots, row, length):
    """Admit a prefilled sequence into ``row`` of the paged cache.

    ``prefill_cache`` is the contiguous decode cache a plain (non-paged)
    prefill apply left behind — per-layer KV strips ``[1, max_seq, heads,
    head_dim]``, flattened here to the pool's ``[max_seq, heads *
    head_dim]`` rows and scattered in place into the donated pool;
    ``slots`` is the row's ``[max_seq]`` physical token-slot
    map (:meth:`PagedKVCache.slots`, trash-padded past the allocation, so
    pad-bucket junk beyond the prompt scatters into trash or into
    positions the decode write overwrites before the mask ever exposes
    them); ``row``/``length`` set the row's cursors to the prompt length.
    A recurrent layer's per-row state leaves (``state``/``conv``:
    :func:`tpusystem.train.cursors.is_row_state`) have no slots: the
    prefill's row 0 overwrites row ``row`` whole, in this same program.
    Tables are not touched here — :func:`write_tables` is the one table
    authority. One compiled program for every admission (prefill strips
    share one shape across buckets: the cache is allocated ``max_seq``
    wide regardless of prompt length)."""
    from tpusystem.train.cursors import is_cursor, is_row_state
    source = {jax.tree_util.keystr(path): leaf for path, leaf
              in jax.tree_util.tree_leaves_with_path(prefill_cache)}

    def fix(path, leaf):
        if _is_kv(path):
            strip = source[jax.tree_util.keystr(path)][0]  # [max_seq, h, d]
            return leaf.at[slots].set(
                strip.reshape(strip.shape[0], -1).astype(leaf.dtype))
        if is_row_state(path):
            return leaf.at[row].set(
                source[jax.tree_util.keystr(path)][0].astype(leaf.dtype))
        if is_cursor(path):
            return leaf.at[row].set(jnp.asarray(length, leaf.dtype))
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def write_tables(cache, tables):
    """Replace every layer's ``table`` cache leaf with the host
    authority's ``[rows, max_blocks]`` map (broadcast over a scanned
    stack's leading layer dim). Admission maps a row's logical blocks to
    its fresh allocation; eviction resets them to trash — either way the
    whole membership change is this table edit plus (for admissions)
    :func:`adopt_prefill`'s block writes."""
    def fix(path, leaf):
        if path[-1] == jax.tree_util.DictKey('table'):
            return jnp.broadcast_to(jnp.asarray(tables, leaf.dtype),
                                    leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


def pool_shardings(cache, mesh, kv_heads: int):
    """Per-leaf :class:`~jax.sharding.NamedSharding` for a paged pool
    under a TP mesh — the mesh-aware half of the pool contract.

    KV leaves ``[..., slots, kv_heads * head_dim]`` shard their minor dim
    on the ``model`` axis: heads lie side by side there, so each device
    holds whole attention heads' lanes for every slot — the same head
    split the TP matmuls already use, so decode reads its KV locally.
    ``kv_heads`` that don't divide the axis fall back replicated (a
    split through the middle of a head serves nobody), the same
    divisibility discipline as
    :meth:`~tpusystem.parallel.sharding.ShardingPolicy.spec`. Everything
    else — block tables, cursors, masks, a recurrent layer's per-row state
    (no engine shards one yet: ``Engine`` refuses a mesh over a recurrent
    module) — replicates: the host-side
    :class:`PagedKVCache` stays the ONE block-table authority and
    ``adopt_prefill``/``write_tables`` keep their contracts unchanged.
    """
    from jax.sharding import NamedSharding, PartitionSpec
    from tpusystem.parallel.mesh import MODEL
    model = dict(mesh.shape).get(MODEL, 1)

    def spec(path, leaf):
        if _is_kv(path) and kv_heads % model == 0:
            axes = [None] * leaf.ndim
            axes[-1] = MODEL
            return NamedSharding(mesh, PartitionSpec(*axes))
        return NamedSharding(mesh, PartitionSpec())
    return jax.tree_util.tree_map_with_path(spec, cache)
