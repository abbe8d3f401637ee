"""Attention ops.

The XLA-first implementation: plain einsum attention that the compiler fuses
and tiles onto the MXU, with softmax accumulated in float32 regardless of the
activation dtype (bf16-safe). The Pallas flash kernel
(:mod:`tpusystem.ops.pallas.flash`) and the ring/sequence-parallel variant
(:mod:`tpusystem.ops.ring`) plug in behind the same signature.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpusystem.parallel.mesh import on_tpu

NEG_INF = -1e30


def causal_mask(query_length: int, key_length: int,
                *, offset: int | jax.Array = 0) -> jax.Array:
    """Boolean [q, k] mask where True = attend. ``offset`` is the position of
    the first query relative to the first key (used by ring attention blocks;
    may be a traced value such as ``rank * chunk``)."""
    query_positions = jnp.arange(query_length)[:, None] + offset
    key_positions = jnp.arange(key_length)[None, :]
    return query_positions >= key_positions


def repeat_kv_heads(query, key, value):
    """Broadcast grouped KV heads up to the query head count (Llama-3 GQA).
    The single implementation behind every attention kernel."""
    query_heads, kv_heads = query.shape[2], key.shape[2]
    if kv_heads == query_heads:
        return key, value
    assert query_heads % kv_heads == 0, (
        f'query heads ({query_heads}) must be a multiple of KV heads '
        f'({kv_heads}) for grouped-query attention')
    group = query_heads // kv_heads
    return jnp.repeat(key, group, axis=2), jnp.repeat(value, group, axis=2)


def attend(query, key, value, *, kernel: str = 'xla', mesh=None,
           causal: bool = True, dropout: float = 0.0, dropout_rng=None):
    """Kernel dispatch shared by the model families.

    ``'xla'`` routes to :func:`dot_product_attention` (GSPMD-shardable,
    GQA-aware, optional probability dropout). ``'flash'`` is the Pallas
    O(seq)-memory kernel — single-shard when ``mesh`` is None, composed
    with DP/FSDP/TP via ``shard_map`` over the (data, fsdp) x model axes
    when a mesh is passed; attention-probability dropout runs in-kernel
    (positional hash masks regenerated in the backward).
    ``'ring'``/``'ulysses'`` are the sequence-parallel variants (need
    ``mesh`` with a seq axis); grouped KV stays grouped on the ring
    variants (group-factor fewer ppermute bytes, KV shared across each
    query-head group by the flash inner kernel) and is broadcast only for
    ulysses, whose all_to_all splits the head axis; probability dropout
    is not implemented there.
    """
    if kernel == 'xla':
        return dot_product_attention(query, key, value, causal=causal,
                                     dropout=dropout, dropout_rng=dropout_rng)
    if kernel == 'flash':  # flash broadcasts GQA heads itself
        from tpusystem.ops.pallas.flash import (flash_attention,
                                                sharded_flash_attention)
        if mesh is not None:  # compose with DP/FSDP/TP via shard_map
            return sharded_flash_attention(query, key, value, mesh,
                                           causal=causal, dropout=dropout,
                                           dropout_rng=dropout_rng)
        return flash_attention(query, key, value, causal=causal,
                               dropout=dropout, dropout_rng=dropout_rng)
    if dropout:
        raise ValueError("attention-probability dropout is only implemented "
                         f"on the 'xla' and 'flash' kernels, not {kernel!r}")
    if kernel in ('ring', 'ulysses'):
        from tpusystem.ops.ring import ring_self_attention
        # grouped KV stays grouped on the ring: the rotating ppermutes then
        # move group-factor fewer bytes and the flash inner kernel shares
        # KV across each query-head group itself (ulysses repeats inside
        # ring_self_attention — its all_to_all splits the head axis)
        if mesh is None:
            raise ValueError(
                f'{kernel!r} attention needs a mesh with a seq axis '
                '(pass mesh=... to the model)')
        return ring_self_attention(query, key, value, mesh,
                                   causal=causal, variant=kernel)
    raise ValueError(f'unknown attention kernel {kernel!r}; '
                     "expected 'xla', 'flash', 'ring' or 'ulysses'")


def _debug_cache_enabled() -> bool:
    """Opt-in runtime verification of decode-cache contracts
    (``TPUSYSTEM_DEBUG_CACHE=1``); read per trace so tests can flip it.

    **Trace time, not run time**: the flag decides whether the check is
    baked into the program, so already-compiled decode programs keep the
    setting they were traced with. Set the env var before the first
    ``generate`` call (or ``jax.clear_caches()`` to force a retrace) —
    flipping it mid-process does not arm checks in cached executables.
    """
    import os
    return os.environ.get('TPUSYSTEM_DEBUG_CACHE', '') == '1'


def _assert_uniform_cursor(cursor):
    """Host-side check behind :func:`_debug_cache_enabled`: the
    ``per_row=False`` fast path writes every row's KV at ``cursor[0]``."""
    import numpy as np
    cursor = np.asarray(cursor)
    if (cursor != cursor[0]).any():
        raise ValueError(
            f'cached_attention(per_row=False) requires a uniform cache '
            f'cursor, got {cursor!r}; pass per_row=True for externally '
            'managed or speculative cursor state')


def paged_read(length: int, heads: int, kv_heads: int, head_dim: int,
               block: int, max_blocks: int, dtype, *, sharded: bool = False
               ) -> tuple[str, str | None]:
    """Which read a paged step of ``length`` new tokens a row takes over
    ``[slots, kv_heads * head_dim]`` pools of ``dtype``, from shapes and the
    platform alone: ``('kernel', None)`` — the pool read in place by
    :func:`tpusystem.ops.pallas.paged_attention.paged_decode_attention` —
    or ``('gather', why)``, the bucketed window of :func:`paged_attention`.
    The one decision behind :func:`paged_attention`'s dispatch and the
    engine's ``paged_read`` record (the ``fused_paged_reason``
    discipline)."""
    if length != 1:
        return 'gather', (f'a query window of {length} tokens a row: the '
                          'paged-attention kernel reads for one')
    if not on_tpu():
        return 'gather', 'not on a TPU'
    if sharded:
        return 'gather', ('the pools are sharded over a mesh and the '
                          'paged-attention kernel is single-device')
    from tpusystem.ops.pallas.paged_attention import paged_plan
    if paged_plan(heads, head_dim, block, max_blocks, dtype, False,
                  kv_heads) is None:
        return 'gather', (
            f'the paged-attention kernel cannot tile heads={heads} on '
            f'{kv_heads} head_dim={head_dim} block_size={block} '
            f'{jnp.dtype(dtype).name} on the TPU (the pool\'s minor dim '
            'must fill 128 lanes and a block whole sublane tiles: 16 '
            'positions of bf16, 8 of f32)')
    return 'kernel', None


def paged_attention(module, query, key, value, max_seq: int,
                    pages: tuple[int, int]):
    """Incremental attention over a **paged** KV cache (block pool +
    per-row block tables) — the serving engine's layout
    (:mod:`tpusystem.serve`, vLLM's PagedAttention block-table idea on
    the :func:`cached_attention` machinery).

    ``pages = (num_blocks, block_size)``. Instead of each row owning a
    contiguous ``[max_seq, heads, head_dim]`` strip, the cache is one
    shared pool of ``num_blocks`` blocks of ``block_size`` tokens
    (``'key'``/``'value'`` cache variables, flattened to
    ``[num_blocks * block_size, kv_heads * head_dim]`` — one position's
    heads side by side on the minor dim, so a block is one contiguous,
    lane-dense tile on the TPU: stored ``[..., kv_heads, head_dim]`` the
    device keeps the *slot* dim minor and every program that scatters or
    gathers rows first transposes the whole pool), and each row
    maps its *logical* block ``j`` (tokens ``j*block_size ...``) to a
    physical block through a ``'table'`` cache variable
    (``[batch, max_seq // block_size]`` int32). A sequence's cache can
    then live in non-contiguous blocks, and batch-row membership changes
    are host-side table edits plus block writes — never a reshape of the
    pool, so the engine's decode program compiles once.

    Contract (owned by :class:`tpusystem.serve.Engine`): physical block
    0 is the **trash block** — every unmapped table entry points there,
    so retired rows' dead writes land in trash instead of a live row's
    blocks; distinct live rows never share a physical block; the table
    rows for a sequence are populated (host-side) before its cursor
    advances into them. Cursors are inherently per-row (the ``index``
    cursor leaf is the same ``[batch]`` int32 the contiguous per-row
    path uses, so :mod:`tpusystem.train.cursors` edits apply
    unchanged).

    Two reads, the same mathematics, chosen by :func:`paged_read` from
    shapes and the platform. One decoded token a row on the TPU, where the
    kernel's plan tiles, reads the pool in place
    (:func:`tpusystem.ops.pallas.paged_attention.paged_decode_attention`:
    a row's own table columns ``0 … cursor // block``, whole blocks into a
    double-buffered VMEM window, online softmax in float32, grouped
    queries against their key/value head as stored): a row pays for the
    positions it holds. Every other read (a longer query window —
    speculative verify —, a shape the plan refuses, a pool sharded over a
    mesh, off the TPU) is bucketed like the contiguous path, in block
    units: the smallest power-of-2 block window covering the deepest
    filled row is gathered from the pool (``lax.switch`` over static
    widths — one compiled program, capacity-independent read cost),
    masked at each row's own depth. Masked positions contribute exact
    zeros, so a row's output is independent of its co-batched traffic in
    window-length-invariant arithmetic (f32; the same caveat as
    speculative verify applies at the TPU MXU's default precision).
    Scopes, as :func:`latent_attention`'s: ``kv_write`` (the scatter) and
    ``kv_read`` (the kernel, or the gather and the attention over it).
    """
    num_blocks, block = pages
    if max_seq % block:
        raise ValueError(f'max_seq ({max_seq}) must be a multiple of the '
                         f'page block_size ({block})')
    batch, length, kv_heads, head_dim = key.shape
    max_blocks = max_seq // block
    pool_shape = (num_blocks * block, kv_heads * head_dim)
    cache_key = module.variable('cache', 'key', jnp.zeros, pool_shape,
                                key.dtype)
    cache_value = module.variable('cache', 'value', jnp.zeros, pool_shape,
                                  value.dtype)
    table = module.variable('cache', 'table', jnp.zeros,
                            (batch, max_blocks), jnp.int32)
    index = module.variable('cache', 'index',
                            lambda: jnp.zeros((batch,), jnp.int32))
    if module.is_initializing():
        return dot_product_attention(query, key, value, causal=True)
    cursor = index.value                                        # [batch]
    positions = cursor[:, None] + jnp.arange(length)[None, :]   # [B, L]
    # physical token slot of each logical position, through the table;
    # past-capacity positions clamp onto the last table column — the
    # engine keeps those columns unmapped (trash), so overflow writes
    # are dead, never corrupting (the generate() capacity contract)
    logical = jnp.minimum(positions // block, max_blocks - 1)
    physical = jnp.take_along_axis(table.value, logical, axis=1)
    slots = (physical * block + positions % block).reshape(-1)  # [B*L]
    with jax.named_scope('kv_write'):
        cache_key.value = cache_key.value.at[slots].set(
            key.reshape(-1, kv_heads * head_dim).astype(
                cache_key.value.dtype))
        cache_value.value = cache_value.value.at[slots].set(
            value.reshape(-1, kv_heads * head_dim).astype(
                cache_value.value.dtype))
    index.value = cursor + length

    read, _ = paged_read(
        length, query.shape[2], kv_heads, head_dim, block, max_blocks,
        cache_key.value.dtype,
        sharded=getattr(module, 'mesh', None) is not None)
    if read == 'kernel':
        from tpusystem.ops.pallas.paged_attention import (
            paged_decode_attention)
        with jax.named_scope('kv_read'):
            return paged_decode_attention(
                query[:, 0], cache_key.value, cache_value.value, table.value,
                cursor, block=block)[:, None]

    # bucketed block-window read: gather the first `width` table columns'
    # blocks and mask at each row's logical depth — the cached_attention
    # bucket discipline, in block units (same starting point: the
    # smallest window is ~256 tokens, or the whole table when smaller)
    def attend_over(width: int):
        def run():
            mapped = jax.lax.slice_in_dim(table.value, 0, width, axis=1)
            tokens = (mapped[:, :, None] * block
                      + jnp.arange(block)[None, None, :]
                      ).reshape(batch, width * block)
            window = (batch, width * block, kv_heads, head_dim)
            keys = jnp.take(cache_key.value, tokens, axis=0).reshape(window)
            values = jnp.take(cache_value.value, tokens,
                              axis=0).reshape(window)
            mask = (jnp.arange(width * block)[None, None, :]
                    <= positions[:, :, None])                  # [B, L, W]
            return dot_product_attention(query, keys, values,
                                         causal=False, mask=mask[:, None])
        return run

    # the contiguous path starts its buckets at 256 tokens (a slice is
    # nearly free, so fine-grained switching buys little); the paged
    # read is a GATHER whose cost is proportional to the window, so it
    # starts at 64 tokens — shallow rows read 4x less pool
    with jax.named_scope('kv_read'):
        return _switch_on_depth(
            _read_buckets(max(1, 64 // block), max_blocks),
            (jnp.max(positions) + block) // block, attend_over)


def cached_attention(module, query, key, value, max_seq: int,
                     per_row: bool = False, pages: tuple | None = None):
    """Incremental (KV-cache) attention for autoregressive decoding.

    Called from inside a flax module in decode mode: maintains
    ``key``/``value``/``index`` variables in the ``'cache'`` collection
    (apply with ``mutable=['cache']``), appends this call's KV at the
    cache cursor, and attends the new queries over every filled position.
    KV is cached at its own head count — grouped-query broadcast happens
    inside :func:`dot_product_attention` — so the cache stays small under
    GQA. The single implementation behind both LM families' decode paths.

    Capacity contract: the caller keeps cumulative tokens within
    ``max_seq`` (:func:`tpusystem.train.generate` enforces it up front).
    Past capacity the cursor is a traced value, so no in-program error is
    possible — out-of-bounds scatter rows are silently dropped (the new
    K/V is never written and attention reads stale/zero positions).

    The cursor (``index``) is **per-row** — ``[batch]`` int32 — so rows
    may sit at different depths: speculative decoding advances each
    sequence by its own acceptance count instead of the batch minimum
    (``per_row=True``). Ordinary decode keeps every row equal, and with
    ``per_row=False`` (default) the cache write uses a single
    ``dynamic_update_slice`` at the shared cursor instead of a
    computed-2D-index scatter — on TPU the scatter in the per-token hot
    loop is the slower lowering. The caller owns the uniformity guarantee
    (``tpusystem.train.generate`` passes ``per_row`` only on the
    speculative path): any externally managed cursor state that may
    diverge per row — e.g. a cache left behind by a speculative run —
    **must** use ``per_row=True``, or rows whose cursor differs from row
    0 are silently corrupted. Set ``TPUSYSTEM_DEBUG_CACHE=1`` to verify
    the contract at runtime: a host callback checks cursor uniformity on
    every cached step and fails on violation — directly as the
    ``ValueError`` in eager code, or (inside ``jit``, where callbacks run
    async) as a callback-failure ``XlaRuntimeError`` at the next sync
    whose log carries the message. Debug-only — it forces a per-step
    host transfer.

    ``pages=(num_blocks, block_size)`` switches the cache to the paged
    block-pool layout (:func:`paged_attention` — the serving engine's
    non-contiguous per-row storage; implies per-row cursors).
    """
    if pages is not None:
        return paged_attention(module, query, key, value, max_seq, pages)
    batch, length, kv_heads, head_dim = key.shape
    if length > max_seq:
        # static shapes let this raise at trace time; per-step overflow
        # (cumulative tokens, a traced cursor) is the caller's contract —
        # tpusystem.train.generate enforces it up front
        raise ValueError(
            f'prompt length {length} exceeds the KV cache capacity '
            f'max_seq={max_seq}; raise max_seq or truncate the prompt')
    # Prefill is the call that creates the cache variables: detect it
    # before declaring them, so the prompt can attend over just its own
    # fresh K/V (causal) instead of the max_seq-wide zero-padded cache —
    # at Llama's max_seq=8192 a 128-token prompt would otherwise build
    # 64x oversized score tensors, all masked away.
    prefill = not module.has_variable('cache', 'index')
    cache_shape = (batch, max_seq, kv_heads, head_dim)
    cache_key = module.variable('cache', 'key', jnp.zeros, cache_shape, key.dtype)
    cache_value = module.variable('cache', 'value', jnp.zeros, cache_shape,
                                  value.dtype)
    index = module.variable('cache', 'index',
                            lambda: jnp.zeros((batch,), jnp.int32))
    if module.is_initializing():
        return dot_product_attention(query, key, value, causal=True)
    cursor = index.value                                    # [batch]
    positions = cursor[:, None] + jnp.arange(length)[None, :]   # [B, L]
    if per_row:
        rows = jnp.arange(batch)[:, None]
        cache_key.value = cache_key.value.at[rows, positions].set(
            key.astype(cache_key.value.dtype))
        cache_value.value = cache_value.value.at[rows, positions].set(
            value.astype(cache_value.value.dtype))
    else:
        if _debug_cache_enabled():
            jax.debug.callback(_assert_uniform_cursor, cursor)
        # uniform cursor: one dynamic_update_slice writes every row at the
        # shared offset (cursor[0] — the caller's uniformity contract).
        # Past-capacity behavior diverges from the scatter path: the slice
        # start clamps so the write lands at max_seq - length instead of
        # being dropped — both are inside the caller's capacity contract.
        start = cursor[0]
        cache_key.value = jax.lax.dynamic_update_slice(
            cache_key.value, key.astype(cache_key.value.dtype),
            (0, start, 0, 0))
        cache_value.value = jax.lax.dynamic_update_slice(
            cache_value.value, value.astype(cache_value.value.dtype),
            (0, start, 0, 0))
    index.value = cursor + length
    if prefill:
        # Long prompts route through the flash kernel: einsum attention
        # materializes the [B, H, L, L] scores tensor — at Llama's
        # max_seq=8192 that is exactly the allocation flash exists to
        # avoid, paid once per generation. flash_attention falls back to
        # the einsum path itself when the length cannot tile, so short
        # prompts lose nothing.
        if length >= 512:
            from tpusystem.ops.pallas.flash import flash_attention
            return flash_attention(query, key, value, causal=True)
        return dot_product_attention(query, key, value, causal=True)
    # attend causally over the filled prefix, per row (key position <=
    # row cursor + query offset). The cache is allocated max_seq wide,
    # but reading all of it every step makes decode cost scale with
    # *capacity*, not fill.
    # Bucketed attention reads only the smallest power-of-2 window
    # covering the filled prefix — lax.switch over static slice widths,
    # so shapes stay static per branch inside one compiled program.
    def attend_over(width: int):
        def run():
            keys = jax.lax.slice_in_dim(cache_key.value, 0, width, axis=1)
            values = jax.lax.slice_in_dim(cache_value.value, 0, width, axis=1)
            mask = (jnp.arange(width)[None, None, :]
                    <= positions[:, :, None])              # [B, L, W]
            return dot_product_attention(query, keys, values,
                                         causal=False, mask=mask[:, None])
        return run

    return _switch_on_depth(_read_buckets(256, max_seq),
                            jnp.max(positions) + 1, attend_over)


def _read_buckets(smallest: int, largest: int) -> list[int]:
    """Window widths doubling from ``smallest`` up to ``largest``."""
    buckets = [min(smallest, largest)]
    while buckets[-1] < largest:
        buckets.append(min(2 * buckets[-1], largest))
    return buckets


def _switch_on_depth(buckets: list[int], filled, attend_over):
    """``attend_over(width)()`` for the smallest bucket covering ``filled``
    (``lax.switch`` over static widths: one compiled program)."""
    if len(buckets) == 1:
        return attend_over(buckets[0])()
    chosen = sum((filled > width).astype(jnp.int32) for width in buckets[:-1])
    return jax.lax.switch(chosen, [attend_over(w) for w in buckets])


# the float32 scores of one block of queries may take this many bytes
# (128 heads x 256 queries x 4096 keys is 512 MiB)
_EXPANDED_SCORE_BYTES = 5 << 27


def expanded_latent_attention(query, key_rope, latent, w_key, w_value,
                               scale: float):
    """Causal attention with keys and values expanded per head from the
    latent rows (the prefill path of :func:`latent_attention`): ``k =
    [c·W_k ; k_rope]`` (the rope part shared by every head), ``v = c·W_v``.
    Queries go through in blocks, each over the keys up to its own last
    position, so the float32 scores never hold the whole square and the
    masked half above the diagonal is skipped block by block."""
    batch, length, heads, _ = query.shape
    rank = w_key.shape[0]
    content = latent[..., :rank]
    key = jnp.concatenate(
        [jnp.einsum('blc,chd->blhd', content, w_key),
         jnp.broadcast_to(key_rope[:, :, None, :],
                          (batch, length, heads, key_rope.shape[-1]))],
        axis=-1)
    value = jnp.einsum('blc,chd->blhd', content, w_value)
    block = length
    while block > 128 and batch * heads * block * length * 4 \
            > _EXPANDED_SCORE_BYTES:
        block //= 2
    if block >= length or length % block:
        return dot_product_attention(query, key, value, causal=True,
                                     scale=scale)
    out = []
    for start in range(0, length, block):
        stop = start + block
        mask = causal_mask(block, stop, offset=start)
        out.append(dot_product_attention(
            query[:, start:stop], key[:, :stop], value[:, :stop],
            causal=False, mask=mask[None, None], scale=scale))
    return jnp.concatenate(out, axis=1)


def latent_attention(module, q_content, q_rope, latent, w_key, w_value, *,
                     scale: float, max_seq: int, per_row: bool = False,
                     pages: tuple | None = None):
    """Incremental attention over a **latent** cache (multi-head latent
    attention, DeepSeek-V2): what is cached per position is one row
    ``[c_kv ; k_rope]`` — the normalised compressed key-value vector and the
    rotated positional key that every head shares — and not a key and a
    value per head.

    ``q_content [batch, len, heads, nope]`` and ``q_rope [batch, len, heads,
    rope]`` (rotated) are the two parts of the query; ``latent [batch, len,
    rank + rope]`` this call's rows; ``w_key [rank, heads, nope]`` and
    ``w_value [rank, heads, v]`` the two halves of the up-projection.
    Returns ``[batch, len, heads, v]``. Two paths, the same mathematics:

    * **prefill** (the call that creates the cache): keys and values are
      expanded per head from the latent and attended causally
      (:func:`expanded_latent_attention`);
    * **decode** (the cache exists): the up-projection is *absorbed* —
      ``q' = q_content · W_k^T`` per head (``nope -> rank``), scores ``=
      (q' · c + q_rope · k_rope) · scale`` straight against the cached rows,
      ``o' = softmax · c`` and ``o = o' · W_v`` — so a decoded token reads
      ``rank + rope`` values a position instead of ``heads x (nope + rope +
      v)``.

    The cache follows :func:`cached_attention`'s conventions with one KV
    leaf: ``'key'`` is ``[batch, max_seq, lanes]`` contiguous, or with
    ``pages = (num_blocks, block_size)`` the pool ``[num_blocks *
    block_size, lanes]`` behind the per-row ``'table'`` of
    :func:`paged_attention` (same trash block, same cursor leaf ``'index'``,
    no ``'value'`` leaf): the serving engine's admission, table and cursor
    edits match the leaves by those names and need no other. ``lanes`` is
    ``rank + rope`` rounded up to whole lanes of 128 (576 -> 640, the rest
    zeros): the TPU's tiled layout pads a row to whole lanes anyway, and
    given a minor dimension that is not lane-dense it keeps the *slot*
    dimension minor instead, so that every program that writes or gathers
    rows first transposes the whole pool.

    One decoded token a row over the paged pool is read in place by the
    Pallas kernel (:func:`tpusystem.ops.pallas.latent_attention.
    paged_latent_attention`) on the TPU where its plan tiles; every other
    read (a longer window, the contiguous cache, off the TPU) gathers a
    bucketed window as :func:`paged_attention` does, whole blocks at a
    time. Scopes: ``kv_write`` and ``kv_read`` (the gather included)."""
    batch, length, width = latent.shape
    rank = w_key.shape[0]
    lanes = -(-width // 128) * 128
    if pages is not None:
        num_blocks, block = pages
        if max_seq % block:
            raise ValueError(f'max_seq ({max_seq}) must be a multiple of '
                             f'the page block_size ({block})')
        shape = (num_blocks * block, lanes)
    else:
        if length > max_seq:
            raise ValueError(
                f'prompt length {length} exceeds the cache capacity '
                f'max_seq={max_seq}; raise max_seq or truncate the prompt')
        shape = (batch, max_seq, lanes)
    prefill = pages is None and not module.has_variable('cache', 'index')
    cache = module.variable('cache', 'key', jnp.zeros, shape, latent.dtype)
    if pages is not None:
        max_blocks = max_seq // block
        table = module.variable('cache', 'table', jnp.zeros,
                                (batch, max_blocks), jnp.int32)
    index = module.variable('cache', 'index',
                            lambda: jnp.zeros((batch,), jnp.int32))
    query = jnp.concatenate([q_content, q_rope], axis=-1)
    if module.is_initializing():
        return expanded_latent_attention(query, latent[..., rank:], latent,
                                          w_key, w_value, scale)
    cursor = index.value
    positions = cursor[:, None] + jnp.arange(length)[None, :]   # [B, L]
    rows = jnp.pad(latent.astype(cache.value.dtype),
                   ((0, 0), (0, 0), (0, lanes - width)))
    with jax.named_scope('kv_write'):
        if pages is not None:
            logical = jnp.minimum(positions // block, max_blocks - 1)
            physical = jnp.take_along_axis(table.value, logical, axis=1)
            slots = (physical * block + positions % block).reshape(-1)
            cache.value = cache.value.at[slots].set(rows.reshape(-1, lanes))
        elif per_row:
            cache.value = cache.value.at[
                jnp.arange(batch)[:, None], positions].set(rows)
        else:
            if _debug_cache_enabled():
                jax.debug.callback(_assert_uniform_cursor, cursor)
            cache.value = jax.lax.dynamic_update_slice(
                cache.value, rows, (0, cursor[0], 0))
        index.value = cursor + length
    if prefill:
        return expanded_latent_attention(query, latent[..., rank:], latent,
                                          w_key, w_value, scale)

    with jax.named_scope('mla_proj'):
        absorbed = jnp.concatenate(
            [jnp.einsum('blhd,chd->blhc', q_content, w_key), q_rope], axis=-1)

    if pages is not None and length == 1 and on_tpu():
        from tpusystem.ops.pallas.latent_attention import (
            latent_plan, paged_latent_attention)
        heads = q_content.shape[2]
        if latent_plan(heads, rank, block, max_blocks, cache.value.dtype,
                       False) is not None:
            with jax.named_scope('kv_read'):
                mixed = paged_latent_attention(
                    absorbed[:, 0], cache.value, table.value, cursor,
                    rank=rank, width=width, block=block, scale=scale)
            with jax.named_scope('mla_proj'):
                return jnp.einsum('blhc,chd->blhd', mixed[:, None], w_value)

    def attend_over(width_: int):
        def run():
            with jax.named_scope('kv_read'):
                if pages is not None:
                    # whole blocks: a block is whole sublane tiles, so the
                    # pool seen block by block is the stored pool itself
                    mapped = jax.lax.slice_in_dim(table.value, 0, width_,
                                                  axis=1)
                    window = jnp.take(
                        cache.value.reshape(num_blocks, block, lanes), mapped,
                        axis=0).reshape(batch, width_ * block, lanes)
                else:
                    window = jax.lax.slice_in_dim(cache.value, 0, width_,
                                                  axis=1)
                window = window[..., :width]
                span = window.shape[1]
                scores = jnp.einsum(
                    'blhc,bwc->bhlw', absorbed, window,
                    preferred_element_type=jnp.float32) * scale
                mask = (jnp.arange(span)[None, None, :]
                        <= positions[:, :, None])              # [B, L, W]
                scores = jnp.where(mask[:, None], scores, NEG_INF)
                weights = jax.nn.softmax(scores, axis=-1)
                return jnp.einsum('bhlw,bwc->blhc',
                                  weights.astype(window.dtype),
                                  window[..., :rank])
        return run

    if pages is not None:
        buckets = _read_buckets(max(1, 64 // block), max_blocks)
        filled = (jnp.max(positions) + block) // block
    else:
        buckets = _read_buckets(256, max_seq)
        filled = jnp.max(positions) + 1
    mixed = _switch_on_depth(buckets, filled, attend_over)
    with jax.named_scope('mla_proj'):
        return jnp.einsum('blhc,chd->blhd', mixed, w_value)


def dot_product_attention(query, key, value, *, causal: bool = True,
                          mask=None, scale: float | None = None,
                          dropout: float = 0.0, dropout_rng=None):
    """Multi-head attention over [batch, length, heads, head_dim] tensors.

    Softmax runs in float32; output returns in the input dtype. Supports
    grouped-query attention: when ``key``/``value`` carry fewer heads than
    ``query``, KV heads are broadcast over query-head groups (Llama-3 GQA).
    ``dropout`` > 0 (with ``dropout_rng``) drops attention probabilities.
    """
    input_dtype = query.dtype
    batch, length, heads, head_dim = query.shape
    kv_heads = key.shape[2]
    scale = scale if scale is not None else head_dim ** -0.5
    if (kv_heads != heads and not causal and dropout == 0.0
            and mask is not None and mask.ndim == 4):
        # grouped queries over a cache window (the decode read): the query
        # heads of a group go against their one key/value head as they are
        # stored — a [group, head_dim] x [head_dim, keys] product a head —
        # and nothing repeats the window's keys and values per query head
        # (at 32 query heads on 2 that is 16 copies of every cached row)
        assert heads % kv_heads == 0, (heads, kv_heads)
        group = heads // kv_heads
        scores = jnp.einsum(
            'bqhgd,bkhd->bhgqk',
            query.reshape(batch, length, kv_heads, group, head_dim), key,
            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask[:, :, None], scores, NEG_INF)
        weights = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum('bhgqk,bkhd->bqhgd', weights.astype(input_dtype),
                          value).reshape(batch, length, heads, head_dim)
    key, value = repeat_kv_heads(query, key, value)

    scores = jnp.einsum('bqhd,bkhd->bhqk', query, key,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        scores = jnp.where(causal_mask(query.shape[1], key.shape[1]),
                           scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    if dropout > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout, weights.shape)
        weights = jnp.where(keep, weights / (1.0 - dropout), 0.0)
    return jnp.einsum('bhqk,bkhd->bqhd', weights.astype(input_dtype), value)


# ------------------------------------------------ learned sparse attention

def _ordered_bits(values):
    """float32 -> uint32 in the same order (negative values' bits flipped
    whole, the others' sign bit set)."""
    bits = jax.lax.bitcast_convert_type(values.astype(jnp.float32),
                                        jnp.uint32)
    return jnp.where(bits >= jnp.uint32(1 << 31), ~bits,
                     bits | jnp.uint32(1 << 31))


def select_top(scores, valid, k: int):
    """``[..., positions]`` bool: the ``min(k, valid count)`` largest of
    ``scores`` among the ``valid`` positions, exactly (no approximate
    top-k): the ``k``-th largest value is found by a binary search over the
    scores' ordered bits, 32 passes of a compare and a count, and every
    valid position at or above it is kept (a tie at it keeps both)."""
    keys = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    need = jnp.broadcast_to(
        jnp.minimum(jnp.sum(valid, axis=-1, keepdims=True), k),
        keys.shape[:-1] + (1,))

    def narrow(bit, floor):
        trial = floor | (jnp.uint32(1) << (31 - bit).astype(jnp.uint32))
        enough = jnp.sum(keys >= trial, axis=-1, keepdims=True) >= need
        return jnp.where(enough, trial, floor)

    floor = jax.lax.fori_loop(0, 32, narrow,
                              jnp.zeros(need.shape, jnp.uint32))
    return valid & (keys >= floor)


def packed_selection(chosen, positions: int):
    """``[..., n]`` bool -> ``[..., positions / 32]`` int32 words: position
    ``p`` at bit ``p % 32`` of word ``p // 32``, positions past ``n``
    clear. What a selection is handed out as (the ``routing`` collection's
    ``selected``, beside the experts' ``chosen``)."""
    pad = [(0, 0)] * (chosen.ndim - 1) + [(0, positions - chosen.shape[-1])]
    bits = jnp.pad(chosen, pad).reshape(
        *chosen.shape[:-1], positions // 32, 32).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                    dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def index_scores(query, weights, keys):
    """``I(t, s) = sum_j w_j relu(q_j(t) · k_s)`` in float32: ``query
    [batch, len, heads, dim]``, ``weights [batch, len, heads]``, ``keys
    [batch, positions, dim]`` -> ``[batch, len, positions]``."""
    dots = jnp.einsum('bljd,bsd->bljs', query, keys,
                      preferred_element_type=jnp.float32)
    return jnp.einsum('blj,bljs->bls', weights.astype(jnp.float32),
                      jnp.maximum(dots, 0.0))


def row_cache(module, name: str, lanes: int, dtype, batch: int, *,
              max_seq: int, pages: tuple | None):
    """Declare one paged (or contiguous) per-position cache leaf ``name``
    of ``module``: ``[num_blocks * block, lanes]`` behind the table, else
    ``[batch, max_seq, lanes]``."""
    shape = ((pages[0] * pages[1], lanes) if pages is not None
             else (batch, max_seq, lanes))
    return module.variable('cache', name, jnp.zeros, shape, dtype)


def write_positions(cache, rows, positions, table, *, pages, per_row: bool):
    """Write ``rows [batch, len, lanes]`` at ``positions [batch, len]`` of
    a :func:`row_cache` leaf (``table`` the per-row block table when
    paged): what :func:`latent_attention` does under ``kv_write``."""
    batch, length, lanes = rows.shape
    rows = rows.astype(cache.value.dtype)
    if pages is not None:
        block, max_blocks = pages[1], table.shape[1]
        logical = jnp.minimum(positions // block, max_blocks - 1)
        physical = jnp.take_along_axis(table, logical, axis=1)
        slots = (physical * block + positions % block).reshape(-1)
        cache.value = cache.value.at[slots].set(rows.reshape(-1, lanes))
    elif per_row:
        cache.value = cache.value.at[
            jnp.arange(batch)[:, None], positions].set(rows)
    else:
        cache.value = jax.lax.dynamic_update_slice(
            cache.value, rows, (0, positions[0, 0], 0))
    return cache.value


def whole_rows(cache, table, pages, batch: int):
    """Every position of every row of a :func:`row_cache` leaf as ``[batch,
    max_seq, lanes]`` (the XLA read: a paged pool gathered whole blocks at
    a time through the table)."""
    if pages is None:
        return cache
    num_blocks, block = pages
    lanes = cache.shape[-1]
    return jnp.take(cache.reshape(num_blocks, block, lanes), table,
                    axis=0).reshape(batch, -1, lanes)


# keys one softmax row may span: on the TPU a longer float32 row of
# [128 heads, queries, keys] scores compiles to a softmax fusion that
# streams it a few lanes at a time (keys past ~4900 at 128 queries)
_SOFTMAX_KEYS = 4096


def _chunked_softmax_attention(query, key, value, mask, scale: float):
    """:func:`dot_product_attention` of ``query [batch, block, heads, d]``
    under ``mask [batch, 1, block, keys]``, its softmax rows cut into
    chunks of at most :data:`_SOFTMAX_KEYS` keys: each chunk's scores,
    largest score, exponential sum and unnormalised mix, joined exactly
    in float32 (the chunks' exponentials rescaled to the largest score of
    all). A chunk every key of which a query masks weighs nothing for it,
    as long as some other chunk holds a key it attends."""
    if key.shape[1] <= _SOFTMAX_KEYS:
        return dot_product_attention(query, key, value, causal=False,
                                     mask=mask, scale=scale)
    parts = []
    for lo in range(0, key.shape[1], _SOFTMAX_KEYS):
        hi = lo + _SOFTMAX_KEYS
        scores = jnp.einsum('bqhd,bkhd->bhqk', query, key[:, lo:hi],
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask[..., lo:hi], scores, NEG_INF)
        top = jnp.max(scores, axis=-1, keepdims=True)
        weights = jnp.exp(scores - top)
        mixed = jnp.einsum('bhqk,bkhd->bhqd', weights.astype(query.dtype),
                           value[:, lo:hi], preferred_element_type=jnp.float32)
        parts.append((top, jnp.sum(weights, axis=-1, keepdims=True), mixed))
    top = parts[0][0]
    for part in parts[1:]:
        top = jnp.maximum(top, part[0])
    scaled = [jnp.exp(part[0] - top) for part in parts]
    total = sum(s * part[1] for s, part in zip(scaled, parts))
    mixed = sum(s * part[2] for s, part in zip(scaled, parts))
    return jnp.swapaxes(mixed / total, 1, 2).astype(query.dtype)


def _blocked(query, key, value, scale: float, mask_of, reach=None):
    """Attention of ``query [batch, len, heads, d]`` over ``key``/``value``
    expanded per head, queries in blocks (as
    :func:`expanded_latent_attention`): a block ``[start, stop)`` attends
    keys ``[lo, stop)``, ``lo = max(0, start - reach)`` (``reach=None``: 0),
    under ``mask_of(start, stop, lo)`` (``[batch, block, stop - lo]``),
    its softmax rows in chunks (:func:`_chunked_softmax_attention`)."""
    batch, length, heads, _ = query.shape
    block = length
    while block > 128 and batch * heads * block \
            * min(length, _SOFTMAX_KEYS) * 4 > _EXPANDED_SCORE_BYTES:
        block //= 2
    if length % block:
        block = length
    out = []
    for start in range(0, length, block):
        stop = start + block
        lo = 0 if reach is None else max(0, start - reach)
        out.append(_chunked_softmax_attention(
            query[:, start:stop], key[:, lo:stop], value[:, lo:stop],
            mask_of(start, stop, lo)[:, None], scale))
    return jnp.concatenate(out, axis=1)


def _expanded(latent, w_key, w_value, key_rope):
    """Keys and values per head from the latent rows (prefill)."""
    batch, length, heads = latent.shape[0], latent.shape[1], w_key.shape[1]
    content = latent[..., :w_key.shape[0]]
    key = jnp.concatenate(
        [jnp.einsum('blc,chd->blhd', content, w_key),
         jnp.broadcast_to(key_rope[:, :, None, :],
                          (batch, length, heads, key_rope.shape[-1]))],
        axis=-1)
    return key, jnp.einsum('blc,chd->blhd', content, w_value)


def selected_latent_attention(query, latent, w_key, w_value, index_query,
                              index_weights, index_keys, *, scale: float,
                              topk: int, keep: int | None = None):
    """The prefill (and uncached) path of :func:`sparse_latent_attention`:
    keys and values expanded per head, queries in blocks, each block's index
    scores and selection made beside its attention. ``keep`` (a number of
    positions): also return every query's selection,
    :func:`packed_selection` ``[batch * len, keep / 32]``."""
    rank = w_key.shape[0]
    key, value = _expanded(latent, w_key, w_value, latent[..., rank:])
    kept = []

    def selected_over(start, stop, lo):
        causal = (jnp.arange(start, stop)[:, None]
                  >= jnp.arange(lo, stop)[None, :])
        with jax.named_scope('indexer'):
            scores = index_scores(index_query[:, start:stop],
                                  index_weights[:, start:stop],
                                  index_keys[:, lo:stop])
            chosen = select_top(scores, causal[None], topk)
            if keep:
                kept.append(packed_selection(chosen, keep))
            return chosen

    out = _blocked(query, key, value, scale, selected_over)
    if not keep:
        return out
    words = jnp.concatenate(kept, axis=1)
    return out, words.reshape(-1, words.shape[-1])


def banded_latent_attention(query, latent, w_key, w_value, *, scale: float,
                            window: int):
    """The prefill (and uncached) path of :func:`window_latent_attention`:
    keys and values expanded per head, queries in blocks over the keys of
    their own window."""
    rank = w_key.shape[0]
    key, value = _expanded(latent, w_key, w_value, latent[..., rank:])

    def banded(start, stop, lo):
        gap = (jnp.arange(start, stop)[:, None]
               - jnp.arange(lo, stop)[None, :])
        return ((gap >= 0) & (gap < window))[None]

    return _blocked(query, key, value, scale, banded, reach=window - 1)


def _latent_cache(module, latent, batch: int, *, max_seq: int,
                  pages: tuple | None):
    """The latent ``key`` leaf, the ``table`` and the ``index`` cursor of a
    layer, as :func:`latent_attention` declares them; ``(cache, table
    value or None, cursor, prefill)``."""
    if pages is not None and max_seq % pages[1]:
        raise ValueError(f'max_seq ({max_seq}) must be a multiple of the '
                         f'page block_size ({pages[1]})')
    prefill = pages is None and not module.has_variable('cache', 'index')
    lanes = -(-latent.shape[-1] // 128) * 128
    cache = row_cache(module, 'key', lanes, latent.dtype, batch,
                      max_seq=max_seq, pages=pages)
    table = None
    if pages is not None:
        table = module.variable('cache', 'table', jnp.zeros,
                                (batch, max_seq // pages[1]),
                                jnp.int32).value
    index = module.variable('cache', 'index',
                            lambda: jnp.zeros((batch,), jnp.int32))
    return cache, table, index, prefill


def _padded(rows, lanes: int):
    return jnp.pad(rows, ((0, 0), (0, 0), (0, lanes - rows.shape[-1])))


def sparse_latent_attention(module, q_content, q_rope, latent, w_key,
                            w_value, index_query, index_weights, index_keys,
                            index_pool, *, scale: float, topk: int,
                            max_seq: int, per_row: bool = False,
                            pages: tuple | None = None):
    """Latent attention over a **learned selection** (DeepSeek-V3.2's sparse
    attention): each query attends only the ``min(topk, t + 1)`` positions
    ``s <= t`` of largest index score ``I(t, s) = sum_j w_j relu(qI_j(t) ·
    kI_s)``, taken exactly (:func:`select_top`).

    Arguments as :func:`latent_attention`'s, plus the indexer's rotated
    queries ``index_query [batch, len, heads_I, dim_I]``, their weights
    ``index_weights [batch, len, heads_I]`` (the softmax scale folded in),
    this call's keys ``index_keys [batch, len, dim_I]`` and, in decode, the
    indexer's key leaf after this call's write ``index_pool`` (a
    :func:`row_cache` leaf of the indexer module, written through the
    layer's ``table``; unread at prefill). The layer's own cache leaves are
    :func:`latent_attention`'s (``key``, ``table``, ``index``).

    * **prefill**: keys and values expanded per head, queries in blocks,
      each block's index scores ``[heads_I, block, keys]`` and selection
      made beside its attention (never the whole square);
    * **decode**: every held position of a row is scored, the top ``topk``
      taken, and the absorbed query attends those: on the TPU one new
      token a row reads the pool through two walks of the row's own
      blocks (:func:`tpusystem.ops.pallas.index_scores.paged_index_scores`,
      then :func:`~tpusystem.ops.pallas.latent_attention.
      paged_latent_attention` with the selection as a mask: every held
      row is fetched, the unselected masked); elsewhere both reads gather
      whole rows.

    Every query's selection is sown into the ``routing`` collection as
    ``selected`` (:func:`packed_selection`, ``[batch * len, max_seq / 32]``)
    where the caller makes it mutable: what a replay of the layer needs
    beside the expert layers' ``chosen`` (``Engine(routing_sink=)``).

    Scopes: ``kv_write``; ``indexer`` (scoring and selection);
    ``kv_read/sparse_read`` (the attend over the selection)."""
    batch, length, width = latent.shape
    rank = w_key.shape[0]
    cache, table, index, prefill = _latent_cache(
        module, latent, batch, max_seq=max_seq, pages=pages)
    query = jnp.concatenate([q_content, q_rope], axis=-1)

    def handed(words):
        # every query's selection, for a caller that replays the layer
        # (the engine's routing_sink), beside the expert layers' choices
        module.sow('routing', 'selected', words,
                   init_fn=lambda: jnp.zeros(words.shape, jnp.int32),
                   reduce_fn=lambda _, new: new)

    def expanded():
        out, words = selected_latent_attention(
            query, latent, w_key, w_value, index_query, index_weights,
            index_keys, scale=scale, topk=topk, keep=max_seq)
        handed(words)
        return out

    if module.is_initializing():
        return expanded()
    cursor = index.value
    positions = cursor[:, None] + jnp.arange(length)[None, :]
    lanes = cache.value.shape[-1]
    with jax.named_scope('kv_write'):
        write_positions(cache, _padded(latent, lanes), positions, table,
                        pages=pages, per_row=per_row)
        index.value = cursor + length
    if prefill:
        with jax.named_scope('kv_read'), jax.named_scope('sparse_read'):
            return expanded()

    with jax.named_scope('mla_proj'):
        absorbed = jnp.concatenate(
            [jnp.einsum('blhd,chd->blhc', q_content, w_key), q_rope], axis=-1)
    heads = q_content.shape[2]
    if pages is not None and length == 1 and on_tpu():
        from tpusystem.ops.pallas.index_scores import (index_plan,
                                                       paged_index_scores)
        from tpusystem.ops.pallas.latent_attention import (
            latent_plan, paged_latent_attention)
        block, max_blocks = pages[1], max_seq // pages[1]
        span = index_plan(index_query.shape[2], index_query.shape[3], block,
                          max_blocks, index_pool.dtype, False)
        if span is not None and latent_plan(
                heads, rank, block, max_blocks, cache.value.dtype, False,
                lanes) is not None:
            with jax.named_scope('indexer'):
                scores = paged_index_scores(
                    index_query[:, 0], index_weights[:, 0], index_pool,
                    table, cursor, block=block)
                flat = scores.reshape(batch, max_seq)
                chosen = select_top(
                    flat, jnp.arange(max_seq)[None] <= cursor[:, None], topk)
                handed(packed_selection(chosen, max_seq))
                chosen = chosen.astype(jnp.int32).reshape(scores.shape)
            with jax.named_scope('kv_read'), jax.named_scope('sparse_read'):
                mixed = paged_latent_attention(
                    absorbed[:, 0], cache.value, table, cursor, rank=rank,
                    width=width, block=block, scale=scale, selected=chosen)
            with jax.named_scope('mla_proj'):
                return jnp.einsum('blhc,chd->blhd', mixed[:, None], w_value)

    with jax.named_scope('indexer'):
        keys = whole_rows(index_pool, table, pages, batch)
        held = jnp.arange(keys.shape[1])[None, None] <= positions[..., None]
        chosen = select_top(index_scores(index_query, index_weights, keys),
                            held, topk)                      # [B, L, S]
        handed(packed_selection(chosen, max_seq).reshape(batch * length, -1))
    with jax.named_scope('kv_read'), jax.named_scope('sparse_read'):
        rows = whole_rows(cache.value, table, pages, batch)[..., :width]
        scores = jnp.einsum('blhc,bwc->bhlw', absorbed, rows,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(chosen[:, None], scores, NEG_INF)
        weights = jax.nn.softmax(scores, axis=-1)
        mixed = jnp.einsum('bhlw,bwc->blhc', weights.astype(rows.dtype),
                           rows[..., :rank])
    with jax.named_scope('mla_proj'):
        return jnp.einsum('blhc,chd->blhd', mixed, w_value)


def window_slots(window: int) -> int:
    """Slots a row's window cache keeps: the window rounded up to whole
    16-position tiles, with at least one slot to spare (513 -> 528)."""
    return (window // 16 + 1) * 16


def window_latent_attention(module, q_content, q_rope, latent, w_key,
                            w_value, *, scale: float, window: int,
                            max_seq: int, per_row: bool = False,
                            length=None):
    """Latent attention over a **sliding window**: position ``t`` attends
    ``t - window < s <= t``. The cache is a **per-row window cache**, leaf
    ``window`` ``[batch, slots, lanes]`` (:func:`window_slots`): position
    ``p`` lies at slot ``p mod slots``, so what a row keeps does not grow
    with its context, and there is no table. A row's slot holds the latest
    position written there; a read masks by the position each slot holds.
    The cursor is the ``index`` leaf, as elsewhere.

    * **prefill** (creates the cache): keys and values expanded per head,
      queries in blocks over the keys of their own window; the last
      ``slots`` positions of the real prompt (``length``, a traced count of
      a right-padded one; ``None``: all of it) are written to their slots;
    * **decode**: the new rows go to their slots and the absorbed query
      attends the row's whole window cache under the mask (at most
      ``slots - window + 1`` new tokens a call).

    Scopes: ``kv_write``, ``kv_read/window_read``."""
    batch, span, width = latent.shape
    rank = w_key.shape[0]
    slots = window_slots(window)
    lanes = -(-width // 128) * 128
    prefill = not module.has_variable('cache', 'index')
    cache = module.variable('cache', 'window', jnp.zeros,
                            (batch, slots, lanes), latent.dtype)
    index = module.variable('cache', 'index',
                            lambda: jnp.zeros((batch,), jnp.int32))
    query = jnp.concatenate([q_content, q_rope], axis=-1)

    def expanded():
        return banded_latent_attention(query, latent, w_key, w_value,
                                       scale=scale, window=window)

    if module.is_initializing():
        return expanded()
    rows = _padded(latent.astype(cache.value.dtype), lanes)
    cursor = index.value
    if prefill:
        real = span if length is None else length
        with jax.named_scope('kv_write'):
            # slot s keeps the latest real position p with p = s (mod slots)
            last = real - 1
            held = last - (last - jnp.arange(slots)) % slots
            kept = jnp.take(rows, jnp.maximum(held, 0), axis=1)
            cache.value = jnp.where((held >= 0)[None, :, None], kept, 0)
            index.value = cursor + span
        with jax.named_scope('kv_read'), jax.named_scope('window_read'):
            return expanded()
    if span > slots - window + 1:
        raise ValueError(f'{span} new tokens overrun a window cache of '
                         f'{slots} slots for a window of {window}')
    positions = cursor[:, None] + jnp.arange(span)[None, :]     # [B, L]
    with jax.named_scope('kv_write'):
        cache.value = cache.value.at[
            jnp.arange(batch)[:, None], positions % slots].set(rows)
        index.value = cursor + span
    with jax.named_scope('mla_proj'):
        absorbed = jnp.concatenate(
            [jnp.einsum('blhd,chd->blhc', q_content, w_key), q_rope], axis=-1)
    with jax.named_scope('kv_read'), jax.named_scope('window_read'):
        newest = positions[:, -1:]                               # [B, 1]
        held = newest - (newest - jnp.arange(slots)[None]) % slots
        gap = positions[:, :, None] - held[:, None, :]          # [B, L, W]
        mask = (held[:, None, :] >= 0) & (gap >= 0) & (gap < window)
        stored = cache.value[..., :width]
        scores = jnp.einsum('blhc,bwc->bhlw', absorbed, stored,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        weights = jax.nn.softmax(scores, axis=-1)
        mixed = jnp.einsum('bhlw,bwc->blhc', weights.astype(stored.dtype),
                           stored[..., :rank])
    with jax.named_scope('mla_proj'):
        return jnp.einsum('blhc,chd->blhd', mixed, w_value)
