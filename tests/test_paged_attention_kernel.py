"""The paged decode-attention kernel (interpret mode on the CPU) against a
float32 ``jax.numpy`` reference written here: seeded pools, ragged depths,
shared blocks, parked rows, grouped queries (several query heads to a stored
key/value head) against ``dot_product_attention`` too, and the in-place write
of the step around it."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusystem.models import gpt2_tiny
from tpusystem.models.gpt2 import GPT2
from tpusystem.ops.attention import dot_product_attention
from tpusystem.ops.pallas.paged_attention import (CHUNK_POSITIONS,
                                                  paged_decode_attention,
                                                  paged_plan)
from tpusystem.train.decode_fused import fused_paged_reason

HEAD_DIM = 64

# query heads, stored key/value heads, head_dim: GPT-2's one to one (the
# kernel's first layout), Nemotron-H's 32 on 2 x 128, and 8 on 2 x 64
SHAPES = {'12': (12, 12, 64), '32on2x128': (32, 2, 128),
          '8on2x64': (8, 2, 64)}
shapes = pytest.mark.parametrize('shape', SHAPES)


def reference(query, key_pool, value_pool, table, cursor, block: int):
    """Each row attends positions ``0 … cursor`` of its own blocks: plain
    float32 softmax attention, one row and one head at a time, a query
    head against key/value head ``head // group`` of the stored row."""
    rows, heads, head_dim = query.shape
    keys = np.asarray(key_pool, np.float32).reshape(
        key_pool.shape[0], -1, head_dim)
    values = np.asarray(value_pool, np.float32).reshape(keys.shape)
    stored = np.arange(heads) // (heads // keys.shape[1])
    out = np.zeros((rows, heads, head_dim), np.float32)
    for row in range(rows):
        held = np.arange(int(cursor[row]) + 1)
        slots = np.asarray(table)[row, held // block] * block + held % block
        scores = np.einsum('hd,thd->ht', np.asarray(query[row], np.float32),
                           keys[slots][:, stored]) * head_dim ** -0.5
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        weights /= weights.sum(-1, keepdims=True)
        out[row] = np.einsum('ht,thd->hd', weights, values[slots][:, stored])
    return out


def pools(seed: int, rows: int, heads: int, block: int, max_blocks: int,
          dtype=jnp.float32, kv_heads: int | None = None,
          head_dim: int = HEAD_DIM):
    """Seeded K and V pools (block 0 is trash, filled like the rest), a
    table of distinct physical blocks per row, and a query."""
    rng = np.random.default_rng(seed)
    blocks = rows * max_blocks + 1
    width = (kv_heads or heads) * head_dim
    key_pool = jnp.asarray(rng.normal(size=(blocks * block, width)), dtype)
    value_pool = jnp.asarray(rng.normal(size=(blocks * block, width)), dtype)
    table = rng.permutation(np.arange(1, blocks)).reshape(
        rows, max_blocks).astype(np.int32)
    query = jnp.asarray(rng.normal(size=(rows, heads, head_dim)), dtype)
    return query, key_pool, value_pool, table


def shaped_pools(shape: str, seed, rows, block, max_blocks,
                 dtype=jnp.float32):
    heads, kv_heads, head_dim = SHAPES[shape]
    return pools(seed, rows, heads, block, max_blocks, dtype, kv_heads,
                 head_dim)


def attend(query, key_pool, value_pool, table, cursor, block):
    return np.asarray(paged_decode_attention(
        query, key_pool, value_pool, jnp.asarray(table),
        jnp.asarray(cursor, jnp.int32), block=block, interpret=True))


# depth 0, exactly one block, one past a block boundary, a chunk boundary
# on each side, and the full max_seq; block 16 x 24 columns = 384 positions
DEPTHS = [0, 15, 16, CHUNK_POSITIONS - 1, CHUNK_POSITIONS, 200, 383]


@pytest.mark.parametrize('depth', DEPTHS)
def test_one_row_at_every_kind_of_depth(depth):
    query, key_pool, value_pool, table = pools(3, 1, 12, 16, 24)
    cursor = np.array([depth], np.int32)
    got = attend(query, key_pool, value_pool, table, cursor, 16)
    want = reference(query, key_pool, value_pool, table, cursor, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize('block', [8, 16])
@pytest.mark.parametrize('heads', [(12,), (20,), (32, 2, 128), (8, 2, 64)],
                         ids=['12', '20', '32on2x128', '8on2x64'])
def test_ragged_rows_block_sizes_and_head_counts(block, heads):
    """Rows of different depths in one call: each reads its own blocks at
    its own depth whatever its neighbours hold (the double buffer hands
    over between rows of unequal chunk counts)."""
    max_blocks = 256 // block
    heads, *grouped = heads
    query, key_pool, value_pool, table = pools(
        5, 6, heads, block, max_blocks, jnp.float32, *grouped)
    cursor = np.array([0, block - 1, block, 255, 130, 77], np.int32)
    got = attend(query, key_pool, value_pool, table, cursor, block)
    want = reference(query, key_pool, value_pool, table, cursor, block)
    np.testing.assert_allclose(got, want, atol=2e-5)


@shapes
def test_rows_whose_tables_share_physical_blocks(shape):
    """``share_prefix``: two rows name the same read-only blocks for their
    common prefix and private ones after it."""
    query, key_pool, value_pool, table = shaped_pools(shape, 7, 3, 16, 8)
    table[1, :3] = table[0, :3]
    cursor = np.array([70, 55, 100], np.int32)
    got = attend(query, key_pool, value_pool, table, cursor, 16)
    want = reference(query, key_pool, value_pool, table, cursor, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the shared prefix really is shared: same query, same depth inside
    # the common blocks -> the same context for both rows
    query = query.at[1].set(query[0])
    cursor = np.array([40, 40, 100], np.int32)
    got = attend(query, key_pool, value_pool, table, cursor, 16)
    np.testing.assert_array_equal(got[0], got[1])


@shapes
def test_a_parked_row_reads_one_position_of_the_trash_block(shape):
    query, key_pool, value_pool, table = shaped_pools(shape, 11, 3, 16, 8)
    table[1] = 0                                  # unmapped: all trash
    cursor = np.array([90, 0, 17], np.int32)
    got = attend(query, key_pool, value_pool, table, cursor, 16)
    want = reference(query, key_pool, value_pool, table, cursor, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # one position: its softmax weight is 1 and the context is that V row,
    # each stored head's part once a query head of its group
    heads, kv_heads, head_dim = SHAPES[shape]
    np.testing.assert_allclose(
        got[1], np.repeat(np.asarray(value_pool[0]).reshape(
            kv_heads, head_dim), heads // kv_heads, axis=0), atol=1e-6)


@shapes
def test_what_lies_past_the_cursor_is_never_read(shape):
    """NaN in every slot a row does not hold — the rest of its last block,
    its later blocks, other rows' blocks — leaves the result finite and
    unchanged: masked inside the last block, never fetched beyond it."""
    query, key_pool, value_pool, table = shaped_pools(shape, 13, 2, 16, 16)
    cursor = np.array([37, 130], np.int32)
    want = reference(query, key_pool, value_pool, table, cursor, 16)
    held = np.zeros(key_pool.shape[0], bool)
    for row in range(2):
        positions = np.arange(cursor[row] + 1)
        held[table[row, positions // 16] * 16 + positions % 16] = True
    poison = jnp.where(jnp.asarray(held)[:, None], 0.0, jnp.nan)
    got = attend(query, key_pool + poison, value_pool, table, cursor, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the value pool's stale tail inside a fetched block is multiplied by
    # an exact zero, so there it must be finite (the engine's pools are)
    got = attend(query, key_pool + poison,
                 value_pool + jnp.where(jnp.isnan(poison), 1e4, 0.0),
                 table, cursor, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize('shape', [(20, 20, 64), *SHAPES.values()],
                         ids=['20', *SHAPES])
def test_bf16_pools_keep_float32_statistics(shape):
    """bf16 operands as on the chip: the result is the float32 reference's
    on the same (rounded) pools to within one bf16 rounding of the output."""
    heads, kv_heads, head_dim = shape
    query, key_pool, value_pool, table = pools(
        17, 4, heads, 16, 16, jnp.bfloat16, kv_heads, head_dim)
    cursor = np.array([255, 3, 100, 16], np.int32)
    got = attend(query, key_pool, value_pool, table, cursor, 16)
    assert got.dtype == jnp.bfloat16
    want = reference(query, key_pool, value_pool, table, cursor, 16)
    np.testing.assert_allclose(got.astype(np.float32), want, atol=0.03)


@pytest.mark.parametrize('dtype, tolerance', [(jnp.float32, 2e-5),
                                              (jnp.bfloat16, 0.02)])
@pytest.mark.parametrize('block', [8, 16])
@pytest.mark.parametrize('shape', ['32on2x128', '8on2x64'])
def test_grouped_queries_read_as_dot_product_attention_does(shape, block,
                                                            dtype, tolerance):
    """The gather's arithmetic, row by row: ``dot_product_attention``'s
    grouped branch over each row's own positions (same operand dtype, same
    float32 scores and statistics, the weights rounded to the operand dtype
    before the value product), at ragged depths that start, end and cross
    chunks."""
    heads, kv_heads, head_dim = SHAPES[shape]
    max_blocks = 384 // block
    query, key_pool, value_pool, table = shaped_pools(
        shape, 29, 5, block, max_blocks, dtype)
    cursor = np.array([0, block, CHUNK_POSITIONS - 1, 383, 200], np.int32)
    got = attend(query, key_pool, value_pool, table, cursor, block)
    for row, depth in enumerate(cursor):
        held = np.arange(depth + 1)
        slots = table[row, held // block] * block + held % block
        window = (1, depth + 1, kv_heads, head_dim)
        want = dot_product_attention(
            query[row][None, None], key_pool[slots].reshape(window),
            value_pool[slots].reshape(window), causal=False,
            mask=jnp.ones((1, 1, 1, depth + 1), bool))[0, 0]
        np.testing.assert_allclose(got[row].astype(np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tolerance)


@pytest.mark.parametrize('heads,block,dtype,chunk', [
    (20, 16, 'bfloat16', 8), (12, 16, 'bfloat16', 8), (12, 32, 'bfloat16', 4),
    (12, 8, 'float32', 16), (12, 256, 'bfloat16', 1),
    (12, 8, 'bfloat16', None),          # half a bf16 sublane tile a block
    (3, 16, 'bfloat16', None)])         # 192 lanes: not whole lane tiles
def test_the_plan_answers_from_shapes_alone(heads, block, dtype, chunk):
    assert paged_plan(heads, HEAD_DIM, block, 1024 // block, dtype,
                      interpret=False) == chunk
    # interpret mode tiles anything, at the same chunk
    assert paged_plan(heads, HEAD_DIM, block, 1024 // block, dtype,
                      interpret=True) == max(1, CHUNK_POSITIONS // block)


@pytest.mark.parametrize('heads, kv_heads, head_dim, chunk', [
    (32, 2, 128, 8),            # Nemotron-H: rows of 256 lanes
    (32, 8, 128, 8),            # Llama-3 8B: rows of 1024
    (8, 2, 64, 8),              # rows of one lane tile
    (4, 2, 32, None),           # a 64-lane pool: not whole lanes
    (12, 1, 64, None),          # one stored head of 64: the same
    (12, 5, 128, None)])        # no whole group
def test_the_plan_for_grouped_queries(heads, kv_heads, head_dim, chunk):
    """The stored row (``kv_heads * head_dim``) is what has to fill lanes;
    where the plan refuses, ``paged_read`` says gather and says why."""
    from tpusystem.ops import attention
    assert paged_plan(heads, head_dim, 16, 96, 'bfloat16', False,
                      kv_heads) == chunk
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, 'on_tpu', lambda: True)
        read, why = attention.paged_read(1, heads, kv_heads, head_dim, 16,
                                         96, 'bfloat16')
    if chunk is None:
        assert read == 'gather' and 'cannot tile' in why
    else:
        assert (read, why) == ('kernel', None)


def test_the_chunk_never_passes_the_table():
    assert paged_plan(12, HEAD_DIM, 8, 4, 'float32', interpret=True) == 4


def test_a_refused_shape_raises_and_the_gate_names_it(monkeypatch):
    query, key_pool, value_pool, table = pools(19, 1, 12, 8, 8, jnp.bfloat16)
    with pytest.raises(ValueError, match='cannot tile'):
        paged_decode_attention(query, key_pool, value_pool,
                               jnp.asarray(table), jnp.zeros(1, jnp.int32),
                               block=8, interpret=False)
    with pytest.raises(ValueError, match='do not hold'):
        paged_decode_attention(query, key_pool[:, :96], value_pool[:, :96],
                               jnp.asarray(table), jnp.zeros(1, jnp.int32),
                               block=8, interpret=True)
    # on the chip the engine's gate names the refusal; off it (interpret)
    # the same clone passes
    decoder = GPT2(vocab_size=256, layers=2, dim=768, heads=12, max_seq=64,
                   dtype='bfloat16', decode=True, per_row_decode=True,
                   decode_pages=(9, 8))
    assert fused_paged_reason(decoder) is None
    monkeypatch.setattr('tpusystem.train.decode_fused.auto_interpret',
                        lambda interpret: False)
    assert 'paged-attention kernel cannot tile' in fused_paged_reason(decoder)


@pytest.mark.parametrize('block', [8, 16])
def test_the_step_writes_one_slot_a_row_and_no_other(block):
    """The fused step around the kernel: each row's new K and V land at
    its own slot (a parked row's at the trash block's first), every other
    slot of every pool is bit for bit what it was, and the context the
    layer attends with includes the token just written."""
    from tpusystem.serve import Engine
    module = gpt2_tiny(dtype='float32', max_seq=64)
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))['params']
    engine = Engine(module, params, rows=3, block_size=block,
                    decode_impl='fused')
    assert engine.paged_read == {'read': 'kernel', 'reason': None}
    rng = np.random.default_rng(23)
    engine.admit([int(t) for t in rng.integers(0, 256, (block + 3,))],
                 max_new=8)
    engine.admit([int(t) for t in rng.integers(0, 256, (5,))], max_new=8)
    before = jax.tree.map(np.asarray, engine._cache)
    cursor = before['h_0']['attn']['index']
    table = before['h_0']['attn']['table']
    assert list(cursor) == [block + 3, 5, 0]              # row 2 is parked
    # the first step() dispatches the tick it reads and the one after it:
    # the cache as the first tick left it is what this test reads
    program, left = engine._step, []

    def dispatch(*operands):
        out = program(*operands)
        left.append(jax.tree.map(np.asarray, out[1]))
        return out
    engine._step = dispatch
    engine.step()
    assert len(left) == 2
    after = left[0]
    slots = table[np.arange(3), cursor // block] * block + cursor % block
    assert slots[2] == 0                                   # trash
    for layer in ('h_0', 'h_1'):
        for name in ('key', 'value'):
            was, now = before[layer]['attn'][name], after[layer]['attn'][name]
            changed = np.flatnonzero((was != now).any(axis=1))
            assert set(changed) <= set(slots)
            assert set(slots[:2]) <= set(changed)          # live rows wrote


def test_the_flax_paged_step_reads_through_the_kernel_on_the_tpu(monkeypatch):
    """A tiny Llama (4 query heads on 2 x 64: rows of 128 lanes) on the
    engine's flax paged step, steered to "on the TPU" with the kernel
    interpreted: one call a layer in the one decode trace, ``generate``'s
    tokens. A query window longer than one token (speculative verify)
    keeps the gather, and ``Engine.paged_read`` says so beforehand."""
    from tpusystem.models import llama_tiny
    from tpusystem.ops import attention
    from tpusystem.ops.pallas import paged_attention as kernel_module
    from tpusystem.serve import Engine
    from tpusystem.train.generate import generate
    module = llama_tiny(dim=256, dtype='float32', max_seq=64)
    params = module.init(jax.random.PRNGKey(4),
                         jnp.zeros((1, 4), jnp.int32))['params']
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, 256, n).tolist() for n in (19, 7)]
    want = [np.asarray(generate(module, params, jnp.asarray([prompt]),
                                steps=5))[0, len(prompt):].tolist()
            for prompt in prompts]
    calls = []
    real = kernel_module.paged_decode_attention
    monkeypatch.setattr(kernel_module, 'paged_decode_attention',
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    monkeypatch.setattr(attention, 'on_tpu', lambda: True)
    engine = Engine(module, params, rows=2, block_size=8, decode_impl='flax')
    assert engine.paged_read == {'read': 'kernel', 'reason': None}
    rows = [engine.admit(prompt, max_new=5).row for prompt in prompts]
    tokens = {}
    while engine.active_rows:
        for row, _reason, out in engine.step().finished:
            tokens[row] = out
    assert len(calls) == 2 and engine.trace_count == 1    # once a layer
    assert [tokens[row] for row in rows] == want
    # a window of three tokens a row: the gather, and the record says why
    window = Engine(module, params, rows=2, block_size=8, speculate=2,
                    draft_module=module, draft_params=params)
    assert window.paged_read['read'] == 'gather'
    assert 'window of 3 tokens' in window.paged_read['reason']
    del calls[:]
    engine._decoder.apply({'params': params, 'cache': engine._cache},
                          jnp.zeros((2, 3), jnp.int32), mutable=['cache'])
    assert not calls


# --- compiled for the chip, without the chip -----------------------------
# The TPU's compiler is installed here and compiles for a described v5e:
# what Mosaic refuses (a slice off the tiling, too much VMEM) fails here,
# at no chip time. Only this file describes the topology, inside a fixture.

@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topology = topologies.get_topology_desc(platform='tpu',
                                                topology_name='v5e:2x2')
    except Exception as error:
        pytest.skip(f'no v5e:2x2 topology can be described here: {error}')
    return SingleDeviceSharding(topology.devices[0])


@pytest.mark.parametrize('rows, heads, kv_heads, head_dim, max_blocks', [
    (32, 20, 20, 64, 64), (8, 12, 12, 64, 64), (96, 32, 2, 128, 96)],
    ids=['gpt2-large', 'gpt2-125m', 'nemotron3'])
def test_the_kernel_compiles_for_a_v5e_at_serving_widths(
        one_chip, rows, heads, kv_heads, head_dim, max_blocks):
    """The cells' shapes (32 rows x 20 heads, block 16, 64 table columns;
    96 rows x 32 query heads on 2 x 128, 96 columns) and the smoke's:
    Mosaic takes the kernel, the pools go in as stored — no copy,
    transpose or slice of a pool-shaped operand around it — and the
    compiler names the call after the kernel, not after the jitted step
    (``decode_chain_roofline`` sums ``step_fn [tpu_custom_call]``)."""
    block = 16
    slots, width = (rows * max_blocks + 1) * block, kv_heads * head_dim
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def step_fn(query, key_pool, value_pool, table, cursor):
        with jax.named_scope('kv_read'):
            return paged_decode_attention(query, key_pool, value_pool, table,
                                          cursor, block=block,
                                          interpret=False)

    compiled = jax.jit(step_fn).lower(
        shaped((rows, heads, head_dim), jnp.bfloat16),
        shaped((slots, width), jnp.bfloat16),
        shaped((slots, width), jnp.bfloat16),
        shaped((rows, max_blocks), jnp.int32),
        shaped((rows,), jnp.int32)).compile().as_text()
    calls = [line for line in compiled.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert '%paged_decode_attention' in calls[0].split(' = ')[0]
    pool = f'bf16[{slots},{width}]'
    moved = [line for line in compiled.splitlines()
             if pool in line.split(' = ')[-1].split('(')[0]
             and 'parameter(' not in line]
    assert not moved, moved


# --------------------------------------------- the latent pool's kernel

def latent_reference(query, pool, table, cursor, block, rank, width, scale):
    """Each row attends positions ``0 … cursor`` of its own blocks' latent
    rows, every head the same rows: float32, one row at a time."""
    rows, heads, _ = query.shape
    stored = np.asarray(pool, np.float32)[:, :width]
    out = np.zeros((rows, heads, rank), np.float32)
    for row in range(rows):
        held = np.arange(int(cursor[row]) + 1)
        slots = np.asarray(table)[row, held // block] * block + held % block
        scores = np.asarray(query[row], np.float32) @ stored[slots].T * scale
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        weights /= weights.sum(-1, keepdims=True)
        out[row] = weights @ stored[slots][:, :rank]
    return out


@pytest.mark.parametrize('dtype, tolerance', [(jnp.float32, 2e-5),
                                              (jnp.bfloat16, 2e-2)])
def test_latent_kernel_reads_each_row_to_its_own_depth(dtype, tolerance,
                                                       monkeypatch):
    """Ragged depths over shuffled blocks, two rows sharing a block, a
    parked row on the trash block, a row that fills its whole table: the
    chunk walk (two chunks of four blocks here) skips what a row does not
    hold and masks inside its last chunk. The pool's rows are padded to
    whole lanes, and the padding is never attended."""
    from tpusystem.ops.pallas import latent_attention as kernel_module
    monkeypatch.setattr(kernel_module, 'CHUNK_POSITIONS', 16)
    rows, heads, rank, rope, block, max_blocks = 5, 8, 32, 8, 4, 8
    width, lanes = rank + rope, 128
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    pool = jnp.pad(jax.random.normal(keys[0], (41 * block, width)),
                   ((0, 0), (0, lanes - width)), constant_values=7.0)
    pool = pool.astype(dtype)
    query = jax.random.normal(keys[1], (rows, heads, width)).astype(dtype)
    table = np.asarray(jax.random.permutation(keys[2], 40)[:rows * max_blocks]
                       ).reshape(rows, max_blocks).astype(np.int32) + 1
    table[1, :2] = table[0, :2]               # a shared prefix
    table[3] = 0                              # parked on the trash block
    cursor = np.array([13, 9, 31, 0, 4], np.int32)
    got = kernel_module.paged_latent_attention(
        query, pool, jnp.asarray(table), jnp.asarray(cursor), rank=rank,
        width=width, block=block, scale=0.2, interpret=True)
    assert got.shape == (rows, heads, rank) and got.dtype == dtype
    want = latent_reference(query, pool, table, cursor, block, rank, width,
                            0.2)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=tolerance, rtol=tolerance)


# four rows over tables of eight blocks of four positions, walked two
# chunks of four blocks (16 positions) to a full table
LATENT_WALKS = {
    'a depth ends exactly on a chunk boundary': dict(cursor=[15, 31, 16, 7]),
    # the full row's last chunk prefetches the next row's first, of one block
    'depth 1 straight after a full table': dict(cursor=[31, 0, 31, 0]),
    'every row parked': dict(cursor=[0, 0, 0, 0], parked=(0, 1, 2, 3)),
    'two rows share all their blocks': dict(cursor=[29, 29, 11, 20],
                                            shared=True),
    'unheld blocks hold inf': dict(cursor=[13, 0, 31, 18], poisoned=True),
}


@pytest.mark.parametrize('walk', LATENT_WALKS)
def test_the_latent_walk_fetches_a_rows_own_blocks_and_no_others(
        walk, monkeypatch):
    """The double-buffered window at its edges, against
    ``latent_reference``. With ``inf`` in every block no row holds (the
    trash block too) nothing but zeros may stand in the window past a
    row's last held block: such a block is never fetched, the window was
    zeroed once, and a position past the cursor inside a held block is
    finite (as in a real pool) and weighs exactly zero."""
    from tpusystem.ops.pallas import latent_attention as kernel_module
    monkeypatch.setattr(kernel_module, 'CHUNK_POSITIONS', 16)
    case = LATENT_WALKS[walk]
    rows, heads, rank, rope, block, max_blocks = 4, 8, 32, 8, 4, 8
    width, lanes = rank + rope, 128
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    pool = jnp.pad(jax.random.normal(keys[0], (33 * block, width)),
                   ((0, 0), (0, lanes - width)), constant_values=7.0)
    query = jax.random.normal(keys[1], (rows, heads, width))
    table = np.asarray(jax.random.permutation(keys[2], 32)).reshape(
        rows, max_blocks).astype(np.int32) + 1
    cursor = np.array(case['cursor'], np.int32)
    for row in case.get('parked', ()):
        table[row] = 0
    if case.get('shared'):
        table[1] = table[0]
        query = query.at[1].set(query[0])
    if case.get('poisoned'):
        held = np.zeros(33, bool)
        for row in range(rows):
            held[table[row, :cursor[row] // block + 1]] = True
        pool = jnp.where(jnp.repeat(jnp.asarray(held), block)[:, None],
                         pool, jnp.inf)
    got = np.asarray(kernel_module.paged_latent_attention(
        query, pool, jnp.asarray(table), jnp.asarray(cursor), rank=rank,
        width=width, block=block, scale=0.2, interpret=True))
    want = latent_reference(query, pool, table, cursor, block, rank, width,
                            0.2)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    if case.get('shared'):
        np.testing.assert_array_equal(got[0], got[1])
    for row in case.get('parked', ()):       # one position: its own content
        np.testing.assert_allclose(
            got[row], np.broadcast_to(np.asarray(pool[0, :rank]),
                                      (heads, rank)), atol=1e-6)


def test_latent_plan_refuses_what_the_tpu_cannot_tile():
    from tpusystem.ops.pallas.latent_attention import (latent_plan,
                                                       paged_latent_attention)
    assert latent_plan(128, 512, 16, 320, jnp.bfloat16, False) == 32
    assert latent_plan(128, 512, 16, 320, jnp.bfloat16, False, 640) == 32
    assert latent_plan(128, 512, 16, 4, jnp.bfloat16, False) == 4
    assert latent_plan(4, 16, 16, 8, jnp.float32, True) == 8   # interpreted
    for heads, rank, block in ((128, 500, 16), (12, 512, 16), (128, 512, 8)):
        assert latent_plan(heads, rank, block, 320, jnp.bfloat16,
                           False) is None
    # what does not fit VMEM: a window of one 4096-position block, the
    # query and result blocks of 4096 heads, rows stored on 8192 lanes
    assert latent_plan(128, 512, 4096, 4, jnp.bfloat16, False) is None
    assert latent_plan(4096, 512, 16, 320, jnp.bfloat16, False) is None
    assert latent_plan(128, 512, 16, 320, jnp.bfloat16, False, 8192) is None
    assert latent_plan(128, 512, 4096, 4, jnp.bfloat16, True) == 1
    with pytest.raises(ValueError, match='cannot tile'):
        paged_latent_attention(
            jnp.zeros((2, 12, 576), jnp.bfloat16),
            jnp.zeros((64, 640), jnp.bfloat16), jnp.zeros((2, 4), jnp.int32),
            jnp.zeros((2,), jnp.int32), rank=512, width=576, block=16,
            scale=0.1, interpret=False)


def test_the_engine_reads_the_latent_pool_through_the_kernel(monkeypatch):
    """On the TPU one decoded token a row goes through the kernel; steered
    here (the look for a TPU says yes, the kernel runs interpreted), the
    engine's tokens are the XLA read's, which are ``generate``'s."""
    from tpusystem.models import deepseek_tiny
    from tpusystem.ops import attention
    from tpusystem.ops.pallas import latent_attention as kernel_module
    from tpusystem.serve import Engine
    from tpusystem.train.generate import generate
    module = deepseek_tiny(layers=2, heads=8, kv_rank=128, max_seq=64,
                           held=(0, 8))
    params = module.init(jax.random.PRNGKey(2),
                         jnp.zeros((1, 4), jnp.int32))['params']
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).tolist() for n in (21, 6)]
    calls = []
    real = kernel_module.paged_latent_attention
    monkeypatch.setattr(kernel_module, 'paged_latent_attention',
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    monkeypatch.setattr(attention, 'on_tpu', lambda: True)
    engine = Engine(module, params, rows=2, block_size=8, decode_impl='flax')
    assert engine.paged_read['read'] is None          # no keys and values
    rows = [engine.admit(prompt, max_new=5).row for prompt in prompts]
    tokens = {}
    while engine.active_rows:
        for row, _reason, out in engine.step().finished:
            tokens[row] = out
    monkeypatch.setattr(attention, 'on_tpu', lambda: False)
    assert len(calls) == 2 and engine.trace_count == 1    # once a layer
    for row, prompt in zip(rows, prompts):
        want = generate(module, params, jnp.asarray([prompt]), steps=5)
        assert tokens[row] == np.asarray(want)[0, len(prompt):].tolist()


def test_the_latent_kernel_compiles_for_a_v5e_at_serving_widths(one_chip):
    """DeepSeek-V2's share as the cell serves it: 64 rows x 128 heads over
    rows of 576 stored on 640 lanes, block 16, 320 table columns. Mosaic
    takes it, and the pool goes in once and as stored: one pool-shaped
    operand of the call, no copy or transpose of one around it (a 576-wide
    pool is kept slot-minor by the TPU and transposed whole around every
    use: why the rows are padded)."""
    from tpusystem.ops.pallas.latent_attention import paged_latent_attention
    rows, heads, block, max_blocks = 64, 128, 16, 320
    slots = (rows * max_blocks + 1) * block
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def step_fn(query, pool, table, cursor):
        with jax.named_scope('kv_read'):
            return paged_latent_attention(query, pool, table, cursor,
                                          rank=512, width=576, block=block,
                                          scale=0.11472, interpret=False)

    compiled = jax.jit(step_fn).lower(
        shaped((rows, heads, 576), jnp.bfloat16),
        shaped((slots, 640), jnp.bfloat16),
        shaped((rows, max_blocks), jnp.int32),
        shaped((rows,), jnp.int32)).compile().as_text()
    calls = [line for line in compiled.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert '%paged_latent_attention' in calls[0].split(' = ')[0]
    operands = calls[0].split('operand_layout_constraints={')[1].split('}}')[0]
    assert operands.count(f'bf16[{slots},640]') == 1, operands
    moved = [line for line in compiled.splitlines()
             if f'bf16[{slots},640]' in line.split(' = ')[-1].split('(')[0]
             and 'parameter(' not in line]
    assert not moved, moved


# ----------------------------- a learned sparse attention's two walks

@pytest.mark.parametrize('kernel', ['paged_index_scores',
                                    'paged_latent_attention'])
def test_the_sparse_walks_compile_for_a_v5e_at_serving_widths(one_chip,
                                                              kernel):
    """dots3-note-prev's share as the cell serves it: 96 rows, block 16,
    704 table columns (11 264 positions); the indexer's 64 heads over keys
    of 128, the full layers' 128 heads over rows of 576 on 640 lanes with
    the selection as a mask. Mosaic takes each walk, and the pool goes in
    once and as stored."""
    from tpusystem.ops.pallas.index_scores import paged_index_scores
    from tpusystem.ops.pallas.latent_attention import (
        chunk_span, paged_latent_attention)
    rows, block, max_blocks = 96, 16, 704
    slots = (rows * max_blocks + 1) * block
    span = chunk_span(block, max_blocks)
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    table = shaped((rows, max_blocks), jnp.int32)
    cursor = shaped((rows,), jnp.int32)
    if kernel == 'paged_index_scores':
        lanes = 128
        lowered = jax.jit(functools.partial(
            paged_index_scores, block=block, interpret=False)).lower(
            shaped((rows, 64, lanes), jnp.bfloat16),
            shaped((rows, 64), jnp.float32),
            shaped((slots, lanes), jnp.bfloat16), table, cursor)
    else:
        lanes = 640
        lowered = jax.jit(lambda query, pool, table, cursor, chosen: (
            paged_latent_attention(query, pool, table, cursor, rank=512,
                                   width=576, block=block, scale=0.0722,
                                   interpret=False, selected=chosen))).lower(
            shaped((rows, 128, 576), jnp.bfloat16),
            shaped((slots, lanes), jnp.bfloat16), table, cursor,
            shaped((rows, max_blocks * block // span, 1, span), jnp.int32))
    compiled = lowered.compile().as_text()
    calls = [line for line in compiled.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert f'%{kernel}' in calls[0].split(' = ')[0]
    moved = [line for line in compiled.splitlines()
             if f'bf16[{slots},{lanes}]' in line.split(' = ')[-1].split('(')[0]
             and 'parameter(' not in line]
    assert not moved, moved
