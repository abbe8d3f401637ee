"""Adoption writes the prompt's blocks whole
(tpusystem/serve/kvcache.py ``adopt_prefill``).

An admission writes the blocks its prompt fills of the prefill's contiguous
strip into the row's own blocks, whole, and nothing else: not the blocks the
row holds for what it will generate, not a shared prefix. Under drill, on a
tiny GPT-2 (the fused step and its paged-attention kernel) and a tiny
DeepSeek-V2 (a latent pool, one ``key`` leaf a layer on whole lanes):

* completions are token for token the standalone greedy reference's when
  the blocks a row is handed still hold what an evicted row left, and a
  finite sentinel written through the pool besides: stale content past
  the prompt is never read;
* prompts of one token, of a whole number of blocks, and prompts whose
  ``max_new`` reaches ``max_seq``;
* a shared prefix's blocks are left byte for byte;
* one trace of ``adopt_prefill`` across every bucket, and
  ``Engine.adoption`` counts what was written and skipped;
* the pool's bookkeeping stays whole across churn.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import deepseek_v2
from tests.chipbench_tests import dsv2
from tpusystem.models import gpt2_tiny
from tpusystem.serve import Engine, Request, Scheduler, adopt_prefill
from tpusystem.serve.kvcache import _is_kv
from tpusystem.train import generate

SENTINEL = 3.0


class Model:
    """A module, its parameters and the engine's options."""

    def __init__(self, kind: str) -> None:
        if kind == 'gpt2-fused':
            self.module = gpt2_tiny(dtype='float32')
            self.params = self.module.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params']
            self.options = dict(block_size=8, decode_impl='fused')
        else:
            config = dsv2.tiny_config()
            self.module = deepseek_v2.serve_module(config)
            self.params = deepseek_v2.make(config, 11)
            self.options = dict(block_size=8)

    def engine(self, **options) -> Engine:
        return Engine(self.module, self.params,
                      **{'rows': 3, **self.options, **options})

    @functools.lru_cache(maxsize=None)
    def reference(self, prompt: tuple, steps: int) -> list:
        out = generate(self.module, self.params, jnp.asarray([prompt]),
                       steps=steps)
        return np.asarray(out)[0, len(prompt):].tolist()


@pytest.fixture(scope='module', params=['gpt2-fused', 'deepseek'])
def model(request):
    return Model(request.param)


def prompt_of(seed: int, length: int) -> list:
    return np.random.default_rng(seed).integers(0, 256, length).tolist()


def drain(engine) -> dict:
    tokens = {}
    while engine.active_rows:
        for row, _reason, out in engine.step().finished:
            tokens[row] = out
    engine.step()              # a tick a stop left in flight
    return tokens


def pools(engine) -> list:
    """Every key/value pool leaf, ``[num_blocks, block, lanes]`` on the
    host."""
    block = engine.block_size
    return [np.asarray(leaf).reshape(-1, block, leaf.shape[-1])
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                engine._cache) if _is_kv(path)]


def blocks_for(length: int, block: int) -> int:
    return -(-length // block)


def test_stale_blocks_past_the_prompt_are_left_and_never_read(model):
    """A first wave fills blocks with its own KV and leaves; every block
    then takes a finite sentinel through the pool. The second wave's
    admissions write their prompts' blocks and no other: the rest of each
    row's blocks still hold the sentinel when it is seated, and its
    tokens are the reference's all the same."""
    engine = model.engine()
    first = [prompt_of(seed, 40) for seed in (1, 2, 3)]
    for prompt in first:
        engine.admit(prompt, 60)
    drain(engine)
    engine._cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.full_like(leaf, SENTINEL)
        if _is_kv(path) else leaf, engine._cache)

    block = engine.block_size
    second = [prompt_of(seed, length)
              for seed, length in ((4, 5), (5, 19), (6, 33))]
    rows = {}
    for prompt in second:
        row = engine.admit(prompt, 30).row
        rows[row] = prompt
        ids = engine.pool.table[row]
        filled = blocks_for(len(prompt), block)
        held = blocks_for(len(prompt) + 30, block)
        for pool in pools(engine):
            assert not (pool[ids[:filled]] == SENTINEL).all()
            assert (pool[ids[filled:held]] == SENTINEL).all()
    tokens = drain(engine)
    for row, prompt in rows.items():
        assert tokens[row] == model.reference(tuple(prompt), 30)
    assert engine.trace_count == 1
    engine.pool.audit()


@pytest.mark.parametrize('length, max_new', [(1, 6), (16, 6), (24, 6),
                                             (100, 28)],
                         ids=['one-token', 'two-blocks', 'three-blocks',
                              'reaches-max-seq'])
def test_prompt_lengths_at_the_edges_of_a_block(model, length, max_new):
    """One token (a block of which one position is the prompt's), a whole
    number of blocks (the next position opens a block adoption left
    alone), and a request whose prompt + ``max_new`` is ``max_seq``."""
    engine = model.engine()
    assert length + max_new <= engine.max_seq
    if length == 100:
        assert length + max_new == engine.max_seq
    prompt = prompt_of(length, length)
    row = engine.admit(prompt, max_new).row
    assert engine.adoption == {
        'admissions': 1, 'blocks': blocks_for(length, engine.block_size),
        'shared_blocks': 0}
    assert drain(engine)[row] == model.reference(tuple(prompt), max_new)


def test_a_shared_prefix_is_left_byte_for_byte(model):
    """The second admission adopts the first one's whole prompt blocks by
    reference: adoption skips them, so they hold the same bytes before
    the admission, after it and after both rows finished, and
    ``shared_blocks`` is the pool's shared depth in blocks."""
    engine = model.engine(rows=2, block_size=4, share_prefix=True)
    head = prompt_of(7, 21)
    engine.admit(head + prompt_of(8, 3), 6)
    engine.step()
    prompt = head + prompt_of(9, 5)
    shared_ids = engine.pool.table[0, :20 // 4].copy()
    before = [pool[shared_ids].copy() for pool in pools(engine)]
    written = engine.adoption['blocks']
    admission = engine.admit(prompt, 6)
    shared = engine.pool.shared_tokens(admission.row)
    assert shared == 20
    assert (engine.pool.table[admission.row, :5] == shared_ids).all()
    assert engine.adoption['shared_blocks'] == shared // 4
    assert engine.adoption['blocks'] - written == \
        blocks_for(len(prompt), 4) - shared // 4
    for old, pool in zip(before, pools(engine)):
        np.testing.assert_array_equal(pool[shared_ids], old)
    tokens = drain(engine)
    assert tokens[admission.row] == model.reference(tuple(prompt), 6)
    for old, pool in zip(before, pools(engine)):
        np.testing.assert_array_equal(pool[shared_ids], old)
    engine.pool.audit()


def test_one_trace_across_buckets_and_every_block_counted(model):
    """Prompts in four buckets, more requests than rows, churned through
    the scheduler: ``adopt_prefill`` is not traced again after the first
    admission, ``blocks`` is the sum of each prompt's ``ceil(length /
    block_size)``, and the pool audits clean after every step."""
    engine = model.engine()
    engine.admit([5, 6, 7], 2)
    drain(engine)
    traces = adopt_prefill._cache_size()
    scheduler = Scheduler(engine)
    lengths = (3, 9, 17, 30, 47, 64, 12, 8)
    prompts = {f'r{index}': prompt_of(30 + index, length)
               for index, length in enumerate(lengths)}
    for tag, prompt in prompts.items():
        scheduler.submit(Request(tag, prompt, max_new=5 + len(prompt) % 4))
    while not scheduler.idle:
        scheduler.step()
        engine.pool.audit()
    for tag, prompt in prompts.items():
        assert scheduler.results[tag].tokens == model.reference(
            tuple(prompt), 5 + len(prompt) % 4), tag
    assert adopt_prefill._cache_size() == traces
    assert engine.adoption == {
        'admissions': 1 + len(lengths),
        'blocks': 1 + sum(blocks_for(n, engine.block_size) for n in lengths),
        'shared_blocks': 0}
    assert engine.trace_count == 1
    assert engine.pool.live_blocks == 0
