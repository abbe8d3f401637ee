"""Continuous-batching serving engine: token-exact under churn.

The engine's contract (tpusystem/serve/): greedy outputs are exactly
standalone ``generate()``'s for every request REGARDLESS of co-batched
traffic — admissions, evictions and cancellations of neighbors must not
change a row's tokens — and batch membership changes never retrace the
one compiled decode step. Free-list exhaustion queues (never crashes),
prompt-length bucketing bounds the prefill program count, and the
request lifecycle narrates on the service bus.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusystem.models import gpt2_tiny, llama_tiny
from tpusystem.parallel.mesh import on_tpu
from tpusystem.serve import (Engine, InferenceService, PagedKVCache,
                             Request, Saturated, Scheduler, TRASH_BLOCK,
                             engine_unsupported_reason, prefill_bucket,
                             serve_levers)
from tpusystem.train import generate


def reference(module, params, prompt, steps, **kwargs):
    """Standalone greedy decode of one prompt — the parity oracle."""
    out = generate(module, params, jnp.asarray(prompt, jnp.int32)[None],
                   steps=steps, **kwargs)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


@pytest.fixture(scope='module')
def served():
    module = gpt2_tiny(dtype='float32')
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (1, 8)), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    return module, params


# ---------------------------------------------------------------------------
# paged pool: free-list + block tables (pure host bookkeeping)
# ---------------------------------------------------------------------------


class TestPagedKVCache:
    def test_free_list_allocates_and_frees(self):
        pool = PagedKVCache(rows=2, blocks=9, block_size=4, max_seq=32)
        assert pool.free_blocks == 8          # block 0 is reserved trash
        pool.admit(0, tokens=10)              # 3 blocks
        assert pool.free_blocks == 5
        assert (pool.table[0, :3] != TRASH_BLOCK).all()
        assert (pool.table[0, 3:] == TRASH_BLOCK).all()
        assert pool.evict(0) == 3
        assert pool.free_blocks == 8
        assert (pool.table[0] == TRASH_BLOCK).all()

    def test_slots_map_logical_positions_through_the_table(self):
        pool = PagedKVCache(rows=1, blocks=5, block_size=4, max_seq=16)
        pool.admit(0, tokens=6)               # blocks for positions 0..7
        slots = pool.slots(0)
        first, second = pool.table[0, 0], pool.table[0, 1]
        np.testing.assert_array_equal(slots[:4], first * 4 + np.arange(4))
        np.testing.assert_array_equal(slots[4:8], second * 4 + np.arange(4))
        # unmapped positions land in the trash block
        assert (slots[8:] < 4).all()

    def test_admission_beyond_free_blocks_raises_and_can_admit_gates(self):
        pool = PagedKVCache(rows=4, blocks=4, block_size=4, max_seq=32)
        assert pool.can_admit(12) and not pool.can_admit(13)
        pool.admit(0, tokens=12)              # all 3 allocatable blocks
        assert not pool.can_admit(1)
        with pytest.raises(ValueError, match='free'):
            pool.admit(1, tokens=4)
        with pytest.raises(ValueError, match='evict first'):
            pool.admit(0, tokens=4)

    def test_sequences_never_share_blocks(self):
        pool = PagedKVCache(rows=3, blocks=10, block_size=4, max_seq=32)
        for row in range(3):
            pool.admit(row, tokens=10)
        owned = pool.table[:, :3]
        assert len(set(owned.flatten().tolist())) == 9


# ---------------------------------------------------------------------------
# engine scope + capacity validation
# ---------------------------------------------------------------------------


def test_engine_gates_unsupported_modules(served):
    _, params = served
    assert engine_unsupported_reason(gpt2_tiny()) is None
    assert 'scan_layers' in engine_unsupported_reason(
        gpt2_tiny(scan_layers=True))
    # the MoE gate is LIFTED: decode dispatch runs full-capacity (no
    # token drops => per-token independence), so MoE modules serve
    assert engine_unsupported_reason(
        gpt2_tiny(moe_experts=2, moe_every=2)) is None
    with pytest.raises(ValueError, match='scan_layers'):
        Engine(gpt2_tiny(scan_layers=True), params)


def test_generate_strips_decode_pages_from_its_clone(served):
    """generate() on a module constructed with decode_pages set must
    decode through its own contiguous cache (the paged layout needs
    externally managed tables — only the engine provides them), token-
    exact with the plain module (found in review: an unstripped field
    silently aliased every row onto the trash block)."""
    module, params = served
    prompt = jnp.asarray(
        np.random.default_rng(37).integers(0, 256, (2, 6)), jnp.int32)
    plain = np.asarray(generate(module, params, prompt, steps=6))
    paged_field = np.asarray(generate(
        gpt2_tiny(dtype='float32', decode_pages=(16, 8)), params, prompt,
        steps=6))
    np.testing.assert_array_equal(paged_field, plain)


def test_engine_validates_capacity_and_saturation(served):
    module, params = served
    engine = Engine(module, params, rows=1, block_size=8)
    with pytest.raises(ValueError, match='max_seq'):
        engine.admit(np.arange(8), max_new=121)    # 8 + 121 > 128
    with pytest.raises(ValueError, match='max_new'):
        engine.admit(np.arange(8), max_new=0)
    engine.admit(np.arange(4) + 1, max_new=4)
    with pytest.raises(Saturated, match='free row'):
        engine.admit(np.arange(4) + 1, max_new=4)


def test_prefill_bucketing_is_bounded_powers_of_two():
    assert prefill_bucket(3, 16, 128) == 16       # floor at block_size
    assert prefill_bucket(17, 16, 128) == 32
    assert prefill_bucket(33, 16, 128) == 64
    assert prefill_bucket(100, 16, 128) == 128
    assert prefill_bucket(128, 16, 128) == 128    # capped at max_seq


def test_prefill_compile_count_is_bounded_by_buckets():
    """A stream of varied prompt lengths compiles one prefill program
    per BUCKET, not one per length (the round-5 retrace-trap
    discipline, applied to serving admission)."""
    from tpusystem.serve import engine as engine_module
    # a config no other test decodes, so the program-cache delta is ours
    module = gpt2_tiny(dtype='float32', max_seq=256)
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))['params']
    engine = Engine(module, params, rows=1, block_size=16)
    before = engine_module._compiled_prefill.cache_info().currsize
    for length in (3, 5, 9, 14, 16, 17, 20, 30):   # buckets: 16, 32
        row = engine.admit(np.arange(length) % 250 + 1, max_new=1)
        assert row.finished                        # max_new=1: done at admit
    added = engine_module._compiled_prefill.cache_info().currsize - before
    assert added == 2, f'{added} prefill programs for 2 buckets'


# ---------------------------------------------------------------------------
# token-exact parity vs standalone generate(), under churn
# ---------------------------------------------------------------------------


def test_engine_single_request_matches_generate(served):
    module, params = served
    prompt = np.random.default_rng(3).integers(0, 256, (7,))
    expected = reference(module, params, prompt, 8)
    engine = Engine(module, params, rows=2, block_size=8)
    engine.admit(prompt, max_new=8)
    tokens = None
    while engine.active_rows:
        for _row, reason, out in engine.step().finished:
            tokens, why = out, reason
    assert tokens == expected and why == 'length'


@pytest.mark.slow
@pytest.mark.parametrize('family', [gpt2_tiny, llama_tiny])
def test_engine_parity_under_churn(family):
    """Admit at step k, evict at step m: every request's tokens equal
    its standalone generate() regardless of co-batched rows — the
    engine's core contract."""
    module = family(dtype='float32')
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, (n,)) for n in (5, 11, 8, 3)]
    steps = [14, 6, 10, 9]
    params = module.init(jax.random.PRNGKey(0),
                         jnp.asarray(prompts[0][None]))['params']
    expected = [reference(module, params, p, s)
                for p, s in zip(prompts, steps)]

    engine = Engine(module, params, rows=2, block_size=8)
    scheduler = Scheduler(engine)
    # r0+r1 start together; r2 joins mid-stream (free-row churn: r1
    # finishes first, r2 takes its row); r3 joins after r0 retires
    scheduler.submit(Request('r0', list(prompts[0]), steps[0]))
    scheduler.submit(Request('r1', list(prompts[1]), steps[1]))
    for _ in range(4):
        scheduler.step()
    scheduler.submit(Request('r2', list(prompts[2]), steps[2]))
    for _ in range(6):
        scheduler.step()
    scheduler.submit(Request('r3', list(prompts[3]), steps[3]))
    results = scheduler.run()
    for index in range(4):
        got = results[f'r{index}']
        assert got.tokens == expected[index], f'r{index} diverged'
        assert got.reason == 'length'
    assert engine.trace_count == 1


def test_compile_guard_one_decode_trace_across_churn(served):
    """Admission/eviction NEVER retraces the decode step: one trace for
    the engine's whole life, across row churn and pool recycling."""
    module, params = served
    rng = np.random.default_rng(9)
    engine = Engine(module, params, rows=2, block_size=8)
    for wave in range(3):
        engine.admit(rng.integers(0, 256, (4 + wave,)), max_new=3)
        engine.admit(rng.integers(0, 256, (6,)), max_new=2 + wave)
        while engine.active_rows:
            engine.step()
    assert engine.trace_count == 1, (
        f'decode step retraced: {engine.trace_count} traces')


@pytest.mark.slow
def test_engine_int8_streaming_matches_generate_int8(served):
    """The PR-7 serving lever composes: an int8-streaming engine is
    token-exact against generate(stream_dtype='int8') — dequantization
    stays inside the one compiled step."""
    module, params = served
    prompt = np.random.default_rng(11).integers(0, 256, (9,))
    expected = reference(module, params, prompt, 10, stream_dtype='int8')
    engine = Engine(module, params, rows=2, block_size=8,
                    stream_dtype='int8')
    engine.admit(prompt, max_new=10)
    tokens = None
    while engine.active_rows:
        for _row, _reason, out in engine.step().finished:
            tokens = out
    assert tokens == expected


@pytest.mark.slow
def test_paged_read_crosses_block_bucket_boundary():
    """A generation whose filled depth crosses the paged read's
    power-of-2 block-window boundary stays token-exact (the switch picks
    a wider gather mid-stream — cached_attention's bucket test, paged
    flavored)."""
    module = gpt2_tiny(dtype='float32', max_seq=512)
    prompt = np.random.default_rng(29).integers(0, 256, (250,))
    params = module.init(jax.random.PRNGKey(1),
                         jnp.asarray(prompt[None, :8]))['params']
    expected = reference(module, params, prompt, 20)       # 250 -> 270
    engine = Engine(module, params, rows=2, block_size=16)
    engine.admit(prompt, max_new=20)
    tokens = None
    while engine.active_rows:
        for _row, _reason, out in engine.step().finished:
            tokens = out
    assert tokens == expected


# ---------------------------------------------------------------------------
# scheduler: exhaustion queues, budgets, cancellation
# ---------------------------------------------------------------------------


def test_free_list_exhaustion_queues_not_crashes(served):
    """More requests than the pool can seat: the overflow WAITS in the
    queue and drains in as rows/blocks free — never a crash, never a
    dropped request."""
    module, params = served
    rng = np.random.default_rng(13)
    # 8 allocatable blocks of 4 = 32 tokens; each request needs 3 blocks
    engine = Engine(module, params, rows=2, block_size=4, blocks=7)
    scheduler = Scheduler(engine)
    prompts = [rng.integers(0, 256, (4,)) for _ in range(5)]
    for index, prompt in enumerate(prompts):
        scheduler.submit(Request(f'r{index}', list(prompt), max_new=6))
    saw_backlog = False
    for _ in range(200):
        if scheduler.idle:
            break
        tick = scheduler.step()
        saw_backlog |= tick.queue_depth > 0
        assert tick.active <= 2
    assert scheduler.idle, 'queue never drained'
    assert saw_backlog, 'workload never actually queued — test has no teeth'
    for index, prompt in enumerate(prompts):
        assert scheduler.results[f'r{index}'].tokens == reference(
            module, params, prompt, 6), f'r{index} diverged under backlog'


def test_scheduler_refuses_never_fitting_requests(served):
    module, params = served
    engine = Engine(module, params, rows=2, block_size=8, blocks=4)
    scheduler = Scheduler(engine)
    with pytest.raises(ValueError, match='capacity'):
        scheduler.submit(Request('big', list(range(1, 100)), max_new=120))
    with pytest.raises(ValueError, match='blocks'):
        scheduler.submit(Request('wide', list(range(1, 30)), max_new=10))
    with pytest.raises(ValueError, match='non-empty'):
        scheduler.submit(Request('empty', [], max_new=4))


def test_prefill_budget_caps_admissions_per_step(served):
    """The prefill token budget separates phases: a step admits at most
    budget-worth of (bucket-padded) prompt tokens, so decode latency is
    bounded even under an admission burst — but one admission always
    proceeds, so an over-budget prompt cannot starve."""
    module, params = served
    rng = np.random.default_rng(17)
    engine = Engine(module, params, rows=4, block_size=16)
    scheduler = Scheduler(engine, prefill_budget=16)   # one 16-bucket/step
    for index in range(3):
        scheduler.submit(Request(f'r{index}',
                                 list(rng.integers(0, 256, (5,))),
                                 max_new=8))
    assert len(scheduler.step().admitted) == 1         # budget, not rows
    assert len(scheduler.step().admitted) == 1
    # a prompt wider than the whole budget still admits (alone)
    scheduler.submit(Request('wide', list(rng.integers(0, 256, (30,))),
                             max_new=4))
    admitted = {request.id
                for request, _, _ in scheduler.step().admitted}
    assert admitted == {'r2'}
    assert {request.id for request, _, _
            in scheduler.step().admitted} == {'wide'}
    scheduler.run()


def test_cancellation_mid_decode_frees_the_row_and_spares_neighbors(served):
    """Cancelling an active request evicts it mid-decode (partial tokens
    kept, reason 'cancelled'), frees its row for the queue, and leaves
    co-batched rows token-exact."""
    module, params = served
    rng = np.random.default_rng(19)
    keep_prompt = rng.integers(0, 256, (6,))
    expected = reference(module, params, keep_prompt, 12)
    engine = Engine(module, params, rows=2, block_size=8)
    scheduler = Scheduler(engine)
    scheduler.submit(Request('keep', list(keep_prompt), max_new=12))
    scheduler.submit(Request('dead', list(rng.integers(0, 256, (5,))),
                             max_new=12))
    scheduler.submit(Request('next', list(rng.integers(0, 256, (4,))),
                             max_new=3))                 # waits for a row
    scheduler.step()
    assert scheduler.queue_depth == 1
    scheduler.step()
    assert scheduler.cancel('dead') == 'active'
    cancelled = scheduler.results['dead']
    assert cancelled.reason == 'cancelled'
    assert 0 < len(cancelled.tokens) < 12
    results = scheduler.run()
    assert results['keep'].tokens == expected
    assert results['next'].reason == 'length'
    assert scheduler.cancel('keep') is None              # already done


def test_scheduler_tolerates_rows_admitted_directly_on_the_engine(served):
    """A row seated via engine.admit() (not through the scheduler)
    retires without a scheduler seat — the scheduler must skip it, not
    KeyError, and its own queued request must still drain in behind it
    (found by the verify drive)."""
    module, params = served
    rng = np.random.default_rng(41)
    engine = Engine(module, params, rows=1, block_size=8, blocks=5)
    engine.admit(rng.integers(0, 256, (5,)), max_new=6)   # foreign row
    scheduler = Scheduler(engine)
    scheduler.submit(Request('late', list(rng.integers(0, 256, (4,))),
                             max_new=5))
    results = scheduler.run()
    assert results['late'].reason == 'length'
    assert len(results['late'].tokens) == 5


def test_cancelling_a_queued_request_drops_it(served):
    module, params = served
    engine = Engine(module, params, rows=1, block_size=8)
    scheduler = Scheduler(engine)
    scheduler.submit(Request('q', [1, 2, 3], max_new=4))
    assert scheduler.cancel('q') == 'queued'
    assert scheduler.idle and 'q' not in scheduler.results


def test_stop_token_completes_early(served):
    module, params = served
    prompt = np.random.default_rng(23).integers(0, 256, (7,))
    expected = reference(module, params, prompt, 12)
    stop = expected[3]
    first_hit = expected.index(stop)                     # tokens repeat
    engine = Engine(module, params, rows=1, block_size=8)
    scheduler = Scheduler(engine)
    scheduler.submit(Request('s', list(prompt), max_new=12,
                             stop_token=stop))
    results = scheduler.run()
    assert results['s'].reason == 'stop'
    assert results['s'].tokens == expected[:first_hit + 1]  # stop included


# ---------------------------------------------------------------------------
# deadlines: saturation starvation becomes a typed expiry, never silence
# ---------------------------------------------------------------------------


def test_deadline_expires_queued_requests_instead_of_starving(served):
    """The fixed gap: under saturation a queued request could wait
    forever. With a deadline it expires — typed reason, empty tokens —
    and the seated neighbor is untouched (still token-exact)."""
    module, params = served
    rng = np.random.default_rng(21)
    engine = Engine(module, params, rows=1, block_size=8)
    scheduler = Scheduler(engine)
    prompt = list(rng.integers(0, 256, (4,)))
    scheduler.submit(Request('hog', prompt, max_new=12))
    scheduler.submit(Request('starved', prompt, max_new=4, deadline=0.05))
    tick = scheduler.step()                # hog seats; starved waits
    assert tick.queue_depth == 1 and not tick.expired
    time.sleep(0.08)
    tick = scheduler.step()
    assert [(completion.request.id, where)
            for completion, where in tick.expired] == [('starved', 'queued')]
    starved = scheduler.results['starved']
    assert starved.reason == 'expired' and starved.tokens == []
    assert starved.seconds >= 0.05
    scheduler.run()
    hog = scheduler.results['hog']
    assert hog.reason == 'length'
    assert hog.tokens == reference(module, params, prompt, 12)


def test_deadline_evicts_active_requests_mid_decode(served):
    """An ACTIVE request past its deadline is evicted mid-decode: partial
    tokens kept, row and blocks freed, neighbors token-exact."""
    module, params = served
    rng = np.random.default_rng(23)
    engine = Engine(module, params, rows=2, block_size=8)
    scheduler = Scheduler(engine)
    slow = list(rng.integers(0, 256, (5,)))
    quick = list(rng.integers(0, 256, (6,)))
    scheduler.submit(Request('slow', slow, max_new=50, deadline=0.05))
    scheduler.submit(Request('quick', quick, max_new=6))
    scheduler.step()                       # both seated, decoding
    time.sleep(0.08)
    tick = scheduler.step()
    assert [(completion.request.id, where)
            for completion, where in tick.expired] == [('slow', 'active')]
    expired = scheduler.results['slow']
    assert expired.reason == 'expired'
    assert 0 < len(expired.tokens) < 50    # partial output survives
    scheduler.run()
    assert scheduler.results['quick'].tokens == reference(
        module, params, quick, 6)


def test_deadline_validation(served):
    module, params = served
    engine = Engine(module, params, rows=1, block_size=8)
    scheduler = Scheduler(engine)
    with pytest.raises(ValueError, match='deadline'):
        scheduler.submit(Request('bad', [1, 2, 3], max_new=4, deadline=0.0))


def test_service_narrates_request_expired(served):
    from tpusystem.observe.events import RequestExpired
    from tpusystem.services.prodcon import Consumer, Producer

    module, params = served
    rng = np.random.default_rng(27)
    witnessed = []
    consumer = Consumer('probe')
    consumer.register(RequestExpired, witnessed.append)
    producer = Producer()
    producer.register(consumer)
    service = InferenceService(module, params, producer=producer, rows=1,
                               block_size=8)
    prompt = list(rng.integers(0, 256, (4,)))
    service.submit(Request('hog', prompt, max_new=8))
    service.submit(Request('starved', prompt, max_new=4, deadline=0.05))
    service.step()
    time.sleep(0.08)
    service.run_until_idle()
    assert len(witnessed) == 1
    event = witnessed[0]
    assert event.id == 'starved' and event.where == 'queued'
    assert event.produced == 0 and event.waited >= 0.05


# ---------------------------------------------------------------------------
# the bus front door
# ---------------------------------------------------------------------------


def test_service_narrates_the_request_lifecycle(served):
    from tpusystem.observe.events import (RequestAdmitted, RequestCompleted,
                                          RequestEvicted, ServeStepped)
    from tpusystem.services.prodcon import Consumer, Producer

    module, params = served
    rng = np.random.default_rng(29)
    witnessed = []
    consumer = Consumer('probe')

    @consumer.handler
    def on_serving(event: RequestAdmitted | RequestCompleted
                   | RequestEvicted | ServeStepped):
        witnessed.append(event)

    producer = Producer()
    producer.register(consumer)
    service = InferenceService(module, params, producer=producer, rows=2,
                               block_size=8)
    service.service.handle('submit',
                           Request('a', list(rng.integers(0, 256, (5,))),
                                   max_new=4))
    service.service.handle('submit',
                           Request('b', list(rng.integers(0, 256, (6,))),
                                   max_new=20))
    service.step()
    service.service.handle('cancel', 'b')
    service.run_until_idle()

    kinds = {type(event).__name__ for event in witnessed}
    assert kinds == {'RequestAdmitted', 'RequestCompleted',
                     'RequestEvicted', 'ServeStepped'}
    admitted = [e for e in witnessed if isinstance(e, RequestAdmitted)]
    assert {e.id for e in admitted} == {'a', 'b'}
    assert all(e.ttft >= 0 for e in admitted)
    evicted = [e for e in witnessed if isinstance(e, RequestEvicted)]
    assert evicted[0].id == 'b' and evicted[0].reason == 'cancelled'
    completed = [e for e in witnessed if isinstance(e, RequestCompleted)]
    assert completed[0].id == 'a' and completed[0].reason == 'length'
    stepped = [e for e in witnessed if isinstance(e, ServeStepped)]
    assert stepped[-1].queue_depth == 0 and stepped[-1].active == 0


def test_tensorboard_serve_handlers_chart_the_events(tmp_path):
    import pytest

    from tests.tb import read_scalars
    from tpusystem.observe.events import RequestAdmitted, ServeStepped
    from tpusystem.observe.tensorboard import (SummaryWriter,
                                               tensorboard_consumer, writer)

    consumer = tensorboard_consumer()
    board = SummaryWriter(tmp_path)
    consumer.dependency_overrides[writer] = lambda: board
    consumer.consume(RequestAdmitted(id='r', row=0, prompt_tokens=5,
                                     ttft=0.01, queue_depth=2))
    consumer.consume(ServeStepped(step=3, active=2, queue_depth=1,
                                  emitted=2, tokens_per_sec=123.4))
    board.flush()
    scalars = read_scalars(tmp_path)        # parsed back, not byte-poked
    value, step = scalars['serve/ttft_seconds']
    assert value == pytest.approx(0.01) and step == 1   # admission counter
    assert scalars['serve/queue_depth_at_admit'] == (2.0, 1)
    assert scalars['serve/queue_depth'] == (1.0, 3)
    assert scalars['serve/active_rows'] == (2.0, 3)
    value, step = scalars['serve/tok_s']
    assert value == pytest.approx(123.4) and step == 3


def test_serve_levers_pick_the_backend_default():
    levers = serve_levers()
    assert levers['stream_dtype'] == ('int8' if on_tpu() else 'auto')


# ---------------------------------------------------------------------------
# radix prefix sharing: refcounted blocks, token-exact adoption
# ---------------------------------------------------------------------------


class TestRadixPrefixSharing:
    def test_refcounted_free_list_survives_interleaved_churn(self):
        """Admit/retire with interleaved shared prefixes: blocks are
        shared only between rows whose prompts actually share the
        prefix, refcounts return to zero on retirement, and the pool's
        accounting matches a from-scratch audit."""
        pool = PagedKVCache(rows=4, blocks=32, block_size=4, max_seq=64,
                            share_prefix=True)
        head = list(range(1, 13))            # 3 full blocks
        other = list(range(100, 112))        # a DIFFERENT 3-block prefix
        pool.admit(0, tokens=14, prompt=head + [50, 51])
        pool.admit(1, tokens=14, prompt=head + [60, 61])
        pool.admit(2, tokens=14, prompt=other + [70, 71])
        # rows 0/1 share exactly the 3 head blocks; row 2 shares nothing
        assert pool.shared_tokens(0) == 0    # first arrival populated it
        assert pool.shared_tokens(1) == 12
        assert pool.shared_tokens(2) == 0
        np.testing.assert_array_equal(pool.table[0, :3], pool.table[1, :3])
        shared = set(pool.table[0, :3].tolist())
        assert not shared & set(pool.table[2, :4].tolist())
        # suffix blocks are PRIVATE even between the sharing rows
        assert pool.table[0, 3] != pool.table[1, 3]
        audit = pool.audit()
        # churn: retire the first owner — the adopter keeps the blocks
        pool.evict(0)
        assert pool.shared_tokens(1) == 12
        pool.admit(3, tokens=14, prompt=head + [80, 81])
        assert pool.shared_tokens(3) == 12
        np.testing.assert_array_equal(pool.table[1, :3], pool.table[3, :3])
        for row in (1, 2, 3):
            pool.evict(row)
        # refcounts all back to zero: nothing live, accounting exact
        audit = pool.audit()
        assert audit['live'] == 0
        assert audit['free'] + audit['cached'] == pool.blocks - 1
        assert pool.free_blocks == pool.blocks - 1

    def test_cached_blocks_are_reclaimed_lru_under_pressure(self):
        pool = PagedKVCache(rows=2, blocks=8, block_size=4, max_seq=64,
                            share_prefix=True)
        pool.admit(0, tokens=10, prompt=list(range(1, 11)))   # 3 blocks
        pool.evict(0)                        # 2 registered blocks go warm
        assert pool.audit()['cached'] == 2
        # a new admission needing every block reclaims the warm ones
        pool.admit(1, tokens=28, prompt=list(range(50, 78)))  # 7 blocks
        assert pool.audit()['cached'] == 0
        pool.evict(1)

    def test_engine_sharing_is_token_exact_and_counts_hits(self, served):
        """Co-batched requests sharing a system prompt adopt its blocks
        and stay token-exact vs standalone generate(); the retired
        prefix is re-adopted warm by a later wave."""
        module, params = served
        rng = np.random.default_rng(43)
        engine = Engine(module, params, rows=4, block_size=4, blocks=64,
                        share_prefix=True)
        scheduler = Scheduler(engine)
        head = [int(t) for t in rng.integers(0, 256, (21,))]
        prompts = [head + [int(t) for t in rng.integers(0, 256, (k,))]
                   for k in (3, 4, 5, 2)]
        for index, prompt in enumerate(prompts):
            scheduler.submit(Request(f'r{index}', prompt, max_new=5))
        results = scheduler.run()
        for index, prompt in enumerate(prompts):
            assert results[f'r{index}'].tokens == reference(
                module, params, prompt, 5), f'r{index} diverged'
        assert engine.sharing['prefix_hits'] == 3      # all but the first
        assert engine.prefix_hit_rate() > 0.5
        assert engine.trace_count == 1
        # second wave: the whole prefix is warm in the radix tree
        assert engine.prefix_cached_len(head + [9]) == 20   # (21-1)//4*4
        scheduler.submit(Request('warm', head + [9, 9], max_new=4))
        results = scheduler.run()
        assert results['warm'].tokens == reference(
            module, params, head + [9, 9], 4)

    def test_sharing_row_tokens_independent_of_cobatched_traffic(self, served):
        """The engine contract under sharing: a row's tokens equal its
        solo run even when neighbors share (or don't share) its
        prefix."""
        module, params = served
        rng = np.random.default_rng(47)
        prompt = [int(t) for t in rng.integers(0, 256, (13,))]
        solo_engine = Engine(module, params, rows=4, block_size=4,
                             share_prefix=True)
        solo_engine.admit(prompt, max_new=6)
        solo = None
        while solo_engine.active_rows:
            for _row, _reason, out in solo_engine.step().finished:
                solo = out
        engine = Engine(module, params, rows=4, block_size=4,
                        share_prefix=True)
        engine.admit(prompt[:9] + [3, 1, 4, 1], max_new=6)   # partial share
        admission = engine.admit(prompt, max_new=6)
        engine.admit([int(t) for t in rng.integers(0, 256, (7,))], max_new=6)
        tokens = {}
        while engine.active_rows:
            for row, _reason, out in engine.step().finished:
                tokens[row] = out
        assert tokens[admission.row] == solo


# ---------------------------------------------------------------------------
# fused decode_impl: the Pallas chain behind the paged step
# ---------------------------------------------------------------------------


class TestFusedDecodeImpl:
    def test_fused_step_is_token_exact_vs_flax(self, served):
        module, params = served
        rng = np.random.default_rng(53)
        prompts = [[int(t) for t in rng.integers(0, 256, (k,))]
                   for k in (7, 5)]
        engine = Engine(module, params, rows=2, block_size=8,
                        decode_impl='fused')
        for prompt in prompts:
            engine.admit(prompt, max_new=6)
        tokens = {}
        while engine.active_rows:
            for row, _reason, out in engine.step().finished:
                tokens[row] = out
        for row, prompt in enumerate(prompts):
            assert tokens[row] == reference(module, params, prompt, 6)
        assert engine.trace_count == 1

    @pytest.mark.slow
    def test_fused_step_composes_with_int8_streaming(self, served):
        module, params = served
        prompt = np.random.default_rng(59).integers(0, 256, (9,))
        expected = reference(module, params, prompt, 8, stream_dtype='int8')
        engine = Engine(module, params, rows=2, block_size=8,
                        decode_impl='fused', stream_dtype='int8')
        engine.admit(prompt, max_new=8)
        tokens = None
        while engine.active_rows:
            for _row, _reason, out in engine.step().finished:
                tokens = out
        assert tokens == expected

    @staticmethod
    def _drain(engine) -> dict:
        tokens = {}
        while engine.active_rows:
            for row, _reason, out in engine.step().finished:
                tokens[row] = out
        return tokens

    @pytest.mark.parametrize('levers', [
        {}, {'stream_dtype': 'int8'}, {'share_prefix': True}],
        ids=['greedy', 'int8', 'share_prefix'])
    def test_fused_engine_emits_the_flax_engines_tokens(self, served, levers):
        """Same requests, same levers, both step implementations: the
        Pallas paged-attention read changes no token — plain greedy, int8
        streaming, and rows whose tables share read-only prefix blocks."""
        module, params = served
        rng = np.random.default_rng(61)
        common = [int(t) for t in rng.integers(0, 256, (17,))]
        prompts = [common + [int(t) for t in rng.integers(0, 256, (k,))]
                   for k in (3, 9)]
        emitted = {}
        for impl in ('flax', 'fused'):
            engine = Engine(module, params, rows=2, block_size=8,
                            decode_impl=impl, **levers)
            for prompt in prompts:
                engine.admit(prompt, max_new=7)
            emitted[impl] = self._drain(engine)
            assert engine.trace_count == 1
            if levers.get('share_prefix'):
                assert engine.sharing['prefix_hits'] == 1
        assert emitted['fused'] == emitted['flax']

    def test_fused_read_follows_each_rows_own_depth(self):
        """Was: the bucket switch widening mid-stream. Now: a deep row
        crosses a block boundary, then the kernel's chunk boundary (128
        positions), beside a shallow row that stays inside its first
        block — each token-exact against its own standalone decode, so a
        row reads to its own cursor whatever the deepest row holds."""
        module = gpt2_tiny(dtype='float32', max_seq=256)
        rng = np.random.default_rng(67)
        deep = [int(t) for t in rng.integers(0, 256, (121,))]
        shallow = [int(t) for t in rng.integers(0, 256, (3,))]
        params = module.init(jax.random.PRNGKey(1),
                             jnp.asarray([deep[:8]]))['params']
        engine = Engine(module, params, rows=2, block_size=16,
                        decode_impl='fused')
        engine.admit(deep, max_new=12)              # 121 -> 133
        engine.admit(shallow, max_new=12)           # 3 -> 15
        tokens = self._drain(engine)
        assert tokens[0] == reference(module, params, deep, 12)
        assert tokens[1] == reference(module, params, shallow, 12)
        assert engine.trace_count == 1

    def test_fused_step_holds_no_conditional_over_read_windows(self, served):
        """The step as traced: one paged-attention kernel a layer beside
        the chain's three, and — outside the kernels — no ``cond`` at all
        (the five-way window switch is gone) and no gather out of a pool.
        ``lowered_step()`` on the CPU holds the interpreter's own
        conditionals, so the witness reads the jaxpr above the kernels."""
        from tpusystem.train.decode_fused import build_fused_paged_step
        module, params = served
        engine = Engine(module, params, rows=2, block_size=8,
                        decode_impl='fused')
        jaxpr = jax.make_jaxpr(build_fused_paged_step(engine._decoder))(
            engine._params, engine._cache, engine._tokens_dev)
        pools = {leaf.shape for path, leaf
                 in jax.tree_util.tree_leaves_with_path(engine._cache)
                 if path[-1].key in ('key', 'value')}
        kernels, seen = [], []

        def walk(closed):
            for eqn in closed.eqns:
                if eqn.primitive.name == 'pallas_call':
                    kernels.append(eqn.params['name'])
                    continue                    # the kernel's own body
                seen.append(eqn)
                for value in eqn.params.values():
                    inner = getattr(value, 'jaxpr', value)
                    if hasattr(inner, 'eqns'):
                        walk(inner)

        walk(jaxpr.jaxpr)
        assert kernels.count('paged_decode_attention') == module.layers
        assert len(kernels) == 4 * module.layers
        assert not [eqn for eqn in seen if eqn.primitive.name == 'cond']
        assert not [eqn for eqn in seen if eqn.primitive.name == 'gather'
                    and eqn.invars[0].aval.shape in pools]
        # (lowering re-traces; the one-trace witness is put back)
        assert 'jit_step_fn' in engine.lowered_step()
        assert engine.trace_count == 0

    def test_fused_refuses_unsupported_and_auto_falls_back(self, served):
        module, params = served
        probe = jnp.zeros((1, 8), jnp.int32)
        moe = gpt2_tiny(dtype='float32', moe_experts=2, moe_every=2)
        moe_params = moe.init(jax.random.PRNGKey(0), probe)['params']
        with pytest.raises(ValueError, match='fused'):
            Engine(moe, moe_params, rows=2, block_size=8,
                   decode_impl='fused')
        # 'auto' serves the same module through the flax step instead
        engine = Engine(moe, moe_params, rows=2, block_size=8,
                        decode_impl='auto')
        assert engine.decode_impl == 'flax'
        with pytest.raises(ValueError, match='decode_impl'):
            Engine(module, params, rows=2, block_size=8,
                   decode_impl='nonsense')


# ---------------------------------------------------------------------------
# the MoE gate, lifted: full-capacity decode dispatch
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_moe_engine_is_token_exact_under_cobatching():
    """Serving an MoE module: full-capacity decode dispatch drops no
    token, so each row's experts see it regardless of co-batched
    neighbors — token-exact vs standalone generate()."""
    module = gpt2_tiny(dtype='float32', moe_experts=2, moe_every=2)
    rng = np.random.default_rng(61)
    probe = jnp.asarray(rng.integers(0, 256, (1, 8)), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), probe)['params']
    engine = Engine(module, params, rows=3, block_size=4)
    prompts = [[int(t) for t in rng.integers(0, 256, (k,))]
               for k in (7, 5, 9)]
    admissions = [engine.admit(p, max_new=6) for p in prompts]
    tokens = {}
    while engine.active_rows:
        for row, _reason, out in engine.step().finished:
            tokens[row] = out
    for admission, prompt in zip(admissions, prompts):
        assert tokens[admission.row] == reference(module, params, prompt, 6)
    assert engine.trace_count == 1


# ---------------------------------------------------------------------------
# speculative rows: draft/verify riding the paged pool
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize('fanout', [1, 2])
def test_speculative_rows_match_target_greedy(served, fanout):
    """Draft rows ride the paged pool as extra batch rows; the output is
    exactly the target's greedy decode and the multi-token steps beat
    one-token-per-step (fewer engine steps than tokens)."""
    module, params = served
    rng = np.random.default_rng(67)
    engine = Engine(module, params, rows=4, block_size=4,
                    draft_module=module, draft_params=params,
                    speculate=3, tree_fanout=fanout)
    prompts = [[int(t) for t in rng.integers(0, 256, (k,))]
               for k in (7, 5)]
    admissions = [engine.admit(p, max_new=8) for p in prompts]
    tokens, steps = {}, 0
    while engine.active_rows:
        engine_report = engine.step()
        steps += 1
        for row, _reason, out in engine_report.finished:
            tokens[row] = out
    for admission, prompt in zip(admissions, prompts):
        assert tokens[admission.row] == reference(module, params, prompt, 8)
    # a self-draft accepts every token: 8 tokens in ceil(8/4) steps
    assert steps < 8
    assert engine.trace_count == 1
    assert engine.pool.free_blocks == engine.pool.blocks - 1


@pytest.mark.slow
def test_speculative_rows_compose_with_sharing_through_scheduler(served):
    module, params = served
    rng = np.random.default_rng(71)
    engine = Engine(module, params, rows=4, block_size=4,
                    share_prefix=True, draft_module=module,
                    draft_params=params, speculate=3, tree_fanout=2)
    scheduler = Scheduler(engine)
    head = [int(t) for t in rng.integers(0, 256, (13,))]
    prompts = [head + [int(t) for t in rng.integers(0, 256, (k,))]
               for k in (3, 2)]
    for index, prompt in enumerate(prompts):
        scheduler.submit(Request(f'r{index}', prompt, max_new=6))
    results = scheduler.run()
    for index, prompt in enumerate(prompts):
        assert results[f'r{index}'].tokens == reference(
            module, params, prompt, 6)
    assert engine.sharing['prefix_hits'] >= 1


def test_speculative_validates_budget_and_stop_token(served):
    module, params = served
    rng = np.random.default_rng(73)
    engine = Engine(module, params, rows=2, block_size=8,
                    draft_module=module, draft_params=params, speculate=3)
    with pytest.raises(ValueError, match='speculate'):
        # 8 + 117 + 3 + 1 > 128: the draft chain would overrun max_seq
        engine.admit(list(rng.integers(0, 256, (8,))), max_new=117)
    prompt = [int(t) for t in rng.integers(0, 256, (7,))]
    expected = reference(module, params, prompt, 12)
    stop = expected[3]
    scheduler = Scheduler(engine)
    scheduler.submit(Request('s', prompt, max_new=12, stop_token=stop))
    results = scheduler.run()
    assert results['s'].reason == 'stop'
    assert results['s'].tokens == expected[:expected.index(stop) + 1]


# ---------------------------------------------------------------------------
# scheduler: suffix-only prefill budgeting
# ---------------------------------------------------------------------------


def test_prefill_budget_counts_only_the_uncached_suffix(served):
    """With sharing on, a second wave of shared-prefix requests costs
    the budget only its uncached suffix buckets — so a budget that
    admits ONE cold request a step admits the whole warm wave at once.
    And a FULLY cached prompt still charges bucket(1): the one-
    admission rule can't spin on zero-cost admissions."""
    module, params = served
    rng = np.random.default_rng(79)
    engine = Engine(module, params, rows=4, block_size=16, blocks=64,
                    share_prefix=True)
    head = [int(t) for t in rng.integers(0, 256, (33,))]   # 2 full blocks
    scheduler = Scheduler(engine, prefill_budget=64)
    scheduler.submit(Request('cold', head + [1], max_new=3))
    scheduler.run()                       # radix tree now holds the head
    assert engine.prefix_cached_len(head + [2]) == 32
    # cold cost: bucket(34) = 64 — one per step under this budget.
    # warm cost: bucket(2) = 16 — four fit in one step's budget
    for index in range(4):
        scheduler.submit(Request(f'w{index}', head + [2 + index], max_new=3))
    tick = scheduler.step()
    assert len(tick.admitted) == 4, [r.id for r, _, _ in tick.admitted]
    assert engine.admit_cost(head + [2]) == 16
    assert engine.admit_cost(head) == 16  # fully cached still costs >0
    scheduler.run()
