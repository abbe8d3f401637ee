"""The program's own spans and scopes: every ``tpusystem.*`` host span of
docs/observability.md lands in a device trace where the work happens,
costs nothing when no profiler runs, the ``Tracer`` gets its ``admit``
span, and the lowered programs carry the ``jax.named_scope`` names."""

from __future__ import annotations

import importlib.util
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusystem.models import GPT2
from tpusystem.observe.trace import Tracer, connected_traces
from tpusystem.serve import (Engine, InferenceService, Request,
                             SamplingParams)

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the tests/chipbench_tests/tiny.py sizes
TINY = dict(vocab_size=128, layers=2, dim=32, heads=4, max_seq=64,
            dropout=0.0)
PROMPTS = [list(range(3, 3 + length)) for length in (6, 20, 11)]


def profiled(work, directory) -> list:
    """Run ``work()`` under ``jax.profiler`` and return the program's host
    spans as ``(name, start_ns, end_ns, stats)``, in time order."""
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    newest = sorted(pathlib.Path(directory).rglob('*.xplane.pb'))[-1]
    spans = []
    for plane in ProfileData.from_file(str(newest)).planes:
        for line in plane.lines:
            spans.extend(
                (event.name, event.start_ns,
                 event.start_ns + event.duration_ns, dict(event.stats))
                for event in line.events
                if event.name.startswith('tpusystem.'))
    return sorted(spans, key=lambda span: (span[1], -span[2]))


def inside(span, parent) -> bool:
    return parent[1] <= span[1] and span[2] <= parent[2]


@pytest.fixture(scope='module')
def served():
    module = GPT2(**TINY)
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))['params']
    return module, params


def serve(served, tracer=None, clock=time.perf_counter):
    module, params = served
    service = InferenceService(module, params, rows=4, block_size=16,
                               clock=clock, tracer=tracer)
    streamed: dict = {}
    for index, prompt in enumerate(PROMPTS):
        service.submit(Request(f'r{index}', prompt, 5),
                       lambda at, token, key=f'r{index}':
                       streamed.setdefault(key, []).append(token))
    return service, streamed


# ------------------------------------------------------------ serving spans

SERVE_SPANS = ['tpusystem.serve.tick', 'tpusystem.scheduler.admit',
               'tpusystem.engine.prefill', 'tpusystem.engine.adopt',
               'tpusystem.engine.seat',
               'tpusystem.engine.dispatch', 'tpusystem.engine.read',
               'tpusystem.engine.rows', 'tpusystem.service.narrate']


@pytest.fixture(scope='module')
def serving_trace(served, tmp_path_factory):
    service, _ = serve(served)
    before = service._clock()
    spans = profiled(service.run_until_idle,
                     tmp_path_factory.mktemp('serve-trace'))
    return service, spans, (before, service._clock())


@pytest.mark.parametrize('name', SERVE_SPANS)
def test_serving_span_lands_inside_its_tick(serving_trace, name):
    _, spans, _ = serving_trace
    ticks = [span for span in spans if span[0] == SERVE_SPANS[0]]
    found = [span for span in spans if span[0] == name]
    assert found, f'no {name} span in the trace'
    for span in found:
        assert any(inside(span, tick) for tick in ticks), span


def test_tick_stats_round_trip(serving_trace):
    service, spans, (before, after) = serving_trace
    ticks = [span for span in spans if span[0] == 'tpusystem.serve.tick']
    assert [tick[3]['step'] for tick in ticks] == list(
        range(1, service.scheduler.steps + 1))
    clocks = [tick[3]['clock'] for tick in ticks]
    assert clocks == sorted(clocks)
    assert before <= clocks[0] <= clocks[-1] <= after
    # one clock offset places every tick: trace time and the service's
    # clock advance together (to well under a millisecond). The clock is
    # read before the span opens, so a host preempted between the two
    # (six xdist workers share it) reads one offset long: all but the
    # largest have to agree.
    offsets = sorted(tick[1] * 1e-9 - clock
                     for tick, clock in zip(ticks, clocks))
    assert offsets[-2] - offsets[0] < 1e-3


def test_dispatch_span_names_the_side_of_selection(served, serving_trace,
                                                   tmp_path):
    """Each ``tpusystem.engine.dispatch`` span carries, as its ``select``
    stat, the side of ``select_tokens``' conditional the tick took — what
    ``Engine.selection`` counts."""
    def sides(spans):
        return [span[3]['select'] for span in spans
                if span[0] == 'tpusystem.engine.dispatch']

    service, spans, _ = serving_trace
    assert sides(spans) == ['greedy'] * service.scheduler.steps
    assert service.engine.selection == {
        'greedy_ticks': service.scheduler.steps, 'sampled_ticks': 0}

    service, _ = serve(served)
    service.submit(Request('sampled', PROMPTS[0], 3, sampling=SamplingParams(
        seed=5, temperature=0.8)))
    mixed = sides(profiled(service.run_until_idle, tmp_path))
    assert mixed == sorted(mixed, reverse=True)      # sampled ticks, then greedy
    assert {side: mixed.count(side) for side in ('greedy', 'sampled')} == {
        side: service.engine.selection[f'{side}_ticks']
        for side in ('greedy', 'sampled')}
    assert mixed.count('sampled') >= 1 and mixed.count('greedy') >= 1


def test_spans_sit_on_the_brackets_the_timings_accumulate(serving_trace):
    service, spans, _ = serving_trace
    seconds = lambda *names: sum(
        span[2] - span[1] for span in spans if span[0] in names) * 1e-9
    timings = service.engine.timings
    for spent, names in (
            (timings['prefill'], ['tpusystem.engine.prefill']),
            (timings['admit'], ['tpusystem.engine.adopt']),
            (timings['step'], ['tpusystem.engine.dispatch',
                               'tpusystem.engine.read'])):
        # each span opens and closes inside the bracket its timing takes
        assert 0.5 * spent <= seconds(*names) <= spent + 1e-4


def test_nothing_else_uses_the_prefix_or_the_profiler_directly():
    opened = []
    for path in list((ROOT / 'tpusystem').rglob('*.py')) + list(
            (ROOT / 'examples').rglob('*.py')):
        if 'jax.profiler.TraceAnnotation' in path.read_text():
            opened.append(str(path.relative_to(ROOT)))
    assert opened == ['tpusystem/observe/profile.py']


def test_no_profiler_same_tokens_one_trace_timings_accumulate(served,
                                                              serving_trace):
    traced_service, _, _ = serving_trace
    service, streamed = serve(served)
    results = service.run_until_idle()
    assert {key: done.tokens for key, done in results.items()} == {
        key: done.tokens for key, done in traced_service.results.items()}
    assert {key: done.tokens for key, done in results.items()} == streamed
    assert service.engine.trace_count == 1
    assert traced_service.engine.trace_count == 1
    assert all(spent > 0 for spent in service.engine.timings.values())
    assert service.engine.last_step_seconds > 0


# -------------------------------------------------------- the Tracer's admit

def test_tracer_gets_one_admit_span_per_request_inside_queued(served):
    clock = iter(np.arange(0.0, 1e4, 0.125))
    tracer = Tracer('serve', clock=lambda: float(next(clock)))
    service, _ = serve(served, tracer, clock=tracer.clock)
    service.run_until_idle()
    events = [event for event in tracer.events() if event['ph'] == 'X'
              and event['cat'] not in ('setup', 'compile')]
    by_trace = connected_traces(events)
    assert len(by_trace) == len(PROMPTS)
    for group in by_trace.values():
        named = {event['name']: event for event in group}
        root = next(event for event in group
                    if event['name'].startswith('request '))
        admit, queued = named['admit'], named['queued']
        assert sum(event['name'] == 'admit' for event in group) == 1
        assert admit['args']['parent'] == root['args']['span_id']
        assert queued['ts'] <= admit['ts']
        assert admit['ts'] + admit['dur'] <= queued['ts'] + queued['dur']
        assert admit['dur'] > 0 and 'open' not in admit['args']
        prompt = PROMPTS[int(admit['args']['request'][1:])]
        assert admit['args']['prompt_tokens'] == len(prompt)
        assert admit['args']['bucket'] == service.engine.bucket(len(prompt))
        assert admit['args']['row'] == named['decode']['args']['row']


def test_the_service_records_its_set_up_and_every_compile():
    """``InferenceService(tracer=)`` watches compiles from before the
    engine: ``setup.engine`` holds the construction, the warm-up traces the
    seat, clear and decode programs and one prefill program a bucket, each
    once, and twenty more ticks with admissions at the warm buckets trace
    nothing. (A module of its own: prefill programs are kept by module and
    bucket, and the other tests' module has its programs already.)"""
    module = GPT2(**{**TINY, 'max_seq': 48})
    params = module.init(jax.random.PRNGKey(1),
                         jnp.zeros((1, 8), jnp.int32))['params']
    tracer = Tracer('serve', clock=time.perf_counter)
    before = time.perf_counter()
    service, _ = serve((module, params), tracer)
    built = time.perf_counter()
    service.run_until_idle()
    (engine,) = [event for event in tracer.events()
                 if event['name'] == 'setup.engine']
    assert engine['cat'] == 'setup'
    assert before <= engine['ts'] * 1e-6
    assert (engine['ts'] + engine['dur']) * 1e-6 <= built
    traced = tracer.compiled('trace')
    buckets = {service.engine.bucket(len(prompt)) for prompt in PROMPTS}
    assert (traced['seat'], traced['clear'], traced['step_fn']) == (1, 1, 1)
    assert traced['run'] == len(buckets) > 1       # the prefill programs
    backend = tracer.compiled('backend')
    for fun in ('seat', 'clear', 'step_fn'):
        assert backend[f'jit({fun})'] == 1, fun
    assert tracer.compiles['trace'] == sum(traced.values())
    assert tracer.compiles['backend'] == sum(backend.values())
    # every compile span lies on the tracer's clock, after the tracer began
    spans = [event for event in tracer.events()
             if event.get('cat') == 'compile']
    assert min(event['ts'] for event in spans) * 1e-6 >= before - 1e-3
    warm = len(tracer)
    for tick in range(20):
        if tick % 4 == 0:
            service.submit(Request(f'again{tick}', PROMPTS[tick // 4 % 3], 3))
        service.step()
    assert service.scheduler.steps and service.results['again16'].tokens
    assert [span.name for span in list(tracer._spans.values())[warm:]
            if span.cat == 'compile'] == []


def test_prefill_only_scheduler_closes_admit_at_the_export(served):
    from tpusystem.serve import Scheduler
    module, params = served
    tracer = Tracer('prefill')
    scheduler = Scheduler(Engine(module, params, rows=2, block_size=16),
                          tracer=tracer, prefill_only=True)
    scheduler.submit(Request('p0', PROMPTS[0], 4))
    scheduler.step()
    named = {event['name']: event for event in tracer.events()
             if event['ph'] == 'X'}
    assert 'open' not in named['admit']['args']
    assert named['handoff']['args'].get('open') is True


def test_lexical_tracer_span_is_a_host_span_too(tmp_path):
    tracer = Tracer('host')

    def work():
        with tracer.span('checkpoint-save'):
            jnp.zeros(()).block_until_ready()

    spans = profiled(work, tmp_path)
    assert [span[0] for span in spans] == ['tpusystem.checkpoint-save']
    assert [span.name for span in tracer._spans.values()] == [
        'checkpoint-save']


def test_compile_cache_keys_on_the_scope_names(tmp_path):
    """A trace is read by scope names, so an executable may not come out
    of the cache under a key that leaves them out (JAX's default): it
    would carry the names of whoever compiled it first."""
    import os
    import subprocess
    import sys
    flag = 'jax.config.jax_compilation_cache_include_metadata_in_key'
    done = subprocess.run(
        [sys.executable, '-c',
         'import jax; from tpusystem.runtime import compile_cache; '
         f'before = {flag}; compile_cache(); print(before, {flag})'],
        cwd=ROOT, text=True, capture_output=True, timeout=120,
        env={**os.environ, 'JAX_PLATFORMS': 'cpu',
             'JAX_COMPILATION_CACHE_DIR': str(tmp_path)})
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.split() == ['False', 'True']


# ----------------------------------------------------------- training spans

TRAIN_SPANS = ['tpusystem.train.epoch', 'tpusystem.train.fetch',
               'tpusystem.train.shard', 'tpusystem.train.dispatch',
               'tpusystem.train.update', 'tpusystem.train.compute']


@pytest.fixture(scope='module')
def lm():
    spec = importlib.util.spec_from_file_location(
        'lm_main_spans', ROOT / 'examples' / 'lm' / 'main.py')
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.provider.override(module.steps_per_dispatch, lambda: 2)
    return module


@pytest.fixture(scope='module')
def trainer(lm):
    from tpusystem.data import Loader
    from tpusystem.parallel import MeshSpec
    from tpusystem.train import AdamW, ChunkedNextTokenLoss
    model = lm.LanguageModel(
        GPT2(**TINY, return_features=True), ChunkedNextTokenLoss(chunks=2),
        AdamW(lr=3e-4, grad_clip=1.0))
    model.place(jnp.zeros((1, 8), jnp.int32), MeshSpec(fsdp=-1).build(),
                lm.policy())
    tokens = np.random.default_rng(5).integers(0, 120, (32, 32), np.int32)

    class Rows:
        def __len__(self):
            return len(tokens)

        def __getitem__(self, index):
            return (tokens[index],)

    return model, Loader(Rows(), batch_size=8, shuffle=False)


@pytest.fixture(scope='module')
def training_trace(lm, trainer, tmp_path_factory):
    model, loader = trainer
    metrics = lm.LMMetrics()
    call = lambda: lm.service.handle('train', model, loader, metrics)
    call()                                              # compiles
    metrics.reset()
    return profiled(call, tmp_path_factory.mktemp('train-trace'))


@pytest.mark.parametrize('name', TRAIN_SPANS)
def test_training_span_lands_inside_its_epoch(training_trace, name):
    (epoch,) = [span for span in training_trace if span[0] == TRAIN_SPANS[0]]
    found = [span for span in training_trace if span[0] == name]
    # 4 batches, 2 a dispatch: two of each, a third fetch that finds the
    # loader spent, and the epoch's one compute
    expected = {'tpusystem.train.epoch': 1, 'tpusystem.train.fetch': 3,
                'tpusystem.train.compute': 1}.get(name, 2)
    assert len(found) == expected
    assert all(inside(span, epoch) for span in found)


def test_validate_handler_has_the_same_spans_under_eval(lm, trainer,
                                                        tmp_path):
    model, loader = trainer
    spans = profiled(lambda: lm.service.handle(
        'validate', model, loader, lm.LMMetrics()), tmp_path)
    assert {span[0] for span in spans} == {
        name.replace('.train.', '.eval.') for name in TRAIN_SPANS}


# ------------------------------------------------------------ device scopes

def scope_paths(lowered) -> set:
    """Every name-stack path of the lowered program's locations."""
    import re
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def components(paths) -> set:
    """The scope names in the paths, transform wrappers peeled off:
    ``transpose(jvp(loss))/loss_head/mul`` holds ``loss`` and
    ``loss_head``."""
    import re
    return {re.sub(r'^(?:\w+\()+|\)+$', '', part)
            for path in paths for part in path.split('/')[:-1]}


def test_train_step_lowers_with_its_scopes(trainer):
    model, _ = trainer
    stack = jnp.zeros((2, 8, 32), jnp.int32)
    paths = scope_paths(model._train_many.lower(model.state, stack, stack))
    assert {'model', 'loss', 'loss_head', 'optimizer', 'clip'} <= components(
        paths)
    assert any(path.startswith('optimizer/clip/') for path in paths)
    assert any('transpose(jvp(loss))/loss_head/' in path for path in paths)
    # flax's own scopes stay innermost inside `model`
    assert any('jvp(model)/GPT2/h_0/attn/' in path for path in paths)


def test_fused_paged_step_lowers_with_its_scopes_and_bare_kernels(served):
    module, params = served
    engine = Engine(module, params, rows=2, block_size=16,
                    decode_impl='fused')
    engine.admit(PROMPTS[0], max_new=4)
    lowered = engine._step.lower(
        engine._params, engine._cache, engine._tokens_dev,
        engine._active_dev, engine._seed_dev, engine._pos_dev,
        engine._temp_dev, engine._topk_dev, engine._topp_dev,
        engine._mask_dev)
    paths = scope_paths(lowered)
    scopes = {'embed', 'ln', 'kv_write', 'kv_read', 'head', 'select'}
    assert scopes <= components(paths)
    # the TPU compiler names a Mosaic call after its innermost scope. The
    # paged-attention kernel sits under `kv_read` (what `scope_share.
    # kv_read` reads) and brings its own name, the trace's
    # `paged_decode_attention [tpu_custom_call]`; no scope encloses the
    # weight chain's kernels, which the trace readers match as `step_fn`
    kernels = [path for path in paths if 'pallas_call' in path]
    paged = [path for path in kernels if 'paged_decode_attention' in path]
    assert paged and all('kv_read' in components([path]) for path in paged)
    chain = [path for path in kernels if path not in paged]
    assert chain and not scopes & components(chain)


def test_flax_step_selects_under_its_scope(served):
    module, params = served
    engine = Engine(module, params, rows=2, block_size=16)
    assert engine.decode_impl == 'flax'
    assert 'select' in components(scope_paths(engine._step.lower(
        engine._params, engine._cache, engine._tokens_dev,
        engine._active_dev, engine._seed_dev, engine._pos_dev,
        engine._temp_dev, engine._topk_dev, engine._topp_dev,
        engine._mask_dev)))
