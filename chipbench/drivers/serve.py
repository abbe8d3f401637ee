"""The serving window: clients against ``InferenceService``.

The loop is the one a caller of the service writes: hand in what is due by
name (``service.handle('submit', request, on_token)``), call
``InferenceService.step()``, read what came back. Tokens reach the
benchmark through the program's own streaming callback and are timed on
the benchmark's clock; completions, admissions and ticks are read from
the service's event bus (a tap on its ``Producer``). A closed loop: each of
``clients`` sends its next request the moment its previous one completed,
and that moment is when the next is due.
"""

from __future__ import annotations

import dataclasses
import gc
import time


@dataclasses.dataclass
class Sent:
    """One request as the benchmark saw it."""
    index: int
    prompt: list
    max_new: int
    due: float                     # when its client's previous one ended
    in_window: bool
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)
    reason: str | None = None
    refused: str | None = None


class Clients:
    """The closed loop's bookkeeping: which clients are idle and since
    when, the seed's request sequence, and every request sent."""

    def __init__(self, seed: int, mix: dict, vocab: int) -> None:
        from chipbench import traffic
        self.seed, self.vocab = seed, vocab
        self.sizes = traffic.request_sizes(seed, mix)
        self.idle = {client: None for client in range(mix['clients'])}
        self.sent: dict = {}           # request id -> Sent
        self.owner: dict = {}          # request id -> client
        # the same shares for every seed (evenly spaced over 0.05..1), dealt
        # to the clients in the seed's order: the fill is the same work
        deal = traffic.seeded(seed, 7).permutation(mix['clients'])
        self.under_way = 0.05 + 0.95 * (deal + 0.5) / mix['clients']
        self.next_index = 0

    def due(self, now: float, in_window: bool) -> list:
        """A request for every idle client, due since its last completion.
        A client's first request is cut to a seeded share of its budget, as
        if it were already under way: the rows then hold requests at every
        stage once they are full, which is the steady state, without a
        warm-up as long as the longest request."""
        from chipbench import traffic
        out = []
        for client, since in list(self.idle.items()):
            index = self.next_index
            self.next_index += 1
            length, max_new = self.sizes[index % len(self.sizes)]
            if since is None:
                max_new = max(1, round(max_new * self.under_way[client]))
            prompt = traffic.request_prompt(self.seed, index, length,
                                            self.vocab)
            request_id = f'r{index}'
            self.sent[request_id] = Sent(
                index, prompt, max_new, now if since is None else since,
                in_window)
            self.owner[request_id] = client
            del self.idle[client]
            out.append(request_id)
        return out

    def completed(self, request_id: str, reason: str, now: float) -> None:
        sent = self.sent.get(request_id)
        if sent is None:               # a warm-up request of the driver's own
            return
        sent.reason = reason
        self.idle[self.owner.pop(request_id)] = now

    def refused(self, request_id: str, why: str, now: float) -> None:
        self.sent[request_id].refused = why
        self.idle[self.owner.pop(request_id)] = now


def run(run) -> dict:
    import jax
    import numpy as np
    from chipbench import check, families, harness
    from tpusystem.observe.trace import Tracer
    from tpusystem.serve import InferenceService, Request
    from tpusystem.services.prodcon import Producer

    config, mix = run.cell.config, run.cell.traffic
    family, as_run = families.of(config), config['as_run']
    clock = time.perf_counter
    module = family.serve_module(config)
    stages = [('start', clock() - run.started)]
    mark = lambda name: stages.append((name, clock() - run.started))
    params = jax.block_until_ready(family.make(config, run.seed))
    mark('weights')
    tracer = Tracer('serve', clock=clock) if run.trace else None
    producer = Producer()
    service = InferenceService(module, params, producer=producer,
                               rows=mix['rows'], block_size=mix['block_size'],
                               share_prefix=mix['share_prefix'],
                               clock=clock, tracer=tracer,
                               **as_run.get('levers', {}))
    del params                         # the engine keeps what it streams
    engine = service.engine
    resolved = {'stream_dtype': engine.stream_dtype,
                'decode_impl': engine.decode_impl}
    for lever, value in resolved.items():
        if as_run[lever] != value:
            raise SystemExit(
                f'the engine resolved {lever}={value!r}; the configuration '
                f'states {as_run[lever]!r}')

    mark('service built')
    clients = Clients(run.seed, mix, family.vocab_size(config))
    ticks: list = []                   # per InferenceService.step()
    bus = {'active': 0, 'queued': 0}

    def tap(event) -> None:
        kind = type(event).__name__
        if kind == 'RequestCompleted':
            clients.completed(event.id, event.reason, clock())
        elif kind == 'ServeStepped':
            bus['active'], bus['queued'] = event.active, event.queue_depth

    producer.taps.append(tap)

    def submit(request_id: str) -> None:
        sent = clients.sent[request_id]

        def on_token(index: int, token: int) -> None:
            sent.tokens.append(token)
            sent.times.append(clock())

        try:
            service.service.handle(
                'submit', Request(request_id, sent.prompt, sent.max_new),
                on_token)
        except (ValueError, RuntimeError) as error:
            clients.refused(request_id, repr(error), clock())

    def tick(in_window: bool) -> None:
        with jax.profiler.TraceAnnotation('chipbench.generate'):
            due = clients.due(clock(), in_window)
        with jax.profiler.TraceAnnotation('chipbench.submit'):
            for request_id in due:
                submit(request_id)
        began = clock()
        before = dict(engine.timings)
        with jax.profiler.TraceAnnotation('chipbench.step'):
            service.step()
        with jax.profiler.TraceAnnotation('chipbench.collect'):
            ended = clock()
            ticks.append({
                'start': began, 'end': ended, 'active': bus['active'],
                'queued': bus['queued'],
                'live_blocks': engine.pool.live_blocks,
                'decode_s': engine.timings['step'] - before['step'],
                'prefill_s': engine.timings['prefill'] - before['prefill'],
                'admit_s': engine.timings['admit'] - before['admit']})

    # set-up: every prefill bucket the mix can reach and the decode step,
    # through the same submit path, then the loop itself until every client
    # has a request under way, so the window opens with every row full
    for index, length in enumerate(mix['warm_prompts']):
        prompt = np.random.default_rng([run.seed, 6, index]).integers(
            0, family.vocab_size(config), size=length).tolist()
        service.service.handle('submit', Request(f'warm{index}', prompt, 4))
    service.run_until_idle()
    mark('buckets warm')
    while not all(sent.times for sent in clients.sent.values()) \
            or len(clients.sent) < mix['clients']:
        tick(False)
    if engine.trace_count != 1:
        raise SystemExit(f'the decode step traced {engine.trace_count} times')
    warm_ticks = len(ticks)
    mark('loop warm')
    setup_s = clock() - run.started

    profile, traced = harness.Profile(run.scratch / 'trace', run.trace), None
    opened = clock()
    while clock() - opened < run.seconds:
        tick(True)
        if profile.running and clock() - opened >= mix['trace_seconds']:
            traced = (opened, clock())
            profile.stop()
    closed = clock()
    if profile.running:           # the window closed before trace_seconds
        traced = (opened, closed)
        profile.stop()
    # drain: nothing new is sent; what was sent in the window is waited for
    deadline = closed + mix['drain_seconds']
    while not service.scheduler.idle and clock() < deadline:
        began = clock()
        service.step()
        ticks.append({'start': began, 'end': clock(), 'drain': True})
    peak = harness.memory_peak_bytes()

    window = [sent for sent in clients.sent.values() if sent.in_window]
    emitted = sum(opened <= moment < closed
                  for sent in clients.sent.values() for moment in sent.times)
    late = closed - opened + mix['drain_seconds']     # a miss reads as this
    wait = lambda sent: (sent.times[0] - sent.due) if sent.times else late
    ttft = [wait(sent) for sent in window]
    gaps = [b - a for sent in window
            for a, b in zip(sent.times, sent.times[1:])]
    failed = [sent for sent in window
              if sent.reason != 'length' or len(sent.tokens) != sent.max_new]
    finished = [(sent.prompt, sent.tokens) for sent in window
                if sent not in failed]
    spans = tracer.events() if tracer is not None else []
    stats = {'admissions': engine.sharing['admissions'],
             'blocks': engine.pool.blocks, 'rows': engine.rows}
    requests = [{'prompt': len(sent.prompt), 'times': sent.times}
                for sent in clients.sent.values()]
    service = engine = producer = module = None      # free the program's
    gc.collect()                                     # state for the reference
    jax.clear_caches()

    began = clock()
    sample = check.sample_requests(run.seed, finished,
                                   config['reference']['sample_requests'])
    if sample:
        widest, covered = family.served_gap(config, run.seed, sample)
    else:
        widest, covered = float('nan'), 0
    limits = run.cell.limits
    quantile_ms = lambda samples, share: 1e3 * (
        harness.percentile(samples, share) if samples else late)
    spread = lambda samples: ' '.join(
        f'p{int(100 * share)} {1e3 * harness.percentile(samples, share):.1f}'
        for share in (0.5, 0.9, 0.93, 0.95, 0.97, 0.99, 1.0)) if samples else 'none'
    # where a slow run is slow: the window's ticks with no admission against
    # those with one, each split into the engine's own step and the rest
    timed = [t for t in ticks[warm_ticks:] if 'drain' not in t]
    plain = [t for t in timed if not t['prefill_s'] and not t['admit_s']]
    busy = [t for t in timed if t['prefill_s'] or t['admit_s']]
    median_ms = lambda values: (1e3 * harness.percentile(values, 0.5)
                                if values else float('nan'))
    wall = lambda group: median_ms([t['end'] - t['start'] for t in group])
    part = lambda group, key: median_ms([t[key] for t in group])
    notes = [f'ttft ms over {len(ttft)}: {spread(ttft)}; token gap ms over '
             f'{len(gaps)}: {spread(gaps)}',
             f'tick ms, medians: {len(plain)} with no admission {wall(plain):.2f} '
             f'(engine step {part(plain, "decode_s"):.2f}); {len(busy)} with '
             f'one {wall(busy):.2f} (step {part(busy, "decode_s"):.2f}, prefill '
             f'{part(busy, "prefill_s"):.2f}, admit {part(busy, "admit_s"):.2f})',
             'set-up: ' + ', '.join(f'{name} {at:.1f} s'
                                    for name, at in stages),
             f'resolved {resolved}; warm-up {warm_ticks} ticks; window '
             f'{closed - opened:.3f} s, {len(window)} requests sent, '
             f'{emitted} tokens emitted; {len(failed)} failed '
             f'({[(s.index, s.reason, s.refused) for s in failed[:5]]})',
             f'reference took {clock() - began:.1f} s over {len(sample)} '
             f'requests, {covered} served tokens']
    return {
        'end_to_end': {
            'serve_tokens_per_s': emitted / (closed - opened),
            'ttft_p50_ms': quantile_ms(ttft, 0.5),
            'ttft_p95_ms': quantile_ms(ttft, 0.95),
            'itl_p95_ms': quantile_ms(gaps, 0.95),
            'setup_s': setup_s},
        'attempted': len(window), 'failed': len(failed),
        'compared': [('logit_gap_max', widest,
                      limits['logit_gap_max']['limit'])],
        'memory_peak_bytes': peak, 'trace_dir': profile.directory, 'notes': notes,
        'sample': sample,                               # for control.py
        # for the per-layer readers
        'config': config, 'traffic': mix, 'chips': run.cell.chips,
        'device_kind': jax.devices()[0].device_kind, 'resolved': resolved,
        'window_s': closed - opened, 'ticks': ticks, 'spans': spans,
        'traced_window': traced, 'engine': stats,
        'requests': requests,
    }
