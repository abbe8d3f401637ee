"""Tracing/profiling subsystem.

The reference has none — its nearest artifacts are TensorBoard scalars and
per-100-batch loss logs (SURVEY.md §5 "tracing/profiling: absent"). Here
profiling is a first-class citizen with two faces:

- **device plane** — :func:`trace` / :func:`annotate` / :func:`annotated`
  wrap ``jax.profiler`` so XLA traces (HLO timelines, memory, TPU util)
  land in a TensorBoard-readable logdir. Annotations are zero-cost when no
  trace is active, so they stay in production code: the program's own
  ``tpusystem.<layer>.<what>`` host spans (docs/observability.md lists
  them) are all opened through :func:`annotate`, and land on the
  ``/host:CPU`` plane of the same ``.xplane.pb`` as the device's
  operations — one clock by construction.
- **bus plane** — :class:`StepTimer` measures host wall-clock around jitted
  step spans and emits :class:`~tpusystem.observe.events.StepTimed` events;
  any consumer (logging, storage, TensorBoard) observes throughput without
  the trainer knowing its observers — the reference's architecture point,
  applied to profiling.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Iterable, Iterator

import jax

from tpusystem.observe.events import StepTimed
from tpusystem.services.prodcon import Producer


class ProfilerBusy(RuntimeError):
    """``jax.profiler.start_trace`` refused — almost always because a
    trace is already active (nested :func:`trace`, or a leftover from a
    span that never stopped). Typed so callers can skip-or-queue instead
    of crashing, and so the ORIGINAL error is what surfaces — the old
    code ran ``stop_trace`` in its ``finally`` even when the start had
    failed, masking the real problem with a second 'no trace running'
    error."""


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a device trace (XLA timeline, memory viewer) for the enclosed
    span into ``logdir``; open with TensorBoard's profile plugin.

    Only a trace this context actually *started* is stopped on exit: a
    failed start (e.g. a trace already active) raises the typed
    :exc:`ProfilerBusy` and leaves the pre-existing trace untouched."""
    try:
        jax.profiler.start_trace(logdir)
    except RuntimeError as error:
        raise ProfilerBusy(
            f'jax.profiler.start_trace({logdir!r}) refused: {error} — a '
            f'device trace is already active; stop it (or nest '
            f'annotate() instead, which composes)') from error
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, **stats: Any) -> Any:
    """Named span on the host timeline of an active trace (no-op
    otherwise). ``stats`` ride the span as keyword stats and come back
    through ``jax.profiler.ProfileData`` (``event.stats``). The only place
    the program opens a ``TraceAnnotation``; the prefix ``tpusystem.`` is
    reserved for the spans docs/observability.md lists."""
    return jax.profiler.TraceAnnotation(name, **stats)


def annotated(name: str, iterable: Iterable) -> Iterator:
    """``iterable``, with every fetch of its next item inside
    ``annotate(name)`` — the span a ``for`` loop cannot put around its own
    ``next()`` (a loader's batch assembly, ``grouped_batches``' stack)."""
    iterator = iter(iterable)
    while True:
        with annotate(name):
            try:
                item = next(iterator)
            except StopIteration:
                return
        yield item


class StepTimer:
    """Wall-clock throughput measurement around step spans.

    The timer brackets a *span* of steps — never a single one; timing a
    single step would force a device sync per step and destroy MFU
    (SURVEY.md §7.3 "keeping the bus off the hot path"). ``stop`` blocks on
    ``result`` (any device value from the last step) so the measurement
    covers real device work rather than async dispatch.

    Example::

        timer = StepTimer(producer)
        timer.start()
        for batch in loader:
            state, out = step(state, batch)
        timer.stop(model, 'train', steps=len(loader), result=out)
    """

    def __init__(self, producer: Producer | None = None) -> None:
        self.producer = producer
        self._started: float | None = None

    def start(self) -> 'StepTimer':
        self._started = time.perf_counter()
        return self

    def stop(self, model: Any, phase: str, steps: int,
             result: Any = None) -> StepTimed:
        if self._started is None:
            raise RuntimeError('StepTimer.stop() without start()')
        if result is not None:
            jax.block_until_ready(result)
        timed = StepTimed(model=model, phase=phase, steps=steps,
                          seconds=time.perf_counter() - self._started)
        self._started = None
        if self.producer is not None:
            self.producer.dispatch(timed)
        return timed
