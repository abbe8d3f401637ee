"""Observability: events, logging, TensorBoard, experiment tracking.

The reference wires three decoupled consumers onto one producer — stdlib
logging summaries, TinyDB metric persistence, TensorBoard scalars
(``examples/tinysys/main.py:49-58``) — so the trainer never knows its
observers. This package ships those consumers as framework components, plus
the canonical training events they consume.

Hot-path rule (SURVEY.md §7.3): every payload on the bus is already a
materialized host value — consumers never touch device arrays, so one epoch
has exactly one device→host sync per phase (``metrics.compute()``).
"""

from tpusystem.observe.events import (AnomalyDetected, BackoffApplied,
                                      CapacityArbitrated, Iterated,
                                      JobAdmitted, JobHalted, JobPreempted,
                                      RecoveryTimeline, ReplicaDiverged,
                                      RequestAdmitted, RequestCompleted,
                                      RequestEvicted, RolledBack,
                                      ServeStepped, StepTimed, Trained,
                                      Validated, WorkerExited,
                                      WorkerRelaunched)
from tpusystem.observe.flight import FlightRecorder
from tpusystem.observe.ledger import EventLedger, LedgerDivergence
from tpusystem.observe.logs import logging_consumer
from tpusystem.observe.metrics import (Histogram, ServeLatency,
                                       serve_metrics_consumer)
# the trace MODULE must import before profile's trace FUNCTION: importing
# a submodule binds it as a package attribute, and the later function
# import deliberately wins — `observe.trace` stays the device-profiler
# context manager it has always been. Span tracing is reached as
# `observe.Tracer` (preferred) or `from tpusystem.observe.trace import
# ...`; NOT via attribute access on the package (`import
# tpusystem.observe.trace; tpusystem.observe.trace.Tracer` resolves the
# shadowing function and fails — the price of keeping the old name).
from tpusystem.observe.trace import Span, TraceContext, Tracer
from tpusystem.observe.profile import (ProfilerBusy, StepTimer, annotate,
                                       annotated, trace)
from tpusystem.observe.tensorboard import SummaryWriter, tensorboard_consumer
from tpusystem.observe.tracking import (
    checkpoint_consumer, experiment, metrics_store, models_store,
    modules_store, iterations_store, repository, tracking_consumer,
)

__all__ = [
    'Trained', 'Validated', 'Iterated', 'StepTimed',
    'AnomalyDetected', 'BackoffApplied', 'RolledBack', 'ReplicaDiverged',
    'WorkerExited', 'WorkerRelaunched', 'RecoveryTimeline',
    'RequestAdmitted', 'RequestEvicted', 'RequestCompleted', 'ServeStepped',
    'JobAdmitted', 'JobPreempted', 'JobHalted', 'CapacityArbitrated',
    'logging_consumer', 'SummaryWriter', 'tensorboard_consumer',
    'tracking_consumer', 'checkpoint_consumer', 'experiment',
    'metrics_store', 'models_store',
    'modules_store', 'iterations_store', 'repository',
    'EventLedger', 'LedgerDivergence', 'StepTimer', 'annotate', 'annotated',
    'trace', 'ProfilerBusy',
    'Tracer', 'Span', 'TraceContext',
    'Histogram', 'ServeLatency', 'serve_metrics_consumer',
    'FlightRecorder',
]
