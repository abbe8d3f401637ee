"""tinysys composition root.

Reference parity: ``examples/tinysys/main.py`` — register types, override
dependencies, wire producer->consumers, build parts, compile the aggregate,
drive epochs. The same file is pod-ready: :class:`tpusystem.Runtime` brings
up the control plane (a no-op Loopback when single-process), storage and
TensorBoard consumers register ``primary_only``, and the early-stop verdict
is collectively agreed each epoch.

Run: ``python main.py [epochs]`` from this directory.
"""

from __future__ import annotations

import contextlib
import logging
import pathlib
import sys

from tpusystem import Runtime
from tpusystem.checkpoint import Repository
from tpusystem.data import Loader, SyntheticDigits
from tpusystem.models import MLP
from tpusystem.observe import (checkpoint_consumer, logging_consumer,
                               tensorboard_consumer, tracking_consumer)
from tpusystem.parallel import MeshSpec
from tpusystem.observe import tensorboard as tb
from tpusystem.observe import tracking
from tpusystem.storage import (DocumentIterations, DocumentMetrics,
                               DocumentModels, DocumentModules, DocumentStore)
from tpusystem.train import Adam, CrossEntropyLoss

from tinysys.metrics import ClassifierMetrics
from tinysys.services import compilation, training

ROOT = pathlib.Path(__file__).parent / 'data'
BATCH = 64


def main(epochs: int = 10) -> None:
    logging.basicConfig(level=logging.INFO, format='%(message)s', force=True)
    for noisy in ('orbax', 'absl', 'jax'):
        logging.getLogger(noisy).setLevel(logging.WARNING)
    runtime = Runtime(ledger=True)

    # --- storage + observability wiring (primary host only) ---------------
    store = DocumentStore(ROOT / 'experiments.json')
    repository = Repository(ROOT / 'weights')
    overrides = {
        tracking.metrics_store: lambda: DocumentMetrics(store),
        tracking.models_store: lambda: DocumentModels(store),
        tracking.modules_store: lambda: DocumentModules(store),
        tracking.iterations_store: lambda: DocumentIterations(store),
        tracking.repository: lambda: repository,
        tb.writer: lambda: tb.SummaryWriter(ROOT / 'tensorboard'),
    }
    for consumer in (tracking_consumer(), tensorboard_consumer()):
        consumer.dependency_overrides.update(overrides)
        runtime.producer.register(consumer, primary_only=True)
    # Checkpoint saves are collective (each host writes its own shards), so
    # this consumer runs on EVERY host, unlike the metadata stores above.
    saver = checkpoint_consumer()
    saver.dependency_overrides[tracking.repository] = lambda: repository
    runtime.producer.register(saver)
    runtime.producer.register(logging_consumer())
    training.producer = runtime.producer   # handlers dispatch on the runtime bus
    # 8 jitted steps per host dispatch: the per-batch Python cost is
    # paid once per 8 batches (events/metrics keep phase cadence)
    training.provider.override(training.steps_per_dispatch, lambda: 8)

    # --- compilation pipeline overrides -----------------------------------
    compilation.provider.override(compilation.models, lambda: DocumentModels(store))
    compilation.provider.override(compilation.repository, lambda: repository)
    # Data-parallel over every chip in the job (global mesh on a pod); the
    # default is a single-device mesh, which would be wrong everywhere else.
    compilation.provider.override(compilation.mesh, lambda: MeshSpec(data=-1).build())
    compilation.provider.override(compilation.batch_size, lambda: BATCH)

    # --- build + compile the aggregate ------------------------------------
    network = MLP(features=(256, 128), classes=10, dropout=0.1)
    classifier = compilation.compiler.compile(
        network, CrossEntropyLoss(), Adam(lr=1e-3))

    loaders = {
        'train': Loader(SyntheticDigits(samples=4096), batch_size=BATCH,
                        shuffle=True, seed=1),
        'evaluation': Loader(SyntheticDigits(samples=1024, train=False),
                             batch_size=BATCH),
    }
    metrics = ClassifierMetrics()

    # --- epoch loop, pod-correct early stop -------------------------------
    print(f'training {classifier.id} from epoch {classifier.epoch}')
    try:
        for _ in range(classifier.epoch, epochs):
            wants_stop = False
            try:
                training.service.handle('iterate', classifier, loaders, metrics)
            except StopIteration:
                wants_stop = True
            runtime.sync()
            if runtime.should_stop(wants_stop):
                print('early stop agreed across hosts')
                break
    finally:
        # LIFO stack: each close runs even if an earlier one (or the async
        # checkpoint wait) raises — a failed save must not leak the control
        # plane or the document store.
        with contextlib.ExitStack() as cleanup:
            cleanup.callback(runtime.close)
            cleanup.callback(store.close)
            repository.close()   # waits for pending async saves, then releases


if __name__ == '__main__':
    from tpusystem.runtime import compile_cache
    compile_cache()
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 10)
