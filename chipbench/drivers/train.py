"""The training window: whole ``train`` handler calls of ``examples/lm``.

Composed as ``examples/lm/main.py::main`` composes the trainer (the same
``compiler`` pipeline, ``provider`` overrides, consumers and stores, in a
fresh store directory), then ``service.handle('train', model, loader,
metrics)`` is called whole until ``--seconds`` have passed. Set-up makes
that same call once: it compiles, warms the input path, and its first
dispatch is what ``correct`` is decided on.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import logging
import time

import numpy as np


def load_lm():
    """``examples/lm/main.py`` as a module (examples are not a package)."""
    from chipbench.harness import ROOT
    spec = importlib.util.spec_from_file_location(
        'chipbench_lm_main', ROOT / 'examples' / 'lm' / 'main.py')
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class FedRows:
    """The token rows as a dataset for ``tpusystem.data.Loader``, keeping
    the row indices of every batch it was asked for: what went to the
    program, in the order it went, is what the reference is given."""

    def __init__(self, tokens: np.ndarray) -> None:
        self.tokens = tokens
        self.asked: list = []

    def __len__(self) -> int:
        return len(self.tokens)

    def __getitem__(self, index):
        if isinstance(index, np.ndarray):
            self.asked.append(index.copy())
        return (self.tokens[index],)


def adam_state(opt_state):
    """The ``ScaleByAdamState`` inside an optax chain's state."""
    import jax
    found = [node for node in jax.tree.leaves(
        opt_state, is_leaf=lambda node: hasattr(node, 'mu'))
        if hasattr(node, 'mu')]
    if len(found) != 1:
        raise ValueError(f'expected one Adam state, found {len(found)}')
    return found[0]


class Recorder:
    """The ``metrics`` object handed to the handler: the program's own
    ``LMMetrics`` underneath, plus what the benchmark reads. After the
    first dispatch it takes the readings ``correct`` needs from the
    program's own state: the losses the dispatch returned, each leaf's
    first-moment norm and how far each leaf moved from the seeded weights."""

    def __init__(self, inner, model, config: dict, seed: int) -> None:
        self.inner, self.model = inner, model
        self.config, self.seed = config, seed
        self.dispatches = 0
        self.first = None

    def update(self, losses) -> None:
        self.inner.update(losses)
        self.dispatches += 1
        if self.first is None:
            self.first = (losses, self._norms())

    def _norms(self):
        import jax
        import jax.numpy as jnp
        from chipbench import families, weights
        config, family = self.config, families.of(self.config)

        def norms(params, mu, key):
            moved = jax.tree.map(jnp.subtract, params,
                                 family.from_key(config, key))
            return family.norms(mu), family.norms(moved)

        state = self.model.state
        return jax.jit(norms)(state.params, adam_state(state.opt_state).mu,
                              weights.seed_key(self.seed))

    def readings(self) -> dict:
        import jax
        losses, (moment, moved) = jax.device_get(self.first)
        return {'losses': [float(x) for x in np.asarray(losses)],
                'moment': {k: float(v) for k, v in moment.items()},
                'moved': {k: float(v) for k, v in moved.items()}}

    def compute(self) -> dict:
        return self.inner.compute()

    def reset(self) -> None:
        self.inner.reset()


def compose(lm, run, stack: contextlib.ExitStack):
    """``main()``'s composition root, minus its loop: stores under the
    run's own directory, the three consumers, the provider overrides."""
    from tpusystem import Runtime
    from tpusystem.checkpoint import Repository
    from tpusystem.observe import (checkpoint_consumer, logging_consumer,
                                   tracking)
    from tpusystem.storage import (DocumentIterations, DocumentMetrics,
                                   DocumentModels, DocumentModules,
                                   DocumentStore)

    logging.basicConfig(level=logging.INFO, format='%(message)s', force=True)
    for noisy in ('orbax', 'absl', 'jax'):
        logging.getLogger(noisy).setLevel(logging.WARNING)
    root = run.scratch / 'store'
    runtime = Runtime()
    store = DocumentStore(root / 'experiments.json')
    repository = Repository(root / 'weights')
    stack.callback(runtime.close)
    stack.callback(store.close)
    stack.callback(repository.close)

    tracker = tracking.tracking_consumer()
    tracker.dependency_overrides.update({
        tracking.metrics_store: lambda: DocumentMetrics(store),
        tracking.models_store: lambda: DocumentModels(store),
        tracking.modules_store: lambda: DocumentModules(store),
        tracking.iterations_store: lambda: DocumentIterations(store),
        tracking.repository: lambda: repository,
        tracking.experiment: lm.experiment,
    })
    runtime.producer.register(tracker, primary_only=True)
    saver = checkpoint_consumer()
    saver.dependency_overrides[tracking.repository] = lambda: repository
    runtime.producer.register(saver)
    runtime.producer.register(logging_consumer())
    lm.producer = runtime.producer

    mix = run.cell.traffic
    lm.provider.override(lm.models, lambda: DocumentModels(store))
    lm.provider.override(lm.repository, lambda: repository)
    lm.provider.override(lm.accumulate, lambda: 1)
    lm.provider.override(lm.steps_per_dispatch,
                         lambda: mix['steps_per_dispatch'])


def build_model(lm, config: dict, seed: int):
    """The aggregate through the program's own ``compiler`` pipeline, then
    its parameters replaced by the seeded ones, placed as it placed them."""
    import jax
    from chipbench import families
    from tpusystem.train import AdamW, ChunkedNextTokenLoss

    family, as_run = families.of(config), config['as_run']
    stated = as_run['optimizer']
    optimizer = AdamW(lr=stated['lr'], grad_clip=stated['grad_clip'])
    for field in ('b1', 'b2', 'eps', 'weight_decay'):
        if getattr(optimizer, field) != stated[field]:
            raise ValueError(
                f'AdamW.{field} is {getattr(optimizer, field)}, the '
                f'configuration states {stated[field]}')
    model = lm.compiler.compile(
        family.train_module(config),
        ChunkedNextTokenLoss(chunks=as_run['criterion']['chunks']), optimizer)
    seeded = family.make(config, seed)
    have = jax.tree.map(lambda leaf: (leaf.shape, leaf.dtype.name),
                        model.state.params)
    want = jax.tree.map(lambda leaf: (leaf.shape, leaf.dtype.name), seeded)
    if have != want:
        raise ValueError('the seeded weights do not match the program\'s '
                         'parameter tree')
    placed = jax.tree.map(
        lambda new, old: jax.device_put(new, old.sharding), seeded,
        model.state.params)
    model.state = model.state.replace(params=placed)
    return model


def run(run) -> dict:
    import jax
    from chipbench import check, families, harness, traffic
    from tpusystem.data import Loader

    config, mix = run.cell.config, run.cell.traffic
    family = families.of(config)
    stages = [('start', time.perf_counter() - run.started)]
    mark = lambda name: stages.append((name,
                                       time.perf_counter() - run.started))
    lm = load_lm()
    with contextlib.ExitStack() as stack:
        compose(lm, run, stack)
        mark('composed')
        rows = FedRows(traffic.bigram_tokens(
            run.seed, samples=mix['epoch_batches'] * mix['batch'],
            seq=mix['seq'], vocab=family.vocab_size(config),
            fanout=mix['bigram_fanout']))
        loader = Loader(rows, batch_size=mix['batch'],
                        shuffle=mix['shuffle'], seed=0)
        model = build_model(lm, config, run.seed)
        jax.block_until_ready(model.state.params)
        mark('model built')
        recorder = Recorder(lm.LMMetrics(), model, config, run.seed)

        def call() -> None:
            with jax.profiler.TraceAnnotation('chipbench.train_call'):
                lm.service.handle('train', model, loader, recorder)
            recorder.reset()

        call()                    # set-up: compiles; its first dispatch is
        program = recorder.readings()          # what `correct` compares
        fed = [rows.tokens[index] for index
               in rows.asked[:mix['steps_per_dispatch']]]
        jax.block_until_ready(model.state.step)
        mark('first call')
        setup_s = time.perf_counter() - run.started

        steps_before = int(model.state.step)
        profile = harness.Profile(run.scratch / 'trace', run.trace)
        calls = traced_calls = 0
        opened = time.perf_counter()
        while time.perf_counter() - opened < run.seconds:
            call()
            calls += 1
            if profile.running and calls == mix['trace_calls']:
                profile.stop()
                traced_calls = calls
        jax.block_until_ready(model.state.step)
        window_s = time.perf_counter() - opened
        if profile.running:       # the window closed before trace_calls did
            profile.stop()
            traced_calls = calls
        steps = int(model.state.step) - steps_before
        tokens = steps * mix['batch'] * mix['seq']
        finite = bool(np.isfinite(program['losses']).all())
        peak = harness.memory_peak_bytes()
        model.state = None        # free the program's state: the reference
        model = recorder.model = None          # gets the chip to itself
    gc.collect()

    began = time.perf_counter()
    reference = family.reference_training(config, run.seed, fed)
    numbers, notes = check.compare_training(program, reference)
    notes.append('set-up: ' + ', '.join(f'{name} {at:.1f} s'
                                        for name, at in stages))
    notes.append(f'reference took {time.perf_counter() - began:.1f} s; '
                 f'window {window_s:.3f} s, {calls} calls, {steps} steps')
    limits = run.cell.limits
    return {
        'end_to_end': {'train_tokens_per_s': tokens / window_s,
                       'setup_s': setup_s},
        'attempted': calls,
        'failed': 0 if finite and steps == calls * mix['epoch_batches']
        else calls,
        'compared': [(name, numbers[name], limits[name]['limit'])
                     for name in limits if name in numbers],
        'memory_peak_bytes': peak,
        'trace_dir': profile.directory,
        'notes': notes,
        'fed': fed, 'reference_reading': reference,    # for control.py
        # for the per-layer readers
        'config': config, 'traffic': mix, 'device_kind':
        jax.devices()[0].device_kind, 'chips': run.cell.chips,
        'window_s': window_s, 'tokens': tokens,
        'traced': {'calls': traced_calls,
                   'steps': traced_calls * mix['epoch_batches'],
                   'dispatches': traced_calls * mix['epoch_batches']
                   // mix['steps_per_dispatch']},
    }
