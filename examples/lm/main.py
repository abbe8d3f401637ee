"""Language-model pretraining system — the tinysys architecture at LM scale.

The same message-driven stack as ``examples/tinysys`` (compiler pipeline,
named service handlers, event consumers, resume-by-identity), applied to
LM pretraining: a GPT-2 aggregate trained with the
fused chunked LM loss under an FSDP sharding policy on the job's mesh.
Every piece is a DI seam: swap the mesh, the policy (e.g.
``TensorParallel(GPT2.partition_rules(), fsdp=True)``), the dataset
(``MemmapTokens('corpus.bin')`` for a real corpus), or the preset from
this composition root without touching the system.

Run: ``python main.py [epochs]``  (tiny preset; ``--full`` for 125M).
"""

from __future__ import annotations

import contextlib
import logging
import pathlib

import jax
import jax.numpy as jnp

from tpusystem import Aggregate, Compiler, Depends, Runtime
from tpusystem.checkpoint import Repository
from tpusystem.data import Loader, MemmapTokens, SyntheticTokens
from tpusystem.depends import Provider
from tpusystem.models import GPT2, gpt2_tiny
from tpusystem.observe import checkpoint_consumer, logging_consumer, tracking
from tpusystem.observe.events import Iterated, Trained, Validated
from tpusystem.observe.profile import StepTimer, annotate, annotated
from tpusystem.parallel import (FullyShardedDataParallel, MeshSpec,
                                batch_sharding)
from tpusystem.registry import gethash
from tpusystem.services import Producer, Service
from tpusystem.storage import (DocumentIterations, DocumentMetrics,
                               DocumentModels, DocumentModules, DocumentStore)
from tpusystem.train import (AdamW, ChunkedNextTokenLoss, Mean, Perplexity,
                             build_eval_step, build_multi_eval_step,
                             build_multi_step, build_train_step, flax_apply,
                             grouped_batches, init_state)

ROOT = pathlib.Path(__file__).parent / 'data'


# --------------------------------------------------------------------------
# aggregate

class LanguageModel(Aggregate):
    """Network + criterion + optimizer as one identity-bearing unit; the
    math is two jitted steps over an FSDP-sharded TrainState."""

    def __init__(self, network, criterion, optimizer, accumulate: int = 1):
        super().__init__()
        self.network = network
        self.criterion = criterion
        self.optimizer = optimizer
        self.state = None
        self.mesh = None
        self.epoch = 0
        self.accumulate = accumulate
        self._build_steps(network)

    def _build_steps(self, network) -> None:
        apply_fn = flax_apply(network)
        self._train_step = build_train_step(apply_fn, self.criterion,
                                            self.optimizer,
                                            accumulate=self.accumulate)
        self._eval_step = build_eval_step(apply_fn, self.criterion)
        # N steps per host dispatch: one lax.scan pays the per-dispatch
        # Python cost once for the N batches
        self._train_many = build_multi_step(
            build_train_step(apply_fn, self.criterion, self.optimizer,
                             accumulate=self.accumulate, jit=False))
        self._eval_many = build_multi_eval_step(
            build_eval_step(apply_fn, self.criterion, jit=False))

    @property
    def id(self) -> str:
        return gethash(self.network)

    def modules(self):
        return {'nn': self.network, 'criterion': self.criterion,
                'optimizer': self.optimizer}

    def place(self, sample_tokens, mesh, policy) -> None:
        self.mesh = mesh
        if getattr(self.network, 'mesh', 'absent') is None:
            # bind the placement mesh into the network so mesh-aware
            # kernels (flash via shard_map, ring, MoE exchanges) compose
            # with the sharding policy; steps rebuild against the clone
            import dataclasses
            self.network = dataclasses.replace(self.network, mesh=mesh)
            self._build_steps(self.network)
        state = init_state(self.network, self.optimizer, sample_tokens)
        self.state = policy.place(state, mesh)

    def shard_batch(self, tokens):
        return jax.device_put(tokens, batch_sharding(self.mesh))

    def shard_batches(self, tokens_stack):
        """Place a [steps, batch, ...] stack: batch axis (dim 1) shards
        over (data, fsdp); the steps axis stays whole on every device."""
        from tpusystem.parallel import stacked_batch_sharding
        return jax.device_put(tokens_stack,
                              stacked_batch_sharding(self.mesh))

    def fit(self, tokens):
        self.state, (_, loss) = self._train_step(self.state, tokens, tokens)
        return loss

    def fit_many(self, tokens_stack):
        """Run ``tokens_stack.shape[0]`` train steps in one dispatch;
        returns the per-step loss vector (exact per-phase metrics)."""
        self.state, losses = self._train_many(self.state, tokens_stack,
                                              tokens_stack)
        return losses

    def evaluate_many(self, tokens_stack):
        return self._eval_many(self.state, tokens_stack, tokens_stack)

    def evaluate(self, tokens):
        _, loss = self._eval_step(self.state, tokens, tokens)
        return loss

    def lowered(self, tokens_stack) -> str:
        """The multi-step train program as lowered text — what
        ``chip_smoke.py`` greps for the Mosaic custom call."""
        return self._train_many.lower(self.state, tokens_stack,
                                      tokens_stack).as_text()

    def onepoch(self) -> None:
        self.events.commit()


# --------------------------------------------------------------------------
# metrics

class LMMetrics:
    """Loss + perplexity, accumulated on device, one sync per phase."""

    def __init__(self):
        self.loss = Mean()
        self.perplexity = Perplexity()

    def update(self, loss) -> None:
        self.loss.update(loss)
        self.perplexity.update(loss)

    def compute(self) -> dict:
        return {'loss': self.loss.compute(),
                'perplexity': self.perplexity.compute()}

    def reset(self) -> None:
        self.loss.reset()
        self.perplexity.reset()


# --------------------------------------------------------------------------
# compilation pipeline

provider = Provider()
compiler = Compiler[LanguageModel](provider=provider)


def mesh():
    """FSDP over every chip in the job (a 1x1 mesh on one chip)."""
    return MeshSpec(fsdp=-1).build()


def policy():
    return FullyShardedDataParallel()


def sample_tokens():
    return jnp.zeros((1, 8), jnp.int32)


def accumulate() -> int:
    """Gradient-accumulation microsteps (override at the composition
    root when the target global batch does not fit)."""
    return 1


def steps_per_dispatch() -> int:
    """Train/validate steps per host dispatch (1 = a dispatch per batch;
    override at the composition root — e.g. 8 pays the host dispatch
    once per 8 batches). Events/metrics keep phase cadence either way."""
    return 1


def models():
    raise NotImplementedError('override the models store dependency')


def repository():
    raise NotImplementedError('override the repository dependency')


def experiment() -> str:
    return 'lm'


@compiler.step
def build(network, criterion, optimizer,
          microsteps: int = Depends(accumulate)) -> LanguageModel:
    return LanguageModel(network, criterion, optimizer, accumulate=microsteps)


@compiler.step
def place_on_mesh(model: LanguageModel, device_mesh=Depends(mesh),
                  sharding=Depends(policy),
                  sample=Depends(sample_tokens)) -> LanguageModel:
    model.place(sample, device_mesh, sharding)
    return model


@compiler.step
def bring_epoch(model: LanguageModel, store=Depends(models),
                name: str = Depends(experiment)) -> LanguageModel:
    from tpusystem.storage import ports
    row = store.read(str(model.id), name)
    if row is None:
        store.create(ports.Model(hash=str(model.id), experiment=name, epoch=0))
        return model
    if row.epoch < model.epoch:
        raise ValueError(f'epoch regression: store at {row.epoch}')
    model.epoch = row.epoch
    return model


@compiler.step
def restore_weights(model: LanguageModel,
                    weights=Depends(repository)) -> LanguageModel:
    if model.epoch > 0:
        weights.restore(model)
    return model


# --------------------------------------------------------------------------
# training service

service = Service(provider=provider)
producer = Producer()


@service.handler
def iterate(model, loaders, metrics) -> None:
    train(model, loaders['train'], metrics)
    metrics.reset()
    validate(model, loaders['evaluation'], metrics)
    metrics.reset()
    try:
        model.epoch += 1
    finally:
        producer.dispatch(Iterated(model, loaders))


def _epoch(layer: str, model, loader, metrics, dispatch: int, run) -> dict:
    """One pass over ``loader``, ``dispatch`` batches a call of ``run``.
    In a device trace each host stage is a ``tpusystem.<layer>.*`` span:
    ``fetch`` (the next group off the loader), ``shard``, ``dispatch``,
    ``update``, and ``compute`` — the pass's one host sync."""
    groups = grouped_batches(loader, dispatch)
    for (stack,) in annotated(f'tpusystem.{layer}.fetch', groups):
        with annotate(f'tpusystem.{layer}.shard'):
            placed = model.shard_batches(stack)
        with annotate(f'tpusystem.{layer}.dispatch'):
            losses = run(placed)
        with annotate(f'tpusystem.{layer}.update'):
            metrics.update(losses)
    with annotate(f'tpusystem.{layer}.compute'):
        return metrics.compute()


@service.handler
def train(model, loader, metrics,
          dispatch: int = Depends(steps_per_dispatch)) -> None:
    with annotate('tpusystem.train.epoch'):
        model.phase = 'train'
        timer = StepTimer(producer).start()
        results = _epoch('train', model, loader, metrics, dispatch,
                         model.fit_many)
        timer.stop(model, 'train', steps=len(loader))
        producer.dispatch(Trained(model, results))


@service.handler
def validate(model, loader, metrics,
             dispatch: int = Depends(steps_per_dispatch)) -> None:
    with annotate('tpusystem.eval.epoch'):
        model.phase = 'evaluation'
        timer = StepTimer(producer).start()
        results = _epoch('eval', model, loader, metrics, dispatch,
                         model.evaluate_many)
        timer.stop(model, 'evaluation', steps=len(loader))
        producer.dispatch(Validated(model, results))


# --------------------------------------------------------------------------
# composition root

def main(epochs: int = 3, full: bool = False, corpus: str | None = None,
         holdout_corpus: str | None = None, microsteps: int = 1,
         dispatch_steps: int = 8,
         root: pathlib.Path | None = None) -> LanguageModel:
    """Train to ``epochs`` and return the aggregate. ``root`` holds the
    experiment store and the weights (default :data:`ROOT`); a store that
    already records this model resumes from its epoch."""
    global producer
    logging.basicConfig(level=logging.INFO, format='%(message)s', force=True)
    for noisy in ('orbax', 'absl', 'jax'):
        logging.getLogger(noisy).setLevel(logging.WARNING)
    root = pathlib.Path(root or ROOT)
    runtime = Runtime()
    store = DocumentStore(root / 'experiments.json')
    weights = Repository(root / 'weights')

    tracker = tracking.tracking_consumer()
    tracker.dependency_overrides.update({
        tracking.metrics_store: lambda: DocumentMetrics(store),
        tracking.models_store: lambda: DocumentModels(store),
        tracking.modules_store: lambda: DocumentModules(store),
        tracking.iterations_store: lambda: DocumentIterations(store),
        tracking.repository: lambda: weights,
        tracking.experiment: experiment,
    })
    runtime.producer.register(tracker, primary_only=True)
    saver = checkpoint_consumer()
    saver.dependency_overrides[tracking.repository] = lambda: weights
    runtime.producer.register(saver)
    runtime.producer.register(logging_consumer())
    producer = runtime.producer

    provider.override(models, lambda: DocumentModels(store))
    provider.override(repository, lambda: weights)
    provider.override(accumulate, lambda: microsteps)
    provider.override(steps_per_dispatch, lambda: dispatch_steps)

    if full:
        # the headline recipe: flash attention (composed with the FSDP mesh
        # via shard_map at placement), fused chunked LM loss, padded vocab
        network = GPT2(vocab_size=50304, dropout=0.0, return_features=True,
                       attention='flash')
        sequence, batch = 1024, 16
    else:
        network = gpt2_tiny(return_features=True)
        sequence, batch = 128, 16
    model = compiler.compile(network, ChunkedNextTokenLoss(chunks=8),
                             AdamW(lr=3e-4, grad_clip=1.0))

    if corpus:
        # MemmapTokens windows are sequence_length + 1 (the loss shifts
        # inputs/targets out of one tensor): size them to the model's cap
        dataset = MemmapTokens(corpus, sequence_length=sequence - 1)
        # evaluate on a separate file, or reuse the training corpus when
        # none is given (then eval loss is training-distribution loss)
        holdout = (MemmapTokens(holdout_corpus, sequence_length=sequence - 1)
                   if holdout_corpus else dataset)
    else:
        dataset = SyntheticTokens(samples=64 * batch, sequence_length=sequence,
                                  vocab_size=min(network.vocab_size, 256))
        holdout = SyntheticTokens(samples=8 * batch, sequence_length=sequence,
                                  vocab_size=min(network.vocab_size, 256),
                                  train=False)  # same bigram table, unseen draws
    loaders = {'train': Loader(dataset, batch_size=batch, shuffle=True, seed=0),
               'evaluation': Loader(holdout, batch_size=batch)}
    metrics = LMMetrics()

    print(f'pretraining {model.id} from epoch {model.epoch}')
    try:
        for _ in range(model.epoch, epochs):
            wants_stop = False
            try:
                service.handle('iterate', model, loaders, metrics)
            except StopIteration:
                wants_stop = True
            runtime.sync()
            if runtime.should_stop(wants_stop):
                break
    finally:
        with contextlib.ExitStack() as cleanup:
            cleanup.callback(runtime.close)
            cleanup.callback(store.close)
            weights.close()
    return model


if __name__ == '__main__':
    import argparse
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('epochs', nargs='?', type=int, default=3)
    parser.add_argument('--full', action='store_true',
                        help='125M preset instead of tiny')
    parser.add_argument('--corpus', help='flat binary token file '
                        '(MemmapTokens layout) instead of synthetic data')
    parser.add_argument('--holdout', help='separate corpus file for eval')
    def positive(value: str) -> int:
        steps = int(value)
        if steps < 1:
            raise argparse.ArgumentTypeError('must be >= 1')
        return steps

    parser.add_argument('--accumulate', type=positive, default=1,
                        help='gradient-accumulation microsteps per batch')
    parser.add_argument('--dispatch', type=positive, default=8,
                        help='train/validate steps per host dispatch')
    args = parser.parse_args()
    from tpusystem.runtime import compile_cache
    compile_cache()
    main(args.epochs, full=args.full, corpus=args.corpus,
         holdout_corpus=args.holdout, microsteps=args.accumulate,
         dispatch_steps=args.dispatch)
