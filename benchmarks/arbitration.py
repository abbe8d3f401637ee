"""Capacity arbitration cost: burst -> shrunk trainer back at work.

The gang orchestrator's (`tpusystem/orchestrator/gang.py`) promise is
that a serving burst costs the trainer a *resize*, not its job — so the
number that matters is the wall clock of the whole arbitration window:

1. ``decision``  — ``request_capacity`` alone: donor selection plus the
   two-phase journal round trip (``decided`` replicated to the plane,
   the resize seam driven, ``done`` replicated) — the pure control-
   plane cost of an arbitration;
2. ``grant``     — the full burst-to-training window: the decision PLUS
   the shrunk trainer hot-resharding its state onto the granted-down
   submesh (`elastic_resume` -> ``hot-reshard``, the exit-46 relaunch's
   restore path) and taking one step there;
3. ``release``   — the ebb: the LIFO debt paid back plus the trainer's
   hot reshard back onto its full submesh and one step.

Medians of TRIALS runs on the tiny model; a fresh orchestrator + plane
per trial (grants mutate placements), compiled steps shared across
trials. Needs 4 accelerator chips; with fewer it exits non-zero.

Every row is one machine-readable JSON line; the LAST line is the
``arbitration_seconds`` headline ``bench.py`` forwards (value = the
full grant window; the decision-only and release arms ride alongside).

Run: ``python benchmarks/arbitration.py [headline]``.
"""

from __future__ import annotations

import sys
sys.path.insert(0, str(__import__('pathlib').Path(__file__).parent.parent))

import json
import tempfile
import time

import jax

TRIALS = 3


class _Runner:
    def __init__(self):
        self.resizes = []

    def poll(self):
        return None

    def resize(self, devices):
        self.resizes.append(tuple(devices))


def main() -> None:
    import jax.numpy as jnp
    import numpy as np

    from bench import materialize, require_chips
    from tpusystem.checkpoint import Checkpointer
    from tpusystem.checkpoint.memstore import HotState, MemStore, blob_digest
    from tpusystem.models import gpt2_tiny
    from tpusystem.orchestrator import JobSpec, Orchestrator, Submesh
    from tpusystem.parallel import MeshSpec, TensorParallel, batch_sharding
    from tpusystem.parallel.elastic import elastic_resume, split_pieces
    from tpusystem.train import (AdamW, NextTokenLoss, build_train_step,
                                 flax_apply, init_state)

    devices = require_chips(4)
    identity = 'bench-arbitration'
    spec = MeshSpec(fsdp=4)
    module = gpt2_tiny()
    optimizer = AdamW(lr=1e-3)
    policy = TensorParallel(module.partition_rules(), fsdp=True,
                            fsdp_min_size=64)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (4, 32)), jnp.int32)
    step = build_train_step(flax_apply(module), NextTokenLoss(), optimizer)

    mesh4 = spec.build(devices[:4])
    mesh2 = spec.resized(2).build(devices[:2])
    state = policy.place(init_state(module, optimizer, tokens[:1]), mesh4)
    batch4 = jax.device_put(tokens, batch_sharding(mesh4))
    batch2 = jax.device_put(tokens, batch_sharding(mesh2))
    state, _ = step(state, batch4, batch4)
    at = int(state.step)
    pieces = [HotState(step=at, digest=blob_digest(blob), blob=blob)
              for blob in split_pieces(state, mesh4, hosts=4)]
    blank2 = policy.place(init_state(module, optimizer, tokens[:1]), mesh2)
    blank4 = policy.place(init_state(module, optimizer, tokens[:1]), mesh4)

    train_spec = JobSpec('train', 'train', priority=1, chips=4, min_chips=2)
    serve_spec = JobSpec('serve', 'serve', priority=2, chips=2, min_chips=2)

    decisions, grants, releases = [], [], []
    with tempfile.TemporaryDirectory() as root, \
            Checkpointer(root, async_save=False) as checkpointer:
        checkpointer.save(identity, at, state, extras={'step': at})
        for _ in range(TRIALS):
            runner = _Runner()
            orchestrator = Orchestrator(tuple(range(8)), client=MemStore())
            orchestrator.admit(train_spec, runner,
                               submesh=Submesh((0, 1, 2, 3)))
            orchestrator.admit(serve_spec, _Runner(), submesh=Submesh((4, 5)))

            start = time.perf_counter()
            orchestrator.request_capacity('serve', chips=4)
            decisions.append(time.perf_counter() - start)
            assert runner.resizes == [(0, 1)], runner.resizes
            shrunk, _, _, source = elastic_resume(
                checkpointer, identity, blank2, pieces)
            assert source == 'hot-reshard', source
            shrunk, _ = step(shrunk, batch2, batch2)
            materialize(shrunk.params)
            grants.append(time.perf_counter() - start)

            shrunk_pieces = [
                HotState(step=int(shrunk.step), digest=blob_digest(blob),
                         blob=blob)
                for blob in split_pieces(shrunk, mesh2, hosts=2)]
            start = time.perf_counter()
            returned = orchestrator.release_capacity('serve')
            assert returned == 2 and runner.resizes[-1] == (0, 1, 2, 3)
            grown, _, _, source = elastic_resume(
                checkpointer, identity, blank4, shrunk_pieces)
            assert source == 'hot-reshard', source
            grown, _ = step(grown, batch4, batch4)
            materialize(grown.params)
            releases.append(time.perf_counter() - start)

    median = lambda times: sorted(times)[len(times) // 2]  # noqa: E731
    print(json.dumps({
        'metric': 'arbitration_seconds',
        'value': round(median(grants), 4),
        'unit': 's (burst -> shrunk trainer stepping, 4->2 chips, '
                'tiny model)',
        'decision_seconds': round(median(decisions), 6),
        'release_seconds': round(median(releases), 4),
    }))


if __name__ == '__main__':
    main()        # 'headline' arg tolerated: the one row IS the headline
