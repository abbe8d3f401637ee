"""Elastic resize cost: hot-reshard vs disk-restore wall clock.

The elastic loop's (`tpusystem/parallel/elastic.py`) promise is that a
preemption wave costs a *reshard*, not a cold restart — so the number
that matters is how long the reshard's state reassembly takes against
the alternative it replaces, a disk restore onto the shrunk mesh:

1. ``hot reshard`` — 4 virtual hosts shrink to 2: merge every host's
   in-memory :class:`ShardedLeaf` pieces (`merge_hot`), reassemble and
   re-lay the training state onto the 2-device mesh's shardings
   (`elastic_resume` -> source ``hot-reshard``);
2. ``disk restore`` — the same step restored from the newest committed
   Orbax checkpoint onto the same shrunk mesh (`checkpointer.resume`,
   what a non-elastic restart would pay *after* the relaunch).

Both arms are medians of TRIALS runs on the tiny model, both end with
the params materialized on host. Needs 4 accelerator chips; with fewer
it exits non-zero.

Every row is one machine-readable JSON line; the LAST line is the
``resize_seconds`` headline ``bench.py`` forwards.

Run: ``python benchmarks/elastic_resize.py [headline]``.
"""

from __future__ import annotations

import sys
sys.path.insert(0, str(__import__('pathlib').Path(__file__).parent.parent))

import json
import tempfile
import time

import jax

TRIALS = 3


def main() -> None:
    import jax.numpy as jnp
    import numpy as np

    from bench import materialize, require_chips
    from tpusystem.checkpoint import Checkpointer
    from tpusystem.checkpoint.memstore import HotState, blob_digest
    from tpusystem.models import gpt2_tiny
    from tpusystem.parallel import MeshSpec, TensorParallel, batch_sharding
    from tpusystem.parallel.elastic import elastic_resume, split_pieces
    from tpusystem.train import (AdamW, NextTokenLoss, build_train_step,
                                 flax_apply, init_state)

    devices = require_chips(4)
    identity = 'bench-elastic'
    spec = MeshSpec(fsdp=4)
    mesh4 = spec.build(devices[:4])
    module = gpt2_tiny()
    optimizer = AdamW(lr=1e-3)
    policy = TensorParallel(module.partition_rules(), fsdp=True,
                            fsdp_min_size=64)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (4, 32)), jnp.int32)
    state = policy.place(init_state(module, optimizer, tokens[:1]), mesh4)
    step = build_train_step(flax_apply(module), NextTokenLoss(), optimizer)
    state, _ = step(state, jax.device_put(tokens, batch_sharding(mesh4)),
                    jax.device_put(tokens, batch_sharding(mesh4)))
    at = int(state.step)
    entries = [HotState(step=at, digest=blob_digest(blob), blob=blob)
               for blob in split_pieces(state, mesh4, hosts=4)]

    mesh2 = spec.resized(2).build(devices[:2])
    blank = policy.place(init_state(module, optimizer, tokens[:1]), mesh2)
    with tempfile.TemporaryDirectory() as root, \
            Checkpointer(root, async_save=False) as checkpointer:
        checkpointer.save(identity, at, state, extras={'step': at})

        def timed(contributions):
            times = []
            for _ in range(TRIALS):
                start = time.perf_counter()
                restored, _, _, source = elastic_resume(
                    checkpointer, identity, blank, contributions)
                materialize(restored.params)
                times.append(time.perf_counter() - start)
            return source, sorted(times)[len(times) // 2]

        hot_source, hot = timed(entries)
        disk_source, disk = timed([])      # no pieces: the disk rung
    assert (hot_source, disk_source) == ('hot-reshard', 'disk'), (
        hot_source, disk_source)
    print(json.dumps({
        'metric': 'resize_seconds',
        'value': round(hot, 4),
        'unit': 's (hot reshard 4->2 hosts, tiny model)',
        'disk_seconds': round(disk, 4),
        'hot_speedup_vs_disk': round(disk / hot, 2) if hot else None,
    }))


if __name__ == '__main__':
    main()        # 'headline' arg tolerated: the one row IS the headline
