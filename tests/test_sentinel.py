"""Divergence-proof training drills: guard, escalation ladder, SDC parity.

Every rung of the sentinel's ladder (tpusystem.train.sentinel) is exercised
with the chaos harness's *internal* fault kinds — deterministic, seeded,
replayable — the same discipline test_chaos.py applies to external faults:

* in-graph guard: NaN/Inf gradients and EMA z-score spikes suppress the
  optimizer update bitwise (params AND moments untouched), inside the one
  fused jitted program;
* policy ladder: skip events → LR backoff (and recovery) → rollback to the
  last committed checkpoint *before* the anomaly with a PaLM-style
  skip-window (post-rollback losses bitwise-match a fault-free reference
  that trained on the same surviving batches) → bounded give-up
  (DivergenceError, exit code 44);
* SDC parity: a FlipParamBit on one DP replica is caught by the
  cross-replica checksum gather before the next checkpoint commits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpusystem.checkpoint import Checkpointer
from tpusystem.data import Loader, SyntheticDigits
from tpusystem.models import MLP
from tpusystem.observe.events import (AnomalyDetected, BackoffApplied,
                                      ReplicaDiverged, RolledBack)
from tpusystem.parallel import MeshSpec, replicated
from tpusystem.parallel.chaos import CorruptBatch, CorruptGrads, FlipParamBit
from tpusystem.parallel.collectives import replica_checksums
from tpusystem.parallel.recovery import (DIVERGED_EXIT, RESTART_EXITS,
                                         DivergenceError, exit_for_restart)
from tpusystem.services.prodcon import Consumer, Producer
from tpusystem.train import (Adam, CrossEntropyLoss, Guard, Sentinel,
                             build_multi_step, build_train_step, flax_apply,
                             grouped_batches, init_state, resume_extras)
from tpusystem.train.sentinel import (HEALTH_GNORM, HEALTH_LOSS, HEALTH_OK,
                                      HEALTH_Z)

IDENTITY = 'sentinel-mlp'


def make_parts(*, guard=None, fault=None, seed=3, dropout=0.2):
    """One training cell: deterministic loader + model + jitted step."""
    dataset = SyntheticDigits(samples=40, seed=4)
    loader = Loader(dataset, batch_size=8, shuffle=True, seed=seed)  # 5/epoch
    module = MLP(features=(16,), classes=10, dropout=dropout)
    optimizer = Adam(lr=1e-2)
    state = init_state(module, optimizer, jnp.zeros((1, 28, 28)), rng=7)
    if guard is not None:
        state = guard.arm(state)
    step = build_train_step(flax_apply(module), CrossEntropyLoss(), optimizer,
                            guard=guard, fault=fault)
    return loader, state, step


def snapshot(tree):
    """Host copies of every leaf, taken BEFORE the buffers are donated."""
    return jax.tree.map(lambda leaf: np.asarray(leaf), tree)


def capture(*event_types):
    """(producer, seen) with every dispatched event of the types recorded."""
    producer = Producer()
    consumer = Consumer()
    seen = []
    for event_type in event_types:
        consumer.register(event_type, seen.append)
    producer.register(consumer)
    return producer, seen


class TestGuardedStep:
    """The in-graph rung: detection + suppression inside the jitted step."""

    def test_healthy_run_matches_unguarded_bitwise(self):
        """guard= must be a bitwise no-op on a healthy trajectory (the
        update path multiplies by lr_scale=1.0 and selects the new branch
        — both exact), so flipping it on mid-project never forks a run."""
        guard = Guard()
        loader, plain_state, plain_step = make_parts()
        loader2, guarded_state, guarded_step = make_parts(guard=guard)
        for (inputs, targets), (inputs2, targets2) in zip(loader, loader2):
            plain_state, (_, plain_loss) = plain_step(plain_state, inputs,
                                                      targets)
            guarded_state, (_, guarded_loss) = guarded_step(guarded_state,
                                                            inputs2, targets2)
            assert float(plain_loss) == float(guarded_loss)
        for a, b in zip(jax.tree.leaves(plain_state.params),
                        jax.tree.leaves(guarded_state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(guarded_state.health.bad_steps) == 0
        assert int(guarded_state.health.count) == 5

    def test_nan_grads_suppress_update_bitwise(self):
        """CorruptGrads NaN at step 3: params and optimizer moments after
        the bad step are bitwise the step-2 values, the step counter still
        advances (the batch was consumed), and training continues finite."""
        guard = Guard()
        loader, state, step = make_parts(guard=guard,
                                         fault=CorruptGrads(step=3))
        frozen = None
        for inputs, targets in loader:
            before_params = snapshot(state.params)
            before_opt = snapshot(state.opt_state)
            before_ema = float(state.health.ema_norm)
            state, (_, loss) = step(state, inputs, targets)
            if int(state.step) == 3:
                frozen = (before_params, before_opt, before_ema)
                row = np.asarray(state.health.last)
                assert row[HEALTH_OK] == 0.0
                assert not np.isfinite(row[HEALTH_GNORM])
                assert int(state.health.bad_steps) == 1
                for before, after in zip(jax.tree.leaves(before_params),
                                         jax.tree.leaves(state.params)):
                    np.testing.assert_array_equal(before, np.asarray(after))
                for before, after in zip(jax.tree.leaves(before_opt),
                                         jax.tree.leaves(state.opt_state)):
                    np.testing.assert_array_equal(before, np.asarray(after))
                # the anomaly must not fold into the EMA it is judged by
                assert float(state.health.ema_norm) == before_ema
            else:
                assert np.isfinite(float(loss))
        assert frozen is not None
        assert int(state.step) == 5 and int(state.health.bad_steps) == 1

    def test_finite_spike_flagged_by_zscore(self):
        """A finite 200x grad spike passes every isfinite check — only the
        EMA z-score rung catches it (armed after warmup). Every step draws
        a fresh batch so the grad-norm history is stationary: one batch
        repeated is memorized within the warmup, the norm falls ~100x, and
        the spike lands inside the range the EMAs have already seen."""
        guard = Guard(warmup=4, zmax=6.0)
        _, state, step = make_parts(
            guard=guard, fault=CorruptGrads(step=8, mode='spike', scale=200.0),
            dropout=0.0)
        rng = np.random.default_rng(0)

        def batch():
            return (jnp.asarray(rng.standard_normal((8, 28, 28)), jnp.float32),
                    jnp.asarray(rng.integers(0, 10, (8,)), jnp.int32))

        for _ in range(7):
            state, _ = step(state, *batch())
        before = snapshot(state.params)
        state, _ = step(state, *batch())
        row = np.asarray(state.health.last)
        assert row[HEALTH_OK] == 0.0 and np.isfinite(row[HEALTH_GNORM])
        assert row[HEALTH_Z] > 6.0
        for a, b in zip(jax.tree.leaves(before),
                        jax.tree.leaves(state.params)):
            np.testing.assert_array_equal(a, np.asarray(b))

    def test_spike_detector_respects_warmup(self):
        """Before ``warmup`` healthy steps the variance estimate is noise:
        the same spike must pass (finite!) instead of tripping a phantom."""
        guard = Guard(warmup=100)
        _, state, step = make_parts(
            guard=guard, fault=CorruptGrads(step=3, mode='spike', scale=200.0),
            dropout=0.0)
        rng = np.random.default_rng(0)
        inputs = jnp.asarray(rng.standard_normal((8, 28, 28)), jnp.float32)
        targets = jnp.asarray(rng.integers(0, 10, (8,)), jnp.int32)
        for _ in range(3):
            state, _ = step(state, inputs, targets)
        assert int(state.health.bad_steps) == 0
        assert np.asarray(state.health.last)[HEALTH_OK] == 1.0

    def test_lr_scale_scales_the_update_exactly(self):
        """HealthStats.lr_scale = 0.5 halves the applied update (the scale
        multiplies the optax update directly, so for Adam/AdamW/SGD it IS a
        learning-rate change) — the backoff lever needs no recompilation.
        Deltas are compared through a params-sized add/subtract, hence
        allclose rather than bitwise."""
        guard = Guard()
        _, state_full, step = make_parts(guard=guard, dropout=0.0)
        _, state_half, _ = make_parts(guard=guard, dropout=0.0)
        state_half = state_half.replace(health=state_half.health.replace(
            lr_scale=jnp.float32(0.5)))
        rng = np.random.default_rng(1)
        inputs = jnp.asarray(rng.standard_normal((8, 28, 28)), jnp.float32)
        targets = jnp.asarray(rng.integers(0, 10, (8,)), jnp.int32)
        before = snapshot(state_full.params)
        state_full, _ = step(state_full, inputs, targets)
        state_half, _ = step(state_half, inputs, targets)
        for initial, full, half in zip(jax.tree.leaves(before),
                                       jax.tree.leaves(state_full.params),
                                       jax.tree.leaves(state_half.params)):
            np.testing.assert_allclose(
                (np.asarray(full) - initial) * 0.5,
                np.asarray(half) - initial, rtol=1e-4, atol=1e-7)

    def test_multi_step_stacks_per_step_health(self):
        """build_multi_step(guard=True): the N-step dispatch returns the
        [N, 4] health matrix alongside the loss vector, so the Sentinel
        reviews every step of the group at one sync."""
        guard = Guard()
        loader, state, _ = make_parts(guard=guard)
        module = MLP(features=(16,), classes=10, dropout=0.2)
        optimizer = Adam(lr=1e-2)
        inner = build_train_step(flax_apply(module), CrossEntropyLoss(),
                                 optimizer, guard=guard,
                                 fault=CorruptGrads(step=2), jit=False)
        multi = build_multi_step(inner, guard=True)
        (inputs, targets), = grouped_batches(loader, 5)
        state, (losses, health) = multi(state, inputs, targets)
        losses, health = np.asarray(losses), np.asarray(health)
        assert losses.shape == (5,) and health.shape == (5, 4)
        assert health[1, HEALTH_OK] == 0.0          # step 2 was the bad one
        assert health[[0, 2, 3, 4], HEALTH_OK].tolist() == [1.0] * 4
        assert int(state.health.bad_steps) == 1

    def test_guard_requires_armed_state(self):
        guard = Guard()
        module = MLP(features=(16,), classes=10)
        optimizer = Adam(lr=1e-2)
        step = build_train_step(flax_apply(module), CrossEntropyLoss(),
                                optimizer, guard=guard)
        state = init_state(module, optimizer, jnp.zeros((1, 28, 28)))  # unarmed
        with pytest.raises(AssertionError, match='arm'):
            step(state, jnp.zeros((8, 28, 28)),
                 jnp.zeros((8,), jnp.int32))


class TestSentinelPolicy:
    """The host-side ladder over the health vector, at review cadence."""

    def drive(self, loader, state, step, sentinel, *, until,
              corrupt=None, checkpointer=None):
        """Epoch loop: step, checkpoint, review — losses recorded in
        arrival order (a rollback revisits step numbers). Terminates once
        step ``until`` completes HEALTHILY: a suppressed step at the target
        must still reach its review (that's where the rollback lives)."""
        losses = []
        while True:
            for inputs, targets in loader:
                if corrupt is not None:
                    inputs = corrupt(inputs)
                state, (_, loss) = step(state, inputs, targets)
                losses.append((int(state.step), float(loss)))
                if checkpointer is not None:
                    checkpointer.save(IDENTITY, int(state.step), state,
                                      extras=resume_extras(state, loader))
                state = sentinel.review(state)
                healthy = bool(
                    np.asarray(state.health.last)[HEALTH_OK] >= 0.5)
                if int(state.step) >= until and healthy:
                    return state, losses

    def test_anomaly_events_emitted_at_review(self):
        producer, seen = capture(AnomalyDetected)
        guard = Guard()
        loader, state, step = make_parts(guard=guard,
                                         fault=CorruptGrads(step=2))
        sentinel = Sentinel(producer=producer, model='drill')
        state, _ = self.drive(loader, state, step, sentinel, until=4)
        assert [event.step for event in seen] == [2]
        assert seen[0].kind == 'nonfinite' and seen[0].model == 'drill'
        assert not np.isfinite(seen[0].gnorm)

    def test_backoff_then_recovery(self):
        """One bad step at backoff_after=1 halves lr_scale (event + hook);
        a healthy streak of recover_after restores full rate."""
        producer, seen = capture(BackoffApplied)
        hook_calls = []
        guard = Guard()
        loader, state, step = make_parts(guard=guard,
                                         fault=CorruptGrads(step=2))
        sentinel = Sentinel(producer=producer, backoff_after=1,
                            recover_after=2, window=8,
                            on_backoff=lambda level, scale:
                            hook_calls.append((level, scale)))
        state, _ = self.drive(loader, state, step, sentinel, until=5)
        assert [(event.level, event.scale) for event in seen] == [
            (1, 0.5), (0, 1.0)]
        assert hook_calls == [(1, 0.5)]          # recovery is not a backoff
        assert float(state.health.lr_scale) == 1.0

    def test_rollback_skip_window_matches_fault_free_reference(self, tmp_path):
        """The acceptance drill: batches feeding steps 6-9 are poisoned
        (CorruptBatch — data-borne, so the skip-window genuinely escapes
        it). The guard suppresses all four updates, the sentinel rolls back
        to the last committed step before the anomaly (5) and keeps the
        loader cursor (the skip-window). From there the trajectory must be
        BITWISE identical to a fault-free reference that trained to step 5
        and skipped the same four batches."""
        guard = Guard()
        producer, seen = capture(RolledBack)

        # fault-free reference: 5 steps, skip the window, 3 more steps
        loader, state, step = make_parts(guard=guard)
        reference = {}
        consumed = 0
        iterator = iter(loader)
        while int(state.step) < 5:
            inputs, targets = next(iterator)
            consumed += 1
            state, (_, loss) = step(state, inputs, targets)
        iterator.close()
        loader.seek({'epoch': 1, 'batch': 4})    # past the 4 poisoned batches
        while int(state.step) < 8:
            for inputs, targets in loader:
                state, (_, loss) = step(state, inputs, targets)
                reference[int(state.step)] = float(loss)
                if int(state.step) >= 8:
                    break

        # chaos run: same seeds, poisoned window, checkpoint every step
        loader, state, step = make_parts(guard=guard)
        with Checkpointer(tmp_path, async_save=False,
                          max_to_keep=None) as checkpointer:
            sentinel = Sentinel(checkpointer=checkpointer, identity=IDENTITY,
                                loader=loader, producer=producer,
                                rollback_after=4, window=8)
            state, losses = self.drive(
                loader, state, step, sentinel, until=8,
                corrupt=CorruptBatch(batch=6, steps=4),
                checkpointer=checkpointer)
            # the rollback happened: steps 6..9 ran suppressed, then the
            # counter rewound to 5 and steps 6..8 reran on fresh batches
            assert [event.to_step for event in seen] == [5]
            assert seen[0].step == 9
            assert seen[0].window['to'] == {'epoch': 1, 'batch': 4}
            assert checkpointer.latest(IDENTITY) == 8   # dead branch pruned
            # rollback resets the backoff ladder: host level and the
            # restored (checkpointed, pre-burst) lr_scale stay in sync
            assert sentinel.level == 0
            assert float(state.health.lr_scale) == 1.0
        resumed = dict(losses[-3:])
        assert sorted(resumed) == [6, 7, 8]
        for at in (6, 7, 8):
            assert resumed[at] == reference[at], (at, resumed, reference)

    def test_persistent_divergence_bounded_giveup(self, tmp_path):
        """CorruptGrads is keyed on the STEP COUNTER, so a rollback rewinds
        straight back into the fault window — the model of a divergence
        that rollback cannot fix. The second rollback attempt must give up
        with DivergenceError -> exit code 44 (not a restart code)."""
        guard = Guard()
        loader, state, step = make_parts(guard=guard,
                                         fault=CorruptGrads(step=6, steps=4))
        with Checkpointer(tmp_path, async_save=False,
                          max_to_keep=None) as checkpointer:
            sentinel = Sentinel(checkpointer=checkpointer, identity=IDENTITY,
                                loader=loader, rollback_after=4, window=8,
                                max_rollbacks=1)
            with pytest.raises(DivergenceError) as excinfo:
                self.drive(loader, state, step, sentinel, until=20,
                           checkpointer=checkpointer)
        assert sentinel.rollbacks == 1
        assert exit_for_restart(excinfo.value).code == DIVERGED_EXIT
        assert DIVERGED_EXIT not in RESTART_EXITS

    def test_rollback_without_predating_checkpoint_gives_up(self, tmp_path):
        """An anomaly on the very first step has nothing committed before
        it: the ladder must give up typed, not restore a bad branch."""
        guard = Guard()
        loader, state, step = make_parts(guard=guard,
                                         fault=CorruptGrads(step=1))
        with Checkpointer(tmp_path, async_save=False) as checkpointer:
            sentinel = Sentinel(checkpointer=checkpointer, identity=IDENTITY,
                                loader=loader, rollback_after=1)
            with pytest.raises(DivergenceError, match='predates'):
                self.drive(loader, state, step, sentinel, until=3,
                           checkpointer=checkpointer)


class TestParity:
    """SDC detection: cross-replica checksums over the mesh data axis."""

    def placed_state(self, mesh):
        module = MLP(features=(16,), classes=10, dropout=0.0)
        optimizer = Adam(lr=1e-2)
        state = init_state(module, optimizer, jnp.zeros((1, 28, 28)), rng=1)
        return jax.tree.map(lambda leaf: jax.device_put(leaf,
                                                        replicated(mesh)),
                            state)

    def test_replicas_agree_and_flip_is_attributed(self):
        mesh = MeshSpec(data=4, model=2).build(jax.devices('cpu')[:8])
        state = self.placed_state(mesh)
        matrix, paths = replica_checksums(state.params, mesh)
        assert matrix.shape[0] == 4 and matrix.shape[1] == len(paths)
        assert bool(np.all(matrix == matrix[0]))
        # one bit, one leaf, one replica — the minority vote names it
        flip = FlipParamBit(replica=2, leaf=1, index=5, bit=12)
        corrupted = flip(state.params, mesh)
        matrix2, _ = replica_checksums(corrupted, mesh)
        assert not bool(np.all(matrix2 == matrix2[0]))
        sentinel = Sentinel()
        replicas, leaves = sentinel.check_parity(
            state.replace(params=corrupted), mesh, raise_on_mismatch=False)
        assert replicas == [2] and len(leaves) == 1

    def test_two_replica_tie_reports_both_sides(self):
        """With two replicas there is no majority: blaming one side of the
        tie arbitrarily would send the operator to swap the healthy host —
        every replica of the disagreeing column must be reported."""
        mesh = MeshSpec(data=2, model=2).build(jax.devices('cpu')[:4])
        state = self.placed_state(mesh)
        corrupted = FlipParamBit(replica=0, leaf=1, index=3, bit=9)(
            state.params, mesh)
        replicas, leaves = Sentinel().check_parity(
            state.replace(params=corrupted), mesh, raise_on_mismatch=False)
        assert replicas == [0, 1] and len(leaves) == 1

    def test_sentinel_checkpointer_requires_identity(self, tmp_path):
        """Satellite of the rollback rung: a misconfigured pair must fail
        at construction, not crash the recovery path hours in."""
        with Checkpointer(tmp_path, async_save=False) as checkpointer:
            with pytest.raises(ValueError, match='identity'):
                Sentinel(checkpointer=checkpointer)

    def test_flip_detected_before_next_checkpoint_commits(self, tmp_path):
        """The acceptance scenario: the parity check sits between the step
        and the save — a corrupted replica raises DivergenceError, so the
        poisoned state never becomes the checkpoint a restart trusts."""
        mesh = MeshSpec(data=4, model=2).build(jax.devices('cpu')[:8])
        state = self.placed_state(mesh)
        producer, seen = capture(ReplicaDiverged)
        sentinel = Sentinel(producer=producer, model='sdc-drill')
        with Checkpointer(tmp_path, async_save=False) as checkpointer:
            assert sentinel.check_parity(state, mesh) is None
            checkpointer.save(IDENTITY, 1, state)      # clean step commits
            state = state.replace(
                params=FlipParamBit(replica=1, leaf=0, index=0, bit=30)(
                    state.params, mesh))
            with pytest.raises(DivergenceError, match='replica'):
                sentinel.check_parity(state, mesh)     # BEFORE save(2)
            assert checkpointer.latest(IDENTITY) == 1  # nothing contaminated
        assert seen and seen[0].replicas == [1]
        assert exit_for_restart(DivergenceError('sdc')).code == DIVERGED_EXIT


def test_debug_nans_env_knob(monkeypatch):
    """TPUSYSTEM_DEBUG_NANS=1 arms jax_debug_nans (the post-mortem sibling
    of the guard's in-graph masking), documented next to
    TPUSYSTEM_DEBUG_CACHE."""
    import __graft_entry__
    previous = jax.config.jax_debug_nans
    try:
        monkeypatch.setenv('TPUSYSTEM_DEBUG_NANS', '1')
        __graft_entry__.configure_debug_nans()
        assert jax.config.jax_debug_nans is True
        # absent (or != '1') the knob must not clobber an existing setting
        jax.config.update('jax_debug_nans', False)
        monkeypatch.delenv('TPUSYSTEM_DEBUG_NANS')
        __graft_entry__.configure_debug_nans()
        assert jax.config.jax_debug_nans is False
    finally:
        jax.config.update('jax_debug_nans', previous)
