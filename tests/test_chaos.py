"""Chaos-tested fault tolerance: preemption, worker loss, corruption.

The ROADMAP's "handles as many scenarios as you can imagine" is enforced
here by *deterministic* fault injection over the real control-plane stack
(:mod:`tpusystem.parallel.chaos`), not by hand-crafted mocks:

* kill-at-step-k → restart → **step-granular resume**: the resumed run's
  losses are bitwise-identical to an uninterrupted reference run (same RNG
  stream, same batch order — the headline acceptance scenario);
* torn/corrupt checkpoint dirs are *skipped with a logged fallback* by
  ``latest``/``restore``, never crashed on;
* SIGTERM preemption surfaces as :class:`Preempted` at the ``sync()``
  drain, fences an emergency checkpoint, and maps to the restartable exit
  code;
* seeded frame drops/delays, heartbeat stalls, and mid-collective socket
  kills leave the collective machinery correct (or degraded exactly as
  documented).
"""

from __future__ import annotations

import json
import logging
import os
import signal as signal_module
import socket
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusystem.checkpoint import Checkpointer, Repository
from tpusystem.data import Loader, SyntheticDigits
from tpusystem.models import MLP
from tpusystem.parallel.chaos import (ChaosHub, ChaosTransport, DieAtStep,
                                      Faults, WorkerKilled)
from tpusystem.parallel.multihost import (ControlPlaneFailover,
                                          DistributedProducer, Hub,
                                          TcpTransport, WorkerJoined,
                                          WorkerLost)
from tpusystem.parallel.recovery import (LOST_WORKER_EXIT, PREEMPTED_EXIT,
                                         RESTART_EXITS, Preempted,
                                         WorkerLostError, exit_for_restart,
                                         recovery_consumer)
from tpusystem.runtime import Runtime
from tpusystem.services.prodcon import Consumer
from tpusystem.train import (Adam, CrossEntropyLoss, build_train_step,
                             flax_apply, init_state, resume_extras)

IDENTITY = 'chaos-mlp'


def wait_until(predicate, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


def make_parts():
    """One training cell: deterministic loader + model + jitted step."""
    dataset = SyntheticDigits(samples=40, seed=4)
    loader = Loader(dataset, batch_size=8, shuffle=True, seed=3)  # 5/epoch
    module = MLP(features=(16,), classes=10, dropout=0.2)
    optimizer = Adam(lr=1e-2)
    state = init_state(module, optimizer, jnp.zeros((1, 28, 28)), rng=7)
    step = build_train_step(flax_apply(module), CrossEntropyLoss(), optimizer)
    return loader, state, step


def drive(loader, state, step, checkpointer, *, until, die=None):
    """Run the epoch loop to global step ``until``, checkpointing each step
    with the loader cursor; returns (state, {step: loss})."""
    losses = {}
    while int(state.step) < until:
        for inputs, targets in loader:
            state, (_, loss) = step(state, inputs, targets)
            at = int(state.step)
            losses[at] = float(loss)
            if checkpointer is not None:
                checkpointer.save(IDENTITY, at, state,
                                  extras=resume_extras(state, loader))
            if die is not None:
                die(at)
            if at == until:
                return state, losses
    return state, losses


class TestStepGranularResume:
    """The acceptance scenario: kill at step k, restart, resume bitwise."""

    def test_kill_at_step_restart_resumes_bitwise(self, tmp_path):
        # uninterrupted reference trajectory (no checkpointing at all)
        loader, state, step = make_parts()
        _, reference = drive(loader, state, step, None, until=10)
        assert sorted(reference) == list(range(1, 11))

        # chaos run: dies at step 6 — mid-epoch 2 (5 batches per epoch),
        # so resume must restart mid-epoch, not at an epoch edge
        loader, state, step = make_parts()
        with Checkpointer(tmp_path, async_save=False) as checkpointer:
            with pytest.raises(WorkerKilled):
                drive(loader, state, step, checkpointer,
                      until=10, die=DieAtStep(step=6))
            checkpointer.fence(IDENTITY)

            # restart: fresh process state — new loader, blank template
            loader, blank, step = make_parts()
            state, resumed_step, extras = checkpointer.resume(IDENTITY, blank)
            assert resumed_step == 6
            assert int(state.step) == 6          # device counter restored
            assert extras['step'] == 6
            assert extras['cursor'] == {'epoch': 1, 'batch': 1}
            loader.seek(extras['cursor'])
            _, resumed = drive(loader, state, step, checkpointer, until=10)

        # bitwise-identical continuation: same RNG key stream (carried in
        # TrainState), same batch order (cursor-seeked loader)
        assert sorted(resumed) == list(range(7, 11))
        for at in range(7, 11):
            assert resumed[at] == reference[at], (at, resumed[at], reference[at])

    def test_kill_over_live_control_plane_then_resume(self, tmp_path):
        """The same drill through the REAL multihost stack: a pod of TCP
        transports, a peer killed at step k (socket death, no 'bye'), the
        survivor's recovery consumer raising at the drain point, emergency
        fence, restart, bitwise resume."""
        hub = Hub(2)
        survivor = TcpTransport(hub.address, 0, 2)
        victim = ChaosTransport(hub.address, 1, 2)
        assert wait_until(lambda: len(hub._clients) == 2)
        producer = DistributedProducer(survivor)
        producer.register(recovery_consumer())
        try:
            loader, state, step = make_parts()
            _, reference = drive(loader, state, step, None, until=8)

            loader, state, step = make_parts()
            checkpointer = Checkpointer(tmp_path, async_save=False)
            die = DieAtStep(step=4, action=victim.kill)
            with pytest.raises(WorkerLostError) as excinfo:
                losses = {}
                while int(state.step) < 8:
                    for inputs, targets in loader:
                        state, (_, loss) = step(state, inputs, targets)
                        losses[int(state.step)] = float(loss)
                        checkpointer.save(IDENTITY, int(state.step), state,
                                          extras=resume_extras(state, loader))
                        die(int(state.step))
                        # drain point: worker loss surfaces HERE, on the
                        # host loop thread, never inside a collective
                        deadline = time.monotonic() + 5
                        while die.fired and time.monotonic() < deadline:
                            producer.drain()
                            time.sleep(0.01)
            assert excinfo.value.rank == 1
            assert excinfo.value.reason == 'socket'   # EOF, not a stall
            fenced = checkpointer.fence(IDENTITY)   # emergency durability
            assert fenced == 4
            assert exit_for_restart(excinfo.value).code == LOST_WORKER_EXIT

            # the scheduler restarts the job: fresh everything, same id
            loader, blank, step = make_parts()
            state, resumed_step, extras = checkpointer.resume(IDENTITY, blank)
            assert resumed_step == 4
            loader.seek(extras['cursor'])
            _, resumed = drive(loader, state, step, checkpointer, until=8)
            checkpointer.close()
            for at in range(5, 9):
                assert resumed[at] == reference[at]
        finally:
            survivor.close()
            hub.close()


class TestCorruptCheckpoints:
    """Torn step dirs are survivable: verify-probe, skip, logged fallback."""

    def plant_truncated(self, root, step):
        """A save torn by a kill: the dir exists, the commit marker and
        item manifests never landed."""
        torn = root / IDENTITY / str(step)
        (torn / 'default').mkdir(parents=True)
        (torn / 'default' / 'manifest.ocdbt').write_bytes(b'torn mid-write')

    def test_truncated_step_dir_skipped_with_logged_fallback(
            self, tmp_path, caplog):
        loader, state, step = make_parts()
        with Checkpointer(tmp_path, async_save=False) as checkpointer:
            state, _ = drive(loader, state, step, checkpointer, until=3)
        self.plant_truncated(tmp_path, 7)   # "newest" step is garbage

        # a fresh process must resume from 3, not crash on 7
        with Checkpointer(tmp_path, async_save=False) as fresh:
            with caplog.at_level(logging.WARNING, 'tpusystem.checkpoint'):
                assert fresh.latest(IDENTITY) == 3
                assert fresh.epochs(IDENTITY) == [1, 2, 3]
                assert not fresh.verify(IDENTITY, 7)
                assert fresh.verify(IDENTITY, 3)
                _, blank, _ = make_parts()
                restored, resumed_step, _ = fresh.resume(IDENTITY, blank)
            assert resumed_step == 3
            np.testing.assert_array_equal(
                np.asarray(restored.step), np.asarray(state.step))
            for expected, loaded in zip(jax.tree.leaves(state.params),
                                        jax.tree.leaves(restored.params)):
                np.testing.assert_array_equal(np.asarray(expected),
                                              np.asarray(loaded))
        assert 'incomplete or corrupt' in caplog.text
        assert '7' in caplog.text

    def test_explicit_missing_epoch_lists_available(self, tmp_path):
        """Satellite: an explicit epoch that is missing (or torn) names the
        committed epochs instead of an opaque Orbax error."""
        loader, state, step = make_parts()
        with Checkpointer(tmp_path, async_save=False) as checkpointer:
            drive(loader, state, step, checkpointer, until=2)
            _, blank, _ = make_parts()
            with pytest.raises(FileNotFoundError, match=r'epoch 9.*\[1, 2\]'):
                checkpointer.restore(IDENTITY, blank, epoch=9)
            self.plant_truncated(tmp_path, 5)
            with pytest.raises(FileNotFoundError, match=r'epoch 5.*\[1, 2\]'):
                checkpointer.restore(IDENTITY, blank, epoch=5)
            # the committed ones still restore explicitly
            restored = checkpointer.restore(IDENTITY, blank, epoch=1)
            assert int(restored.step) == 1

    def test_resume_falls_back_when_probe_passing_payload_is_torn(
            self, tmp_path, caplog):
        """A payload torn in a way the cheap probe cannot see (markers
        intact, array bytes gone) must still fall back, not crash the
        one-call resume path."""
        loader, state, step = make_parts()
        with Checkpointer(tmp_path, async_save=False) as checkpointer:
            step_two = None
            while int(state.step) < 3:
                for inputs, targets in loader:
                    state, _ = step(state, inputs, targets)
                    checkpointer.save(IDENTITY, int(state.step), state)
                    if int(state.step) == 2:
                        # a host copy: the next step donates `state`
                        step_two = np.asarray(state.step)
                    if int(state.step) == 3:
                        break
        # corrupt step 3's payload but keep every integrity marker
        (tmp_path / IDENTITY / '3' / 'default' /
         'manifest.ocdbt').write_bytes(b'probe-passing garbage')
        with Checkpointer(tmp_path, async_save=False) as fresh:
            assert fresh.verify(IDENTITY, 3)     # the probe cannot tell
            _, blank, _ = make_parts()
            with caplog.at_level(logging.WARNING, 'tpusystem.checkpoint'):
                restored, resumed_step, _ = fresh.resume(IDENTITY, blank)
            assert resumed_step == 2
            np.testing.assert_array_equal(np.asarray(restored.step),
                                          step_two)
        assert 'falling back' in caplog.text

    def test_repository_auto_version_respects_in_flight_async_save(
            self, tmp_path):
        """Regression: latest() only sees committed steps, so the auto
        increment must consult the in-flight async save too — reusing its
        step number would make Orbax raise StepAlreadyExists."""
        loader, state, step = make_parts()

        class Model:
            id = IDENTITY
        model = Model()
        model.state = state
        repository = Repository(tmp_path, async_save=True)
        try:
            repository.store(model)      # -> version 0, commits in background
            repository.store(model)      # must allocate 1, not 0 again
            repository.wait()
            assert repository.latest(model) == 1
        finally:
            repository.close()

    def test_fence_is_monotonic(self, tmp_path):
        import shutil
        loader, state, step = make_parts()
        with Checkpointer(tmp_path, async_save=False) as checkpointer:
            drive(loader, state, step, checkpointer, until=3)
            assert checkpointer.fenced(IDENTITY) is None
            assert checkpointer.fence(IDENTITY) == 3
            assert checkpointer.fenced(IDENTITY) == 3
            # losing the newest dir cannot move the fence backwards
            shutil.rmtree(tmp_path / IDENTITY / '3')
            assert checkpointer.fence(IDENTITY) == 3
            assert checkpointer.latest(IDENTITY) == 2

    def test_extras_sidecar_pruned_with_gc(self, tmp_path):
        loader, state, step = make_parts()
        with Checkpointer(tmp_path, async_save=False,
                          max_to_keep=2) as checkpointer:
            drive(loader, state, step, checkpointer, until=6)
            kept = checkpointer.epochs(IDENTITY)
            assert kept == [5, 6]        # window of 2
            sidecars = sorted(int(p.stem) for p in
                              (tmp_path / IDENTITY / '.extras').glob('*.json'))
            assert set(sidecars) <= {4, 5, 6}   # stale ones pruned
            assert checkpointer.extras(IDENTITY, 6)['step'] == 6


class TestChaosControlPlane:
    """Seeded frame faults over real sockets: the documented contracts
    hold under drops, delays, stalls, and kills."""

    def chaos_pod(self, size, faults, **hub_kwargs):
        hub = Hub(size, **hub_kwargs)
        transports = [
            ChaosTransport(hub.address, rank, size,
                           faults=faults[rank] if faults else None,
                           heartbeat_interval=hub_kwargs.get(
                               'heartbeat_timeout') and 0.05)
            for rank in range(size)]
        assert wait_until(lambda: len(hub._clients) == size)
        return hub, transports

    def shutdown(self, hub, transports):
        for transport in transports:
            transport.close()
        hub.close()

    def test_same_seed_same_fault_schedule(self):
        script = ['event', 'reduce', 'event', 'event', 'gather'] * 20
        first, second = Faults(seed=5, drop=0.3), Faults(seed=5, drop=0.3)
        decisions = [(first.decide(k), second.decide(k)) for k in script]
        assert all(a == b for a, b in decisions)
        assert first.dropped == second.dropped and first.dropped

    def test_explicit_kinds_override_default_spare(self):
        """Naming a kind in ``kinds`` is the opt-in that defeats the
        default spare list — else result/hb scenarios run fault-free and
        pass vacuously."""
        faults = Faults(seed=0, drop=1.0, kinds=('result',))
        assert faults.decide('result') is None       # spared by default, faulted on opt-in
        assert faults.decide('reduce') == 0.0        # outside kinds: passes
        assert Faults(seed=0, drop=1.0).decide('result') == 0.0  # default spare

    def test_dropped_events_leave_collectives_intact(self):
        """Events are at-most-once by contract; collectives are the
        agreement primitive and must survive a lossy event plane."""
        faults = [Faults(seed=rank, drop=1.0, kinds=('event',))
                  for rank in range(3)]
        hub, transports = self.chaos_pod(3, faults)
        try:
            seen = []
            transports[1].subscribe('test', seen.append)
            transports[0].send_event('test', 'vanishes')
            results = {}

            def contribute(rank):
                results[rank] = transports[rank].allreduce(rank, op='sum',
                                                           timeout=10)
            threads = [threading.Thread(target=contribute, args=(rank,))
                       for rank in range(3)]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join(timeout=10)
            assert results == {0: 3, 1: 3, 2: 3}
            assert faults[0].dropped == ['event']
            assert seen == []                    # the event truly vanished
        finally:
            self.shutdown(hub, transports)

    def test_delayed_frames_do_not_corrupt_collectives(self):
        """Per-rank jitter reorders contributions across ranks; the hub's
        (kind, op, sequence) keying must still pair them correctly."""
        faults = [Faults(seed=rank, delay=0.7, delay_seconds=0.03,
                         kinds=('reduce', 'gather'))
                  for rank in range(3)]
        hub, transports = self.chaos_pod(3, faults)
        try:
            results = {}

            def contribute(rank):
                total = transports[rank].allreduce(rank, op='sum', timeout=10)
                gathered = transports[rank].gather(10 * rank, timeout=10)
                peak = transports[rank].allreduce(rank, op='max', timeout=10)
                results[rank] = (total, sorted(gathered), peak)
            threads = [threading.Thread(target=contribute, args=(rank,))
                       for rank in range(3)]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join(timeout=15)
            assert all(results[rank] == (3, [0, 10, 20], 2)
                       for rank in range(3))
            assert any(faults[rank].delayed for rank in range(3))
        finally:
            self.shutdown(hub, transports)

    def test_heartbeat_stall_surfaces_worker_lost(self):
        """A host alive but unresponsive past the liveness timeout is a
        loss: excluded from the quota, broadcast as WorkerLost."""
        faults = Faults(seed=1)
        hub = Hub(3, heartbeat_timeout=0.3)
        transports = [
            TcpTransport(hub.address, 0, 3, heartbeat_interval=0.05),
            TcpTransport(hub.address, 1, 3, heartbeat_interval=0.05),
            ChaosTransport(hub.address, 2, 3, faults=faults,
                           heartbeat_interval=0.05),
        ]
        try:
            assert wait_until(lambda: len(hub._clients) == 3)
            producer = DistributedProducer(transports[0])
            lost = []
            consumer = Consumer()
            consumer.register(WorkerLost, lost.append)
            producer.register(consumer)
            faults.stall_heartbeats(30.0)
            assert wait_until(lambda: 2 in hub._excluded, timeout=5)
            assert wait_until(lambda: (producer.drain(), bool(lost))[1],
                              timeout=5)
            assert lost[0].rank == 2
            # satellite: a stall is detected by the liveness monitor, and
            # the event says so — different MTTR profile than socket death
            assert lost[0].reason == 'heartbeat'
            # the stalled rank is out of the quota: fail-fast, and the
            # survivors' collectives degrade to the live set
            with pytest.raises(RuntimeError, match='excluded'):
                transports[2].allreduce(True, op='and', timeout=15)
            results = {}

            def contribute(rank):
                results[rank] = transports[rank].allreduce(rank, op='sum',
                                                           timeout=10)
            threads = [threading.Thread(target=contribute, args=(rank,))
                       for rank in (0, 1)]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join(timeout=10)
            assert results == {0: 1, 1: 1}
        finally:
            self.shutdown(hub, transports)

    def test_mid_collective_kill_completes_for_survivors(self):
        """DieAtStep(action=kill) mid-collective: the victim's socket dies
        with its contribution pending; survivors complete on the quota."""
        hub, transports = self.chaos_pod(3, None)
        try:
            results = {}

            def contribute(rank):
                results[rank] = transports[rank].allreduce(rank, op='sum',
                                                           timeout=10)
            threads = [threading.Thread(target=contribute, args=(rank,))
                       for rank in (0, 1)]
            for thread in threads:
                thread.start()
            assert wait_until(lambda: len(hub._pending) == 1)
            die = DieAtStep(step=3, action=transports[2].kill)
            die(3)                       # the scripted death fires
            assert die.fired
            for thread in threads:
                thread.join(timeout=10)
            assert results == {0: 1, 1: 1}
        finally:
            self.shutdown(hub, transports)

    def test_chaotic_hub_fanout_drops_are_at_most_once(self):
        """Faults on the router side: a dropped event fanout loses that
        delivery (at-most-once, documented) without wedging the hub."""
        faults = Faults(seed=3, drop=1.0, kinds=('event',))
        hub = ChaosHub(2, faults=faults)
        transports = [TcpTransport(hub.address, rank, 2) for rank in range(2)]
        try:
            assert wait_until(lambda: len(hub._clients) == 2)
            seen = []
            transports[1].subscribe('test', seen.append)
            transports[0].send_event('test', 'dropped-at-the-hub')
            time.sleep(0.2)
            assert seen == [] and faults.dropped == ['event']
            # collectives (not in kinds) still flow through the same hub
            results = {}

            def contribute(rank):
                results[rank] = transports[rank].allreduce(rank, op='sum',
                                                           timeout=10)
            threads = [threading.Thread(target=contribute, args=(rank,))
                       for rank in range(2)]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join(timeout=10)
            assert results == {0: 1, 1: 1}
        finally:
            self.shutdown(hub, transports)


class TestClose:
    """Satellite regression: teardown racing in-flight collectives must
    surface ControlPlaneFailover, not hang to the collective timeout."""

    def test_hub_close_mid_collective_fails_over_every_waiter(self):
        hub = Hub(3)
        transports = [TcpTransport(hub.address, rank, 3) for rank in range(3)]
        assert wait_until(lambda: len(hub._clients) == 3)
        try:
            outcomes = {}

            def contribute(rank):
                start = time.monotonic()
                try:
                    transports[rank].allreduce(rank, op='sum', timeout=60)
                    outcomes[rank] = 'completed'
                except ControlPlaneFailover:
                    outcomes[rank] = ('failover', time.monotonic() - start)
            threads = [threading.Thread(target=contribute, args=(rank,))
                       for rank in (0, 1)]   # rank 2 withholds: op stays pending
            for thread in threads:
                thread.start()
            assert wait_until(lambda: len(hub._pending) == 1)
            hub.close()
            for thread in threads:
                thread.join(timeout=10)
            assert set(outcomes) == {0, 1}
            for rank in (0, 1):
                verdict, elapsed = outcomes[rank]
                assert verdict == 'failover' and elapsed < 5
        finally:
            for transport in transports:
                transport.close()
            hub.close()

    def test_transport_close_mid_collective_fails_typed_not_timeout(self):
        """The fixed hang: closing a transport with its own collective in
        flight used to leave the waiter for the full timeout and then
        raise a raw queue.Empty."""
        hub = Hub(2)
        transports = [TcpTransport(hub.address, rank, 2) for rank in range(2)]
        assert wait_until(lambda: len(hub._clients) == 2)
        try:
            outcome = {}

            def contribute():
                start = time.monotonic()
                try:
                    transports[0].allreduce(0, op='sum', timeout=60)
                    outcome['verdict'] = 'completed'
                except ControlPlaneFailover:
                    outcome['verdict'] = 'failover'
                except Exception as error:
                    outcome['verdict'] = type(error).__name__
                outcome['elapsed'] = time.monotonic() - start
            thread = threading.Thread(target=contribute)
            thread.start()
            assert wait_until(lambda: len(hub._pending) == 1)
            transports[0].close()
            thread.join(timeout=10)
            assert outcome['verdict'] == 'failover'
            assert outcome['elapsed'] < 5
        finally:
            transports[1].close()
            hub.close()


class TestPreemption:
    """SIGTERM → Preempted at the drain → emergency fence → restart code."""

    def test_sigterm_surfaces_at_sync_not_in_handler(self):
        previous = signal_module.getsignal(signal_module.SIGTERM)
        with Runtime() as runtime:
            runtime.install_preemption_handler()
            assert not runtime.preempted
            os.kill(os.getpid(), signal_module.SIGTERM)
            assert wait_until(lambda: runtime.preempted)
            with pytest.raises(Preempted) as excinfo:
                runtime.sync()
            assert excinfo.value.signum == signal_module.SIGTERM
            assert exit_for_restart(excinfo.value).code == PREEMPTED_EXIT
        # close() restored whatever disposition was there before
        assert signal_module.getsignal(signal_module.SIGTERM) is previous

    def test_reinstall_keeps_the_original_previous_handler(self):
        """Regression: a second install must not record the Runtime's own
        handler as 'previous', or close() would leave it armed forever."""
        previous = signal_module.getsignal(signal_module.SIGTERM)
        with Runtime(preemption=True) as runtime:
            runtime.install_preemption_handler()   # re-install
        assert signal_module.getsignal(signal_module.SIGTERM) is previous

    def test_queued_events_still_drain_before_the_raise(self):
        """The raise happens AFTER the drain: consumers see everything that
        arrived before the preemption unwinds the loop."""
        from tpusystem.services.prodcon import event

        @event
        class Tick:
            n: int

        with Runtime(preemption=True) as runtime:
            seen = []
            consumer = Consumer()
            consumer.register(Tick, seen.append)
            runtime.producer.register(consumer)
            runtime.producer._inbox.put(Tick(n=1))
            os.kill(os.getpid(), signal_module.SIGTERM)
            assert wait_until(lambda: runtime.preempted)
            with pytest.raises(Preempted):
                runtime.sync()
            assert seen == [Tick(1)]

    def test_preemption_mid_training_fences_and_resumes(self, tmp_path):
        """End to end: SIGTERM mid-epoch, Preempted at the next drain, the
        emergency checkpoint fences, the 'restarted' job resumes at the
        fenced step with bitwise-identical continuation."""
        loader, state, step = make_parts()
        _, reference = drive(loader, state, step, None, until=8)

        loader, state, step = make_parts()
        checkpointer = Checkpointer(tmp_path, async_save=True)
        with Runtime(preemption=True) as runtime:
            with pytest.raises(Preempted) as excinfo:
                while int(state.step) < 8:
                    for inputs, targets in loader:
                        state, (_, loss) = step(state, inputs, targets)
                        checkpointer.save(IDENTITY, int(state.step), state,
                                          extras=resume_extras(state, loader))
                        if int(state.step) == 5:   # the scheduler's notice
                            os.kill(os.getpid(), signal_module.SIGTERM)
                            assert wait_until(lambda: runtime.preempted)
                        runtime.sync()             # drain point raises
            # emergency path: fence the in-flight async save, then exit
            fenced = checkpointer.fence(IDENTITY)
            assert fenced == 5
            assert exit_for_restart(excinfo.value).code in RESTART_EXITS
        checkpointer.close()

        with Checkpointer(tmp_path, async_save=False) as fresh:
            loader, blank, step = make_parts()
            state, resumed_step, extras = fresh.resume(IDENTITY, blank)
            assert resumed_step == 5
            loader.seek(extras['cursor'])
            _, resumed = drive(loader, state, step, fresh, until=8)
        for at in range(6, 9):
            assert resumed[at] == reference[at]


class TestRecoveryPaths:
    """Satellite: the recovery consumer's untested decision paths."""

    def test_observe_policy_continues_in_live_pod(self, caplog):
        """policy='observe' over a real pod: the loss is logged, nothing
        raises at the drain, and the survivors keep agreeing stops."""
        hub = Hub(3)
        transports = [TcpTransport(hub.address, rank, 3) for rank in range(3)]
        assert wait_until(lambda: len(hub._clients) == 3)
        try:
            producer = DistributedProducer(transports[0])
            producer.register(recovery_consumer('observe'))
            transports[2]._sock.shutdown(socket.SHUT_RDWR)
            transports[2]._sock.close()
            assert wait_until(lambda: 2 in hub._lost)
            with caplog.at_level(logging.WARNING, 'tpusystem.recovery'):
                assert wait_until(
                    lambda: (producer.drain(),
                             'worker 2 lost' in caplog.text)[1])
            # no raise: the survivors still run the agreement machinery
            results = {}

            def contribute(rank):
                results[rank] = transports[rank].allreduce(rank == 0, op='or',
                                                           timeout=10)
            threads = [threading.Thread(target=contribute, args=(rank,))
                       for rank in (0, 1)]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join(timeout=10)
            assert results == {0: True, 1: True}
        finally:
            for transport in transports[:2]:
                transport.close()
            hub.close()

    def test_worker_joined_surfaces_through_live_pod(self, caplog):
        """The WorkerJoined handler path, driven by a real (re)join: a new
        rank dialing the hub broadcasts 'joined' to every other host."""
        hub = Hub(3)
        transports = [TcpTransport(hub.address, rank, 3) for rank in range(2)]
        assert wait_until(lambda: len(hub._clients) == 2)
        try:
            producer = DistributedProducer(transports[0])
            joined = []
            consumer = Consumer()
            consumer.register(WorkerJoined, joined.append)
            producer.register(consumer)
            producer.register(recovery_consumer('observe'))
            late = TcpTransport(hub.address, 2, 3)
            transports.append(late)
            with caplog.at_level(logging.INFO, 'tpusystem.recovery'):
                # the broadcasts for the INITIAL joins may still be in
                # flight when on_control hooks up — wait for rank 2's
                assert wait_until(
                    lambda: (producer.drain(),
                             any(j.rank == 2 for j in joined))[1])
            assert 'worker 2 joined' in caplog.text
        finally:
            for transport in transports:
                transport.close()
            hub.close()

    def test_worker_lost_unwinds_with_pending_async_save(self, tmp_path):
        """Satellite: WorkerLostError through runtime.sync() with an async
        save still in flight — repository.wait() in the handler keeps the
        last good checkpoint restorable."""
        loader, state, step = make_parts()

        class Model:
            id = IDENTITY

        model = Model()
        model.state = state
        repository = Repository(tmp_path, async_save=True)
        with Runtime() as runtime:
            runtime.producer.register(recovery_consumer())
            for inputs, targets in loader:
                model.state, _ = step(model.state, inputs, targets)
                repository.store(model, int(model.state.step),
                                 extras=resume_extras(model.state, loader))
                break
            # the loss lands while the save may still be in flight
            runtime.producer._inbox.put(WorkerLost(rank=1, last_seen=2.0))
            with pytest.raises(WorkerLostError) as excinfo:
                runtime.sync()
            assert excinfo.value.rank == 1
            repository.wait()            # the docstring contract
            assert repository.fence(model) == 1
        # a fresh process restores the fenced checkpoint
        fresh = Repository(tmp_path, async_save=False)
        try:
            _, blank, _ = make_parts()
            clone = Model()
            clone.state = blank
            resumed_step, extras = fresh.resume(clone)
            assert resumed_step == 1 and int(clone.state.step) == 1
            assert extras['cursor'] == {'epoch': 0, 'batch': 1}
        finally:
            fresh.close()
            repository.close()


class TestFlakySaves:
    """Satellite: checkpoint durability under a flaky filesystem — bounded
    retry+backoff on the save, and background async failures surfacing at
    the NEXT save()/newest() instead of hiding until wait()/fence()."""

    def test_save_retries_transient_fs_errors(self, tmp_path, monkeypatch,
                                              caplog):
        loader, state, step = make_parts()
        with Checkpointer(tmp_path, async_save=False,
                          retry_backoff=0.01) as checkpointer:
            manager = checkpointer._manager(IDENTITY)
            real_save, calls = manager.save, []

            def flaky(*args, **kwargs):
                calls.append(1)
                if len(calls) <= 2:
                    raise OSError('EIO: flaky mount')
                return real_save(*args, **kwargs)

            monkeypatch.setattr(manager, 'save', flaky)
            with caplog.at_level(logging.WARNING, 'tpusystem.checkpoint'):
                checkpointer.save(IDENTITY, 1, state)
            assert len(calls) == 3
            assert checkpointer.verify(IDENTITY, 1)
        assert 'retry 1/2' in caplog.text and 'retry 2/2' in caplog.text

    def test_save_gives_up_after_bounded_retries(self, tmp_path, monkeypatch):
        loader, state, step = make_parts()
        with Checkpointer(tmp_path, async_save=False, save_retries=2,
                          retry_backoff=0.01) as checkpointer:
            manager = checkpointer._manager(IDENTITY)
            calls = []

            def dead(*args, **kwargs):
                calls.append(1)
                raise OSError('ENOSPC: disk full')

            monkeypatch.setattr(manager, 'save', dead)
            with pytest.raises(OSError, match='ENOSPC'):
                checkpointer.save(IDENTITY, 1, state)
            assert len(calls) == 3               # 1 try + save_retries

    def test_async_failure_surfaces_at_next_save_and_newest(
            self, tmp_path, monkeypatch):
        """The fixed gap: a background commit that failed used to stay
        silent until wait()/fence() — the training loop kept 'saving' into
        a void. It must raise at the very next save() or newest()."""
        loader, state, step = make_parts()
        checkpointer = Checkpointer(tmp_path, async_save=True)
        try:
            checkpointer.save(IDENTITY, 1, state)
            checkpointer.wait()
            manager = checkpointer._managers[IDENTITY]

            def boom():
                raise OSError('async commit failed: disk full')

            monkeypatch.setattr(manager, 'check_for_errors', boom,
                                raising=False)
            with pytest.raises(OSError, match='async commit failed'):
                checkpointer.save(IDENTITY, 2, state)
            with pytest.raises(OSError, match='async commit failed'):
                checkpointer.newest(IDENTITY)
        finally:
            monkeypatch.undo()
            checkpointer.close()

    def test_legacy_checkpoint_restores_into_grown_train_state(self,
                                                               tmp_path):
        """Regression (review finding): TrainState grew the optional
        ``health`` field — a checkpoint written before it existed must
        still restore/resume (the leafless field is pruned from the
        restore target and None grafted back), and only an ARMED target
        fails loudly."""
        from tpusystem.train import Guard
        loader, state, step = make_parts()
        state, _ = step(state, *next(iter(loader)))
        legacy = {'params': state.params, 'opt_state': state.opt_state,
                  'rng': state.rng, 'step': state.step}   # the PR-3 shape
        with Checkpointer(tmp_path, async_save=False) as checkpointer:
            checkpointer.save(IDENTITY, 1, legacy,
                              extras=resume_extras(state, loader))
            _, blank, _ = make_parts()
            restored, resumed_step, extras = checkpointer.resume(IDENTITY,
                                                                 blank)
            assert resumed_step == 1 and int(restored.step) == 1
            assert restored.health is None
            for expected, loaded in zip(jax.tree.leaves(state.params),
                                        jax.tree.leaves(restored.params)):
                np.testing.assert_array_equal(np.asarray(expected),
                                              np.asarray(loaded))
            # training continues from the grafted state, and arming works
            armed = Guard().arm(restored)
            assert armed.health is not None
            with pytest.raises(Exception):
                checkpointer.restore(IDENTITY, Guard().arm(blank), epoch=1)

    def test_discard_after_prunes_dead_branch_and_lowers_fence(
            self, tmp_path):
        """The rollback epilogue: steps beyond the target vanish (so the
        retrained steps cannot collide) and a fence pointing into the dead
        branch is lowered to the target."""
        loader, state, step = make_parts()
        with Checkpointer(tmp_path, async_save=False,
                          max_to_keep=None) as checkpointer:
            state, _ = drive(loader, state, step, checkpointer, until=6)
            assert checkpointer.fence(IDENTITY) == 6
            dead = checkpointer.discard_after(IDENTITY, 3)
            assert dead == [4, 5, 6]
            assert checkpointer.epochs(IDENTITY) == [1, 2, 3]
            assert checkpointer.fenced(IDENTITY) == 3
            # the retrained branch reuses the freed numbers without clashing
            checkpointer.save(IDENTITY, 4, state)
            assert checkpointer.latest(IDENTITY) == 4


class TestBuddyDoubleLoss:
    """Satellite: BOTH hosts of a replica pair die in one wave. Their
    pieces exist only in each other's replica slots, so the hot tier is
    unrecoverable for both — the elastic resize path must fall back to
    disk and still land bitwise on the last committed step (the drill's
    loss-equivalence), never deliver a partial hot state."""

    def test_double_buddy_loss_falls_back_to_disk(self, tmp_path, caplog):
        from tpusystem.checkpoint import Checkpointer, MemStoreClient
        from tpusystem.models import gpt2_tiny
        from tpusystem.parallel import (MeshSpec, Supervisor, TensorParallel,
                                        batch_sharding)
        from tpusystem.parallel.chaos import PreemptionWave
        from tpusystem.parallel.elastic import (ElasticCoordinator,
                                                ElasticPolicy, ResizeDecision,
                                                collect_pieces, elastic_resume,
                                                split_pieces)
        from tpusystem.train import (AdamW, NextTokenLoss, build_train_step,
                                     flax_apply, init_state)
        identity = 'double-loss'
        devices = jax.devices('cpu')
        spec = MeshSpec(fsdp=4)          # every host holds UNIQUE shards
        mesh4 = spec.build(devices[:4])
        hub = Hub(4)
        transports = [ChaosTransport(hub.address, rank, 4,
                                     faults=Faults(seed=rank))
                      for rank in range(4)]
        assert wait_until(lambda: len(hub._clients) == 4)
        supervisors = [Supervisor(['w'], rank=rank,
                                  transport=transports[rank], buddy=rank ^ 1)
                       for rank in range(4)]
        policy = ElasticPolicy(settle_window=0.25, rebroadcast=0.1)
        coords = [ElasticCoordinator(transports[rank], rank, 4,
                                     policy=policy).start()
                  for rank in (0, 1)]
        clients = [MemStoreClient(supervisor.server.address)
                   for supervisor in supervisors]
        checkpointer = Checkpointer(tmp_path, async_save=False)
        try:
            module = gpt2_tiny(layers=2, dim=32, heads=2, max_seq=32)
            optimizer = AdamW(lr=1e-3)
            place = TensorParallel(module.partition_rules(), fsdp=True,
                                   fsdp_min_size=16)
            tokens = jnp.asarray(
                np.random.default_rng(1).integers(0, 256, (4, 16)), jnp.int32)
            state = place.place(init_state(module, optimizer, tokens[:1]),
                                mesh4)
            step = build_train_step(flax_apply(module), NextTokenLoss(),
                                    optimizer)
            placed = jax.device_put(tokens, batch_sharding(mesh4))
            die_at = 2
            # ranks 2 and 3 ARE a buddy pair: one wave takes both copies
            wave = PreemptionWave(step=die_at, kills=(transports[2].kill,
                                                      transports[3].kill))
            while int(state.step) < die_at:
                state, _ = step(state, placed, placed)
                at = int(state.step)
                checkpointer.save(identity, at, state, extras={'step': at})
                for rank, blob in enumerate(split_pieces(state, mesh4, 4)):
                    clients[rank].push(identity, at, blob,
                                       extras={'step': at})
                wave(at)
            assert wave.fired

            # the survivors agree the shrink — one epoch for the pair loss
            assert wait_until(lambda: all(coord.decisions
                                          for coord in coords))
            for coord in coords:
                assert coord.decisions == [
                    ResizeDecision(epoch=1, members=(0, 1))]

            # hot reshard CANNOT cover ranks 2/3's shards: typed fallback
            mesh2 = spec.resized(2).build(devices[:2])
            blank = place.place(init_state(module, optimizer, tokens[:1]),
                                mesh2)
            with caplog.at_level(logging.WARNING, 'tpusystem.elastic'):
                pieces = collect_pieces(
                    identity, rank=0, members=range(4), survivors=(0, 1),
                    store=supervisors[0].store, transport=transports[0],
                    buddy_of=lambda member: member ^ 1)
                assert len(pieces) == 2          # only the survivors' own
                restored, at, extras, source = elastic_resume(
                    checkpointer, identity, blank, pieces)
            assert 'no surviving buddy' in caplog.text
            assert 'restore from disk' in caplog.text
            assert source == 'disk' and at == die_at

            # loss-equivalence: the fallen-back state IS the disk restore
            # of the last committed step, and continues identically
            disk = checkpointer.restore(identity, blank, epoch=die_at)
            for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(disk)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            placed2 = jax.device_put(tokens, batch_sharding(mesh2))
            resumed, (_, loss_resumed) = step(restored, placed2, placed2)
            reference, (_, loss_reference) = step(disk, placed2, placed2)
            assert np.isfinite(float(loss_resumed))
            assert float(loss_resumed) == float(loss_reference)
        finally:
            for client in clients:
                client.close()
            for coord in coords:
                coord.close()
            for supervisor in supervisors:
                supervisor.close()
            checkpointer.close()
            for transport in transports:
                transport.close()
            hub.close()


class TestBarrierTimeout:
    """Satellite: a peer dead/hung between sync points must surface as a
    typed CollectiveTimeout instead of hanging the barrier forever."""

    def test_barrier_timeout_raises_typed(self):
        from tpusystem.parallel.multihost import CollectiveTimeout
        hub = Hub(2)
        transports = [TcpTransport(hub.address, rank, 2) for rank in range(2)]
        assert wait_until(lambda: len(hub._clients) == 2)
        try:
            start = time.monotonic()
            # rank 1 never contributes: it is alive (heartbeats would keep
            # it in the quota) but stuck between sync points
            with pytest.raises(CollectiveTimeout, match='timed out'):
                transports[0].barrier(timeout=1.0)
            assert time.monotonic() - start < 5
            assert isinstance(CollectiveTimeout('x'), ControlPlaneFailover)
            # the late straggler completes the op on the hub; its result
            # fanout must NOT leak a fresh never-read box into the timed-out
            # rank's _results (regression: setdefault in the recv loop)
            transports[1].barrier(timeout=5.0)
            assert wait_until(lambda: not transports[0]._results)
        finally:
            for transport in transports:
                transport.close()
            hub.close()

    def test_runtime_barrier_forwards_timeout(self):
        from tpusystem.parallel.multihost import CollectiveTimeout
        hub = Hub(2)
        transports = [TcpTransport(hub.address, rank, 2) for rank in range(2)]
        assert wait_until(lambda: len(hub._clients) == 2)
        runtime = Runtime()                      # Loopback: timeout is a no-op
        runtime.barrier(timeout=0.1)
        runtime.transport = transports[0]        # the pod-shaped wiring
        try:
            with pytest.raises(CollectiveTimeout):
                runtime.barrier(timeout=1.0)
        finally:
            for transport in transports:
                transport.close()
            hub.close()


# ---------------------------------------------------------------------------
# cross-process chaos: the real thing, over real processes

CHAOS_WORKER = r'''
import json, os, sys, time
rank, nprocs = int(sys.argv[1]), int(sys.argv[2])
coordinator, out_path = sys.argv[3], sys.argv[4]
ckpt_root, die_at, total_steps = sys.argv[5], int(sys.argv[6]), int(sys.argv[7])

os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax
jax.config.update('jax_platforms', 'cpu')
import jax.numpy as jnp
import numpy as np

from tpusystem.checkpoint import Checkpointer
from tpusystem.data import ArrayDataset, Loader
from tpusystem.models import gpt2_tiny
from tpusystem.parallel import MeshSpec, batch_sharding, replicated
from tpusystem.parallel.chaos import DieAtStep
from tpusystem.parallel.recovery import (WorkerLostError, exit_for_restart,
                                         recovery_consumer)
from tpusystem.registry import gethash
from tpusystem.runtime import Runtime
from tpusystem.train import (NextTokenLoss, SGD, build_train_step, flax_apply,
                             init_state, resume_extras)

victim = nprocs - 1               # never rank 0: the hub must survive
record = {'rank': rank, 'losses': {}}
runtime = Runtime(coordinator=coordinator, num_processes=nprocs,
                  process_id=rank, heartbeat=1.0)
runtime.producer.register(recovery_consumer())
mesh = MeshSpec(data=-1).build()
module = gpt2_tiny(attention='xla', dtype='float32')
identity = gethash(module)
optimizer = SGD(lr=0.1)
tokens = np.random.default_rng(0).integers(0, 256, (8 * nprocs, 32)).astype(np.int32)
loader = Loader(ArrayDataset(tokens), batch_size=2 * nprocs, shuffle=True,
                seed=5)           # 4 batches per epoch
state = init_state(module, optimizer, jnp.asarray(tokens[:1]))
state = jax.tree.map(
    lambda leaf: jax.make_array_from_process_local_data(
        replicated(mesh), np.asarray(leaf)), state)
ckpt = Checkpointer(ckpt_root, async_save=False)
sharding = batch_sharding(mesh)
step_fn = build_train_step(flax_apply(module), NextTokenLoss(), optimizer)

record['start_step'] = ckpt.latest(identity) or 0
record['fenced_at_start'] = ckpt.fenced(identity)
if record['start_step']:
    state, _, extras = ckpt.resume(identity, state)
    loader.seek(extras['cursor'])

die = DieAtStep(step=die_at, action='exit') if rank == victim else None

def place(batch):
    host = np.asarray(jax.device_get(batch))
    per = host.shape[0] // nprocs
    return jax.make_array_from_process_local_data(
        sharding, host[rank * per:(rank + 1) * per])

try:
    done = False
    while not done:
        for (batch,) in loader:
            placed = place(batch)
            state, (_, loss) = step_fn(state, placed, placed)
            at = int(state.step)
            record['losses'][str(at)] = float(loss)
            ckpt.save(identity, at, state, extras=resume_extras(state, loader))
            if at >= total_steps:
                done = True
                break
            if die_at and at == die_at:
                # rendezvous: step k is committed on EVERY rank before the
                # death, so no collective save races a dead peer
                runtime.barrier()
                if die is not None:
                    die(at)                  # os._exit(1): no bye, no atexit
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    runtime.sync()           # WorkerLostError raises here
                    time.sleep(0.05)
                raise SystemExit('worker loss never surfaced at the drain')
except WorkerLostError as loss_error:
    ckpt.wait()
    record['fenced'] = ckpt.fence(identity)  # keep the last good checkpoint
    record['lost_rank'] = loss_error.rank
    with open(out_path, 'w') as handle:
        json.dump(record, handle)
        handle.flush()
        os.fsync(handle.fileno())
    if rank == 0:
        time.sleep(1)        # hub: let the lost fanout reach every survivor
    os._exit(exit_for_restart(loss_error).code)

record['fenced'] = ckpt.fence(identity)
ckpt.close()
runtime.barrier()
record['end_step'] = int(state.step)
with open(out_path, 'w') as handle:
    json.dump(record, handle)
runtime.close()
'''


@pytest.mark.slow
def test_multiprocess_kill_at_step_restart_resumes_bitwise(tmp_path):
    """The full acceptance drill over REAL processes: a 2-host DP job is
    killed at step 3 (rank 1 dies abruptly, mid-epoch), the survivor
    fences and exits with the restartable code, the relaunched job resumes
    at the checkpoint step and its losses from step 4 on are
    bitwise-identical to an uninterrupted reference run."""
    from tests.test_multiprocess import _launch_workers
    nprocs, die_at, total = 2, 3, 6

    def launch(run, root, die):
        run_dir = tmp_path / run
        run_dir.mkdir()
        procs, outputs = _launch_workers(run_dir, CHAOS_WORKER, nprocs,
                                         timeout=420,
                                         extra_args=(root, die, total))
        return procs, outputs, run_dir

    # uninterrupted reference trajectory
    procs, outputs, run_dir = launch('ref', tmp_path / 'ref-ckpt', 0)
    if any('Multiprocess computations aren\'t implemented' in output
           for output in outputs):
        # same jaxlib gap that fails tests/test_multiprocess.py's training
        # workers on this host: the CPU backend cannot execute
        # cross-process computations at all
        pytest.skip('this jaxlib cannot run multiprocess computations '
                    'on the CPU backend')
    for proc, output in zip(procs, outputs):
        assert proc.returncode == 0, f'reference worker failed:\n{output[-3000:]}'
    reference = json.loads((run_dir / 'out0.json').read_text())
    assert sorted(map(int, reference['losses'])) == list(range(1, total + 1))

    # phase 1: the kill — victim dies at step 3, survivor fences and exits
    # with the restart contract's code
    root = tmp_path / 'ckpt'
    procs, outputs, run_dir = launch('run1', root, die_at)
    assert procs[1].returncode == 1              # the scripted death
    assert procs[0].returncode == LOST_WORKER_EXIT, outputs[0][-3000:]
    survivor = json.loads((run_dir / 'out0.json').read_text())
    assert survivor['lost_rank'] == 1
    assert survivor['fenced'] == die_at
    assert sorted(map(int, survivor['losses'])) == list(range(1, die_at + 1))

    # phase 2: the scheduler restarts the job — step-granular resume
    procs, outputs, run_dir = launch('run2', root, 0)
    for proc, output in zip(procs, outputs):
        assert proc.returncode == 0, f'resumed worker failed:\n{output[-3000:]}'
    resumed = json.loads((run_dir / 'out0.json').read_text())
    assert resumed['start_step'] == die_at
    assert resumed['fenced_at_start'] == die_at
    assert resumed['end_step'] == total
    assert sorted(map(int, resumed['losses'])) == list(range(die_at + 1,
                                                             total + 1))
    # bitwise-identical continuation: pre-kill steps match, post-resume
    # steps match the uninterrupted run exactly
    for at in range(1, die_at + 1):
        assert survivor['losses'][str(at)] == reference['losses'][str(at)]
    for at in range(die_at + 1, total + 1):
        assert resumed['losses'][str(at)] == reference['losses'][str(at)]
