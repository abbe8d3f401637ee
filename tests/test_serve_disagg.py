"""Disaggregated serving: TP-sharded engine steps, KV handoff over the
blob plane, role-aware fleet routing (tpusystem/serve/{engine,disagg,
scheduler,fleet}.py + parallel/schedule.decode_tp_plan).

Three layers of drill:

* **Sharded steps** — an engine built with ``mesh=MeshSpec(model=N)``
  GSPMD-shards its compiled prefill/decode programs and the paged pool
  over the virtual CPU mesh; decode is TOKEN-EXACT vs a single-device
  engine for BOTH served families (GPT-2 and Llama), with the
  ``trace_count`` witness proving the sharded step still compiles once.
* **KV handoff** — ``export_prefill`` on engine A seats token-exact on
  engine B through ``admit_prefilled`` (the ``adopt_prefill``/
  ``write_tables`` seam); the wire payload is digest-verified end to
  end (``pack_handoff``/``unpack_handoff``/:class:`KVStripStore`).
* **Role-aware fleet** — a prefill-role replica admits prompts, the
  router pumps finished strips to decode-role replicas (blob plane when
  both ends carry a transport), and the chaos drills kill each role
  mid-flight: every completion stays token-exact vs an uninterrupted
  colocated fleet, journal and trace surviving the role hop.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_serve_fleet import FakeClock, witness
from tpusystem.models import gpt2_tiny, llama_tiny
from tpusystem.observe import Tracer
from tpusystem.observe.trace import connected_traces
from tpusystem.parallel import MeshSpec, decode_tp_plan
from tpusystem.parallel.chaos import PreemptionWave
from tpusystem.parallel.multihost import Loopback
from tpusystem.checkpoint.memstore import MemStore
from tpusystem.serve import (Engine, HandoffCorrupt, KVHandoff, KVStripStore,
                             PagedKVCache, ReplicaHandle, Request, RoleMismatch,
                             Router, SamplingParams, Scheduler, ServingReplica,
                             engine_unsupported_reason, fetch_handoff,
                             kv_namespace, pack_handoff, pool_shardings,
                             unpack_handoff)
from tpusystem.services.prodcon import Producer
from tpusystem.train.decode_fused import fused_paged_reason


def submesh(count=2, **axes):
    """A live mesh over the first ``count`` virtual devices — the
    engine takes a built Mesh as readily as a MeshSpec, and tier-1's
    8-device harness rarely wants all of them on one axis."""
    return MeshSpec(**axes).build(jax.devices()[:count])


@pytest.fixture(scope='module')
def gpt2():
    module = gpt2_tiny(dtype='float32')
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (1, 8)), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    return module, params


@pytest.fixture(scope='module')
def llama():
    module = llama_tiny(dtype='float32')
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, 256, (1, 8)), jnp.int32)
    params = module.init(jax.random.PRNGKey(1), prompt)['params']
    return module, params


def drain(engine, steps=64):
    """Step until every row retires; returns id -> emitted tokens."""
    tokens: dict = {}
    for _ in range(steps):
        report = engine.step()
        for tag, new in report.emitted.items():
            tokens.setdefault(tag, []).extend(int(t) for t in new)
        if not engine.active_rows:
            break
    return tokens


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------


class TestDecodeTpPlan:

    def test_no_mesh_is_single(self):
        plan = decode_tp_plan(None)
        assert (plan.path, plan.model) == ('single', 1)

    def test_model_axis_of_one_is_single(self):
        plan = decode_tp_plan(submesh(1, model=1))
        assert plan.path == 'single'

    def test_model_axis_shards_gspmd(self):
        plan = decode_tp_plan(submesh(2, model=2))
        assert (plan.path, plan.model) == ('gspmd', 2)

    def test_nontrivial_data_axis_is_typed_unsupported(self):
        plan = decode_tp_plan(submesh(2, data=2))
        assert plan.path == 'unsupported'
        assert "'model' axis only" in plan.reason

    def test_engine_raises_the_plan_reason(self, gpt2):
        module, params = gpt2
        with pytest.raises(ValueError, match="'model' axis only"):
            Engine(module, params, rows=2, block_size=8,
                   mesh=submesh(2, data=2))


# ---------------------------------------------------------------------------
# TP-sharded engine: token-exact for both served families
# ---------------------------------------------------------------------------


class TestShardedEngine:

    def _exact(self, module, params, *, rows=2, budget=6):
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, 256, (n,)).tolist() for n in (5, 9)]

        single = Engine(module, params, rows=rows, block_size=8)
        sharded = Engine(module, params, rows=rows, block_size=8,
                         mesh=submesh(2, model=2))
        assert sharded.tp_plan.path == 'gspmd'
        assert sharded.decode_impl == 'flax'
        for engine in (single, sharded):
            for index, prompt in enumerate(prompts):
                engine.admit(prompt, budget, tag=f'r{index}')
        reference, tokens = drain(single), drain(sharded)
        assert tokens == reference
        # the compile-once witness survives sharding: ONE decode trace
        # on each engine, however many steps the drain took
        assert single.trace_count == 1
        assert sharded.trace_count == 1

    def test_gpt2_tp_decode_token_exact(self, gpt2):
        self._exact(*gpt2)

    def test_llama_tp_decode_token_exact(self, llama):
        self._exact(*llama)

    def test_pool_shardings_split_heads_replicate_tables(self, gpt2):
        module, params = gpt2
        engine = Engine(module, params, rows=2, block_size=8)
        mesh = submesh(2, model=2)
        specs = pool_shardings(engine._cache, mesh, module.heads)
        leaves = jax.tree_util.tree_leaves_with_path(specs)
        kv = [s for path, s in leaves
              if path[-1] in (jax.tree_util.DictKey('key'),
                              jax.tree_util.DictKey('value'))]
        rest = [s for path, s in leaves
                if path[-1] not in (jax.tree_util.DictKey('key'),
                                    jax.tree_util.DictKey('value'))]
        assert kv and all('model' in str(s.spec) for s in kv)
        assert rest and all(s.spec == jax.sharding.PartitionSpec()
                            for s in rest)

    def test_speculative_rows_refuse_the_mesh(self, gpt2):
        module, params = gpt2
        with pytest.raises(ValueError, match='speculative rows'):
            Engine(module, params, rows=2, block_size=8,
                   mesh=submesh(2, model=2), draft_module=module,
                   draft_params=params, speculate=2)


# ---------------------------------------------------------------------------
# satellite: the capability-gate reason matrix (docs/serving.md)
# ---------------------------------------------------------------------------


class TestReasonMatrix:
    """Every gate's reason string must match what docs/serving.md
    documents — the matrix rows below are the documented phrases, so a
    reworded gate fails here until the docs move with it."""

    def test_engine_serves_both_families_and_moe(self, gpt2, llama):
        assert engine_unsupported_reason(gpt2[0]) is None
        assert engine_unsupported_reason(llama[0]) is None
        assert engine_unsupported_reason(
            gpt2_tiny(moe_experts=4, dtype='float32')) is None

    def test_engine_gate_names_the_family_conventions(self):
        from tpusystem.models import MLP
        reason = engine_unsupported_reason(MLP(features=(8, 8)))
        assert 'family decode conventions' in reason
        reason = engine_unsupported_reason(gpt2_tiny(scan_layers=True))
        assert 'unrolled' in reason

    def test_fused_paged_gate_under_tp_names_the_fallback(self, gpt2):
        import dataclasses
        decoder = dataclasses.replace(gpt2[0], mesh=submesh(2, model=2))
        reason = fused_paged_reason(decoder)
        assert 'no ring arms' in reason
        assert 'sharded flax' in reason and 'token-exact' in reason
        # and an auto engine under the mesh actually takes that fallback
        engine = Engine(gpt2[0], gpt2[1], rows=2, block_size=8,
                        mesh=submesh(2, model=2), decode_impl='auto')
        assert engine.decode_impl == 'flax'

    def test_fused_paged_gate_matrix(self, gpt2, llama):
        paged = gpt2_tiny(decode_pages=(16, 8))      # dense GPT-2 runs
        assert fused_paged_reason(paged) is None
        assert 'GPT2 family only' in fused_paged_reason(llama[0])
        moe = fused_paged_reason(gpt2_tiny(moe_experts=4))
        assert 'flax paged step serves MoE' in moe
        assert 'full-capacity' in moe
        assert 'leading layer dim' in fused_paged_reason(
            gpt2_tiny(scan_layers=True))

    def test_tp_mesh_rejection_reason_is_the_planner_text(self, gpt2):
        with pytest.raises(ValueError) as err:
            Engine(gpt2[0], gpt2[1], rows=2, block_size=8,
                   mesh=submesh(2, data=2))
        assert decode_tp_plan(
            submesh(2, data=2)).reason in str(err.value)


# ---------------------------------------------------------------------------
# the handoff payload + wire
# ---------------------------------------------------------------------------


class TestHandoffWire:

    def _handoff(self):
        return KVHandoff(request=Request('a', [1, 2, 3], 4), first=7,
                         kv={'k': np.arange(6, dtype=np.float32)},
                         prefix=[9], waited=1.5)

    def test_pack_unpack_roundtrip(self):
        received = unpack_handoff(pack_handoff(self._handoff()))
        assert received.request.id == 'a'
        assert (received.first, received.prefix,
                received.waited) == (7, [9], 1.5)
        np.testing.assert_array_equal(received.kv['k'], np.arange(6))

    def test_corrupt_payload_is_typed(self):
        data = bytearray(pack_handoff(self._handoff()))
        data[-1] ^= 0xFF
        with pytest.raises(HandoffCorrupt, match='digest'):
            unpack_handoff(bytes(data))
        with pytest.raises(HandoffCorrupt):
            unpack_handoff(data[: len(data) // 2])

    def test_wrong_object_is_typed(self):
        import pickle

        from tpusystem.parallel.multihost import _blob_digest
        payload = pickle.dumps({'not': 'a handoff'})
        framed = _blob_digest(payload).encode('ascii') + b':' + payload
        with pytest.raises(HandoffCorrupt, match='not KVHandoff'):
            unpack_handoff(framed)

    def test_strip_store_offers_answers_releases(self):
        wire = Loopback()
        store = KVStripStore()
        store.attach(wire)
        store.offer('a', b'payload')
        assert wire.fetch_blob(0, kv_namespace('a')) == b'payload'
        assert len(store) == 1
        store.release('a')
        assert len(store) == 0

    def test_strip_store_chains_the_prior_hook(self):
        wire = Loopback()
        wire.on_blob_request = lambda key: b'prior' if key == 'x' else None
        store = KVStripStore()
        store.attach(wire)
        store.offer('a', b'strip')
        assert wire.on_blob_request(kv_namespace('a')) == b'strip'
        assert wire.on_blob_request('x') == b'prior'  # falls through

    def test_fetch_handoff_verifies_end_to_end(self):
        wire = Loopback()
        store = KVStripStore()
        store.attach(wire)
        store.offer('a', pack_handoff(self._handoff()))
        received = fetch_handoff(wire, 0, 'a')
        assert received.request.id == 'a'
        corrupt = bytearray(pack_handoff(self._handoff()))
        corrupt[-1] ^= 0xFF
        store.offer('b', bytes(corrupt))
        with pytest.raises(HandoffCorrupt):
            fetch_handoff(wire, 0, 'b')


# ---------------------------------------------------------------------------
# export_prefill -> admit_prefilled: the engine seam
# ---------------------------------------------------------------------------


class TestExportAdmit:

    def test_prefill_on_a_decodes_on_b_token_exact(self, gpt2):
        module, params = gpt2
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 256, (n,)).tolist() for n in (5, 9)]

        colocated = Engine(module, params, rows=2, block_size=8)
        for index, prompt in enumerate(prompts):
            colocated.admit(prompt, 6, tag=f'r{index}')
        reference = drain(colocated)

        prefiller = Engine(module, params, rows=2, block_size=8)
        decoder = Engine(module, params, rows=2, block_size=8)
        for index, prompt in enumerate(prompts):
            first, kv = prefiller.export_prefill(prompt)
            # the strips cross a (simulated) wire digest-verified
            received = unpack_handoff(pack_handoff(KVHandoff(
                request=Request(f'r{index}', prompt, 6), first=first,
                kv=kv)))
            decoder.admit_prefilled(prompt, 6, received.first, received.kv,
                                    tag=f'r{index}')
        # export seats nothing on the prefill engine
        assert prefiller.active_rows == 0 and prefiller.pool.live_blocks == 0
        assert drain(decoder) == reference

    def test_export_validates_the_prompt(self, gpt2):
        module, params = gpt2
        engine = Engine(module, params, rows=2, block_size=8)
        with pytest.raises(ValueError, match='empty'):
            engine.export_prefill([])
        with pytest.raises(ValueError, match='decode room'):
            engine.export_prefill(list(range(module.max_seq)))

    def test_geometry_mismatch_is_caught_before_seating(self, gpt2):
        module, params = gpt2
        engine = Engine(module, params, rows=2, block_size=8)
        first, kv = engine.export_prefill([1, 2, 3])
        missing = dict(kv)
        missing.pop(sorted(missing)[0])
        with pytest.raises(ValueError, match='missing KV leaf'):
            engine.admit_prefilled([1, 2, 3], 4, first, missing)
        short = {name: strip[:, :-8] for name, strip in kv.items()}
        with pytest.raises(ValueError, match='same module geometry'):
            engine.admit_prefilled([1, 2, 3], 4, first, short)
        assert engine.active_rows == 0     # nothing half-seated

    def test_adopted_strips_share_prefix_blocks(self, gpt2):
        """Strip adoptions run through the radix index exactly like
        local admissions: the second adopted request with the same head
        scores a prefix hit and shares blocks."""
        module, params = gpt2
        source = Engine(module, params, rows=2, block_size=8)
        engine = Engine(module, params, rows=4, block_size=8,
                        share_prefix=True)
        head = list(range(1, 17))
        for index, tail in enumerate(([21, 22], [23, 24])):
            first, kv = source.export_prefill(head + tail)
            engine.admit_prefilled(head + tail, 4, first, kv,
                                   tag=f'r{index}')
        assert engine.sharing['prefix_hits'] >= 1
        assert engine.sharing['shared_tokens'] >= 16
        engine.pool.audit()


# ---------------------------------------------------------------------------
# satellite: pool audit under adopted-strip churn at refcount boundaries
# ---------------------------------------------------------------------------


class TestAuditUnderAdoptChurn:

    def test_audit_across_adopt_free_churn(self, gpt2):
        """Seat/evict adopted strips through the shared radix pool in a
        pattern that walks refcounts through every boundary (0 -> 1 ->
        2 -> 1 -> 0 -> warm -> re-owned), auditing after every
        transition — adoption must leave the pool indistinguishable
        from local admission."""
        module, params = gpt2
        source = Engine(module, params, rows=2, block_size=8)
        engine = Engine(module, params, rows=4, block_size=8,
                        share_prefix=True)
        head = list(range(1, 17))            # two full shared blocks

        def seat(tag, tail):
            first, kv = source.export_prefill(head + tail)
            return engine.admit_prefilled(head + tail, 3, first, kv,
                                          tag=tag)
        a = seat('a', [31, 32])              # refs 0 -> 1
        engine.pool.audit()
        b = seat('b', [33, 34])              # refs 1 -> 2 (shared head)
        engine.pool.audit()
        engine.evict(a.row)            # refs 2 -> 1: b still owns
        audit = engine.pool.audit()
        assert audit['live'] > 0
        c = seat('c', [35, 36])              # re-share while b holds
        engine.pool.audit()
        engine.evict(b.row)
        engine.evict(c.row)            # refs -> 0: head goes WARM
        audit = engine.pool.audit()
        assert audit['cached'] > 0, 'shared head should park warm'
        d = seat('d', [37, 38])              # warm -> re-owned
        engine.pool.audit()
        assert engine.sharing['prefix_hits'] >= 3
        engine.evict(d.row)
        final = engine.pool.audit()
        assert final['live'] == 0

    def test_audit_interleaved_local_and_adopted(self, gpt2):
        """Local admissions and adopted strips interleave over ONE pool
        (the colocated 'both' role under partial disaggregation) —
        audit holds at every step and eviction order doesn't matter."""
        module, params = gpt2
        source = Engine(module, params, rows=2, block_size=8)
        engine = Engine(module, params, rows=4, block_size=8,
                        share_prefix=True)
        head = list(range(40, 56))
        local = engine.admit(head + [1], 3, tag='local')
        engine.pool.audit()
        first, kv = source.export_prefill(head + [2])
        adopted = engine.admit_prefilled(head + [2], 3, first, kv,
                                         tag='adopted')
        engine.pool.audit()
        assert engine.sharing['prefix_hits'] >= 1
        engine.evict(local.row)        # the ORIGINAL owner first
        engine.pool.audit()
        engine.evict(adopted.row)
        assert engine.pool.audit()['live'] == 0


# ---------------------------------------------------------------------------
# the role-aware fleet
# ---------------------------------------------------------------------------


def role_fleet(module, params, clock, roles, *, wire=None, tracer=False,
               producer=None, rows=2, clients=None, **engine_knobs):
    """One replica per role string; a shared Loopback ``wire`` puts the
    handoffs on the blob plane; ``clients`` gives each replica a journal
    store that outlives a kill (the supervisor-RAM analogue). Returns
    (router, handles, tracers)."""
    handles, tracers = [], []
    for index, role in enumerate(roles):
        t = Tracer(f'rep{index}', clock=clock) if tracer else None
        tracers.append(t)

        def build(role=role, t=t):
            return Scheduler(
                Engine(module, params, rows=rows, block_size=8,
                       **engine_knobs),
                clock=clock, tracer=t, prefill_only=(role == 'prefill'))
        replica = ServingReplica(build, identity=f'rep{index}',
                                 clock=clock, role=role,
                                 client=clients[index] if clients else None)
        handles.append(ReplicaHandle(replica, transport=wire, rank=0))
    router_tracer = Tracer('router', clock=clock) if tracer else None
    router = Router(handles, clock=clock, tracer=router_tracer,
                    producer=producer)
    return router, handles, (router_tracer, tracers)


def reference_results(module, params, clock, requests, **engine_knobs):
    def build():
        return Scheduler(Engine(module, params, rows=2, block_size=8,
                                **engine_knobs), clock=clock)
    router = Router([ReplicaHandle(ServingReplica(build, identity='colo',
                                                  clock=clock))],
                    clock=clock)
    for rid, prompt, budget in requests:
        router.submit(Request(rid, list(prompt), budget))
    return router.run_until_idle()


def mixed_requests(seed=7, n=6):
    rng = np.random.default_rng(seed)
    lengths = (5, 9, 7, 4, 11, 6, 8, 5, 10)[:n]
    budgets = (8, 6, 9, 5, 7, 8, 6, 9, 7)[:n]
    return [(f'r{i}', rng.integers(0, 256, (k,)).tolist(), b)
            for i, (k, b) in enumerate(zip(lengths, budgets))]


def sampled_specs(seed=13, n=5):
    """Mixed greedy + seeded-sampled prompts sharing a system-prompt
    head, so ``share_prefix=True`` radix hits ride the drill too."""
    rng = np.random.default_rng(seed)
    head = rng.integers(0, 256, (10,)).tolist()
    specs = []
    for i, k in enumerate((3, 5, 2, 4, 6)[:n]):
        tail = rng.integers(0, 256, (k,)).tolist()
        sampling = (dict(temperature=0.7, seed=300 + i, top_k=16)
                    if i % 2 == 0 else None)
        specs.append((f's{i}', head + tail, 5 + (i % 3), sampling))
    return specs


def sampled_requests(specs):
    """Fresh Request objects from specs (one set per fleet — requests
    must never be shared between the reference and the chaos run)."""
    return [Request(rid, list(prompt), budget,
                    sampling=None if sampling is None
                    else SamplingParams(**sampling))
            for rid, prompt, budget, sampling in specs]


def sampled_reference(module, params, clock, specs):
    def build():
        return Scheduler(Engine(module, params, rows=2, block_size=8,
                                share_prefix=True), clock=clock)
    router = Router([ReplicaHandle(ServingReplica(build, identity='colo',
                                                  clock=clock))],
                    clock=clock)
    for request in sampled_requests(specs):
        router.submit(request)
    return router.run_until_idle()


class TestRoleFleet:

    def test_prefill_only_scheduler_refuses_hot_restores(self, gpt2):
        module, params = gpt2
        clock = FakeClock()
        scheduler = Scheduler(Engine(module, params, rows=2, block_size=8),
                              clock=clock, prefill_only=True)
        with pytest.raises(RoleMismatch):
            scheduler.restore(Request('a', [1, 2], 4), waited=1.0,
                              prefix=[5])
        assert not isinstance(RoleMismatch('x'), ValueError)

    def test_role_and_scheduler_contract_must_agree(self, gpt2):
        module, params = gpt2
        clock = FakeClock()
        with pytest.raises(ValueError, match='must agree'):
            ServingReplica(
                lambda: Scheduler(Engine(module, params, rows=2,
                                         block_size=8), clock=clock),
                identity='bad', clock=clock, role='prefill')

    def test_disagg_fleet_token_exact_over_blob_plane(self, gpt2):
        """The acceptance path: prompts admitted on the prefill replica,
        KV strips shipped over the (digest-verified) blob plane, every
        request decoded on a decode replica — token-exact vs colocated,
        strips released on ack, and the move narrated as
        ``PrefillHandoff`` with real byte weights."""
        from tpusystem.observe.events import PrefillHandoff
        module, params = gpt2
        requests = mixed_requests()
        clock = FakeClock()
        reference = reference_results(module, params, clock, requests)

        wire = Loopback()
        producer = Producer()
        router, handles, _ = role_fleet(
            module, params, clock, ('prefill', 'decode', 'decode'),
            wire=wire, producer=producer)
        seen = witness(producer, PrefillHandoff)
        for rid, prompt, budget in requests:
            assert router.submit(Request(rid, list(prompt), budget)) \
                == 'rep0'            # every prompt lands on the prefill tier
        moved = []
        for _ in range(400):
            if router.idle:
                break
            moved.extend(router.step().handoffs)
        assert router.idle
        assert sorted(moved) == sorted(rid for rid, _, _ in requests)
        assert set(router.results) == set(reference)
        for rid, completion in router.results.items():
            assert completion.tokens == reference[rid].tokens, rid
            assert completion.reason == reference[rid].reason, rid
        # narration carries the wire weight; the outbox store drained
        assert len(seen) == len(requests)
        assert all(event.origin == 'rep0' and event.bytes > 0
                   and event.target in ('rep1', 'rep2') for event in seen)
        assert handles[0].strips is not None and len(handles[0].strips) == 0
        # the prefill engine never seated a decode row
        assert handles[0].scheduler.engine.active_rows == 0

    def test_corrupt_handoff_falls_back_to_cold_prefill(self, gpt2):
        """A payload torn on the wire must NOT seat: the router re-
        places the request cold on the decode tier and the completion
        is still token-exact."""
        module, params = gpt2
        requests = mixed_requests(n=2)
        clock = FakeClock()
        reference = reference_results(module, params, clock, requests)
        wire = Loopback()
        router, handles, _ = role_fleet(
            module, params, clock, ('prefill', 'decode'), wire=wire)
        original = wire.fetch_blob

        def torn(peer, key, timeout=30.0):
            data = bytearray(original(peer, key, timeout))
            data[-1] ^= 0xFF
            return bytes(data)
        wire.fetch_blob = torn
        for rid, prompt, budget in requests:
            router.submit(Request(rid, list(prompt), budget))
        results = router.run_until_idle()
        for rid, _, _ in requests:
            assert results[rid].tokens == reference[rid].tokens, rid

    def test_handoff_parks_until_a_decode_replica_exists(self, gpt2):
        """No healthy decode target: the strip parks in the undelivered
        queue (the fleet is NOT idle) and delivers the moment a decode
        replica is adopted — no silent drop."""
        module, params = gpt2
        clock = FakeClock()
        router, handles, _ = role_fleet(module, params, clock, ('prefill',))
        router.submit(Request('a', [1, 2, 3, 4], 5))
        for _ in range(5):
            router.step()
        assert not router.idle and len(router._undelivered) == 1

        def build():
            return Scheduler(Engine(module, params, rows=2, block_size=8),
                             clock=clock)
        router.adopt(ReplicaHandle(
            ServingReplica(build, identity='late', clock=clock,
                           role='decode')))
        results = router.run_until_idle()
        reference = reference_results(module, params, clock,
                                      [('a', [1, 2, 3, 4], 5)])
        assert results['a'].tokens == reference['a'].tokens

    def test_sharing_counters_and_trace_parentage_survive_the_role_hop(
            self, gpt2, tmp_path):
        """Satellite drill: requests sharing a system prompt hop from
        the prefill replica to a decode replica — the decode-side radix
        pool scores the prefix hits (sharing works through adopted
        strips), and the merged trace export holds ONE connected trace
        per request whose spans cross both replicas (queued/handoff on
        the prefill process, seated/decode on the decode process), zero
        orphans."""
        module, params = gpt2
        rng = np.random.default_rng(19)
        head = rng.integers(0, 256, (12,)).tolist()
        requests = [(f'r{i}', head + rng.integers(0, 256, (k,)).tolist(), 5)
                    for i, k in enumerate((3, 2, 4))]
        clock = FakeClock()
        reference = reference_results(module, params, clock, requests,
                                      share_prefix=True)
        router, handles, (router_tracer, tracers) = role_fleet(
            module, params, clock, ('prefill', 'decode'),
            tracer=True, share_prefix=True)
        for rid, prompt, budget in requests:
            router.submit(Request(rid, list(prompt), budget))
        results = router.run_until_idle()
        for rid, _, _ in requests:
            assert results[rid].tokens == reference[rid].tokens, rid
        decode_engine = handles[1].scheduler.engine
        assert decode_engine.sharing['prefix_hits'] >= 2
        assert decode_engine.sharing['shared_tokens'] > 0

        for tracer in tracers:
            router_tracer.merge(tracer)
        payload = json.loads(
            router_tracer.export(tmp_path / 'disagg.json').read_text())
        by_trace = connected_traces(payload['traceEvents'])    # 0 orphans
        events = [e for e in payload['traceEvents'] if e['ph'] in ('X', 'i')]
        processes = {e['pid']: e['args']['name']
                     for e in payload['traceEvents'] if e['ph'] == 'M'}
        for rid, _, _ in requests:
            roots = [e for e in events if e['name'] == f'request {rid}']
            assert len(roots) == 1, rid              # ONE trace per request
            group = by_trace[roots[0]['args']['trace_id']]
            crossed = {processes[e['pid']] for e in group
                       if processes[e['pid']].startswith('rep')}
            assert crossed == {'rep0', 'rep1'}, (rid, crossed)
            names = {e['name'] for e in group}
            assert 'handoff' in names, (rid, names)


class TestRoleChaosDrill:

    def test_kill_prefill_mid_transfer_token_exact(self, gpt2):
        """SIGKILL the prefill replica while strips are queued/ready to
        ship: journal recovery re-homes its rows onto the second
        prefill replica (cold), nothing is dropped or double-decoded,
        and every completion is token-exact vs an uninterrupted
        colocated fleet."""
        module, params = gpt2
        requests = mixed_requests(n=6)
        clock = FakeClock()
        reference = reference_results(module, params, clock, requests)
        router, handles, _ = role_fleet(
            module, params, clock, ('prefill', 'prefill', 'decode'))
        for rid, prompt, budget in requests:
            router.submit(Request(rid, list(prompt), budget))
        wave = PreemptionWave(step=2, kills=(handles[0].kill,))
        for _ in range(400):
            if router.idle:
                break
            wave(router.ticks + 1)
            router.step()
        assert router.idle and wave.fired and not handles[0].healthy
        assert set(router.results) == set(reference)
        for rid, completion in router.results.items():
            assert completion.tokens == reference[rid].tokens, rid
            assert completion.reason == reference[rid].reason, rid

    def test_kill_decode_mid_stream_token_exact(self, gpt2):
        """SIGKILL a decode replica mid-decode: seated rows re-home HOT
        (prompt + emitted prefix replayed on the surviving decode
        replica — never onto the prefill tier), and every completion is
        token-exact vs uninterrupted."""
        module, params = gpt2
        requests = mixed_requests(n=6)
        clock = FakeClock()
        reference = reference_results(module, params, clock, requests)
        router, handles, _ = role_fleet(
            module, params, clock, ('prefill', 'decode', 'decode'))
        for rid, prompt, budget in requests:
            router.submit(Request(rid, list(prompt), budget))
        victim = handles[1]
        wave = PreemptionWave(step=4, kills=(victim.kill,))
        placements = {}
        for _ in range(400):
            if router.idle:
                break
            wave(router.ticks + 1)
            router.step()
            if not victim.healthy and 'v' not in placements:
                placements['v'] = victim.placements
            if not handles[0].healthy:
                raise AssertionError('prefill replica must survive')
        assert router.idle and wave.fired and not victim.healthy
        assert victim.placements == placements['v']  # never routed again
        assert set(router.results) == set(reference)
        for rid, completion in router.results.items():
            assert completion.tokens == reference[rid].tokens, rid
            assert completion.reason == reference[rid].reason, rid
        # hot rows landed on the decode survivor, not the prefill tier
        assert handles[0].scheduler.engine.active_rows == 0

    def test_kill_prefill_between_export_and_ship_token_exact(self, gpt2):
        """The undrilled window: the prefill replica dies AFTER
        ``export_prefill`` filled its outbox but BEFORE the router
        shipped a single strip. No prefill survivor exists, so every
        row re-prefills cold on the decode tier (the colocated degrade
        — role is placement policy, not capability), token-exact with
        ``share_prefix=True`` + seeded sampling in the pot."""
        from tpusystem.observe.events import RequestRerouted
        module, params = gpt2
        specs = sampled_specs()
        clock = FakeClock()
        reference = sampled_reference(module, params, clock, specs)
        producer = Producer()
        reroutes = witness(producer, RequestRerouted)
        router, handles, _ = role_fleet(
            module, params, clock, ('prefill', 'decode', 'decode'),
            producer=producer, share_prefix=True,
            clients=[MemStore() for _ in range(3)])
        for request in sampled_requests(specs):
            assert router.submit(request) == 'rep0'
        # drive the prefill replica's own loop WITHOUT the router pump:
        # strips are exported into the outbox but never shipped
        for _ in range(8):
            if handles[0].replica.scheduler.outbox:
                break
            handles[0].replica.step()
        exported = [handoff.request.id
                    for handoff in handles[0].replica.scheduler.outbox]
        assert exported, 'prefill never exported a strip'
        handles[0].kill()
        results = router.run_until_idle()
        assert not handles[0].healthy
        assert set(results) == {rid for rid, _, _, _ in specs}
        for rid, completion in results.items():
            assert completion.tokens == reference[rid].tokens, rid
            assert completion.reason == reference[rid].reason, rid
        # every row (exported-but-unshipped AND still-queued) re-homed
        # cold onto a decode replica — never silently dropped
        moved = {event.id: event for event in reroutes}
        assert set(moved) == set(results)
        assert all(event.where == 'cold'
                   and event.target in ('rep1', 'rep2')
                   for event in moved.values())

    def test_kill_decode_holding_seated_handoffs_token_exact(self, gpt2):
        """The other undrilled window: a decode replica dies HOLDING
        rows it seated from shipped KV strips. Its journal (in the
        supervisor-RAM store the kill leaves behind) replays the rows
        HOT onto the decode survivor — emitted prefixes re-prefilled,
        never routed to the prefill tier — token-exact with
        ``share_prefix=True`` + seeded sampling."""
        from tpusystem.observe.events import RequestRerouted
        module, params = gpt2
        specs = sampled_specs(seed=17)
        clock = FakeClock()
        reference = sampled_reference(module, params, clock, specs)
        producer = Producer()
        reroutes = witness(producer, RequestRerouted)
        router, handles, _ = role_fleet(
            module, params, clock, ('prefill', 'decode', 'decode'),
            producer=producer, share_prefix=True,
            clients=[MemStore() for _ in range(3)])
        for request in sampled_requests(specs):
            router.submit(request)
        victim, shipped = None, []
        for _ in range(400):
            if router.idle:
                break
            shipped.extend(router.step().handoffs)
            if victim is None and shipped:
                seated = [handle for handle in handles[1:]
                          if handle.healthy
                          and handle.scheduler.engine.active_rows > 0]
                if seated:           # a decode replica holds seated rows
                    victim = seated[0]
                    victim.kill()
        assert router.idle and victim is not None and not victim.healthy
        assert handles[0].healthy, 'prefill replica must survive'
        assert set(router.results) == {rid for rid, _, _, _ in specs}
        for rid, completion in router.results.items():
            assert completion.tokens == reference[rid].tokens, rid
            assert completion.reason == reference[rid].reason, rid
        # the journal replayed the seated rows hot, onto the survivor
        # (still-queued cold rows may re-enter via the prefill front
        # door instead — that is the role-aware cold rung, not a leak)
        from_victim = [event for event in reroutes
                       if event.origin == victim.name]
        assert from_victim, 'the dead decode replica held no rows?'
        survivor = ({'rep1', 'rep2'} - {victim.name}).pop()
        hot = [event for event in from_victim if event.where == 'hot']
        assert hot, 'no seated row re-homed hot'
        assert all(event.target == survivor for event in hot)
        # the prefill engine never seated a decode row
        assert handles[0].scheduler.engine.active_rows == 0


class TestRoleAutoscale:

    def _provisioned(self, module, params, clock):
        built = []

        def provision(role='decode'):
            index = len(built)

            def build(role=role):
                return Scheduler(
                    Engine(module, params, rows=2, block_size=8),
                    clock=clock, prefill_only=(role == 'prefill'))
            replica = ServingReplica(build, identity=f'grown{index}',
                                     clock=clock, role=role)
            built.append(role)
            return ReplicaHandle(replica)
        return built, provision

    def test_breathe_grows_the_decode_tier_for_parked_handoffs(self, gpt2):
        """Undelivered handoffs are decode-tier pressure: the autoscaler
        provisions a DECODE replica (rebalancing the prefill:decode
        ratio) and the parked strip seats on it."""
        from tpusystem.serve import AutoscalePolicy
        module, params = gpt2
        clock = FakeClock()
        built = []
        router, handles, _ = role_fleet(module, params, clock, ('prefill',))
        built, provision = self._provisioned(module, params, clock)
        router.autoscale = AutoscalePolicy(min_replicas=1, max_replicas=3,
                                           grow_after=1, shrink_after=10_000,
                                           cooldown=0)
        router._provision = provision
        router.submit(Request('a', [1, 2, 3, 4], 5))
        results = router.run_until_idle()
        assert built and built[0] == 'decode'
        reference = reference_results(module, params, clock,
                                      [('a', [1, 2, 3, 4], 5)])
        assert results['a'].tokens == reference['a'].tokens

    def test_shrink_never_empties_a_tier(self, gpt2):
        """An idle split fleet shrinks, but never below one replica per
        tier — a fleet with prompts and no prefill tier (or strips and
        no decode tier) deadlocks until the next grow."""
        from tpusystem.serve import AutoscalePolicy
        module, params = gpt2
        clock = FakeClock()
        router, handles, _ = role_fleet(
            module, params, clock, ('prefill', 'decode'))
        router.autoscale = AutoscalePolicy(min_replicas=1, max_replicas=4,
                                           grow_after=10_000, shrink_after=1,
                                           cooldown=0)
        router._provision = lambda: None
        for _ in range(20):
            router.step()
        assert {handle.role for handle in router.healthy} \
            == {'prefill', 'decode'}
