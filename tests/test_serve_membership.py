"""A membership change is one compiled program
(tpusystem/serve/engine.py ``_build_membership``).

Seating a request and clearing its rows rewrite the engine's per-row
device arrays in one jitted call each. The contract under drill: the
arrays hold, element for element, what one eager write per element would
have left (the expectation is kept on the host, by the rules the eager
writes followed); each program traces once per engine whatever is
admitted — greedy, sampled, grammar-masked, resumed through ``emitted=``,
finished at admission, cancelled — under speculative ``tree_fanout``
groups, under a TP mesh (where the arrays also stay replicated so the
decode step is not retraced) and over a latent pool with one KV leaf a
layer alike; and an unsampled admission brings no
``[vocab]`` operand of its own.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from tpusystem.models import deepseek_tiny, gpt2_tiny
from tpusystem.observe import Tracer
from tpusystem.parallel import MeshSpec
from tpusystem.serve import Engine, SamplingParams
from tpusystem.serve import engine as engine_module

ARRAYS = ('_tokens_dev', '_active_dev', '_seed_dev', '_pos_dev',
          '_temp_dev', '_topk_dev', '_topp_dev', '_mask_dev')
KINDS = ('plain', 'speculative', 'sharded', 'latent')
VOCAB = 256


@pytest.fixture(scope='module')
def served():
    module = gpt2_tiny(dtype='float32')
    assert module.vocab_size == VOCAB
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))['params']
    return module, params


def build(kind, served) -> Engine:
    module, params = served
    if kind == 'latent':     # a pool whose row is one latent, one KV leaf
        module = deepseek_tiny(held=(4, 8))
        assert module.vocab_size == VOCAB
        params = module.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))['params']
        return Engine(module, params, rows=4, block_size=8)
    if kind == 'speculative':
        return Engine(module, params, rows=4, block_size=8,
                      draft_module=module, draft_params=params,
                      speculate=2, tree_fanout=2)
    if kind == 'sharded':
        return Engine(module, params, rows=4, block_size=8,
                      mesh=MeshSpec(model=2).build(jax.devices()[:2]))
    return Engine(module, params, rows=4, block_size=8)


def parity_mask(emitted):
    """A grammar that moves with the stream: position ``p`` allows the
    tokens of parity ``p % 2``."""
    return np.arange(VOCAB) % 2 == len(emitted) % 2


def requests(masks: bool) -> list:
    """24 admissions of six kinds in turn: greedy, sampled, greedy under a
    grammar, sampled under one, resumed with an emitted prefix, finished
    by its first token. Where the engine refuses a grammar
    (speculative rows) those two kinds come unmasked."""
    rng = np.random.default_rng(31)
    grammar = parity_mask if masks else None
    out = []
    for index in range(24):
        prompt = [int(t) for t in rng.integers(0, VOCAB, 3 + index % 9)]
        kind = index % 6
        emitted, max_new = (), 2 + index % 4
        if kind == 0:
            sampling = None
        elif kind == 1:
            sampling = SamplingParams(seed=2**32 - 1 - index,
                                      temperature=0.7 + 0.01 * index,
                                      top_k=index, top_p=0.9)
        elif kind == 2:
            sampling = SamplingParams(mask_fn=grammar)
        elif kind == 3:
            sampling = SamplingParams(seed=index, temperature=1.1,
                                      top_p=0.5, mask_fn=grammar)
        elif kind == 4:
            emitted = tuple(int(t) for t in rng.integers(0, VOCAB, 3))
            prompt, sampling = prompt + list(emitted), SamplingParams(
                seed=7 * index, temperature=0.9, top_k=12)
        else:
            sampling, max_new = None, 1
        out.append((prompt, max_new, sampling, emitted))
    return out


class PerElement:
    """The per-row arrays as one eager write per element leaves them,
    kept on the host."""

    def __init__(self, engine) -> None:
        self.arrays = snapshot(engine)
        self.seated: dict = {}        # representative row -> its request

    def seat(self, rows, first, sampling, emitted) -> None:
        for row in rows:
            self.arrays['_tokens_dev'][row] = first
            self.arrays['_active_dev'][row] = True
            self.arrays['_seed_dev'][row] = np.uint32(
                0 if sampling is None or sampling.seed is None
                else sampling.seed)
            self.arrays['_pos_dev'][row] = len(emitted) + 1
            self.arrays['_temp_dev'][row] = np.float32(
                0.0 if sampling is None else sampling.temperature)
            self.arrays['_topk_dev'][row] = (
                0 if sampling is None else sampling.top_k)
            self.arrays['_topp_dev'][row] = np.float32(
                1.0 if sampling is None else sampling.top_p)
        self.seated[rows[0]] = dict(rows=rows, sampling=sampling,
                                    stream=list(emitted) + [first])

    def regrammar(self, rep) -> None:
        held = self.seated[rep]
        if held['sampling'] is not None and held['sampling'].mask_fn:
            for row in held['rows']:
                self.arrays['_mask_dev'][row] = held['sampling'].mask_fn(
                    held['stream'])

    def clear(self, rep) -> None:
        held = self.seated.pop(rep)
        for row in held['rows']:
            self.arrays['_active_dev'][row] = False
            if held['sampling'] is not None:
                self.arrays['_temp_dev'][row] = 0.0
                if held['sampling'].mask_fn is not None:
                    self.arrays['_mask_dev'][row] = True

    def stepped(self, engine, report) -> None:
        """A decode step hands back the tokens and stream positions itself
        (its program is not what a membership change touches); what
        follows it on the host is a clear for every finished request and
        the next position's grammar for the others."""
        for name in ('_tokens_dev', '_pos_dev'):
            self.arrays[name] = np.array(getattr(engine, name))
        finished = {rep for rep, _, _ in report.finished}
        for rep, tokens in report.emitted.items():
            self.seated[rep]['stream'].extend(tokens)
            if rep in finished:
                self.clear(rep)
            else:
                self.regrammar(rep)

    def differences(self, engine, when: str) -> list:
        found = snapshot(engine)
        return [f'{when}: {name} holds {found[name].tolist()}, one write '
                f'per element leaves {self.arrays[name].tolist()}'
                for name in ARRAYS
                if found[name].dtype != self.arrays[name].dtype
                or not np.array_equal(found[name], self.arrays[name])]


def snapshot(engine) -> dict:
    return {name: np.array(getattr(engine, name)) for name in ARRAYS}


@pytest.fixture(scope='module', params=KINDS)
def churned(request, served):
    """One engine of each kind after the 24 admissions, a cancellation and
    every eviction, with each difference from the per-element expectation
    noted where it arose."""
    tracer = Tracer('churn').watch_compiles()
    engine = build(request.param, served)
    fanout = engine.tree_fanout if request.param == 'speculative' else 1
    expected, differences = PerElement(engine), []
    pending = requests(masks=request.param != 'speculative')
    admitted = cancelled = 0
    while pending or engine.active_rows:
        while pending and engine.free_rows:
            prompt, max_new, sampling, emitted = pending.pop(0)
            admission = engine.admit(prompt, max_new, sampling=sampling,
                                     emitted=emitted)
            admitted += 1
            rows = list(range(admission.row, admission.row + fanout))
            expected.seat(rows, admission.token, sampling, emitted)
            if admission.finished:
                expected.clear(admission.row)
            else:
                expected.regrammar(admission.row)
            differences += expected.differences(
                engine, f'admission {admitted}')
            if admitted == 10:        # a sampled row under a grammar
                engine.evict(admission.row)
                expected.clear(admission.row)
                cancelled += 1
                differences += expected.differences(engine, 'cancellation')
        expected.stepped(engine, engine.step())
        differences += expected.differences(
            engine, f'the step after admission {admitted}')
    return request.param, engine, differences, admitted, cancelled, tracer


def test_arrays_hold_what_one_write_per_element_leaves(churned):
    _, engine, differences, admitted, cancelled, _ = churned
    assert (admitted, cancelled) == (24, 1)
    assert differences == []
    assert not engine.active_rows and engine.sampled_rows == 0
    # every row is back at the idle greedy default
    assert not np.asarray(engine._active_dev).any()
    assert not np.asarray(engine._temp_dev).any()
    assert np.asarray(engine._mask_dev).all()


def test_each_program_traced_once_whatever_was_admitted(churned):
    _, engine, _, admitted, _, tracer = churned
    assert admitted >= 20
    traced = tracer.compiled('trace')
    assert (traced['seat'], traced['clear']) == (1, 1)
    assert engine.trace_count == 1


def test_arrays_stay_where_the_engine_placed_them(churned):
    kind, engine, _, _, _, _ = churned
    for name in ARRAYS:
        array = getattr(engine, name)
        if kind == 'sharded':
            assert array.sharding.is_equivalent_to(
                NamedSharding(engine.mesh, PartitionSpec()), array.ndim), name
            assert len(array.sharding.device_set) == 2, name
        else:
            assert len(array.sharding.device_set) == 1, name


def test_an_unsampled_admission_brings_no_operand_of_its_own(served):
    module, params = served
    engine = Engine(module, params, rows=2, block_size=8)
    built = engine._greedy[VOCAB]
    assert engine._greedy_ops(VOCAB) is built
    assert [op.shape for op in built] == [()] * 5 + [(VOCAB,)]

    # all but the position are the construction-time objects; the position
    # is typed on the host and rides the prefill call
    for emitted in ((), (5, 6, 7)):
        ops = engine._sampling_ops(None, emitted)
        assert all(op is made for index, (op, made)
                   in enumerate(zip(ops, built)) if index != 1)
        assert type(ops[1]) is np.int32 and ops[1] == len(emitted)

    # no [vocab] write either: the mask array is the very object it was
    mask = engine._mask_dev
    admission = engine.admit([3, 4, 5, 6], 4)
    assert engine._mask_dev is mask

    # a sampled request without a grammar types its scalars on the host
    # and shares the engine's one all-True mask
    sampled = SamplingParams(seed=2**32 - 1, temperature=0.8, top_k=3,
                             top_p=0.7)
    ops = engine._sampling_ops(sampled, ())
    assert [type(op) for op in ops[:5]] == [np.uint32, np.int32, np.float32,
                                            np.int32, np.float32]
    assert ops[0] == 2**32 - 1 and ops[5] is built[5]
    engine.admit([7, 8, 9], 4, sampling=sampled)
    assert engine._mask_dev is mask

    # only a grammar brings a mask, and only its row write replaces the array
    engine.evict(admission.row)
    masked = SamplingParams(mask_fn=parity_mask)
    ops = engine._sampling_ops(masked, ())
    assert ops[5] is not built[5]
    assert np.array_equal(np.asarray(ops[5]), parity_mask([]))
    mask = engine._mask_dev
    admission = engine.admit([3, 4, 5], 4, sampling=masked)
    assert engine._mask_dev is not mask
    assert np.array_equal(np.asarray(engine._mask_dev)[admission.row],
                          parity_mask([admission.token]))


def test_host_typed_operands_retrace_no_prefill_program(served):
    """One prefill program per bucket serves every kind of request: the
    host-typed scalars carry the types the device-built ones had."""
    module, params = served
    tracer = Tracer('prefill').watch_compiles()
    engine = Engine(module, params, rows=4, block_size=8)
    prompt = [9, 8, 7, 6, 5]
    engine.admit(prompt, 3)
    run = engine_module._compiled_prefill(engine._prefiller,
                                          engine.bucket(len(prompt)))
    traced = run._cache_size()
    engine.admit(prompt, 3, sampling=SamplingParams(seed=4, temperature=0.6))
    engine.admit(prompt, 3, sampling=SamplingParams(mask_fn=parity_mask))
    engine.admit(prompt + [1, 2], 3, emitted=(1, 2),
                 sampling=SamplingParams(seed=9, temperature=1.2, top_k=4))
    assert run._cache_size() == traced
    programs = tracer.compiled('trace')
    assert (programs['seat'], programs['clear']) == (1, 0)
