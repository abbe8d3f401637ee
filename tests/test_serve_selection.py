"""Token selection pays for sampling only when a row of the batch samples
(tpusystem/train/generate.py::select_tokens and its four engine sites).

The contract: ``select_tokens`` is ``vmap(sample_token)`` token for token
on every batch — all greedy, one row sampled, every row sampled — while a
batch with no sampled row runs the masked argmax alone: one ``lax.cond``
on the device, outside the ``vmap``, under the ``select`` scope, with the
sort and the scatter over the vocabulary inside its sampled branch only.
The choice is per dispatch, never retraces, and the engine's host counter
names the side the device took.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusystem.models import gpt2_tiny
from tpusystem.serve import Engine, Request, SamplingParams, Scheduler
from tpusystem.train.generate import sample_token, select_tokens

ROWS, VOCAB = 6, 300


@pytest.fixture(scope='module')
def served():
    module = gpt2_tiny(dtype='float32')
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))['params']
    return module, params


def batch(seed: int, window: tuple = ()):
    """Seeded ``[ROWS, *window, VOCAB]`` logits on a coarse grid (so the
    best value ties, and the lowest id has to win on both sides), random
    masks that each allow a token, and greedy per-row params."""
    rng = np.random.default_rng(seed)
    logits = np.round(rng.normal(0, 2, (ROWS, *window, VOCAB)), 1)
    mask = rng.random((ROWS, VOCAB)) < 0.7
    mask[np.arange(ROWS), rng.integers(0, VOCAB, ROWS)] = True
    position = rng.integers(0, 500, (ROWS, *window))
    return dict(
        logits=jnp.asarray(logits, jnp.bfloat16 if seed % 2 else jnp.float32),
        seed=jnp.asarray(rng.integers(0, 2 ** 31, ROWS), jnp.uint32),
        position=jnp.asarray(position, jnp.int32),
        temperature=jnp.zeros(ROWS, jnp.float32),
        top_k=jnp.zeros(ROWS, jnp.int32),
        top_p=jnp.ones(ROWS, jnp.float32),
        mask=jnp.asarray(mask))


def with_sampled(ops: dict, rows, temperature, top_k, top_p) -> dict:
    rows = jnp.asarray(rows)
    return dict(
        ops,
        temperature=ops['temperature'].at[rows].set(temperature),
        top_k=ops['top_k'].at[rows].set(top_k),
        top_p=ops['top_p'].at[rows].set(top_p))


def per_row(ops: dict):
    """The parent's program: ``sample_token`` under ``vmap`` over rows
    (and, for a verify window, over its slots at their own positions)."""
    def window(logits, seed, position, *rest):
        if logits.ndim == 1:
            return sample_token(logits, seed, position, *rest)
        return jax.vmap(lambda logits_j, position_j: sample_token(
            logits_j, seed, position_j, *rest))(logits, position)
    return jax.jit(jax.vmap(window))(*ops.values())


def selected(ops: dict):
    return jax.jit(select_tokens)(*ops.values())


# ---------------------------------------------------------------------------
# (a), (b): the batched entry is vmap(sample_token), token for token
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('window', [(), (4,)], ids=['step', 'verify-window'])
@pytest.mark.parametrize('seed', [0, 1, 2])
def test_all_greedy_batch_equals_per_row_sampling(seed, window):
    ops = batch(seed, window)
    tokens = selected(ops)
    np.testing.assert_array_equal(tokens, per_row(ops))
    # and it is the masked argmax: a disallowed token never wins
    allowed = np.asarray(ops['mask'])[
        np.arange(ROWS).reshape((ROWS,) + (1,) * len(window)),
        np.asarray(tokens)]
    assert allowed.all()
    assert tokens.dtype == jnp.int32


@pytest.mark.parametrize('window', [(), (4,)], ids=['step', 'verify-window'])
@pytest.mark.parametrize('which', ['one', 'every'])
@pytest.mark.parametrize('temperature, top_k, top_p', [
    (0.7, 0, 1.0), (1.0, 16, 1.0), (1.3, 0, 0.8), (0.9, 16, 0.95),
    (2.0, 1, 1.0)], ids=['plain', 'top_k', 'top_p', 'both', 'top_k-1'])
def test_batch_with_sampled_rows_equals_per_row_sampling(
        which, temperature, top_k, top_p, window):
    """One sampled row puts the whole batch on the sampled side: every
    row, greedy ones too, reads bit for bit what the parent gives."""
    rows = [3] if which == 'one' else list(range(ROWS))
    ops = with_sampled(batch(7, window), rows, temperature, top_k, top_p)
    tokens, expected = selected(ops), per_row(ops)
    np.testing.assert_array_equal(tokens, expected)
    greedy = selected(batch(7, window))
    untouched = [row for row in range(ROWS) if row not in rows]
    np.testing.assert_array_equal(np.asarray(tokens)[untouched],
                                  np.asarray(greedy)[untouched])
    if top_k != 1:       # sampling is real: somewhere it leaves the argmax
        assert (np.asarray(tokens) != np.asarray(greedy)).any()


@pytest.mark.parametrize('temperature', [0.0, 0.9])
def test_one_row_entry_equals_sample_token(temperature):
    """The prefill programs' call: scalars, ``[vocab]`` logits."""
    ops = with_sampled(batch(3), list(range(ROWS)), temperature, 8, 0.9)
    for row in range(ROWS):
        one = [leaf[row] for leaf in ops.values()]
        assert int(jax.jit(select_tokens)(*one)) == int(
            jax.jit(sample_token)(*one))


# ---------------------------------------------------------------------------
# (c): one conditional under `select`; sort and scatter on its sampled side
# ---------------------------------------------------------------------------

def functions(text: str) -> dict:
    """``name -> body lines`` of every ``func.func`` of a lowered module."""
    found, name = {}, None
    for line in text.splitlines():
        opened = re.match(r'\s*func\.func \w+ @"?([^\s("]+)', line)
        if opened:
            name = opened.group(1)
            found[name] = []
        elif name is not None:
            found[name].append(line)
    return found


def region_ops(lines: list, kind: str) -> list:
    """``(first, last)`` line indices of every ``stablehlo.<kind>`` in
    ``lines`` that holds regions: the op closes on the next ``})`` at its
    own indentation, the line that carries its type and its location."""
    spans = []
    for first, line in enumerate(lines):
        if f'"stablehlo.{kind}"(' not in line:
            continue
        indent = len(line) - len(line.lstrip())
        last = next(index for index in range(first + 1, len(lines))
                    if lines[index].startswith(' ' * indent + '})'))
        spans.append((first, last))
    return spans


def reachable(lines: list, module: dict) -> list:
    """``lines`` and the bodies of every function they call, transitively."""
    seen, out, queue = set(), list(lines), list(lines)
    while queue:
        for callee in re.findall(r'call @"?([^\s("]+)', queue.pop()):
            if callee not in seen:
                seen.add(callee)
                out.extend(module[callee])
                queue.extend(module[callee])
    return out


def vocab_scatters(lines: list, rows: int, vocab: int) -> int:
    return sum(f'-> tensor<{rows}x{vocab}xf32>' in lines[last]
               for _, last in region_ops(lines, 'scatter'))


@pytest.mark.parametrize('impl', ['flax', 'fused'])
def test_lowered_step_sorts_and_scatters_only_when_a_row_samples(served,
                                                                 impl):
    module, params = served
    rows, vocab = 2, module.vocab_size
    engine = Engine(module, params, rows=rows, block_size=8,
                    decode_impl=impl)
    text = engine.lowered_step(debug_info=True)
    assert engine.trace_count == 0       # lowering is not a trace to count
    names = dict(re.findall(r'^#loc(\d+) = loc\("([^"]+)"', text, re.M))
    module_text = functions(text)
    main = module_text['main']

    def scope(last: int) -> str:
        return names.get(re.search(r'loc\(#loc(\d+)\)', main[last]).group(1),
                         '')

    cases = region_ops(main, 'case')
    under_select = [span for span in cases if '/select' in scope(span[1])]
    assert len(under_select) == 1, [scope(last) for _, last in cases]
    first, last = under_select[0]
    assert scope(last).endswith('/select/cond')
    indent = len(main[first]) - len(main[first].lstrip())
    cuts = [index for index in range(first, last)
            if main[index] == ' ' * indent + '}, {']
    assert len(cuts) == 1                # two branches: cond(pred, ...)
    greedy = reachable(main[first + 1:cuts[0]], module_text)
    sampled = reachable(main[cuts[0] + 1:last], module_text)
    outside = reachable(main[:first] + main[last + 1:], module_text)

    def holds(lines, kind):
        return any(f'stablehlo.{kind}' in line for line in lines)

    assert holds(sampled, 'sort') and vocab_scatters(sampled, rows, vocab)
    assert not holds(greedy, 'sort') and not holds(greedy, 'scatter')
    assert not holds(greedy, 'reduce_window')        # no cumulative sum
    assert not any('threefry' in line for line in greedy)
    assert not holds(outside, 'sort')
    assert not vocab_scatters(outside, rows, vocab)
    # the plain text chip_smoke.py reads has the same one sort
    assert engine.lowered_step().count('stablehlo.sort') == 1


# ---------------------------------------------------------------------------
# (d), (e): per dispatch, on the device, counted on the host, never retraced
# ---------------------------------------------------------------------------

SAMPLED = SamplingParams(seed=11, temperature=0.9, top_k=16, top_p=0.95)
ENGINES = {
    'flax': dict(decode_impl='flax'),
    'fused': dict(decode_impl='fused'),
    'speculative': dict(speculate=2),
}


def build(served, kind: str) -> Engine:
    module, params = served
    knobs = dict(ENGINES[kind])
    if kind == 'speculative':
        knobs.update(draft_module=module, draft_params=params)
    return Engine(module, params, rows=2, block_size=8, **knobs)


@pytest.mark.parametrize('kind', list(ENGINES))
def test_greedy_mixed_greedy_churn_traces_once(served, kind):
    """Greedy ticks, then ticks beside a sampled row, then greedy ticks
    again on ONE engine: one trace, both sides of the conditional taken,
    and the greedy request reads the same tokens on either side."""
    engine = build(served, kind)
    scheduler = Scheduler(engine)
    rng = np.random.default_rng(5)
    prompt, other = (list(rng.integers(0, 256, (n,))) for n in (5, 7))
    streams = []
    for phase, sampling in enumerate([None, SAMPLED, None]):
        scheduler.submit(Request(f'greedy{phase}', prompt, 8))
        if sampling is not None:
            scheduler.submit(Request('sampled', other, 8, sampling=sampling))
        before = dict(engine.selection)
        results = scheduler.run()
        streams.append(results[f'greedy{phase}'].tokens)
        took = {side: engine.selection[side] - before[side]
                for side in before}
        if sampling is None:
            assert took['greedy_ticks'] > 0 and took['sampled_ticks'] == 0
        else:
            assert took['sampled_ticks'] > 0
    assert streams[0] == streams[1] == streams[2]
    assert engine.trace_count == 1, (
        f'greedy/sampled churn retraced the step: {engine.trace_count}')
    assert engine.sampled_rows == 0


@pytest.mark.parametrize('kind', ['flax', 'speculative'])
def test_counter_names_the_side_the_device_takes(served, kind):
    """Before every dispatch the device arrays the compiled step will read
    give its predicate; the host counter, kept from ``SamplingParams`` at
    register and evict, must move on that side — through a sampled row
    seating beside a greedy one and retiring before it."""
    engine = build(served, kind)
    rng = np.random.default_rng(9)
    long, short = (list(rng.integers(0, 256, (n,))) for n in (6, 5))
    engine.admit(long, 24)
    sides = []

    def tick():
        device = bool(jnp.any(engine._temp_dev > 0))
        before = dict(engine.selection)
        engine.step()
        took = {side: engine.selection[side] - before[side]
                for side in before}
        assert took == {'greedy_ticks': int(not device),
                        'sampled_ticks': int(device)}
        assert (engine.sampled_rows > 0) == bool(
            jnp.any(engine._temp_dev > 0))
        sides.append(device)

    tick()
    tick()
    engine.admit(short, 5, sampling=SAMPLED)
    assert engine.sampled_rows == 1
    while engine.active_rows:
        tick()
    # greedy, then sampled while the short row lives, then greedy again
    changes = [side for index, side in enumerate(sides)
               if index == 0 or side != sides[index - 1]]
    assert changes == [False, True, False]
    assert engine.selection == {'greedy_ticks': sides.count(False),
                                'sampled_ticks': sides.count(True)}
    assert engine.trace_count == 1


def test_sampled_request_finished_at_admission_holds_no_tick(served):
    """A sampled request whose first token completes it never seats: the
    running count and the device's temperatures both return to greedy."""
    engine = build(served, 'flax')
    engine.admit([1, 2, 3, 4], 6)
    admitted = engine.admit([5, 6, 7], 1, sampling=SAMPLED)
    assert admitted.finished and engine.sampled_rows == 0
    assert not bool(jnp.any(engine._temp_dev > 0))
    engine.step()
    assert engine.selection == {'greedy_ticks': 1, 'sampled_ticks': 0}
