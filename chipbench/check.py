"""What decides ``correct``: the numbers compared and how each is taken.

The drivers hand in what the timed path produced; the configuration's
family (``chipbench/families/<family>.py``: ``reference_training``,
``served_gap``) runs its plain reference over the same inputs, and this
file, which names no model, measures the gaps. ``control.py`` takes the same
readings with the reference computed in the precision below the
configuration's, or with a fault planted, to read the upper ends that the
limits in ``chipbench/limits/`` are set under.

Training follows the program through its first dispatch: the loss of each
of its steps, the first moment the optimizer holds after them (the
gradients as it got them, clipped and averaged), and how far each leaf
has moved. Moments and moves are compared leaf by leaf as the gap between
the two norms (never the norm of a difference), measured against the
reference's norm of that leaf or of the median leaf, whichever is larger.
Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone under Adam and are left out of the move.

Serving compares the widest gap by which a served token's logit lies
below the reference's best, over a seeded sample of the finished requests.
"""

from __future__ import annotations

import statistics

import numpy as np

SILENT_LEAF = 1e-3       # of the median leaf's gradient norm


def relative_gaps(program: dict, reference: dict) -> dict:
    """Per leaf: ``|‖p‖ - ‖r‖| / max(‖r‖, median ‖r‖)``."""
    floor = statistics.median(reference.values())
    return {name: abs(program[name] - norm) / max(norm, floor)
            for name, norm in reference.items()}


def worst(gaps: dict) -> tuple[float, str]:
    name = max(gaps, key=lambda leaf: (gaps[leaf] != gaps[leaf], gaps[leaf]))
    return gaps[name], name


def compare_training(program: dict, reference: dict) -> tuple[dict, list]:
    """``{number: value}`` and notes, from two readings of the first
    dispatch: ``{'losses': [...], 'moment': {leaf: norm}, 'moved': {...}}``."""
    losses = [abs(p - r) / abs(r)
              for p, r in zip(program['losses'], reference['losses'])]
    if len(program['losses']) != len(reference['losses']):
        losses.append(float('nan'))
    moment, moment_leaf = worst(relative_gaps(program['moment'],
                                              reference['moment']))
    floor = SILENT_LEAF * statistics.median(reference['moment'].values())
    moving = {name for name, norm in reference['moment'].items()
              if norm >= floor}
    moved_gaps = relative_gaps(
        {name: program['moved'][name] for name in moving},
        {name: reference['moved'][name] for name in moving})
    moved, moved_leaf = worst(moved_gaps)
    notes = [
        'losses program ' + ' '.join(f'{x:.5f}' for x in program['losses']),
        'losses reference ' + ' '.join(f'{x:.5f}' for x in
                                       reference['losses']),
        f'moment_gap worst leaf {moment_leaf}; update_gap worst leaf '
        f'{moved_leaf}; {len(reference["moment"]) - len(moving)} silent '
        f'leaves left out of the move']
    return {'loss_gap': max(losses), 'moment_gap': moment,
            'update_gap': moved}, notes


def sample_requests(seed: int, finished: list, count: int) -> list:
    """A seeded sample of the finished requests with the longest in it;
    ``finished`` is ``[(prompt, tokens), ...]``."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng([int(seed), 5])
    picked = rng.permutation(len(rest))[:max(count - 1, 0)]
    return [finished[longest]] + [finished[rest[i]] for i in picked]


def sequence(prompt, tokens, length: int):
    """Prompt and served tokens right-padded to ``length`` (padding sits
    after everything compared and is causally invisible), and the slice of
    next-token positions whose successor is a served token."""
    ids = list(prompt) + list(tokens)
    padded = np.zeros(length, np.int32)
    padded[:len(ids)] = ids
    return padded, slice(len(prompt) - 1, len(ids) - 1)
