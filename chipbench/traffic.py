"""The traffic generators: one for training batches, one for requests.

A traffic mix is a data file under ``chipbench/traffic/``; these two
functions are all the code traffic has. Everything is drawn from
``--seed``; the same seed gives the same inputs. Every seed gets the same
set of sizes in another order, so that seeds change the inputs and not
the amount of work.
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def seeded(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def bigram_tokens(seed: int, *, samples: int, seq: int, vocab: int,
                  fanout: int = 4) -> np.ndarray:
    """``[samples, seq]`` int32 token rows with learnable structure: each
    token is followed by one of ``fanout`` successors from a seeded table
    over the whole published vocabulary; every row starts elsewhere."""
    rng = seeded(seed, 1)
    table = rng.integers(0, vocab, size=(vocab, fanout), dtype=np.int32)
    tokens = np.empty((samples, seq), np.int32)
    tokens[:, 0] = rng.integers(0, vocab, size=samples)
    choices = rng.integers(0, fanout, size=(samples, seq))
    for position in range(1, seq):
        tokens[:, position] = table[tokens[:, position - 1],
                                    choices[:, position]]
    return tokens


def _lognormal_quantiles(count: int, median: float, sigma: float,
                         low: int, high: int) -> np.ndarray:
    """``count`` lengths at the evenly spaced quantiles of a lognormal,
    clipped: the distribution itself, with no sampling noise."""
    normal = statistics.NormalDist()
    values = [median * math.exp(sigma * normal.inv_cdf((i + 0.5) / count))
              for i in range(count)]
    return np.clip(np.rint(values), low, high).astype(np.int64)


def request_sizes(seed: int, mix: dict) -> list[tuple[int, int]]:
    """The mix's pool of ``(prompt length, max_new)`` pairs in this seed's
    order. The pool is the same for every seed (lengths at the quantiles
    of the two lognormals, paired by the mix's own ``pairing_seed``)."""
    count = mix['pool']
    prompts = _lognormal_quantiles(count, **mix['prompt'])
    outputs = _lognormal_quantiles(count, **mix['max_new'])
    outputs = outputs[seeded(mix['pairing_seed'], 2).permutation(count)]
    order = seeded(seed, 3).permutation(count)
    return [(int(prompts[i]), int(outputs[i])) for i in order]


def request_prompt(seed: int, index: int, length: int,
                   vocab: int) -> list[int]:
    """Request ``index``'s prompt: ``length`` ids uniform below ``vocab``."""
    return seeded(seed, 4, index).integers(0, vocab, size=length).tolist()
