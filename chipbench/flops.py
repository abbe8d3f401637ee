"""Operations and bytes the algorithm needs, computed from shapes.

The convention is fixed here and is never changed by a PR that claims a
gain. Matrix-product parameters are ``12·L·d² + V·d`` (the tied head
counted once over the published vocabulary, embedding look-ups and biases
not counted). A token costs 2 operations per such parameter forward and 6
forward + backward. Causal attention adds ``2·L·S·d`` per token forward
(half of the full ``4·L·S·d`` square) and three times that with the
backward. A decoded token at cache depth ``p`` attends ``p`` keys:
``4·L·p·d``. Recomputed operations never count.
"""

from __future__ import annotations

import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    table = json.loads((HERE / 'peaks.json').read_text())
    if device_kind not in table['kinds']:
        raise KeyError(f'no peaks for device kind {device_kind!r}; '
                       f'chipbench/peaks.json has {sorted(table["kinds"])}')
    return table['kinds'][device_kind]


def matmul_params(config: dict) -> int:
    layers, dim = config['n_layer'], config['n_embd']
    return 12 * layers * dim * dim + config['vocab_size'] * dim


def train_ops_per_token(config: dict, seq: int) -> float:
    """Forward + backward operations per trained token at sequence ``seq``."""
    attention = 2 * config['n_layer'] * seq * config['n_embd']
    return 6 * matmul_params(config) + 3 * attention


def prefill_ops(config: dict, length: int) -> float:
    """Forward operations of one causal pass over ``length`` tokens."""
    attention = 2 * config['n_layer'] * length * config['n_embd']
    return length * (2 * matmul_params(config) + attention)


def decode_ops(config: dict, depth: int) -> float:
    """Forward operations of one token attending ``depth`` cached keys."""
    return (2 * matmul_params(config)
            + 4 * config['n_layer'] * depth * config['n_embd'])


def flash_ops_and_bytes(config: dict, rows: int, seq: int,
                        backward: bool) -> tuple[float, float]:
    """One layer's causal attention over ``[rows, seq, dim]`` in bf16:
    (operations, HBM bytes). Forward is the two products over the causal
    half; backward the four products a backward pass needs (the scores'
    recomputation does not count). Bytes: q, k, v read and the output
    written forward; q, k, v, o, do read and dq, dk, dv written backward."""
    dim = config['n_embd']
    forward_ops = 2.0 * rows * seq * seq * dim
    tensor = rows * seq * dim * 2.0
    if backward:
        return 2 * forward_ops, 8 * tensor
    return forward_ops, 4 * tensor


def decode_chain_ops_and_bytes(config: dict, rows: int,
                               weight_bytes: float) -> tuple[float, float]:
    """One decode tick's four matrix products per layer for ``rows`` rows:
    (operations, HBM bytes). Bytes are the streamed weights at
    ``weight_bytes`` each with one float32 scale per output channel, plus
    the bf16 activations in and out of each of the three kernels."""
    layers, dim = config['n_layer'], config['n_embd']
    weights = 12 * layers * dim * dim
    ops = 2.0 * rows * weights
    scales = layers * (3 * dim + dim + 4 * dim + dim) * 4.0
    activations = layers * rows * (dim + 3 * dim + dim + dim + dim + dim) * 2.0
    return ops, weights * weight_bytes + scales + activations


def roofline_seconds(ops: float, bytes_moved: float, peak: dict,
                     ops_key: str = 'bf16_flops_per_s') -> tuple[float, str]:
    """The least time the chip could take and which bound sets it."""
    by_ops = ops / peak[ops_key]
    by_bytes = bytes_moved / peak['hbm_bytes_per_s']
    return (by_ops, 'compute') if by_ops >= by_bytes else (by_bytes, 'memory')
