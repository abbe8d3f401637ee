"""Operations and bytes the algorithm needs, computed from shapes.

The convention is fixed and is never changed by a PR that claims a gain.
A token costs 2 operations per matrix-product parameter forward and 6
forward + backward (the tied head counted once over the published
vocabulary, embedding look-ups and biases not counted); causal attention
counts the causal half of the square; a decoded token attends the keys
its row holds; recomputed operations never count. What that comes to for
a configuration is its family's to say (``chipbench/families/<family>.py``,
found by the configuration's ``family`` key): every count here hands the
configuration to its family. The peaks and the roofline are of no family.
"""

from __future__ import annotations

import json
import pathlib

from chipbench import families

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
    """Parameters that enter a matrix product once per token."""
    return families.of(config).matmul_params(config)


def train_ops_per_token(config: dict, seq: int) -> float:
    """Forward + backward operations per trained token at sequence ``seq``."""
    return families.of(config).train_ops_per_token(config, seq)


def prefill_ops(config: dict, length: int) -> float:
    """Forward operations of one causal pass over ``length`` tokens."""
    return families.of(config).prefill_ops(config, length)


def decode_ops(config: dict, depth: int) -> float:
    """Forward operations of one token attending ``depth`` cached keys."""
    return families.of(config).decode_ops(config, depth)


def flash_layers(config: dict) -> int:
    """How many layers run the causal attention kernel in one step."""
    return families.of(config).flash_layers(config)


def flash_ops_and_bytes(config: dict, rows: int, seq: int,
                        backward: bool) -> tuple[float, float]:
    """One layer's causal attention over ``[rows, seq, dim]`` in bf16:
    (operations, HBM bytes), forward or backward."""
    return families.of(config).flash_ops_and_bytes(config, rows, seq,
                                                   backward)


def decode_chain_ops_and_bytes(config: dict, rows: int,
                               weight_bytes: float) -> tuple[float, float]:
    """One decode tick's streamed matrix products for ``rows`` rows, the
    weights at ``weight_bytes`` each: (operations, HBM bytes)."""
    return families.of(config).decode_chain_ops_and_bytes(config, rows,
                                                          weight_bytes)


def kv_bytes_per_position(config: dict) -> float:
    """Bytes of keys and values one cached position holds, all layers."""
    return families.of(config).kv_bytes_per_position(config)


def roofline_seconds(ops: float, bytes_moved: float, peak: dict,
                     ops_key: str = 'bf16_flops_per_s') -> tuple[float, str]:
    """The least time the chip could take and which bound sets it."""
    by_ops = ops / peak[ops_key]
    by_bytes = bytes_moved / peak['hbm_bytes_per_s']
    return (by_ops, 'compute') if by_ops >= by_bytes else (by_bytes, 'memory')
