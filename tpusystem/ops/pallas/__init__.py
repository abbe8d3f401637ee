"""Hand-written Pallas TPU kernels (flash attention, grouped gather-matmul,
fused decode matmuls, embedding row movement)."""

import jax
from jax.experimental.pallas import tpu as pltpu

from tpusystem.parallel.mesh import on_tpu


def auto_interpret(interpret: bool | None) -> bool:
    """Every kernel's ``interpret=None`` default: compiled by Mosaic on
    the chip, the Pallas interpreter everywhere else (so the CPU tests
    run the same kernel bodies)."""
    return not on_tpu() if interpret is None else interpret


def streamed_from_hbm(operand, interpret: bool):
    """Pin a kernel operand that the kernel streams (weights, KV pools) to
    HBM. Left free, XLA's memory-space assignment stages such an operand
    through VMEM with asynchronous copies of its own *around* the Mosaic
    call: the bytes still cross HBM once, but outside the kernel's event
    in a device trace (a roofline share read from that event then passes
    100 %), and a staged pool is copied back whole. Only a traced operand
    can carry the constraint; an eager call's or an interpreted kernel's
    is returned as it is."""
    if interpret or not isinstance(operand, jax.core.Tracer):
        return operand
    return pltpu.with_memory_space_constraint(operand, pltpu.HBM)
