"""Hand-written Pallas TPU kernels (flash attention, grouped gather-matmul,
fused decode matmuls, embedding row movement)."""

from tpusystem.parallel.mesh import on_tpu


def auto_interpret(interpret: bool | None) -> bool:
    """Every kernel's ``interpret=None`` default: compiled by Mosaic on
    the chip, the Pallas interpreter everywhere else (so the CPU tests
    run the same kernel bodies)."""
    return not on_tpu() if interpret is None else interpret
