"""Fused decode-chain matmuls — Pallas TPU kernels for the serving path.

One greedy-decode token-step at small batch is weight-streaming bound:
the ``[B, dim]`` activation is a few KB while every matrix param crosses
HBM once per step. These kernels attack the two byte levers at once:

* :func:`decode_matmul` — one fused dequantize-matmul. The activation
  block is **resident in VMEM for the whole weight sweep** (its index
  map is constant) while the weight is streamed column-tile by
  column-tile through Pallas's double-buffered grid pipeline. With an
  int8/fp8 :class:`~tpusystem.ops.precision.QuantizedLeaf` the *narrow*
  values are the streamed operand — the tile is widened to the compute
  dtype in VMEM (a VPU convert that never touches HBM) and the
  per-output-channel scale multiplies the f32 accumulator once in the
  epilogue (the scale is a per-column constant, so it factors out of the
  contraction exactly). XLA cannot hoist a dequantized wide copy out of
  the decode loop here: the dequant lives inside an opaque kernel, which
  is what makes quantized streaming and fusion compose.

* :func:`decode_ffn` — the fc→gelu→proj **chain** in one kernel: the
  grid walks the hidden dimension; each step dequantizes one fc column
  tile, applies bias+activation to the ``[B, block_h]`` hidden slab
  while it is still in VMEM, and folds it into the proj contraction's
  f32 accumulator. The ``[B, 4*dim]`` hidden activation never exists in
  HBM, and both weight streams ride one grid.

Module discipline (flash/grouped_matmul): ``interpret=None``
auto-selects interpreter mode off-TPU so tier-1 CPU tests exercise the
kernel numerics directly; shapes
the TPU cannot tile fall back to the einsum path
(:func:`tpusystem.ops.precision.qdot` — also the parity reference),
pinned by the pure :func:`decode_plan`. Accumulation is float32
throughout (``preferred_element_type``), bias/activation applied to the
f32 accumulator and rounded once to the output dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpusystem.ops.pallas import auto_interpret, streamed_from_hbm
from tpusystem.ops.precision import QuantizedLeaf, qdot

LANES = 128   # lane tile; TPU block minor dims must be multiples


def _pick_block(size: int, want: int, granule: int) -> int | None:
    """Largest divisor of ``size`` that is <= ``want`` and a multiple of
    ``granule`` (1 in interpret mode — no tiling constraints there)."""
    want = min(want, size)
    best = None
    for candidate in range(granule, want + 1, granule):
        if size % candidate == 0:
            best = candidate
    return best


def decode_plan(inner: int, out_cols: int, interpret: bool,
                want: int = 512) -> int | None:
    """Pure tiling decision for one streamed weight ``[inner, out_cols]``:
    the output-column block size, or ``None`` when the shape cannot tile
    on TPU (minor dims must divide into LANES multiples) — the caller
    then takes the einsum fallback. Pinned by tests so a jax upgrade
    cannot silently change which shapes run fused."""
    granule = 1 if interpret else LANES
    if not interpret and inner % granule:
        return None     # the weight tile's minor dim under transpose-free
        # streaming is out_cols; inner rides sublanes, which Mosaic pads —
        # but a non-lane-multiple inner also breaks the x block, so refuse
    return _pick_block(out_cols, want, granule)


def _split(w) -> tuple[jax.Array, jax.Array | None]:
    """(streamed operand, per-output-channel scale row or None)."""
    if isinstance(w, QuantizedLeaf):
        return w.values, w.scales.reshape(1, -1)
    return w, None


def _row(vec, cols: int) -> jax.Array:
    """[cols] -> [1, cols] f32 (a compact vector is not Mosaic-tileable;
    one replicated sublane row is — the grouped_matmul SCALE_LANES
    lesson, minor-dim flavored)."""
    return jnp.asarray(vec, jnp.float32).reshape(1, cols)


def _matmul_kernel(x_ref, w_ref, *rest, activation, have_scale, have_bias,
                   out_dtype):
    refs = list(rest)
    scale_ref = refs.pop(0) if have_scale else None
    bias_ref = refs.pop(0) if have_bias else None
    (out_ref,) = refs
    tile = w_ref[...].astype(x_ref.dtype)
    acc = jax.lax.dot_general(x_ref[...], tile, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    if scale_ref is not None:
        acc = acc * scale_ref[...]
    if bias_ref is not None:
        acc = acc + bias_ref[...]
    if activation is not None:
        acc = activation(acc)
    out_ref[...] = acc.astype(out_dtype)


def decode_matmul(x, w, bias=None, *, activation=None, out_dtype=None,
                  block_cols: int = 512, interpret: bool | None = None):
    """Fused ``activation(x @ dequant(w) + bias)`` with ``x`` VMEM-resident
    and ``w`` streamed in column tiles.

    Args:
        x: ``[B, K]`` activations (the compute dtype — bf16 on TPU).
        w: ``[K, N]`` weight, plain or a
            :class:`~tpusystem.ops.precision.QuantizedLeaf` (int8/fp8
            values + ``[1, N]`` scales dequantized in-kernel).
        bias: ``[N]`` or None; added to the f32 accumulator.
        activation: applied to the f32 accumulator (e.g. ``jax.nn.gelu``).

    Returns ``[B, N]`` in ``out_dtype`` (default ``x.dtype``). Falls back
    to the :func:`~tpusystem.ops.precision.qdot` einsum path when
    :func:`decode_plan` refuses the shape.
    """
    interpret = auto_interpret(interpret)
    values, scales = _split(w)
    (batch, inner), (inner_w, out_cols) = x.shape, values.shape
    if inner != inner_w:
        raise ValueError(f'x cols {inner} != w rows {inner_w}')
    out_dtype = out_dtype or x.dtype
    block = decode_plan(inner, out_cols, interpret, block_cols)
    if block is None:       # einsum fallback — same math, XLA-tiled
        acc = qdot(x, w)
        if bias is not None:
            acc = acc + jnp.asarray(bias, jnp.float32)
        if activation is not None:
            acc = activation(acc)
        return acc.astype(out_dtype)

    in_specs = [
        pl.BlockSpec((batch, inner), lambda n: (0, 0)),     # resident
        pl.BlockSpec((inner, block), lambda n: (0, n)),     # streamed
    ]
    operands = [x, streamed_from_hbm(values, interpret)]
    if scales is not None:
        in_specs.append(pl.BlockSpec((1, block), lambda n: (0, n)))
        operands.append(scales)
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block), lambda n: (0, n)))
        operands.append(_row(bias, out_cols))
    kernel = functools.partial(
        _matmul_kernel, activation=activation, have_scale=scales is not None,
        have_bias=bias is not None, out_dtype=out_dtype)
    flops = 2 * batch * inner * out_cols
    bytes_accessed = (x.nbytes + values.nbytes
                      + (scales.nbytes if scales is not None else 0)
                      + batch * out_cols * jnp.dtype(out_dtype).itemsize)
    return pl.pallas_call(
        kernel,
        grid=(out_cols // block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((batch, block), lambda n: (0, n)),
        out_shape=jax.ShapeDtypeStruct((batch, out_cols), out_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=('arbitrary',)),
        cost_estimate=pl.CostEstimate(flops=flops,
                                      bytes_accessed=bytes_accessed,
                                      transcendentals=0),
        interpret=interpret,
    )(*operands)


def _ffn_kernel(x_ref, w1_ref, *rest, activation, have_s1, have_s2,
                out_dtype):
    refs = list(rest)
    s1_ref = refs.pop(0) if have_s1 else None
    b1_ref = refs.pop(0)
    w2_ref = refs.pop(0)
    s2_ref = refs.pop(0) if have_s2 else None
    b2_ref, out_ref, acc = refs
    step, steps = pl.program_id(0), pl.num_programs(0)

    @pl.when(step == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    hidden = jax.lax.dot_general(
        x_ref[...], w1_ref[...].astype(x_ref.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if s1_ref is not None:      # per-hidden-channel scale BEFORE the
        hidden = hidden * s1_ref[...]   # nonlinearity — real values needed
    hidden = activation(hidden + b1_ref[...])
    acc[...] += jax.lax.dot_general(
        hidden.astype(x_ref.dtype), w2_ref[...].astype(x_ref.dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(step == steps - 1)
    def _epilogue():
        total = acc[...]
        if s2_ref is not None:  # per-output scale factors out of the sum
            total = total * s2_ref[...]
        out_ref[...] = (total + b2_ref[...]).astype(out_dtype)


def decode_ffn(x, w1, b1, w2, b2, *, activation=jax.nn.gelu,
               out_dtype=None, block_hidden: int = 512,
               interpret: bool | None = None):
    """The fused FFN chain ``(activation(x @ dequant(w1) + b1)) @
    dequant(w2) + b2`` in one kernel: the grid walks the hidden
    dimension, so the ``[B, hidden]`` activation lives only as one
    ``[B, block_hidden]`` VMEM slab per step and both weight streams
    share one double-buffered pipeline. ``w1``/``w2`` may each be plain
    or quantized; ``w1``'s per-hidden-channel scale is applied per tile
    *before* the nonlinearity (the math needs real values there),
    ``w2``'s per-output scale once in the epilogue."""
    interpret = auto_interpret(interpret)
    v1, s1 = _split(w1)
    v2, s2 = _split(w2)
    (batch, inner), (inner_w, hidden) = x.shape, v1.shape
    hidden_w, out_cols = v2.shape
    if inner != inner_w or hidden != hidden_w:
        raise ValueError(f'chain shapes do not compose: x {x.shape}, '
                         f'w1 {v1.shape}, w2 {v2.shape}')
    out_dtype = out_dtype or x.dtype
    # the hidden dim is the streamed/blocked one; the output width N must
    # itself be lane-tileable since the whole [B, N] accumulator is
    # resident
    block = decode_plan(inner, hidden, interpret, block_hidden)
    if block is None or (not interpret and out_cols % LANES):
        mid = qdot(x, w1)
        mid = activation(mid + jnp.asarray(b1, jnp.float32))
        acc = qdot(mid.astype(x.dtype), w2)
        return (acc + jnp.asarray(b2, jnp.float32)).astype(out_dtype)

    in_specs = [
        pl.BlockSpec((batch, inner), lambda h: (0, 0)),      # resident
        pl.BlockSpec((inner, block), lambda h: (0, h)),      # fc stream
    ]
    operands = [x, streamed_from_hbm(v1, interpret)]
    if s1 is not None:
        in_specs.append(pl.BlockSpec((1, block), lambda h: (0, h)))
        operands.append(s1.reshape(1, hidden))
    in_specs.append(pl.BlockSpec((1, block), lambda h: (0, h)))
    operands.append(_row(b1, hidden))
    in_specs.append(pl.BlockSpec((block, out_cols), lambda h: (h, 0)))
    operands.append(streamed_from_hbm(v2, interpret))        # proj stream
    if s2 is not None:
        in_specs.append(pl.BlockSpec((1, out_cols), lambda h: (0, 0)))
        operands.append(s2.reshape(1, out_cols))
    in_specs.append(pl.BlockSpec((1, out_cols), lambda h: (0, 0)))
    operands.append(_row(b2, out_cols))

    kernel = functools.partial(
        _ffn_kernel, activation=activation, have_s1=s1 is not None,
        have_s2=s2 is not None, out_dtype=out_dtype)
    flops = 2 * batch * inner * hidden + 2 * batch * hidden * out_cols
    bytes_accessed = (x.nbytes + v1.nbytes + v2.nbytes
                      + batch * out_cols * jnp.dtype(out_dtype).itemsize)
    return pl.pallas_call(
        kernel,
        grid=(hidden // block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((batch, out_cols), lambda h: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, out_cols), out_dtype),
        scratch_shapes=[pltpu.VMEM((batch, out_cols), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=('arbitrary',)),
        cost_estimate=pl.CostEstimate(flops=flops,
                                      bytes_accessed=bytes_accessed,
                                      transcendentals=batch * hidden),
        interpret=interpret,
    )(*operands)
