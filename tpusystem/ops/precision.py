"""Mixed-precision matmul helpers shared by every LM head — and the
quantized weight-streaming pair behind the serving-path decode.

Two rule sets live here:

* **Head precision** (training): operands in the compute dtype (bf16 —
  MXU rate), accumulation and result in float32 (loss-stable softmax).
  Centralized so the GPT-2 tied head, the pipelined variant, the Llama
  untied head, and the fused chunked loss stay numerically in lockstep.

* **Streamed quantization** (decode): small-batch decode is weight-
  STREAMING bound, so the lever is HBM bytes per step.
  :func:`quantize_streamed` rounds the decoder's matrix params to
  int8/fp8 with **per-output-channel symmetric scales**
  computed once at stream time; :func:`qdot` is the matching matmul —
  the scale is a per-column constant, so it factors out of the
  contraction exactly (``x @ (q * s) == (x @ q) * s``) and is applied
  once to the f32 accumulator, never to the streamed tiles. Vector
  leaves (biases, layernorms), embedding tables, and MoE routers stay
  untouched — the same exclusion rule the decode caster applies
  (``tpusystem.train.generate``), for the same reasons.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


def f32_accum_dot(a, b, dimension_numbers, precision=None,
                  preferred_element_type=None):
    """``lax.dot_general`` that always accumulates into float32 (the
    ``preferred_element_type`` argument of callers is deliberately ignored —
    this signature doubles as a ``flax.linen.Dense`` ``dot_general=``)."""
    return jax.lax.dot_general(a, b, dimension_numbers, precision=precision,
                               preferred_element_type=jnp.float32)


def head_logits(features, table, *, tied: bool | None = None) -> jax.Array:
    """Project ``[..., dim]`` features onto the vocabulary: f32 logits from
    compute-dtype operands.

    ``tied=True`` means ``table`` is a ``[vocab, dim]`` embedding table
    (GPT-2 convention); ``tied=False`` a ``[dim, vocab]`` head kernel
    (Llama convention). ``tied=None`` infers from the shapes but refuses a
    square table, where the orientation is ambiguous and guessing would
    silently transpose the head."""
    dim = features.shape[-1]
    if tied is None:
        if table.shape[0] == table.shape[1]:
            raise ValueError(
                f'square head table {table.shape}: pass tied= explicitly')
        tied = table.shape[-1] == dim
    table_dim = 1 if tied else 0
    if table.shape[table_dim] != dim:
        raise ValueError(
            f'feature dim {dim} does not match table {table.shape} '
            f'(tied={tied})')
    features = features.astype(table.dtype)
    return f32_accum_dot(
        features, table, (((features.ndim - 1,), (table_dim,)), ((), ())))


# --- streamed quantization (serving-path decode) -------------------------

# symmetric range per streamable narrow dtype: int8 uses the full signed
# range minus the asymmetric -128 (so negation is exact), fp8 e4m3fn its
# largest finite (the cast saturates NaN-ward past it, hence the clip in
# the quantizer)
QMAX = {'int8': 127.0, 'fp8': 448.0}


def _qdtype(mode: str):
    if mode == 'int8':
        return jnp.dtype(jnp.int8)
    if mode == 'fp8':
        return jnp.dtype(jnp.float8_e4m3fn)
    raise ValueError(f'unknown quantized stream mode {mode!r}; '
                     f"expected one of {tuple(QMAX)}")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedLeaf:
    """A matrix param streamed narrow: ``values`` (int8/fp8, the original
    leaf's shape) plus per-output-channel f32 ``scales`` (the leaf's
    shape with the contraction dim — second-to-last — reduced to 1), so
    ``values * scales`` broadcasts back to the dequantized matrix. A
    registered pytree node: quantized param trees pass through ``jit``
    boundaries and ``tree_map`` like plain trees."""

    values: jax.Array
    scales: jax.Array

    def tree_flatten(self):
        return (self.values, self.scales), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def shape(self):
        return self.values.shape

    @property
    def nbytes(self) -> int:
        return self.values.nbytes + self.scales.nbytes


def quantize_leaf(leaf, mode: str) -> QuantizedLeaf:
    """Per-output-channel symmetric quantization of one ``[..., in, out]``
    matrix: ``scales = absmax(leaf, axis=-2) / QMAX``, values rounded
    (int8) or cast (fp8) after clipping into the representable range.
    All-zero columns get scale 1 so the dequant stays finite."""
    qdtype = _qdtype(mode)
    qmax = QMAX[mode]
    wide = leaf.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wide), axis=-2, keepdims=True)
    scales = jnp.where(absmax > 0.0, absmax, qmax) / qmax
    scaled = jnp.clip(wide / scales, -qmax, qmax)
    if mode == 'int8':
        scaled = jnp.round(scaled)
    return QuantizedLeaf(scaled.astype(qdtype), scales)


def dequantize_leaf(leaf: QuantizedLeaf, compute=None) -> jax.Array:
    """``values * scales`` in f32, rounded once to ``compute`` (default:
    float32) — what the non-fused decode path feeds the model per step."""
    wide = leaf.values.astype(jnp.float32) * leaf.scales
    return wide if compute is None else wide.astype(compute)


def _is_quantized(node) -> bool:
    return isinstance(node, QuantizedLeaf)


def quantize_streamed(params, mode: str):
    """Quantize a param tree's streamed matrices to ``mode``
    (``'int8'``/``'fp8'``), leaving every other leaf untouched.

    The leaf rule is exactly the decode caster's
    (:func:`tpusystem.train.generate._caster`): float matrices with
    ``ndim >= 2``, excluding embedding tables (the embed step adds
    wte+wpe rows in f32; for a tied head the table must stay exact) and
    MoE routers (f32 gate logits — a quantized router could flip
    near-tie expert choices). Biases and layernorm params are vectors
    and fall through unchanged. Jit this once per mode
    (``generate``'s ``_quantizer`` cache) — an uncached quantize would
    retrace per call, the round-5 trap."""
    qdtype = _qdtype(mode)   # validates the mode eagerly
    del qdtype

    def quantize(path, leaf):
        from tpusystem.parallel.sharding import leaf_path
        path = leaf_path(path)
        if 'embedding' in path or 'router' in path:
            return leaf
        if leaf.ndim >= 2 and jnp.issubdtype(leaf.dtype, jnp.floating):
            return quantize_leaf(leaf, mode)
        return leaf

    return jax.tree_util.tree_map_with_path(quantize, params)


def dequantize_streamed(params, compute=None):
    """Replace every :class:`QuantizedLeaf` in ``params`` with its
    dequantized matrix (identity — the same tree object — when nothing
    is quantized, so wrapping an unquantized path costs nothing and
    changes no bits)."""
    leaves = jax.tree_util.tree_leaves(params, is_leaf=_is_quantized)
    if not any(_is_quantized(leaf) for leaf in leaves):
        return params
    return jax.tree_util.tree_map(
        lambda leaf: dequantize_leaf(leaf, compute) if _is_quantized(leaf)
        else leaf,
        params, is_leaf=_is_quantized)


def qdot(x, w, *, compute=None):
    """Quantization-aware ``x @ w`` with f32 accumulation.

    For a :class:`QuantizedLeaf`, the streamed narrow values are the
    matmul operand (cast to the compute dtype tile-side — the form whose
    HBM traffic is the narrow bytes) and the per-channel scale multiplies
    the f32 accumulator once — the exact epilogue the Pallas decode
    kernels apply, so this is their einsum fallback/reference. Plain
    arrays degrade to a cast matmul. Returns float32."""
    contract = (((x.ndim - 1,), (0,)), ((), ()))
    if isinstance(w, QuantizedLeaf):
        compute = jnp.dtype(compute or x.dtype)
        product = f32_accum_dot(x, w.values.astype(compute), contract)
        return product * w.scales.reshape(-1)
    return f32_accum_dot(x, w.astype(compute or x.dtype), contract)


@functools.lru_cache(maxsize=None)
def fp8_unsupported_reason() -> str | None:
    """Capability probe: can the current backend cast to and matmul from
    ``float8_e4m3fn``? ``None`` when it can, else a reason string for
    ``pytest.mark.skipif`` / the ``stream_dtype='fp8'`` gate. One small
    compile, cached for the life of the process."""
    try:
        @jax.jit
        def probe(x):
            narrow = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            return f32_accum_dot(narrow, narrow, (((1,), (0,)), ((), ())))
        total = float(jnp.sum(probe(jnp.ones((8, 8), jnp.float32))))
    except Exception as error:   # unsupported lowering on this backend
        return (f'fp8 ops failed on {jax.default_backend()}: '
                f'{str(error)[:200]}')
    return (None if total == 8.0 ** 3
            else f'fp8 round trip returned {total}, expected 512')
