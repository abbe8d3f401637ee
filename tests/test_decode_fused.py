"""Serving-path decode: quantized weight streaming + the fused Pallas
decode chain.

Two layers of parity, mirroring the test_moe kernel discipline:
the quantize/dequantize pair's error bounds and leaf rule
(`ops/precision.py`) and the Pallas kernels directly against their einsum
references in interpret mode (`ops/pallas/decode_matmul.py`). The third,
the engine's whole fused step (`train/decode_fused.py`) token for token
against its flax step, is `tests/test_serve.py::TestFusedDecodeImpl`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusystem.ops.pallas.decode_matmul import (decode_ffn, decode_matmul,
                                                decode_plan)
from tpusystem.ops.precision import (QuantizedLeaf, dequantize_leaf,
                                     dequantize_streamed,
                                     fp8_unsupported_reason, qdot,
                                     quantize_leaf, quantize_streamed)

fp8_reason = fp8_unsupported_reason()
needs_fp8 = pytest.mark.skipif(fp8_reason is not None, reason=fp8_reason or '')


# --- quantize/dequantize pair --------------------------------------------

def test_quantize_leaf_int8_roundtrip_error_bound():
    """Per-output-channel symmetric int8: the dequantized matrix is within
    half a quantization step of the original, column by column."""
    leaf = jnp.asarray(np.random.default_rng(0).normal(size=(32, 48)) * 0.3,
                       jnp.float32)
    quantized = quantize_leaf(leaf, 'int8')
    assert quantized.values.dtype == jnp.int8
    assert quantized.scales.shape == (1, 48)
    roundtrip = dequantize_leaf(quantized)
    error = np.abs(np.asarray(roundtrip) - np.asarray(leaf))
    bound = np.asarray(quantized.scales)[0] / 2 + 1e-7
    assert (error <= bound[None, :]).all()


def test_quantize_leaf_all_zero_column_stays_finite():
    leaf = jnp.zeros((8, 4), jnp.float32)
    quantized = quantize_leaf(leaf, 'int8')
    roundtrip = np.asarray(dequantize_leaf(quantized))
    assert np.isfinite(roundtrip).all() and (roundtrip == 0).all()


def test_quantize_streamed_applies_the_decode_caster_leaf_rule():
    """Matrices quantize; embedding tables, MoE routers, and vector leaves
    (biases, layernorms) pass through untouched — exactly the exclusion
    set of generate's bf16 caster."""
    params = {
        'wte': {'embedding': jnp.ones((16, 8), jnp.float32)},
        'h_0': {'attn': {'qkv': {'kernel': jnp.ones((8, 24), jnp.float32),
                                 'bias': jnp.zeros((24,), jnp.float32)}},
                'ln_1': {'scale': jnp.ones((8,), jnp.float32)},
                'moe': {'router': {'kernel': jnp.ones((8, 4), jnp.float32)}}},
    }
    quantized = quantize_streamed(params, 'int8')
    assert isinstance(quantized['h_0']['attn']['qkv']['kernel'],
                      QuantizedLeaf)
    for untouched in (quantized['wte']['embedding'],
                      quantized['h_0']['attn']['qkv']['bias'],
                      quantized['h_0']['ln_1']['scale'],
                      quantized['h_0']['moe']['router']['kernel']):
        assert not isinstance(untouched, QuantizedLeaf)
        assert untouched.dtype == jnp.float32
    with pytest.raises(ValueError, match='int8'):
        quantize_streamed(params, 'int3')


def test_quantized_leaf_rides_pytrees_and_jit():
    leaf = quantize_leaf(jnp.ones((4, 8), jnp.float32) * 0.5, 'int8')
    doubled = jax.jit(lambda q: jax.tree.map(lambda a: a, q))(leaf)
    assert isinstance(doubled, QuantizedLeaf)
    np.testing.assert_array_equal(np.asarray(doubled.values),
                                  np.asarray(leaf.values))
    assert leaf.shape == (4, 8)
    assert leaf.nbytes == leaf.values.nbytes + leaf.scales.nbytes


def test_dequantize_streamed_is_identity_for_plain_trees():
    params = {'a': jnp.ones((4, 4)), 'b': jnp.zeros((3,))}
    assert dequantize_streamed(params) is params


def test_fp8_capability_probe_is_cached_and_stable():
    assert fp8_unsupported_reason() == fp8_unsupported_reason()


@needs_fp8
def test_quantize_leaf_fp8_roundtrip_is_bounded():
    leaf = jnp.asarray(np.random.default_rng(1).normal(size=(16, 16)) * 0.2,
                       jnp.float32)
    quantized = quantize_leaf(leaf, 'fp8')
    roundtrip = np.asarray(dequantize_leaf(quantized))
    assert np.isfinite(roundtrip).all()
    # e4m3 keeps ~2 mantissa-digit relative accuracy after per-channel
    # rescaling into its range
    np.testing.assert_allclose(roundtrip, np.asarray(leaf), atol=0.05)


# --- decode_plan: pinned tiling decisions --------------------------------

def test_decode_plan_pins_which_shapes_run_fused():
    # TPU mode: out-column blocks are the largest <=want lane multiple
    # dividing the width; non-lane-tileable shapes refuse (einsum path)
    assert decode_plan(256, 768, interpret=False) == 384
    assert decode_plan(256, 512, interpret=False) == 512
    assert decode_plan(256, 2304, interpret=False, want=512) == 384
    assert decode_plan(100, 768, interpret=False) is None   # inner % 128
    assert decode_plan(256, 130, interpret=False) is None   # no 128-divisor
    # interpret mode has no tiling constraint: any divisor works
    assert decode_plan(5, 7, interpret=True) == 7
    assert decode_plan(5, 6, interpret=True, want=4) == 3


# --- kernels vs einsum references (interpret mode on CPU) ----------------

@pytest.fixture(scope='module')
def operands():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 64)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    return x, w, bias


def test_decode_matmul_matches_qdot_reference(operands):
    x, w, bias = operands
    np.testing.assert_allclose(np.asarray(decode_matmul(x, w)),
                               np.asarray(qdot(x, w)), atol=1e-5)
    fused = decode_matmul(x, w, bias, activation=jax.nn.gelu, block_cols=16)
    reference = jax.nn.gelu(qdot(x, w) + bias).astype(x.dtype)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(reference),
                               atol=1e-5)


def test_decode_matmul_dequantizes_int8_tiles_in_kernel(operands):
    x, w, bias = operands
    quantized = quantize_leaf(w, 'int8')
    fused = decode_matmul(x, quantized, bias, block_cols=16)
    reference = (qdot(x, quantized) + bias).astype(x.dtype)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(reference),
                               atol=1e-5)


@needs_fp8
def test_decode_matmul_dequantizes_fp8_tiles_in_kernel(operands):
    x, w, bias = operands
    quantized = quantize_leaf(w, 'fp8')
    fused = decode_matmul(x, quantized, bias, block_cols=16)
    reference = (qdot(x, quantized) + bias).astype(x.dtype)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(reference),
                               atol=1e-5)


def test_decode_ffn_matches_the_two_matmul_chain(operands):
    """The fc->gelu->proj chain in one kernel, multi-tile grid (the
    scratch accumulator crosses 4 grid steps), plain and quantized."""
    x, w1, b1 = operands
    rng = np.random.default_rng(1)
    w2 = jnp.asarray(rng.normal(size=(64, 16)) * 0.3, jnp.float32)
    b2 = jnp.asarray(rng.normal(size=(16,)), jnp.float32)

    fused = decode_ffn(x, w1, b1, w2, b2, block_hidden=16)
    reference = (jax.nn.gelu(qdot(x, w1) + b1).astype(x.dtype) @ w2
                 + b2)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(reference),
                               atol=1e-4)

    q1, q2 = quantize_leaf(w1, 'int8'), quantize_leaf(w2, 'int8')
    fused = decode_ffn(x, q1, b1, q2, b2, block_hidden=16)
    mid = jax.nn.gelu(qdot(x, q1) + b1).astype(x.dtype)
    reference = qdot(mid, q2) + b2
    np.testing.assert_allclose(np.asarray(fused), np.asarray(reference),
                               atol=1e-4)


def test_untileable_shapes_take_the_einsum_fallback():
    """interpret=False with non-lane shapes must never reach pallas_call
    (it would fail on CPU): decode_plan refuses and the einsum path
    answers — same math."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 10)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(10, 6)), jnp.float32)
    bias = jnp.zeros((6,), jnp.float32)
    out = decode_matmul(x, w, bias, interpret=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w), atol=1e-6)
    w2 = jnp.asarray(rng.normal(size=(6, 10)), jnp.float32)
    out = decode_ffn(x, w, bias, w2, jnp.zeros((10,)), interpret=False)
    reference = jax.nn.gelu(x @ w) @ w2
    np.testing.assert_allclose(np.asarray(out), np.asarray(reference),
                               atol=1e-5)


def test_decode_matmul_rejects_mismatched_shapes():
    with pytest.raises(ValueError, match='cols'):
        decode_matmul(jnp.ones((2, 4)), jnp.ones((5, 8)))
    with pytest.raises(ValueError, match='compose'):
        decode_ffn(jnp.ones((2, 4)), jnp.ones((4, 8)), jnp.zeros(8),
                   jnp.ones((9, 4)), jnp.zeros(4))
