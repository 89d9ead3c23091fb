"""Int8 per-output-channel quantization for adapter / basis weights.

Same symmetric absmax scheme as `kv_quant.py`, applied per weight matrix
instead of per 128-token KV block: every *output channel* (a row of a LoRA
``A``/``B`` factor or a column of a shared basis ``V``) gets one float32
scale computed over its *input* axis, so the matmul against a quantized
bank is exact up to a single per-channel rescale that the fused decode
kernel (`fused_decode.py`) folds into its epilogue.

Layouts (``axis`` = the input/reduction axis of the matrix):

* LoRA ``A`` bank ``(..., r, d_in)``     -> ``axis=-1``, scales ``(..., r, 1)``
* LoRA ``B`` / basis ``U`` ``(..., d, r)`` -> ``axis=-1``, scales ``(..., d, 1)``
* basis ``V`` ``(..., d_in, r)``          -> ``axis=-2``, scales ``(..., 1, r)``

Residency math: a quantized bank costs ``values * 1 byte + channels * 4
bytes`` against ``values * 4`` for float32 training-output banks — a
~3.2-3.9x cut in `PagedPool` adapter pages for the ranks we serve.
Validated against the `ref.py` oracles `adapter_quant_ref` /
`adapter_dequant_ref`; the roundtrip error is bounded by the same
per-channel `ERROR_BOUND` as the KV kernels (absmax / 254 for int8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .kv_quant import ERROR_BOUND, QMAX
from .sgmv import _interpret, _pick_block

Array = jax.Array

INT8_SCALE_BYTES = 4                     # one f32 scale per output channel


def _quant_matrix(x, axis: int):
    """Symmetric per-channel int8 over one reduction axis (kv_quant's
    `_quant_body` scheme, matrix-shaped)."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / QMAX[8], 1.0)
    q = jnp.clip(jnp.round(xf / scale), -QMAX[8], QMAX[8])
    return q.astype(jnp.int8), scale


def _quant_rows_kernel(x_ref, q_ref, s_ref):
    q, s = _quant_matrix(x_ref[0], axis=1)   # (br, C): scale per row
    q_ref[0], s_ref[0] = q, s


def _quant_cols_kernel(x_ref, q_ref, s_ref):
    q, s = _quant_matrix(x_ref[0], axis=0)   # (R, bc): scale per column
    q_ref[0], s_ref[0] = q, s


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[0] = (q_ref[0].astype(jnp.float32) * s_ref[0]).astype(o_ref.dtype)


def _norm_axis(ndim: int, axis: int) -> int:
    axis = axis % ndim
    if axis not in (ndim - 1, ndim - 2):
        raise ValueError("axis must be one of the trailing two (matrix) dims")
    return axis


@functools.partial(jax.jit, static_argnames=("axis", "block", "interpret"))
def adapter_quantize(w: Array, *, axis: int = -1, block: int = 256,
                     interpret: bool | None = None):
    """Quantize a bank of weight matrices ``w (..., R, C)`` to int8 plus
    float32 per-output-channel scales (keepdims along ``axis``)."""
    if w.ndim < 2:
        raise ValueError("adapter_quantize expects a bank of matrices")
    axis = _norm_axis(w.ndim, axis)
    lead = w.shape[:-2]
    R, C = w.shape[-2:]
    n = 1
    for d in lead:
        n *= d
    x = w.reshape(n, R, C)
    rows = axis == w.ndim - 1                # reduce over columns
    if rows:
        br = _pick_block(R, block)
        grid = (n, R // br)
        blk = (1, br, C)
        idx = lambda i, j: (i, j, 0)
        s_blk, s_shape = (1, br, 1), (n, R, 1)
    else:
        bc = _pick_block(C, block)
        grid = (n, C // bc)
        blk = (1, R, bc)
        idx = lambda i, j: (i, 0, j)
        s_blk, s_shape = (1, 1, bc), (n, 1, C)
    q, s = pl.pallas_call(
        _quant_rows_kernel if rows else _quant_cols_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(blk, idx)],
        out_specs=[pl.BlockSpec(blk, idx), pl.BlockSpec(s_blk, idx)],
        out_shape=[jax.ShapeDtypeStruct((n, R, C), jnp.int8),
                   jax.ShapeDtypeStruct(s_shape, jnp.float32)],
        interpret=_interpret(interpret),
    )(x)
    s_out = lead + ((R, 1) if rows else (1, C))
    return q.reshape(w.shape), s.reshape(s_out)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block",
                                             "interpret"))
def adapter_dequantize(q: Array, scales: Array, *,
                       out_dtype=jnp.float32, block: int = 256,
                       interpret: bool | None = None) -> Array:
    """Inverse of `adapter_quantize`; the reduction axis is recovered from
    the keepdims position in ``scales``."""
    rows = scales.shape[-1] == 1
    lead = q.shape[:-2]
    R, C = q.shape[-2:]
    n = 1
    for d in lead:
        n *= d
    if rows:
        br = _pick_block(R, block)
        grid, blk = (n, R // br), (1, br, C)
        idx = lambda i, j: (i, j, 0)
        s_blk, s_shape = (1, br, 1), (n, R, 1)
    else:
        bc = _pick_block(C, block)
        grid, blk = (n, C // bc), (1, R, bc)
        idx = lambda i, j: (i, 0, j)
        s_blk, s_shape = (1, 1, bc), (n, 1, C)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(blk, idx), pl.BlockSpec(s_blk, idx)],
        out_specs=pl.BlockSpec(blk, idx),
        out_shape=jax.ShapeDtypeStruct((n, R, C), out_dtype),
        interpret=_interpret(interpret),
    )(q.reshape(n, R, C), scales.reshape(s_shape))
    return out.reshape(q.shape)


def quantized_nbytes(shape, *, axis: int = -1) -> int:
    """Bytes of the packed representation: int8 values + one f32 scale per
    output channel (what the quantized bank actually occupies in the pool)."""
    axis = _norm_axis(len(shape), axis)
    values = 1
    for d in shape:
        values *= d
    channels = values // shape[axis]
    return values + INT8_SCALE_BYTES * channels


def int8_error_bound(w: Array, *, axis: int = -1) -> Array:
    """Worst-case absolute roundtrip error per channel (same bound family
    as kv_quant's `ERROR_BOUND`)."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis,
                     keepdims=True)
    return absmax * ERROR_BOUND[8]
