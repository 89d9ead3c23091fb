"""Per-channel symmetric KV-cache quantization Pallas kernels.

The wire-compression path of the disaggregated KV handoff (see
``repro.serving.resources.KVCompressionConfig``) ships quantized KV blocks
over the prefill->decode fabric and dequantizes on the decode replica.
These kernels are the measured artifact that grounds the simulator's
compression parameters:

  - **wire ratio** — the packed artifact's bytes per raw bf16 byte is read
    off the actual kernel outputs (:func:`measured_wire_ratio`), not
    guessed: int8 values + one f32 scale per channel per 128-token block
    give ``33/64``; int4 packs two values per byte for ``17/64``.
  - **error bound** — per-channel symmetric round-to-nearest bounds the
    absolute error by ``scale/2 = absmax / (2 * qmax)`` per channel, i.e.
    ``1/254`` (int8) / ``1/14`` (int4) of the channel absmax; asserted
    against the pure-JAX oracle in tests/test_kvcomp.py.

Layout: a KV block is (T, C) — T tokens (the fabric's canonical block is
``BLOCK_T = 128``) by C channels (layers x kv-heads x head_dim flattened).
Scales are per *channel* (axis 0 reduction): decode-time dequantization
streams the block once and rescales columns, which is HBM-bandwidth bound —
exactly the cost model ``KVCompressionConfig`` charges.

The grid runs over channel blocks; each kernel instance sees all T tokens
of its channels so the absmax reduction stays in-kernel (no cross-block
pass).  int4 packs adjacent token pairs into one byte (lo nibble = even
token), so T must be even.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import kv_dequant_ref, kv_quant_ref
from .sgmv import _interpret, _pick_block

Array = jax.Array

BLOCK_T = 128                        # canonical KV wire block, in tokens
QMAX = {8: 127, 4: 7}
# wire bytes per raw bf16 byte at the canonical block, as measured from the
# packed kernel artifacts (values + f32 scales; see measured_wire_ratio)
WIRE_RATIO = {8: (BLOCK_T + 4) / (2 * BLOCK_T),
              4: (BLOCK_T // 2 + 4) / (2 * BLOCK_T)}
# worst-case |dequant - x| per channel, as a fraction of the channel absmax
ERROR_BOUND = {8: 1 / 254, 4: 1 / 14}


def _quant_body(x_ref, qmax: float):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=0, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    return q, scale


def _quant8_kernel(x_ref, q_ref, s_ref):
    q, scale = _quant_body(x_ref, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _quant4_kernel(x_ref, q_ref, s_ref):
    _, scale = _quant_body(x_ref, 7.0)
    rows = q_ref.shape[0]

    def nibble(start):                           # even / odd tokens
        x = x_ref[pl.ds(start, rows, stride=2), :].astype(jnp.float32)
        q = jnp.clip(jnp.round(x / scale), -7.0, 7.0)
        return q.astype(jnp.int32) & 0xF         # two's-complement nibble

    q_ref[...] = (nibble(0) | (nibble(1) << 4)).astype(jnp.uint8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bits", "block_c", "interpret"))
def kv_quantize(x: Array, *, bits: int = 8, block_c: int = 128,
                interpret: bool | None = None):
    """Quantize a (T, C) KV block per channel.

    Returns ``(packed, scales)``: packed is (T, C) int8 for 8 bits or
    (T//2, C) uint8 for 4 bits (token pairs share a byte); scales is
    (1, C) f32.  The packed + scale bytes ARE the wire bytes the serving
    fabric accounts for.
    """
    T, C = x.shape
    if bits not in QMAX:
        raise ValueError(f"bits must be one of {sorted(QMAX)}, got {bits}")
    if bits == 4 and T % 2:
        raise ValueError("int4 packing needs an even token count")
    bc = _pick_block(C, block_c)
    rows = T if bits == 8 else T // 2
    kernel = _quant8_kernel if bits == 8 else _quant4_kernel
    vdtype = jnp.int8 if bits == 8 else jnp.uint8
    return pl.pallas_call(
        kernel,
        grid=(C // bc,),
        in_specs=[pl.BlockSpec((T, bc), lambda j: (0, j))],
        out_specs=[pl.BlockSpec((rows, bc), lambda j: (0, j)),
                   pl.BlockSpec((1, bc), lambda j: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((rows, C), vdtype),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)],
        interpret=_interpret(interpret),
    )(x)


def _dequant8_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32)
                  * s_ref[...]).astype(o_ref.dtype)


def _dequant4_kernel(q_ref, s_ref, o_ref):
    v = q_ref[...].astype(jnp.int32)
    lo = ((v & 0xF) ^ 8) - 8                     # sign-extend low nibble
    hi = ((v >> 4) ^ 8) - 8
    rows, bc = v.shape
    q = jnp.stack([lo, hi], axis=1).reshape(2 * rows, bc)
    o_ref[...] = (q.astype(jnp.float32) * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bits", "out_dtype", "block_c",
                                    "interpret"))
def kv_dequantize(packed: Array, scales: Array, *, bits: int = 8,
                  out_dtype=jnp.float32, block_c: int = 128,
                  interpret: bool | None = None) -> Array:
    """Invert :func:`kv_quantize`; returns the (T, C) dequantized block."""
    rows, C = packed.shape
    if bits not in QMAX:
        raise ValueError(f"bits must be one of {sorted(QMAX)}, got {bits}")
    T = rows if bits == 8 else 2 * rows
    bc = _pick_block(C, block_c)
    kernel = _dequant8_kernel if bits == 8 else _dequant4_kernel
    return pl.pallas_call(
        kernel,
        grid=(C // bc,),
        in_specs=[pl.BlockSpec((rows, bc), lambda j: (0, j)),
                  pl.BlockSpec((1, bc), lambda j: (0, j))],
        out_specs=pl.BlockSpec((T, bc), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((T, C), out_dtype),
        interpret=_interpret(interpret),
    )(packed, scales)


def kv_roundtrip_ref(x: Array, bits: int = 8) -> Array:
    """Pure-JAX reference round trip (oracle for the Pallas pair)."""
    q, s = kv_quant_ref(x, bits)
    return kv_dequant_ref(q, s)


def measured_wire_ratio(bits: int, n_tokens: int = BLOCK_T,
                        n_channels: int = 256) -> float:
    """Wire bytes per raw bf16 byte, read off the packed kernel artifacts
    (this is where the serving simulator's ratios come from)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (n_tokens, n_channels),
                          jnp.bfloat16)
    packed, scales = kv_quantize(x.astype(jnp.float32), bits=bits)
    return (packed.nbytes + scales.nbytes) / x.nbytes
