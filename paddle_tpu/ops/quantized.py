"""Low-bit weight storage: true int4 packing + fused dequant-matmul.

Reference parity: the slim/quant family (weight_only_linear / weight_quantize
/ llm.int8 in the phi kernel zoo) stores int4 weights two-nibbles-per-byte
and dequantizes inside the GEMM. Until this round the TPU port quantized
"int4" at int8 resolution — zero additional bandwidth saved. PERF.md round 5
showed weight-only decode is bandwidth-bound (int8 = stable 1.67×, int8
*compute* a wash), so the only thing that matters is the bytes the weight
stream moves: this module makes the packed bytes the ONLY HBM traffic for
the weight.

Layout — split-half, NOT interleaved: a [K, N] int4 tensor packs as
[ceil(K/2), N] int8 where packed row i holds logical row i in the LOW nibble
and row ceil(K/2)+i in the HIGH nibble. Unpacking is two shifts and a
concat — no lane shuffles, TPU-sublane-friendly (an interleaved layout would
need an odd/even de-shuffle across sublanes). Odd K pads one zero row. The
same rule applies along any axis (`axis=`), which is how the paged KV cache
packs int4 along its block_size (token) axis.

Three consumers share ONE quantization rule and ONE dequant-matmul:
  - `weight_quantize(algo="weight_only_int4")` / `weight_only_linear`
    (incubate/nn/functional) — the public op surface;
  - the dense block's `_mm` (text/models/dense_block.py) — stacked
    per-layer weights ride lax.scan as (packed, scale) pytree leaves;
  - the paged ServingEngine's per-slot decode matmuls + lm_head
    (inference/engine.py).
int8 vs int4 is disambiguated by shape — packed storage has ceil(K/2) rows
where x has K columns — so the (q, scale) 2-tuple convention the scan
carriers already use is unchanged.

Routing follows ops/pallas_decode.py: `quant_gate_reason` is the ONE
definition consulted by both the router and analysis D4/D20, so the
reported reason is the real one. The XLA take-bits composition
(shift/shift/concat, fused by XLA into the dequant consumer) is the oracle
and the everywhere-else path; the Pallas kernel unpacks + scales in VMEM so
the packed bytes are the only weight bytes fetched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ._pallas_common import ceil_to as _ceil_to
from ._pallas_common import interpret as _interpret
from ._pallas_common import pltpu
from ._pallas_common import x64_guard as _x64_guard

#: routing floor: below this many MACs the launch overhead beats the
#: bandwidth saving (decode matmuls at serving batch sizes sit well above)
_MIN_MACS = 1 << 20
#: int4 value range: symmetric, -7..7 (one code unused, keeps the scale rule
#: identical in form to the int8 127 rule)
INT4_QMAX = 7.0


def packed_rows(k: int) -> int:
    """Packed extent along the quantized axis for a logical extent k."""
    return (k + 1) // 2


# ---------------------------------------------------------------- pack bits

def int4_pack(q, axis=0):
    """Pack an int8 tensor holding int4 values (-8..7) two-per-byte along
    `axis` (split-half layout, see module docstring). Odd extents pad one
    zero slot. Returns int8 with shape[axis] == ceil(k/2)."""
    q = jnp.asarray(q, jnp.int8)
    axis = axis % q.ndim
    k = q.shape[axis]
    h = packed_rows(k)
    lo = lax.slice_in_dim(q, 0, h, axis=axis)
    hi = lax.slice_in_dim(q, h, k, axis=axis)
    if k % 2:  # pad the high half back to h slots
        pad = [(0, 0)] * q.ndim
        pad[axis] = (0, 1)
        hi = jnp.pad(hi, pad)
    # low nibble = first half's bits, high nibble = second half (int8 shifts
    # wrap, which is exactly two's-complement nibble placement)
    return jnp.bitwise_or(jnp.left_shift(hi, 4),
                          jnp.bitwise_and(lo, jnp.int8(0x0F))).astype(jnp.int8)


def int4_unpack(p, k, axis=0):
    """Inverse of int4_pack: int8 packed tensor -> int8 values in -8..7 with
    shape[axis] == k. Pure take-bits: left-shift wraps the low nibble into
    the sign position, arithmetic right-shift sign-extends it back."""
    p = jnp.asarray(p, jnp.int8)
    axis = axis % p.ndim
    lo = jnp.right_shift(jnp.left_shift(p, 4), 4)
    hi = jnp.right_shift(p, 4)
    out = jnp.concatenate([lo, hi], axis=axis)
    return lax.slice_in_dim(out, 0, k, axis=axis)


# -------------------------------------------------------------- quantize

def quantize_int4(w, group_size: int = -1):
    """Symmetric int4 quantization of a [K, N] weight: per-OUTPUT-channel
    absmax scales ([N], matching weight_quantize_raw's int8 rule) or
    group-wise along K ([K//group_size, N]) when group_size > 0. Returns
    (packed [ceil(K/2), N] int8, scale f32)."""
    w = jnp.asarray(w)
    k, n = w.shape[-2], w.shape[-1]
    if group_size and group_size > 0:
        if k % group_size:
            raise ValueError(
                f"group_size {group_size} does not divide K={k}")
        g = k // group_size
        wg = w.reshape(w.shape[:-2] + (g, group_size, n))
        amax = jnp.max(jnp.abs(wg), axis=-2)                    # [..., G, N]
        scale = jnp.maximum(amax / INT4_QMAX, 1e-8).astype(jnp.float32)
        q = jnp.clip(jnp.round(wg / scale[..., :, None, :]),
                     -INT4_QMAX, INT4_QMAX)
        q = q.reshape(w.shape).astype(jnp.int8)
    else:
        amax = jnp.max(jnp.abs(w), axis=-2)                     # [..., N]
        scale = jnp.maximum(amax / INT4_QMAX, 1e-8).astype(jnp.float32)
        q = jnp.clip(jnp.round(w / scale[..., None, :]),
                     -INT4_QMAX, INT4_QMAX).astype(jnp.int8)
    return int4_pack(q, axis=-2), scale


def dequant_int4(packed, scale, k, dtype=jnp.float32):
    """Materializing dequant (tests / weight_dequantize): packed + scale ->
    [K, N] in `dtype`."""
    q = int4_unpack(packed, k, axis=-2).astype(dtype)
    if scale.ndim == q.ndim - 1:          # per-channel [N]
        return q * scale.astype(dtype)[..., None, :]
    g = scale.shape[-2]
    gs = k // g
    n = q.shape[-1]
    wg = q.reshape(q.shape[:-2] + (g, gs, n))
    wg = wg * scale.astype(dtype)[..., :, None, :]
    return wg.reshape(q.shape)


# ------------------------------------------------------------------ kernel

def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, *, k):
    """One N-tile: unpack the packed int4 block and scale INSIDE the kernel
    so the packed bytes are the only HBM weight traffic for this tile."""
    # widened first: v5e's Mosaic legalizes no shift on vector<i8>
    p = w_ref[...].astype(jnp.int32)                   # [K/2, bn]
    lo = jnp.right_shift(jnp.left_shift(p, 28), 28)
    hi = jnp.right_shift(p, 4)
    q = jnp.concatenate([lo, hi], axis=0)[:k]          # [K, bn]
    x = x_ref[...].astype(jnp.float32)                 # [Mp, K]
    acc = jax.lax.dot_general(x, q.astype(jnp.float32),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def quant_matmul_raw(x, packed, scale, k):
    """The Pallas fused dequant-matmul path. x [M, K]; packed
    [ceil(K/2), N] int8; scale [N] f32 per-channel. Returns [M, N] in
    x.dtype."""
    with _x64_guard():
        return _qmm_x32(x, packed, scale, k)


def _qmm_x32(x, packed, scale, k):
    m = x.shape[0]
    n = packed.shape[1]
    bn = 128
    mp = _ceil_to(max(m, 16), 16)
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
    s2 = scale.astype(jnp.float32).reshape(1, n)
    kernel = functools.partial(_qmm_kernel, k=k)
    out = pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((mp, k), lambda i: (0, 0)),
            pl.BlockSpec((packed.shape[0], bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((mp, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((mp, n), x.dtype),
        interpret=_interpret(), name="quant_matmul_int4",
    )(x, packed, s2)
    return out[:m]


# --------------------------------------------------------------- routing

def quant_gate_reason(m, k, n, dtype, platform, grouped=False):
    """Why the int4 dequant-matmul router would decline this shape — ONE
    definition consulted by the router AND analysis (D4/D20), mirroring
    pallas_decode.decode_gate_reason. Returns (reason, severity)."""
    from ..core.flags import flag

    if not flag("FLAGS_pallas_quant_matmul"):
        return ("FLAGS_pallas_quant_matmul=0 (fused dequant-matmul "
                "kernel disabled)"), "note"
    if platform != "tpu":
        return ("not on TPU — the XLA take-bits composition is the "
                "intended fallback path here"), "note"
    if grouped:
        return ("group-wise scales ride the XLA take-bits composition "
                "(the kernel streams per-channel scales only)"), "note"
    if dtype is not None and dtype not in ("float32", "bfloat16"):
        return f"dtype {dtype} unsupported by the dequant-matmul kernel", \
            "note"
    if k % 64:
        return (f"K={k} not packed-sublane-aligned (64: K/2 must hit the "
                "int8 sublane minimum 32)"), "note"
    if n % 128:
        return f"N={n} not lane-aligned (128)", "note"
    if m is not None and m * k * n < _MIN_MACS:
        return (f"below the dequant-matmul size threshold ({m * k * n} < "
                f"{_MIN_MACS} MACs: launch overhead beats the bandwidth "
                "saving)"), "note"
    return ("no gating reason — this composition should have routed to "
            "the Pallas dequant-matmul kernel"), "warning"


def use_quant_matmul(m, k, n, dtype, grouped=False) -> bool:
    _, sev = quant_gate_reason(m, k, n, dtype, jax.default_backend(),
                               grouped=grouped)
    return sev == "warning"


def quant_matmul(x, w, scale):
    """Routed dequant-matmul over a quantized weight pair — the single
    shared routine behind the dense block's `_mm`, `weight_only_linear`
    and the serving engine's per-slot matmuls.

    x [..., K]; (w, scale) is either int8 (w [K, N], the historical pair)
    or packed int4 (w [ceil(K/2), N]) — disambiguated by shape. scale [N]
    per-channel or [G, N] group-wise. Returns [..., N] in x.dtype."""
    k = x.shape[-1]
    grouped = scale.ndim == 2
    if w.shape[0] == k:  # int8 — preserve the exact historical math
        if grouped:
            g = scale.shape[0]
            gs = k // g
            n = w.shape[1]
            wf = (w.reshape(g, gs, n).astype(x.dtype)
                  * scale.astype(x.dtype)[:, None, :]).reshape(k, n)
            return x @ wf
        return (x @ w.astype(x.dtype)) * scale.astype(x.dtype)
    if w.shape[0] != packed_rows(k):
        raise ValueError(
            f"quantized weight rows {w.shape[0]} match neither K={k} "
            f"(int8) nor ceil(K/2)={packed_rows(k)} (packed int4)")
    n = w.shape[1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    if not grouped and use_quant_matmul(m, k, n, str(x.dtype)):
        return quant_matmul_raw(x2, w, scale, k).reshape(lead + (n,))
    # XLA take-bits composition — dequant to x.dtype (NOT f32: D20's
    # dequantize-to-f32 scan treats a widening here as a stream leak)
    if grouped:
        wf = dequant_int4(w, scale, k, x.dtype)
        return (x2 @ wf).reshape(lead + (n,))
    q = int4_unpack(w, k, axis=0)
    return ((x2 @ q.astype(x.dtype))
            * scale.astype(x.dtype)).reshape(lead + (n,))
