"""Pallas TPU flash-decode attention over a block-paged KV cache.

Reference parity: block_multihead_attention — the paged/block-KV decode
kernel the reference ships for serving
(/root/reference/paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu)
— crossed with Flash-Decoding's split-K cache reads (Dao et al.) and
PagedAttention's block tables (Kwon et al., vLLM).

TPU-native design (NOT a kernel translation):
  - The KV cache lives as fixed-size blocks `[num_blocks, H_kv,
    block_size, D]` and each sequence owns a BLOCK TABLE `[pages]` of
    block ids. The kernel grid is `(seq, kv_head, page)`; the page axis is
    the innermost grid dimension, so the f32 running-max/sum/acc scratch
    persists across the cache sweep — exactly the flash-decode split-K
    merge, with the block table consulted by the BlockSpec index_map via
    scalar prefetch (the DMA engine gathers non-contiguous cache blocks;
    no gather tensor is ever materialized).
  - Layout note: the issue-level sketch writes `[num_blocks, block_size,
    H_kv, D]`; the cache here is `[num_blocks, H_kv, block_size, D]` so a
    per-(block, head) tile is the contiguous (sublane=tokens, lane=D)
    MXU tile — with H_kv inside, every block fetch would stride by head.
  - GQA packing: all `H_q/H_kv` query heads sharing a KV head ride ONE
    [group, D] tile (padded to the sublane minimum), so the whole group's
    scores come from one MXU pass per cache block. Decode is pure HBM
    bandwidth: every cache byte is read exactly once per step.
  - Optional int8 KV: the cache stores int8 with ONE f32 scale per block
    (text/paged_cache.py maintains them by block requantization on
    append); the kernel reads per-(seq, page) scales from scalar-prefetch
    SMEM and folds k's scale into the logits, v's into the pv partial —
    decode cache reads halve again on top of bf16.

Same layering as pallas_attention.py / pallas_norm.py: bf16/f32 in/out
with f32 VMEM accumulation, `interpret` mode off-TPU (how the parity
tests run on CPU), routing via `use_pallas_decode` with the XLA
composition (`paged_decode_attention_xla`) as the everywhere-else path,
and the gating reasons mirrored by analysis D4 (`decode_gate_reason`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ._pallas_common import ceil_to as _ceil_to
from ._pallas_common import interpret as _interpret
from ._pallas_common import pltpu
from ._pallas_common import x64_guard as _x64_guard

# see pallas_attention.py: paddle_tpu enables x64 globally, so every kernel
# scalar must be an explicitly-typed np.float32 or Mosaic sees f64
_NEG_INF = np.float32(-1e30)
_ZERO = np.float32(0.0)
_ONE = np.float32(1.0)

#: reporting/routing floor: potential score elements (S * H_q * pages *
#: block_size) below this are launch-overhead-bound — the XLA composition
#: wins (mirrored by analysis D4's decode gate reason)
_MIN_ELEMS = 1 << 16
#: cache dtypes the kernel can stream (int8 needs the per-block scales;
#: "int4" is packed int8 storage — two tokens per byte along the token
#: axis — unpacked inside the kernel)
_SUPPORTED_DTYPES = ("float32", "bfloat16", "float16", "int8", "int4")


# ------------------------------------------------------------------ kernel

def _decode_kernel(tab_ref, len_ref, *rest, scale, block_size, has_scale,
                   packed=False):
    """One (seq, kv_head, page) grid step: the GQA query group attends to
    one cache block, merged into the running flash state.

    tab_ref/len_ref (+ ks_ref/vs_ref when has_scale): scalar-prefetch SMEM
    (block table [S, P], kv lengths [S], per-(seq, page) dequant scales).
    q is [1, 1, Gp, D]; k/v blocks are [1, 1, block_size, D] picked by the
    index_map from the block table — or [1, 1, block_size/2, D] int4-packed
    when `packed` (split-half along tokens: byte t holds token t in the low
    nibble, token bs/2 + t in the high — unpacked HERE so the packed bytes
    are the only cache traffic).
    """
    if has_scale:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s = rest
    else:
        q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s = rest
    si = pl.program_id(0)
    pi = pl.program_id(2)
    n_p = pl.num_programs(2)

    def unpack(p):
        # widened first: v5e's Mosaic legalizes no shift on vector<i8>
        p = p.astype(jnp.int32)
        lo = jnp.right_shift(jnp.left_shift(p, 28), 28)
        hi = jnp.right_shift(p, 4)
        return jnp.concatenate([lo, hi], axis=0)       # [bs, D]

    @pl.when(pi == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    seq_len = len_ref[si]
    page_start = pi * block_size

    @pl.when(page_start < seq_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * np.float32(scale)  # [Gp, D]
        k = k_ref[0, 0]                                          # [bs, D]
        if packed:
            k = unpack(k)
        k = k.astype(jnp.float32)
        if has_scale:
            k = k * ks_ref[si, pi]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # the tail page is partially valid; interior pages are full — one
        # masked path keeps the kernel small (the page grid is the cost)
        cols = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < seq_len
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, _ZERO)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0]                                          # [bs, D]
        if packed:
            v = unpack(v)
        v = v.astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if has_scale:
            pv = pv * vs_ref[si, pi]
        acc[:] = acc[:] * alpha + pv
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(pi == n_p - 1)
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l == _ZERO, _ONE, l)
        o_ref[0, 0] = (acc[:] / safe_l).astype(o_ref.dtype)


def paged_decode_attention_raw(q, k_cache, v_cache, block_tables, seq_lens,
                               k_scale=None, v_scale=None, kv_int4=False):
    """The Pallas kernel path. q [S, H_q, D]; caches [N, H_kv, bs, D]
    (int8 when k_scale/v_scale [N] f32 are given; int4-packed
    [N, H_kv, bs/2, D] when kv_int4); block_tables [S, P] int32 (entries
    < 0 tolerated as padding); seq_lens [S] valid kv lengths. Returns
    [S, H_q, D] in q.dtype."""
    with _x64_guard():
        return _paged_decode_x32(q, k_cache, v_cache, block_tables,
                                 seq_lens, k_scale, v_scale, kv_int4)


def _paged_decode_x32(q, k_cache, v_cache, block_tables, seq_lens,
                      k_scale=None, v_scale=None, kv_int4=False):
    s_n, hq, d = q.shape
    n_blocks, hkv, bs, dc = k_cache.shape
    if kv_int4:
        if k_scale is None:
            raise ValueError("int4 KV needs per-block scales")
        bs = bs * 2          # logical tokens per block (two per byte)
    if d != dc:
        raise ValueError(f"head_dim mismatch: q {d} vs cache {dc}")
    if hq % hkv:
        raise ValueError(f"H_q {hq} not a multiple of H_kv {hkv}")
    g = hq // hkv
    # GQA pack: q heads [i*g, (i+1)*g) share kv head i; pad the group axis
    # to the bf16 sublane minimum so one tile serves every input dtype
    gp = _ceil_to(max(g, 16), 16)
    q4 = q.reshape(s_n, hkv, g, d)
    q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    tables = jnp.maximum(block_tables, 0).astype(jnp.int32)
    lens = seq_lens.astype(jnp.int32)
    pages = tables.shape[1]
    scale = 1.0 / float(np.sqrt(d))
    has_scale = k_scale is not None

    kernel = functools.partial(_decode_kernel, scale=scale, block_size=bs,
                               has_scale=has_scale, packed=kv_int4)

    # index maps see (grid ids..., *scalar-prefetch refs); the cache block
    # index comes straight from the prefetched block table — the grid
    # pipeline DMAs non-contiguous pages, no gather materializes. Pages at
    # or past the sequence length clamp to the LAST VALID page: the
    # pipeline elides the DMA when consecutive grid steps resolve to the
    # same block, so a long-budget request early in decode (table full of
    # allocated-but-unwritten pages) doesn't stream dead cache blocks —
    # the in-kernel pl.when already skips their compute.
    def kv_index(s, h, p, tab, lens_ref, *refs):
        last = jnp.maximum(lens_ref[s] - 1, 0) // bs
        return (tab[s, jnp.minimum(p, last)], h, 0, 0)

    q_spec = pl.BlockSpec((1, 1, gp, d),
                          lambda s, h, p, *refs: (s, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, k_cache.shape[2], d), kv_index)
    o_spec = pl.BlockSpec((1, 1, gp, d),
                          lambda s, h, p, *refs: (s, h, 0, 0))
    args = [tables, lens]
    if has_scale:
        # per-(seq, page) dequant scales, gathered host-of-kernel from the
        # per-block scales (tiny: S*P f32 in SMEM)
        args += [k_scale[tables].astype(jnp.float32),
                 v_scale[tables].astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(args),
        grid=(s_n, hkv, pages),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[o_spec],
        scratch_shapes=[
            pltpu.VMEM((gp, d), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
        ],
    )
    out, = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s_n, hkv, gp, d), q.dtype)],
        interpret=_interpret(), name="paged_decode",
    )(*args, q4, k_cache, v_cache)
    return out[:, :, :g].reshape(s_n, hq, d)


# ------------------------------------------------------- XLA composition

def paged_decode_attention_xla(q, k_cache, v_cache, block_tables, seq_lens,
                               k_scale=None, v_scale=None, kv_int4=False):
    """The gather + masked-softmax composition — the numerics oracle for
    the kernel and the off-TPU / gated-off route. Score/output dtype
    conventions match text/generation.py's dense decode attention so the
    paged engine is token-parity-comparable with the single-program one.
    """
    s_n, hq, d = q.shape
    n_blocks, hkv, bs, _ = k_cache.shape
    pages = block_tables.shape[1]
    tabs = jnp.maximum(block_tables, 0)
    k = k_cache[tabs]                        # [S, P, Hkv, bs(/2), D]
    v = v_cache[tabs]
    if kv_int4:
        from .quantized import int4_unpack

        bs = bs * 2
        k = int4_unpack(k, bs, axis=-2)
        v = int4_unpack(v, bs, axis=-2)
    if k_scale is not None:
        k = (k.astype(jnp.float32)
             * k_scale[tabs][:, :, None, None, None]).astype(q.dtype)
        v = (v.astype(jnp.float32)
             * v_scale[tabs][:, :, None, None, None]).astype(q.dtype)
    t = pages * bs
    k = jnp.swapaxes(k, 2, 3).reshape(s_n, t, hkv, d)
    v = jnp.swapaxes(v, 2, 3).reshape(s_n, t, hkv, d)
    rep = hq // hkv
    if rep != 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("shd,sthd->sht", q, k) / np.sqrt(d).astype(
        np.float32)
    valid = jnp.arange(t)[None, :] < seq_lens[:, None]
    scores = jnp.where(valid[:, None, :], scores,
                       jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("sht,sthd->shd", probs, v)


# --------------------------------------------------------------- routing

def decode_gate_reason(n_elems, dtype, platform, head_dim=None,
                       block_size=None):
    """Why the decode router would decline this shape — ONE definition
    consulted by both `use_pallas_decode` and analysis D4, so the reported
    reason is the real one. Returns (reason, severity): legitimate gates
    are notes, no-reason is the should-have-routed warning."""
    from ..core.flags import flag

    if not flag("FLAGS_pallas_decode"):
        return "FLAGS_pallas_decode=0 (decode kernel disabled)", "note"
    if platform != "tpu":
        return ("not on TPU — the XLA composition is the intended "
                "fallback path here"), "note"
    if n_elems is not None and n_elems < _MIN_ELEMS:
        return (f"below the decode-kernel size threshold ({n_elems} < "
                f"{_MIN_ELEMS} score elements: launch overhead beats the "
                "bandwidth saving)"), "note"
    if dtype is not None and dtype not in _SUPPORTED_DTYPES:
        return f"dtype {dtype} unsupported by the decode kernel", "note"
    if head_dim is not None and head_dim % 128:
        return (f"head_dim {head_dim} not lane-aligned (128) — the cache "
                "tile would need repacking"), "note"
    if block_size is not None and block_size % 8:
        return (f"kv block_size {block_size} not sublane-aligned (8)"), \
            "note"
    if dtype == "int4" and block_size is not None and block_size % 16:
        return (f"kv block_size {block_size} not packed-sublane-aligned "
                "(16: the int4 tile holds block_size/2 bytes)"), "note"
    return ("no gating reason — this composition should have routed to "
            "the Pallas decode kernel"), "warning"


def use_pallas_decode(q, k_cache, block_tables, kv_int4=False) -> bool:
    """True when the paged decode should ride the Pallas kernel here."""
    s_n, hq, d = q.shape
    _, _, bs, _ = k_cache.shape
    if kv_int4:
        bs = bs * 2
    n = s_n * hq * block_tables.shape[1] * bs
    _, sev = decode_gate_reason(n, "int4" if kv_int4
                                else str(k_cache.dtype),
                                jax.default_backend(), head_dim=d,
                                block_size=bs)
    return sev == "warning"


def paged_decode_attention(q, k_cache, v_cache, block_tables, seq_lens,
                           k_scale=None, v_scale=None, kv_int4=False):
    """Routed paged decode attention (kernel on TPU above threshold, XLA
    composition everywhere else). Same contract as the _raw kernel;
    `kv_int4=True` declares the caches int4-packed along the token axis
    (k_scale/v_scale required)."""
    if use_pallas_decode(q, k_cache, block_tables, kv_int4):
        return paged_decode_attention_raw(q, k_cache, v_cache,
                                          block_tables, seq_lens,
                                          k_scale, v_scale, kv_int4)
    return paged_decode_attention_xla(q, k_cache, v_cache, block_tables,
                                      seq_lens, k_scale, v_scale, kv_int4)
