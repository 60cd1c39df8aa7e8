"""Pallas TPU flash-decode attention over a block-paged KV cache.

Reference parity: block_multihead_attention — the paged/block-KV decode
kernel the reference ships for serving
(/root/reference/paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu)
— crossed with Flash-Decoding's split-K cache reads (Dao et al.) and
PagedAttention's block tables (Kwon et al., vLLM).

TPU-native design (NOT a kernel translation):
  - The KV cache lives as fixed-size blocks (pages) `[num_blocks, H_kv,
    block_size, D]` and each sequence owns a BLOCK TABLE `[pages]` of
    block ids. The kernel grid is `(slot, kv_block)`: one step attends
    every KV head of a slot to one COMPUTE BLOCK of `pages_per_step` pages
    (512 tokens at 8 KV heads x 128 in bf16). The kv_block axis is the
    innermost grid dimension, so the f32 running-max/sum/acc scratch (one
    a KV head) persists across the cache sweep — exactly the flash-decode
    split-K merge.
  - The pools stay in HBM and the kernel copies pages itself
    (`make_async_copy`, page ids from the scalar-prefetched block table)
    into a double-buffered VMEM scratch: the next block's pages — or the
    next slot's first block — are in flight while this block computes. No
    gather tensor is ever materialized, only pages that hold tokens are
    copied, and a step wholly past its slot's end does nothing. The cost
    of the walk is its step and descriptor count, not its bytes (a step a
    page of one head took 32,768 steps a layer to read 50 MB: PERF.md, PR
    29), hence many pages a step.
  - Layout note: with H_kv OUTSIDE the tokens a page's slab of all heads
    `[H_kv, block_size, D]` is contiguous, so one descriptor a page moves
    every head, and a per-(page, head) tile is the contiguous (sublane=
    tokens, lane=D) MXU tile; `[num_blocks, block_size, H_kv, D]` would
    stride every head's tile by head.
  - `pages_per_step` follows from the shapes alone (`pages_per_step()`:
    K and V, double-buffered, in a fixed quarter of the scoped VMEM); a
    geometry whose one page fills that share streams a page a step and
    still gets the all-heads copy. analysis D5 reads the same function.
  - GQA packing: all `H_q/H_kv` query heads sharing a KV head ride ONE
    [group, D] tile (padded to the sublane minimum), so the whole group's
    scores come from one MXU pass per compute block. Decode is pure HBM
    bandwidth: every live cache byte is read exactly once per step.
  - The two matmuls take the cache's own float dtype (the query's, for a
    quantized cache) with f32 accumulation, as the XLA oracle and the
    chunk-prefill path do: in f32 the MXU passes, not the bytes, bound a
    step. Scores are scaled, masked and exponentiated in f32; the sum is
    taken before the probabilities are rounded for the second matmul.
  - Optional int8 KV: the cache stores int8 with ONE f32 scale per block
    (text/paged_cache.py maintains them by block requantization on
    append); the kernel reads per-(slot, page) scales from scalar-prefetch
    SMEM and folds k's scale into the logits, v's into the probabilities,
    a page of the compute block at a time — decode cache reads halve again
    on top of bf16. int4 packs two tokens a byte and is unpacked in VMEM.

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


# ------------------------------------------------------------------ sizing

#: VMEM the K and V stream buffers take together, both double-buffered: a
#: quarter of the 16 MiB a kernel gets by default, the rest left for the
#: per-head temporaries (dequantized keys, scores) and the accumulators
_STREAM_VMEM_BYTES = 4 << 20


def pages_per_step(pages, block_rows, kv_heads, head_dim, itemsize):
    """Cache pages one grid step streams: the largest power of two whose K
    and V slabs (every KV head of a page: `kv_heads * block_rows *
    head_dim * itemsize` bytes each), double-buffered, fit
    `_STREAM_VMEM_BYTES`; never more than the table holds, never fewer
    than one. `block_rows` is the STORED rows of a page (block_size / 2 for
    int4). ONE definition: the kernel's grid, the engine's `kv_steps` span
    attribute and analysis D5 all read it."""
    page = int(kv_heads) * int(block_rows) * int(head_dim) * int(itemsize)
    fit = max(1, _STREAM_VMEM_BYTES // (4 * page))
    return max(1, min(1 << (fit.bit_length() - 1), int(pages)))


def kv_steps(slots, pages, block_rows, kv_heads, head_dim, itemsize):
    """Grid steps of one kernel call: `slots` x compute blocks a slot."""
    pps = pages_per_step(pages, block_rows, kv_heads, head_dim, itemsize)
    return int(slots) * -(-int(pages) // pps)


# ------------------------------------------------------------------ kernel

def _cdiv(a, b: int):
    # pl.cdiv mixes an i32 tracer with an i64 weak int under x64
    return (a + (b - 1)) // b


def _decode_kernel(tab_ref, len_ref, *rest, scale, block_size, pps,
                   has_scale, packed, has_start=False, v_cols=None):
    """One (slot, kv_block) grid step: every KV head's GQA query group
    attends to one compute block of `pps` cache pages, merged into the
    running flash state.

    tab_ref/len_ref (+ ks_ref/vs_ref when has_scale): scalar-prefetch SMEM
    (block table [S, P], kv lengths [S], per-(slot, page) dequant scales).
    q/o are [1, H_kv, Gp, D] VMEM blocks; the pools stay in HBM and pages
    come by DMA into k_buf/v_buf [2, pps, H_kv, rows, D] — one descriptor a
    page moves every head (the pool's [N, H_kv, rows, D] layout makes that
    slab contiguous). `rows` is block_size, or block_size / 2 int4-packed
    (split-half along tokens: byte t holds token t in the low nibble, token
    bs/2 + t in the high — unpacked HERE so the packed bytes are the only
    cache traffic). Only pages that hold tokens are copied; a step wholly
    past its slot's end does nothing. Every slot owns at least its first
    step (a zero length reads one page and masks all of it), so the step
    after a slot's last live one is always the next slot's first: that is
    the block whose copies start before this block's compute and are
    waited on by the step that consumes them. step_ref counts live steps;
    its parity names the buffer.

    `has_start` (a windowed layer): a third scalar-prefetch array gives
    each slot's first live position. Pages wholly before it are not
    copied, compute blocks wholly before it do nothing, the first live
    page is masked from below, and a slot's first step is the block that
    holds that page instead of block 0. Without it the program is the one
    above, unchanged.

    `v_cols` (a latent pool, `paged_latent_decode`): there is no V pool —
    a position's value is the first `v_cols` columns of its key's own row,
    so each page is copied ONCE and its tile serves both matmuls (all its
    columns for the scores, the first `v_cols` for the values); the refs
    then come without `v_hbm` and `v_buf`, one DMA semaphore row is used,
    and o/acc are `v_cols` wide.
    """
    if has_start:
        start_ref, *rest = rest
    if has_scale:
        ks_ref, vs_ref, *rest = rest
    if v_cols is None:
        (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, step_ref,
         acc, m_s, l_s) = rest
    else:
        q_ref, k_hbm, o_ref, k_buf, sems, step_ref, acc, m_s, l_s = rest
        v_buf = k_buf
    si, ji = pl.program_id(0), pl.program_id(1)
    n_s, n_j = pl.num_programs(0), pl.num_programs(1)
    hkv, rows, d = k_buf.shape[2:]
    gp = q_ref.shape[2]
    t = pps * block_size
    # matmul operands: the cache's own float dtype, or the query's for a
    # quantized cache (int8 values are exact in bf16); f32 accumulation
    cdt = k_buf.dtype if jnp.issubdtype(k_buf.dtype, jnp.floating) \
        else q_ref.dtype

    def live_pages(s):
        return jnp.maximum(_cdiv(len_ref[s], block_size), 1)

    def first_page(s):
        """The page that holds slot s's first live position (0 without
        `has_start`); never past the slot's last live page."""
        if not has_start:
            return 0
        return jnp.minimum(start_ref[s] // block_size, live_pages(s) - 1)

    def first_blk(s):
        return first_page(s) // pps

    def each_copy(s, j, buf, do):
        """`do` on the K and the V copy of every live page of block j of
        slot s into buffer buf (a copy is waited on through a descriptor
        like the one that started it); returns the [lo, n) of the
        buffer's pages that hold them."""
        first = j * pps
        n = jnp.minimum(live_pages(s) - first, pps)
        lo = jnp.clip(first_page(s) - first, 0, pps) if has_start else 0

        def page(i, c):
            pid = tab_ref[s, first + i]
            do(pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[buf, i],
                                     sems.at[0, buf]))
            if v_cols is None:
                do(pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[buf, i],
                                         sems.at[1, buf]))
            return c
        jax.lax.fori_loop(lo, n, page, None)
        return lo, n

    def start(s, j, buf):
        each_copy(s, j, buf, lambda cp: cp.start())

    def tokens(ref, buf, h):
        """Head h's [t, D] operand from the pages of buffer buf."""
        x = ref[buf, :, h]                               # [pps, rows, D]
        if packed:
            # widened first: v5e's Mosaic legalizes no shift on vector<i8>
            x = x.astype(jnp.int32)
            x = jnp.concatenate(
                [jnp.right_shift(jnp.left_shift(x, 28), 28),
                 jnp.right_shift(x, 4)], axis=1)         # [pps, bs, D]
        if x.dtype != cdt:
            x = x.astype(jnp.float32).astype(cdt)
        return x.reshape(t, d)

    def scale_row(ref):
        """[1, t] per-token dequant scales of this block, a page at a
        time from SMEM."""
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
        return jax.lax.fori_loop(
            0, pps, lambda i, row: jnp.where(cols >= i * block_size,
                                             ref[si, ji * pps + i], row),
            jnp.zeros((1, t), jnp.float32))

    @pl.when((si == 0) & (ji == 0))
    def _first():
        step_ref[0] = 0
        start(0, first_blk(0), 0)

    @pl.when(ji == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    seq_len = len_ref[si]
    n_blk = _cdiv(live_pages(si), pps)

    live = ji < n_blk
    if has_start:
        live = live & (ji >= first_blk(si))

    @pl.when(live)
    def _block():
        buf = step_ref[0] % 2
        step_ref[0] = step_ref[0] + 1
        more = ji + 1 < n_blk
        nxt_s = jnp.where(more, si, si + 1)

        @pl.when(nxt_s < n_s)
        def _prefetch():
            nxt_first = first_blk(nxt_s) if has_start else 0
            start(nxt_s, jnp.where(more, ji + 1, nxt_first), 1 - buf)

        lo, n = each_copy(si, ji, buf, lambda cp: cp.wait())
        if jnp.issubdtype(v_buf.dtype, jnp.floating):
            # pages of the block that no copy wrote (past the tail, or
            # before the first live page): whatever the buffer held (NaN
            # bits included) would meet p = 0 in pv
            def zero(i, c):
                v_buf[buf, i] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
                return c
            jax.lax.fori_loop(n, pps, zero, None)
            if has_start:
                jax.lax.fori_loop(0, lo, zero, None)

        # the tail block is partially valid, interior blocks are full: one
        # masked path keeps the kernel small
        cols = ji * t + jax.lax.broadcasted_iota(jnp.int32, (gp, t), 1)
        mask = cols < seq_len
        if has_start:
            mask = mask & (cols >= start_ref[si])
        if has_scale:
            ks_row, vs_row = scale_row(ks_ref), scale_row(vs_ref)

        def head(h, c):
            q = q_ref[0, h].astype(cdt)                          # [Gp, D]
            s = jax.lax.dot_general(q, tokens(k_buf, buf, h),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * np.float32(scale)
            if has_scale:
                s = s * ks_row
            s = jnp.where(mask, s, _NEG_INF)

            m_prev = m_s[h][:, :1]
            l_prev = l_s[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), _ZERO)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            if has_scale:
                p = p * vs_row
            v = tokens(v_buf, buf, h)
            if v_cols is not None:
                v = v[:, :v_cols]
            pv = jax.lax.dot_general(p.astype(cdt), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc[h] = acc[h] * alpha + pv
            m_s[h] = jnp.broadcast_to(m_new, m_s.shape[1:])
            l_s[h] = jnp.broadcast_to(l_new, l_s.shape[1:])
            return c
        jax.lax.fori_loop(0, hkv, head, None)

    @pl.when(ji == n_j - 1)
    def _finish():
        l = l_s[...][:, :, :1]
        safe_l = jnp.where(l == _ZERO, _ONE, l)
        o_ref[0] = (acc[...] / safe_l).astype(o_ref.dtype)


def paged_decode_attention_raw(q, k_cache, v_cache, block_tables, seq_lens,
                               k_scale=None, v_scale=None, kv_int4=False,
                               pages_per_step_=None, kv_start=None,
                               name="paged_decode"):
    """The Pallas kernel path. q [S, H_q, D]; caches [N, H_kv, bs, D]
    (int8 when k_scale/v_scale [N] f32 are given; int4-packed
    [N, H_kv, bs/2, D] when kv_int4); block_tables [S, P] int32 (entries
    < 0 tolerated as padding); seq_lens [S] valid kv lengths. Returns
    [S, H_q, D] in q.dtype. `pages_per_step_` overrides the derived
    compute block (tests and sweeps; nothing in the library passes it).
    `kv_start` [S]: each slot's first live position (a windowed layer:
    positions before it are neither read nor attended); `name`: the
    kernel's name in programs and traces."""
    with _x64_guard():
        return _paged_decode_x32(q, k_cache, v_cache, block_tables,
                                 seq_lens, k_scale, v_scale, kv_int4,
                                 pages_per_step_, kv_start, name)


def _paged_decode_x32(q, k_cache, v_cache, block_tables, seq_lens,
                      k_scale=None, v_scale=None, kv_int4=False,
                      pages_per_step_=None, kv_start=None,
                      name="paged_decode", v_cols=None, scale=None):
    """`v_cols`/`scale`: the latent pool's call (`v_cache` None; see the
    kernel); `scale` defaults to 1 / sqrt(D)."""
    s_n, hq, d = q.shape
    n_blocks, hkv, rows, dc = k_cache.shape
    bs = rows
    if kv_int4:
        if k_scale is None:
            raise ValueError("int4 KV needs per-block scales")
        bs = rows * 2        # logical tokens per block (two per byte)
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
    pages = block_tables.shape[1]
    pps = pages_per_step_ or pages_per_step(pages, rows, hkv, d,
                                            k_cache.dtype.itemsize)
    n_blk = -(-pages // pps)
    # whole compute blocks: the pad pages are never live, so never copied
    tables = jnp.pad(jnp.maximum(block_tables, 0).astype(jnp.int32),
                     ((0, 0), (0, n_blk * pps - pages)))
    lens = seq_lens.astype(jnp.int32)
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    has_scale = k_scale is not None
    has_start = kv_start is not None
    shared = v_cols is not None
    d_out = v_cols if shared else d

    kernel = functools.partial(_decode_kernel, scale=scale, block_size=bs,
                               pps=pps, has_scale=has_scale, packed=kv_int4,
                               has_start=has_start, v_cols=v_cols)

    def qo_spec(width):
        return pl.BlockSpec((1, hkv, gp, width),
                            lambda s, j, *refs: (s, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    args = [tables, lens]
    if has_start:
        args.append(jnp.maximum(kv_start, 0).astype(jnp.int32))
    if has_scale:
        # per-(slot, page) dequant scales, gathered host-of-kernel from
        # the per-block scales (tiny: S*P f32 in SMEM)
        args += [k_scale[tables].astype(jnp.float32),
                 v_scale[tables].astype(jnp.float32)]
    pools = [k_cache] if shared else [k_cache, v_cache]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(args),
        grid=(s_n, n_blk),
        in_specs=[qo_spec(d)] + [pool_spec] * len(pools),
        out_specs=[qo_spec(d_out)],
        scratch_shapes=[
            pltpu.VMEM((2, pps, hkv, rows, d), c.dtype) for c in pools
        ] + [
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hkv, gp, d_out), jnp.float32),
            pltpu.VMEM((hkv, gp, 128), jnp.float32),
            pltpu.VMEM((hkv, gp, 128), jnp.float32),
        ],
    )
    out, = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s_n, hkv, gp, d_out), q.dtype)],
        interpret=_interpret(), name=name,
    )(*args, q4, *pools)
    return out[:, :, :g].reshape(s_n, hq, d_out)


# ------------------------------------------------------- XLA composition

def paged_decode_attention_xla(q, k_cache, v_cache, block_tables, seq_lens,
                               k_scale=None, v_scale=None, kv_int4=False,
                               kv_start=None):
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
    if kv_start is not None:
        valid = valid & (jnp.arange(t)[None, :] >= kv_start[:, None])
    scores = jnp.where(valid[:, None, :], scores,
                       jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("sht,sthd->shd", probs, v)


# --------------------------------------------------------------- routing

def decode_gate_reason(n_elems, dtype, platform, head_dim=None,
                       block_size=None, latent_cols=None):
    """Why the decode router would decline this shape — ONE definition
    consulted by both `use_pallas_decode` and analysis D4, so the reported
    reason is the real one. Returns (reason, severity): legitimate gates
    are notes, no-reason is the should-have-routed warning.
    `latent_cols`: the call is over a latent pool whose rows are
    `head_dim` wide and whose first `latent_cols` columns are the values
    (576 -> 640 and 512 for rkv 512, dr 64); such a pool is float."""
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
    if latent_cols is not None and (latent_cols % 128
                                    or dtype in ("int8", "int4")):
        return (f"latent pool of {latent_cols} value columns in {dtype}: "
                "the kernel slices the values off the key's tile at a "
                "lane boundary (128) and reads no per-block scales"), "note"
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
                           k_scale=None, v_scale=None, kv_int4=False,
                           kv_start=None, name="paged_decode"):
    """Routed paged decode attention (kernel on TPU above threshold, XLA
    composition everywhere else). Same contract as the _raw kernel;
    `kv_int4=True` declares the caches int4-packed along the token axis
    (k_scale/v_scale required). `kv_start` [S] (a windowed layer): row s
    attends positions [kv_start[s], seq_lens[s]) of its table alone, and
    the kernel does not copy the pages before them; its call carries
    `name`, so that a trace tells windowed calls from full ones."""
    if use_pallas_decode(q, k_cache, block_tables, kv_int4):
        return paged_decode_attention_raw(q, k_cache, v_cache,
                                          block_tables, seq_lens,
                                          k_scale, v_scale, kv_int4,
                                          kv_start=kv_start, name=name)
    return paged_decode_attention_xla(q, k_cache, v_cache, block_tables,
                                      seq_lens, k_scale, v_scale, kv_int4,
                                      kv_start)


# ------------------------------------------------------ latent (MLA) pool
# One row a position, shared by every query head: the first `v_cols`
# columns are the latent c (the value, in the absorbed form), the next
# ones the rotary key, the rest padding up to whole lanes
# (`paged_cache.latent_row_width`). The query comes absorbed
# (`latent_block.absorb_query`) and padded to the row's width, so a
# score is ONE dot product over the row; what comes back is the sum of
# P c in the latent space.

def paged_latent_decode_raw(q, pool, block_tables, seq_lens, v_cols, scale,
                            pages_per_step_=None):
    """The Pallas kernel path: the `paged_decode` body with the ONE cache
    row as the single "KV head" and all the query heads as its group (the
    matmuls' M), each page copied once and its tile used for the scores
    (all columns) and the values (the first `v_cols`). q [S, H, W]; pool
    [N, 1, bs, W]; returns [S, H, v_cols]."""
    with _x64_guard():
        return _paged_decode_x32(q, pool, None, block_tables, seq_lens,
                                 pages_per_step_=pages_per_step_,
                                 name="paged_latent_decode", v_cols=v_cols,
                                 scale=scale)


def paged_latent_decode_xla(q, pool, block_tables, seq_lens, v_cols, scale):
    """The gather + masked-softmax composition: the kernel's oracle and
    the off-TPU route."""
    s_n = q.shape[0]
    _, _, bs, w = pool.shape
    rows = pool[jnp.maximum(block_tables, 0)].reshape(s_n, -1, w)
    t = rows.shape[1]
    scores = jnp.einsum("shw,stw->sht", q, rows.astype(q.dtype),
                        preferred_element_type=jnp.float32) \
        * np.float32(scale)
    valid = jnp.arange(t)[None, :] < seq_lens[:, None]
    scores = jnp.where(valid[:, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("sht,str->shr", probs,
                      rows[..., :v_cols].astype(q.dtype))


def use_pallas_latent_decode(q, pool, block_tables, v_cols) -> bool:
    s_n, hq, w = q.shape
    bs = pool.shape[2]
    _, sev = decode_gate_reason(
        s_n * hq * block_tables.shape[1] * bs, str(pool.dtype),
        jax.default_backend(), head_dim=w, block_size=bs,
        latent_cols=v_cols)
    return sev == "warning"


def paged_latent_decode(q, pool, block_tables, seq_lens, v_cols, scale):
    """Routed decode attention over a latent pool (kernel on TPU above
    threshold, XLA composition everywhere else): row s of q [S, H, W]
    attends positions [0, seq_lens[s]) of its table's pages; returns
    sum_j P_j c_j, [S, H, v_cols]."""
    if use_pallas_latent_decode(q, pool, block_tables, v_cols):
        return paged_latent_decode_raw(q, pool, block_tables, seq_lens,
                                       v_cols, scale)
    return paged_latent_decode_xla(q, pool, block_tables, seq_lens, v_cols,
                                   scale)
