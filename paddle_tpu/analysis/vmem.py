"""D5 — static VMEM-footprint estimates for the Pallas launch configs.

A bad flash-attention autotune entry (hand-edited cache file, an entry
tuned on different hardware, or a corrupt merge) fails at RUNTIME with a
Mosaic "exceeded VMEM" error deep inside a train step; this detector fails
it at lint time instead by re-deriving each config's VMEM working set from
the kernels' actual block specs (ops/pallas_attention.py forward/backward,
ops/pallas_norm.py row kernels) and comparing against the per-core budget
(~16 MiB on current TPUs — FLAGS_analysis_vmem_limit_mb).

These are ESTIMATES of the dominant terms — streamed input/output blocks
double-buffered by the grid pipeline plus the f32 scratch the kernels
declare — not a Mosaic allocation replay; the gate severities reflect
that: > limit is a warning, > 80% of the limit is a note.
"""
from __future__ import annotations

from .findings import Finding


def _limit_bytes(limit_mb=None) -> int:
    if limit_mb is None:
        from ..core.flags import flag

        limit_mb = flag("FLAGS_analysis_vmem_limit_mb")
    return int(limit_mb) * 2**20


def _ceil128(x: int) -> int:
    return (int(x) + 127) // 128 * 128


def flash_vmem_bytes(block_q: int, block_k: int, d: int,
                     itemsize: int = 4) -> tuple[int, int]:
    """(forward, backward) VMEM working-set estimates for one grid step of
    the flash kernels at head dim `d` and input itemsize.

    forward (ops/pallas_attention._flash_forward_x32): q[bq,dp] + k/v[bk,dp]
    input blocks and o[bq,dp] + lse[bq,128] outputs, each double-buffered by
    the pipeline, plus declared f32 scratch acc[bq,dp] + m/l[bq,128]x2.
    backward (dq/dkv kernels): q/o/do[bq,dp] + k/v[bk,dp] + lse/delta
    [bq,128] blocks with a dq-or-dkv accumulator in f32 scratch.
    """
    dp = _ceil128(d)
    lanes = 128
    fwd_io = (block_q * dp              # q
              + 2 * block_k * dp        # k, v
              + block_q * dp            # o
              + block_q * lanes)        # lse
    fwd_scratch = (block_q * dp + 2 * block_q * lanes) * 4
    fwd = 2 * fwd_io * itemsize + fwd_scratch

    bwd_io = (3 * block_q * dp          # q, o, do
              + 2 * block_k * dp        # k, v
              + 2 * block_q * lanes     # lse, delta
              + max(block_q, block_k) * dp)  # dq or dk/dv out
    bwd_scratch = max(block_q, block_k) * dp * 4
    bwd = 2 * bwd_io * itemsize + bwd_scratch
    return fwd, bwd


def norm_vmem_bytes(block_rows: int, hidden: int, itemsize: int = 2,
                    fused_add: bool = False) -> int:
    """Working-set estimate for one grid step of the fused norm kernels
    (ops/pallas_norm): x (+residual) input blocks and y (+summed stream)
    outputs at [block_rows, Hp] in the caller's dtype, each DOUBLE-buffered
    by the grid pipeline, one f32 compute copy, parameter rows and per-row
    stats. (At 256 rows, H=4096, bf16, fused add this gives 20.3 MiB; the
    chip's compiler reports 16.25 MiB — the estimate errs high.)"""
    hp = _ceil128(hidden)
    n_stream = 2 if fused_add else 1
    io = 2 * n_stream * 2 * block_rows * hp * itemsize  # (in + out) x 2 bufs
    f32_work = block_rows * hp * 4                      # xf accumulation
    params = 2 * 8 * hp * itemsize                      # w/b lane blocks
    stats = 2 * block_rows * 128 * 4                    # rstd/mean
    return io + f32_work + params + stats


def _entry_findings(key, blocks, limit, loc) -> list[Finding]:
    """Findings for one flash tune-cache entry ("flash", sq, sk, d, dtype,
    causal) -> (fwd_q, fwd_k, bwd_q, bwd_k)."""
    import numpy as np

    _, sq, sk, d, dtype, causal = key
    if dtype in ("bfloat16", "float16"):  # np.dtype rejects bfloat16
        itemsize = 2
    else:
        try:
            itemsize = int(np.dtype(dtype).itemsize)
        except TypeError:
            itemsize = 4
    fq, fk, bq, bk = blocks
    fwd, _ = flash_vmem_bytes(fq, fk, d, itemsize)
    _, bwd = flash_vmem_bytes(bq, bk, d, itemsize)
    out = []
    for tag, blocks_pair, est in (("fwd", (fq, fk), fwd),
                                  ("bwd", (bq, bk), bwd)):
        if est > limit:
            sev, verdict = "warning", "exceeds"
        elif est > 0.8 * limit:
            sev, verdict = "note", "is within 20% of"
        else:
            continue
        out.append(Finding(
            "vmem-budget", sev, loc,
            f"flash {tag} blocks {blocks_pair} for "
            f"(sq={sq}, sk={sk}, d={d}, {dtype}) estimate "
            f"{est / 2**20:.1f} MiB VMEM — {verdict} the "
            f"{limit / 2**20:.0f} MiB per-core budget; this entry would "
            "fail Mosaic at runtime",
            {"key": [str(x) for x in key], "blocks": list(blocks_pair),
             "estimate_bytes": est, "limit_bytes": limit, "stage": tag}))
    return out


def audit_tune_cache(entries=None, limit_mb=None,
                     loc: str = "flash-tune-cache") -> list[Finding]:
    """D5 over the flash autotune cache: the in-process + user-scoped disk
    entries (the ones a compile would actually consume), or an explicit
    {key: blocks} mapping."""
    from ..ops import pallas_attention as pa

    limit = _limit_bytes(limit_mb)
    if entries is None:
        pa._tune_cache_load()
        entries = dict(pa._TUNE_CACHE)
    findings = []
    for key, val in entries.items():
        # validate with the loader's own rule (_valid_blocks) BEFORE
        # normalizing: wrong-arity / non-sequence / out-of-range values
        # must become findings, not unpack crashes
        vv = tuple(val) if isinstance(val, (list, tuple)) else None
        if vv is None or not pa._valid_blocks(vv) or len(key) != 6:
            findings.append(Finding(
                "vmem-budget", "warning", loc,
                f"malformed tune-cache entry {key!r} -> {val!r}",
                {"key": str(key)}))
            continue
        findings += _entry_findings(key, pa._norm4(vv), limit, loc)
    return findings


def _decode_pages_per_step(head_dim, block_size, itemsize, kv_heads, pages):
    """The kernel's own compute block at this geometry (`pages` None: a
    table longer than any block)."""
    from ..ops.pallas_decode import pages_per_step

    return pages_per_step(pages if pages is not None else 1 << 30,
                          block_size, kv_heads, _ceil128(head_dim), itemsize)


def decode_vmem_bytes(head_dim: int, block_size: int, group: int = 16,
                      itemsize: int = 2, kv_heads: int = 1,
                      pages: int | None = None) -> int:
    """Working-set estimate for one grid step of the paged flash-decode
    kernel (ops/pallas_decode._decode_kernel). The kernel sizes its own
    compute block — `pallas_decode.pages_per_step`, asked here with the
    same numbers — and one step holds: the K and V stream buffers
    [2, pages_per_step, kv_heads, block_size, D] it fills by DMA; the q
    and o blocks [kv_heads, gp, D] the grid pipeline double-buffers; the
    declared f32 scratch, one accumulator acc[gp, D] + m/l[gp, 128]x2 a
    KV head; and one head's temporaries (its key and value operands over
    the whole compute block, an f32 copy on the way when the cache is
    quantized, and the f32 score and probability tiles). `pages` caps the
    compute block at a table's length (None: a table longer than any
    block)."""
    dp = _ceil128(head_dim)
    gp = max(16, (int(group) + 15) // 16 * 16)
    lanes = 128
    pps = _decode_pages_per_step(head_dim, block_size, itemsize, kv_heads,
                                 pages)
    t = pps * block_size
    op_size = max(2, itemsize)          # a quantized cache computes in bf16
    stream = 2 * 2 * pps * kv_heads * block_size * dp * itemsize
    io = 2 * 2 * kv_heads * gp * dp * op_size
    scratch = kv_heads * (gp * dp + 2 * gp * lanes) * 4
    head = (2 * t * dp * op_size + (t * dp * 4 if itemsize < 2 else 0)
            + 4 * gp * t * 4)
    return stream + io + scratch + head


def audit_decode_config(head_dim: int, block_size: int, group: int = 16,
                        itemsize: int = 2, limit_mb=None,
                        pool_blocks=None, slots=None, seq_pages=None,
                        cached_blocks: int = 0, kv_heads: int = 1,
                        loc: str = "pallas-decode-config") -> list[Finding]:
    """D5 for the decode kernel's launch config at a model's head
    geometry. The kernel shrinks its compute block to its VMEM share by
    itself, down to one page; a geometry whose single page (every KV
    head's rows, K and V, double-buffered) is already over the budget
    fails lint here instead of Mosaic at serving time.

    When `pool_blocks`/`slots`/`seq_pages` are given it also audits the
    BLOCK-POOL budget: a pool that cannot hold `slots` full-length
    sequences serializes the engine through admission control.
    `cached_blocks` (round 13) credits prefix-cache sharing — blocks
    already holding a reusable prefix are paid once, not per slot, so a
    pool that is too small for `slots` cold sequences can still be
    healthy under a shared-prompt workload."""
    limit = _limit_bytes(limit_mb)
    est = decode_vmem_bytes(head_dim, block_size, group, itemsize,
                            kv_heads, seq_pages)
    findings = []
    if est > 0.8 * limit:
        sev = "warning" if est > limit else "note"
        verdict = "exceeds" if est > limit else "is within 20% of"
        pps = _decode_pages_per_step(head_dim, block_size, itemsize,
                                     kv_heads, seq_pages)
        findings.append(Finding(
            "vmem-budget", sev, loc,
            f"paged decode at {pps} page(s) a grid step (block_size="
            f"{block_size}, kv_heads={kv_heads}, head_dim={head_dim}, "
            f"group={group}, itemsize {itemsize}) estimates "
            f"{est / 2**20:.1f} MiB VMEM — {verdict} the "
            f"{limit / 2**20:.0f} MiB per-core budget; the kernel already "
            "streams as few pages a step as its share allows, so a page "
            "of every KV head is too large: lower FLAGS_kv_block_size for "
            "this geometry",
            {"head_dim": head_dim, "block_size": block_size,
             "kv_heads": kv_heads, "pages_per_step": pps,
             "estimate_bytes": est, "limit_bytes": limit}))
    if pool_blocks is not None and slots is not None \
            and seq_pages is not None:
        cached = max(0, min(int(cached_blocks),
                            int(slots) * int(seq_pages)))
        need = int(slots) * int(seq_pages) - cached
        usable = int(pool_blocks) - 1           # block 0 is trash
        if need > usable:
            findings.append(Finding(
                "vmem-budget", "note", loc,
                f"kv block pool ({usable} usable blocks) cannot hold "
                f"{slots} full-context sequences ({slots}x{seq_pages} "
                f"pages, {cached} credited to shared prefix-cache "
                f"blocks): worst-case admission serializes at "
                f"{usable // max(int(seq_pages), 1)} concurrent "
                "full-length requests — size num_kv_blocks (or rely on "
                "shorter/shared prompts) accordingly",
                {"pool_blocks": int(pool_blocks), "slots": int(slots),
                 "seq_pages": int(seq_pages), "cached_blocks": cached,
                 "need": need}))
    return findings


def audit_norm_config(hidden_size: int, itemsize: int = 2,
                      block_rows: int | None = None, limit_mb=None,
                      loc: str = "pallas-norm-config") -> list[Finding]:
    """D5 for the norm kernels' launch config at a model width: the row
    block the kernels size for themselves (pallas_norm.block_rows), or an
    explicit `block_rows` to be judged."""
    from ..ops.pallas_norm import block_rows as kernel_rows

    limit = _limit_bytes(limit_mb)
    # rows=1<<30: a tensor tall enough that only the width bounds the block
    br = block_rows or kernel_rows(1 << 30, _ceil128(hidden_size), 4,
                                   itemsize)
    est = norm_vmem_bytes(br, hidden_size, itemsize, fused_add=True)
    if est <= 0.8 * limit:
        return []
    sev = "warning" if est > limit else "note"
    verdict = "exceeds" if est > limit else "is within 20% of"
    return [Finding(
        "vmem-budget", sev, loc,
        f"fused add+norm at H={hidden_size} with block_rows={br} "
        f"(itemsize {itemsize}) estimates {est / 2**20:.1f} MiB VMEM — "
        f"{verdict} the {limit / 2**20:.0f} MiB per-core budget; pass a "
        "smaller block_rows to pallas_norm at this width",
        {"hidden": hidden_size, "block_rows": br,
         "estimate_bytes": est, "limit_bytes": limit})]
