"""Continuous-batching serving engine over the paged KV cache.

Reference parity: the serving stack the reference builds around
block_multihead_attention (paged/block KV) — here grown into the full
PagedAttention/continuous-batching engine shape (Kwon et al., vLLM): a
fixed SLOT array, block-granular KV allocation with admission control,
and requests that join freed slots mid-flight instead of waiting for a
whole static batch to drain.

TPU-native design:
  - The scheduler runs FOUR compiled-program families, all static-shaped:
    a whole-prompt PREFILL program per joining request whose prompt fits
    one (keyed by the prompt-length bucket; rides the Pallas flash kernel
    on TPU and scatters the prompt's K/V into its pages), a CHUNK program
    (one chunk of one prompt, keyed by chunk and context-pages buckets;
    chunked prefill and prefix-cache suffixes), ONE DECODE program
    advancing every active slot one token (keyed by the active-slot-count
    bucket — 1/2/4/8/... — so a half-empty engine doesn't pay the full
    slot array), and with speculative decoding a VERIFY program scoring
    K+1 candidates a slot. The host decides which program touches each
    slot; the programs never branch dynamically.
  - The dense architectures' layer is ONE functional block,
    `text/models/dense_block.block`, and what a program adds is where the
    keys and values live: its `attend` closure writes the step's K/V
    through the block table and attends over the pool (`_paged_attn` for
    decode; scatter, gather and `dense_block.attend_many` for chunk and
    verify; the sequence in hand for the whole-prompt prefill). A model
    that declares its layers one by one has `inference/layered.py`'s
    programs around its block module (`parallel_block`, `latent_block`,
    `gated_delta_block`), in the same idiom; a linear-attention layer's
    state lives a SLOT, and the first chunk of every request resets it.
  - Slot state entering the decode program is COMPACTED: tokens /
    positions / block-table rows / sampling params of the active slots
    are gathered into bucket-sized arrays (cheap — the KV pool itself is
    shared and addressed through the tables, it never moves). Padded rows
    point at the reserved trash block and their outputs are dropped.
  - What the host decides a call (tokens, positions, table rows, a
    prompt's ids, the scalars) reaches a program as ONE int32 operand,
    filled in place and unpacked inside the program by static slices: a
    transfer costs the host a quarter of a millisecond whatever its size
    (PERF.md, PR 36), so a call makes one, not one a value. A `.build`
    span's `h2d` says how many its call made.
  - Per-request sampling params thread as BATCHED arrays (temperature /
    top-k / top-p / greedy mask per slot), so mixed sampling configs share
    one program. An all-greedy call's program reads none of them and is
    handed one cached device copy a bucket size (`ServingEngine._samp`).
  - Cache buffers are DONATED to the step programs on TPU: the pool is
    updated in place, never copied (a [L, N, Hkv, bs, D] pool is the
    dominant HBM tenant at serving time).

The scheduler (admission, eos/length finish, block free/reuse, stats) is
host-side Python — it runs while the device executes, and its decisions
only ever pick which compiled program to invoke next.

Round 13 (serving tier 2) adds two levers on the same substrate:

  - PREFIX CACHING (`FLAGS_prefix_cache`): admission content-hashes the
    prompt's full KV blocks and points the block table at cached blocks
    for the shared prefix — zero prefill for those pages. Finish
    releases through the `PrefixCache` refcounts (a shared block is
    decref'd, never free-listed out from under another request), and a
    shared block that a request must partially overwrite (the suffix
    starts mid-block after a whole-prompt hit) is COPY-ON-WRITE
    duplicated inside the first chunk program.
  - CHUNKED PREFILL (`FLAGS_chunked_prefill_tokens`): a long prompt is
    prefilled `chunk_tokens` at a time, ONE chunk per scheduler tick,
    interleaved with the decode program — an 8k-token prompt no longer
    head-of-line blocks every decoding slot for its whole prefill. The
    same chunk program computes a prefix-cache hit's suffix (its first
    position starts at cached_len, not 0), so both levers share one
    program family keyed by (chunk bucket, context-pages bucket).
"""
from __future__ import annotations

import functools
import itertools
import time
from collections import OrderedDict, deque

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import DEFAULT_EXACT_CAP
from ..obs.trace import span as _span
from ..ops._pallas_common import ceil_to as _ceil_to
from ..text.generation import _stacked_params, _stacked_params_gpt
from ..text.models import dense_block as db
from ..text.models.dense_block import _GenSpec, _logits
from ..text.paged_cache import (TRASH_BLOCK, BlockAllocator, PagedKVCache,
                                PrefixCache, append_rows,
                                append_token_int4, append_token_int8,
                                blocks_for, copy_block, flat_pool,
                                gather_context, hash_blocks,
                                scatter_chunk_int4, scatter_chunk_int8,
                                scatter_chunk_rows, scatter_prefill,
                                scatter_prefill_int4, scatter_prefill_int8)
from . import layered

#: quantized KV-cache modes and their (append, scatter_prefill,
#: scatter_chunk) triples — the step programs dispatch on the STATIC
#: kv_mode string ("model" | "int8" | "int4"), so each mode compiles its
#: own program and the scan carries (ksc, vsc) only when quantized.
_KV_FNS = {
    "int8": (append_token_int8, scatter_prefill_int8, scatter_chunk_int8),
    "int4": (append_token_int4, scatter_prefill_int4, scatter_chunk_int4),
}


def _scan_layers(layer, x, params, tables, kc, vc, ksc, vsc):
    """The stacked programs' layer scan. The pools `[L, N, H_kv, bs, D]`
    (and their scales `[L, N]`, None for a float cache) ride it as CARRY,
    seen as `[L * N, ...]`, and come back in the shape they came in; the
    scan's xs are the stacked weights and each layer's first block, `l *
    N`. `layer(x, lw, tables, kf, vf, ksf, vsf) -> (x, kf, vf, ksf, vsf)`
    is handed the program's block table(s) with layer l's offset added
    (negative padding entries first clamped to the trash block, so layer
    l's trash block is `l * N`), and every per-layer function of
    text/paged_cache.py and the decode kernel work on the whole pool
    unchanged. No slice of a pool leaves the donated buffer and none is
    stacked back: as xs/ys, a layer's slice of both pools was copied out
    and back every layer of every call (ten pool-slice copies a layer,
    72% of the chip's busy time in mistral-7b.serve-chat: PERF.md, PR
    32)."""
    n_layers, n_blocks = kc.shape[:2]
    pools = (kc, vc, ksc, vsc)          # None (no scales) has no leaves
    tables = jnp.maximum(tables, TRASH_BLOCK)

    def body(carry, per_layer):
        lw, base = per_layer
        return layer(carry[0], lw, tables + base, *carry[1:]), None

    flat = jax.tree_util.tree_map(flat_pool, pools)
    base = jnp.arange(n_layers, dtype=jnp.int32) * n_blocks
    (x, *flat), _ = jax.lax.scan(body, (x,) + flat,
                                 (params["layers"], base))
    return (x,) + jax.tree_util.tree_map(
        lambda f, a: f.reshape(a.shape), tuple(flat), pools)


# ------------------------------------------------------ batched sampling

def _filter_logits(logits, temperature, top_k, top_p):
    """The (temperature, top-k, top-p) logit filter over [B, V] with the
    sampling params as BATCHED arrays — top-k before top-p, same order
    as the single-program engine. Categorical over the result IS the
    request's sampling distribution, which is exactly what speculative
    verification needs per candidate position (accept with prob p(x),
    resample from the residual), so the filter is shared between
    _sample_batched and _verify_tokens — the two can never drift."""
    v = logits.shape[-1]
    lg = logits.astype(jnp.float32) / jnp.maximum(temperature,
                                                  1e-6)[:, None]
    srt = jnp.sort(lg, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(srt, jnp.clip(top_k - 1, 0, v - 1)[:, None],
                              axis=-1)
    lg = jnp.where((top_k > 0)[:, None] & (lg < kth), -jnp.inf, lg)
    srt2 = jnp.sort(lg, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt2, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < top_p[:, None]
    cutoff = jnp.min(jnp.where(keep, srt2, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where((top_p < 1.0)[:, None] & (lg < cutoff), -jnp.inf, lg)


def _sample_batched(logits, key, do_sample, temperature, top_k, top_p):
    """Per-slot (greedy | temperature/top-k/top-p) sampling over [B, V]
    logits with the sampling params as BATCHED arrays — one program serves
    mixed per-request configs. Greedy rows are exact argmax (token-parity
    with text/generation._sample_token)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = _filter_logits(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)
    return jnp.where(do_sample, sampled, greedy)


# --------------------------------------------------- paged decode layers

def _paged_attn(hn_q, k_new, v_new, kc, vc, ksc, vsc, tables, pos,
                block_size, kv_mode):
    """Shared append+attend: write this step's K/V through the block
    table, then paged decode attention over lens = pos + 1 (the just-
    written token included, matching the single-program engine's
    `arange <= pos` mask). kv_mode is the STATIC cache mode string
    ("model" | "int8" | "int4")."""
    from ..ops.pallas_decode import paged_decode_attention

    b = hn_q.shape[0]
    blk = tables[jnp.arange(b), pos // block_size]
    off = (pos % block_size).astype(jnp.int32)
    if kv_mode != "model":
        app = _KV_FNS[kv_mode][0]
        kc, ksc = app(kc, ksc, k_new, blk, off)
        vc, vsc = app(vc, vsc, v_new, blk, off)
    else:
        kc = append_rows(kc, k_new, blk, off)
        vc = append_rows(vc, v_new, blk, off)
    out = paged_decode_attention(hn_q, kc, vc, tables, pos + 1, ksc, vsc,
                                 kv_int4=kv_mode == "int4")
    return out, kc, vc, ksc, vsc


def _scatter_rows(k, v, kc, vc, ksc, vsc, start, end, row, block_size,
                  kv_mode):
    """Write positions [start, end) of one slot's new K/V rows through
    its block table `row` (token-granular), in the cache's mode."""
    if kv_mode != "model":
        scat = _KV_FNS[kv_mode][2]
        kc, ksc = scat(kc, ksc, k, start, end, row, block_size)
        vc, vsc = scat(vc, vsc, v, start, end, row, block_size)
    else:
        kc = scatter_chunk_rows(kc, k, start, end, row, block_size)
        vc = scatter_chunk_rows(vc, v, start, end, row, block_size)
    return kc, vc, ksc, vsc


# ------------------------------------------------------- step programs

def _decode_step_impl(spec: _GenSpec, block_size: int, kv_mode: str,
                      any_sample: bool, params, ints, kc, vc, ksc, vsc,
                      samp, key):
    """ONE decode step for a compacted slot bucket: every row consumes
    its token, appends K/V through its block table, attends over its own
    length, and samples its next token with its own params. `ints` [B, 2
    + pages], a row a slot (`_StackedPrograms.decode` packs it): its
    token, its position, its block table row. Cache pools ride the layer
    scan as its carry (`_scan_layers`), a layer's blocks addressed by
    offset. `any_sample` is STATIC (part of the program key): an
    all-greedy bucket — the common serving case — compiles to a bare
    argmax instead of the sort/softmax/cumsum sampling machinery over
    [B, V] every tick, and reads nothing of `samp`.
    """
    tok, pos, tables = ints[:, 0], ints[:, 1], ints[:, 2:]
    xt, rope = db.embed(params, tok, pos, spec)          # [B, H]

    def layer(xc, lw, tabs, *pools):
        pools = list(pools)

        def attend(q, k, v):
            out, *pools[:] = _paged_attn(q, k, v, *pools, tabs, pos,
                                         block_size, kv_mode)
            return out

        return (db.block(xc, lw, spec, attend, rope), *pools)

    xt, kc, vc, ksc, vsc = _scan_layers(layer, xt, params, tables, kc, vc,
                                        ksc, vsc)
    lg = _logits(xt, params, spec)                       # [B, V] f32
    if any_sample:
        key, sub = jax.random.split(key)
        nxt = _sample_batched(lg, sub, samp["do_sample"],
                              samp["temperature"], samp["top_k"],
                              samp["top_p"])
    else:
        nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    return nxt, kc, vc, ksc, vsc, key


def _prefill_impl(spec: _GenSpec, block_size: int, kv_mode: str,
                  any_sample: bool, pages: int, params, ints, kc, vc, ksc,
                  vsc, samp, key):
    """Prefill one joining request: full-prompt forward (Pallas flash on
    TPU), page-scatter the prompt K/V through the slot's block table, and
    sample the first token from the last REAL prompt position. `ints` [1
    + pages + S] (`ServingEngine._prefill` packs it): true_len, the
    slot's block table row, the prompt's ids padded to the bucket S."""
    true_len, table_row = ints[0], ints[1:1 + pages]
    ids = ints[None, 1 + pages:]                         # [1, S]
    x, ks, vs = db.forward_sequence(params, ids, spec)
    ks, vs = ks[:, 0], vs[:, 0]                          # [L, S, Hkv, D]
    if kv_mode != "model":
        scat = _KV_FNS[kv_mode][1]
        kc, ksc = scat(kc, ksc, ks, true_len, table_row, block_size)
        vc, vsc = scat(vc, vsc, vs, true_len, table_row, block_size)
    else:
        kc = scatter_prefill(kc, ks, true_len, table_row, block_size)
        vc = scatter_prefill(vc, vs, true_len, table_row, block_size)
    x_last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1,
                                          axis=1)[:, 0]
    lg = _logits(x_last, params, spec)                   # [1, V]
    if any_sample:
        key, sub = jax.random.split(key)
        tok = _sample_batched(lg, sub, samp["do_sample"],
                              samp["temperature"], samp["top_k"],
                              samp["top_p"])
    else:
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    return tok, kc, vc, ksc, vsc, key


def _chunk_prefill_impl(spec: _GenSpec, block_size: int, kv_mode: str,
                        any_sample: bool, emit_token: bool, ctx_pages: int,
                        pages: int, params, ints, kc, vc, ksc, vsc, samp,
                        key):
    """Prefill ONE chunk of one prompt. `ints` [5 + pages + C]
    (`_StackedPrograms.chunk` packs it): start, true_end, last_idx,
    cow_src, cow_dst, the slot's block table row, the chunk's ids padded
    to the bucket C. Compute Q/K/V for positions [start, true_end),
    scatter the chunk's K/V through the block table
    (token-granular — a prefix-cache suffix may start mid-block), and
    attend each chunk position over the WHOLE context so far (cached
    prefix pages + earlier chunks + this chunk) gathered from the paged
    cache under a `kv_pos <= q_pos` mask. `emit_token` (static) is True
    only for the prompt's final chunk: it samples the first token from
    the chunk-local index `last_idx`; earlier chunks skip the vocab
    matmul entirely. `cow_src`/`cow_dst` implement copy-on-write: the
    shared block a whole-prompt cache hit must partially overwrite is
    duplicated into a private block BEFORE any write (both TRASH_BLOCK
    = no-op). Context length is static via `ctx_pages` (bucketed): pages
    past the written watermark gather garbage the causal mask never
    reaches."""
    start, true_end, last_idx, cow_src, cow_dst = (ints[i] for i in range(5))
    table_row = ints[5:5 + pages]
    ids = ints[5 + pages:]                               # [C]
    c = ids.shape[0]
    kc = copy_block(kc, cow_src, cow_dst)
    vc = copy_block(vc, cow_src, cow_dst)
    if kv_mode != "model":
        ksc = copy_block(ksc, cow_src, cow_dst)
        vsc = copy_block(vsc, cow_src, cow_dst)
    pos = start + jnp.arange(c)
    x, rope = db.embed(                                  # [C, H]
        params, ids,
        jnp.clip(pos, 0, db.num_positions(params, spec) - 1), spec)
    kv_pos = jnp.arange(ctx_pages * block_size)
    q_mask = kv_pos[None, :] <= pos[:, None]             # [C, T]
    i4 = kv_mode == "int4"

    def layer(xc, lw, row, *pools):
        pools = list(pools)

        def attend(q, k, v):
            pools[:] = _scatter_rows(k, v, *pools, start, true_end, row,
                                     block_size, kv_mode)
            kx = gather_context(pools[0], pools[2], row, ctx_pages, int4=i4)
            vx = gather_context(pools[1], pools[3], row, ctx_pages, int4=i4)
            return db.attend_many(q, kx.astype(q.dtype),      # [T, Hkv, D]
                                  vx.astype(q.dtype), q_mask)

        return (db.block(xc, lw, spec, attend, rope), *pools)

    x, kc, vc, ksc, vsc = _scan_layers(layer, x, params, table_row, kc, vc,
                                       ksc, vsc)
    if emit_token:
        x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=0)
        lg = _logits(x_last, params, spec)               # [1, V]
        if any_sample:
            key, sub = jax.random.split(key)
            tok = _sample_batched(lg, sub, samp["do_sample"],
                                  samp["temperature"], samp["top_k"],
                                  samp["top_p"])
        else:
            tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    else:
        tok = jnp.zeros((1,), jnp.int32)
    return tok, kc, vc, ksc, vsc, key


def _verify_tokens(lg, proposed, samp, key, any_sample):
    """Speculative accept/emit over the verify program's [B, C, V]
    logits (C = K+1 candidate positions; `proposed` [B, K] = candidates
    1..K). Greedy rows accept while each proposal matches the verifier's
    own argmax (accept-longest-prefix — token parity with the
    non-speculative engine by construction). Sampling rows run
    Leviathan-style rejection sampling against the row's FILTERED
    distribution p (the draft proposes deterministically, a point-mass
    q): accept x with probability p(x); a rejection resamples from the
    residual normalize(max(p - q, 0)) = p with x zeroed; position K's
    draw is the all-accepted bonus token. The emitted marginal is
    exactly p at every position. Returns (acc [B, K] bool, tgt [B, C]
    int32, key): tgt[:, j] is the token to emit when acceptance stops
    at position j (correction for j < K, bonus at K)."""
    b, c, v = lg.shape
    kk = c - 1
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)       # [B, C]
    acc = proposed == greedy[:, :kk]
    if not any_sample:
        return acc, greedy, key
    flat = lg.reshape(b * c, v)
    filt = _filter_logits(flat, jnp.repeat(samp["temperature"], c),
                          jnp.repeat(samp["top_k"], c),
                          jnp.repeat(samp["top_p"], c)).reshape(b, c, v)
    probs = jax.nn.softmax(filt, axis=-1)
    key, k_acc, k_res, k_bonus = jax.random.split(key, 4)
    u = jax.random.uniform(k_acc, (b, kk))
    p_prop = jnp.take_along_axis(probs[:, :kk], proposed[..., None],
                                 axis=-1)[..., 0]
    acc_s = u < p_prop    # p(x)=1 always accepts: the residual is empty
    res = jnp.where(jax.nn.one_hot(proposed, v, dtype=bool), -jnp.inf,
                    filt[:, :kk])
    resample = jax.random.categorical(
        k_res, res.reshape(b * kk, v), axis=-1).reshape(b, kk)
    bonus = jax.random.categorical(k_bonus, filt[:, kk], axis=-1)
    tgt_s = jnp.concatenate([resample, bonus[:, None]],
                            axis=1).astype(jnp.int32)
    ds = samp["do_sample"][:, None]
    return (jnp.where(ds, acc_s, acc), jnp.where(ds, tgt_s, greedy), key)


def _spec_verify_impl(spec: _GenSpec, block_size: int, kv_mode: str,
                      any_sample: bool, pages: int, params, ints, kc, vc,
                      ksc, vsc, samp, key):
    """Score C = K+1 candidate positions per slot in ONE paged-attention
    pass — the verify half of speculative decoding, costing the same
    weight sweep as a single decode tick. `ints` [B, 2 + pages + C], a
    row a slot (`ServingEngine._spec_decode` packs it): pos, limit, the
    block table row, toks. toks[:, 0] is each slot's last
    emitted (not yet consumed) token, toks[:, 1:] its K proposals; row b
    writes candidate K/V at positions pos[b] + [0, C) through its block
    table (positions >= limit[b], the slot's allocated-token watermark,
    route to the trash block — candidates past the block budget are
    never emitted, their garbage context never feeds an emitted token)
    and attends each candidate over `kv_pos <= q_pos`. Scores stay the
    chunk program's rank-4 multi-query-over-pages shape, NOT the rank-3
    seq-1 shape D4's decode anchor matches. Rollback of rejected
    candidates is the host simply not advancing kv_len past the
    accepted prefix: the cache's stale-data contract (reads bounded by
    length masks, appends overwrite before the mask exposes a slot)
    makes leftover K/V unreachable, and the next window's writes at the
    same positions are idempotent re-derivations. The accept/emit split
    lives in _verify_tokens; this returns (acc [B, K], tgt [B, C],
    caches..., key)."""
    pos, limit = ints[:, 0], ints[:, 1]
    tables, toks = ints[:, 2:2 + pages], ints[:, 2 + pages:]
    b, c = toks.shape
    qpos = pos[:, None] + jnp.arange(c)[None, :]          # [B, C]
    x, rope = db.embed(                                   # [B, C, H]
        params, toks,
        jnp.clip(qpos, 0, db.num_positions(params, spec) - 1), spec)
    end = jnp.minimum(pos + c, limit)
    kv_pos = jnp.arange(pages * block_size)
    q_mask = kv_pos[None, None, :] <= qpos[:, :, None]    # [B, C, T]
    i4 = kv_mode == "int4"

    def layer(xc, lw, rows, *pools):
        pools = list(pools)

        def attend(q, k, v):
            # per-row window scatter: the slot bucket is small, so the
            # unrolled loop reuses the chunk programs' token-granular
            # scatter (+ its int8 self-healing requantization) unchanged
            for bi in range(b):
                pools[:] = _scatter_rows(k[bi], v[bi], *pools, pos[bi],
                                         end[bi], rows[bi], block_size,
                                         kv_mode)
            kx = jax.vmap(lambda tr: gather_context(
                pools[0], pools[2], tr, pages, int4=i4))(rows)
            vx = jax.vmap(lambda tr: gather_context(
                pools[1], pools[3], tr, pages, int4=i4))(rows)
            return db.attend_many(q, kx.astype(q.dtype),   # [B, T, Hkv, D]
                                  vx.astype(q.dtype), q_mask)

        return (db.block(xc, lw, spec, attend, rope), *pools)

    x, kc, vc, ksc, vsc = _scan_layers(layer, x, params, tables, kc, vc,
                                       ksc, vsc)
    lg = _logits(x.reshape(b * c, -1), params, spec).reshape(
        b, c, -1)                                          # [B, C, V] f32
    acc, tgt, key = _verify_tokens(lg, toks[:, 1:], samp, key,
                                   any_sample)
    return acc, tgt, kc, vc, ksc, vsc, key


# what the host decides a call (tokens, positions, tables, ids) reaches a
# program as ONE int32 operand, unpacked by static slices: one transfer a
# call, not one a value. Only the pools are donated.
_decode_step = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3),
    donate_argnums=(6, 7, 8, 9))(_decode_step_impl)
_prefill_step = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3, 4),
    donate_argnums=(7, 8, 9, 10))(_prefill_impl)
_chunk_prefill_step = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6),
    donate_argnums=(9, 10, 11, 12))(_chunk_prefill_impl)
_spec_verify_step = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3, 4),
    donate_argnums=(7, 8, 9, 10))(_spec_verify_impl)


# ------------------------------------------------------------ scheduler

#: host-side mirror of the step programs' cache keys (shared across
#: engines, like the executables themselves) — obs compile watchdog.
#: Kept SEPARATE from the executable cache below so tests can clear the
#: event mirror (forcing compile events to re-record) without forcing a
#: real recompile.
# thread-safe: GIL-atomic set adds from contract-owned engine threads;
# tests clear it between runs with no engine ticking
_SEEN_SERVING_PROGRAMS: set = set()

#: monotonically-increasing engine names for the shared /metrics
#: endpoint's `engine` label (round 16).
# thread-safe: next() on an itertools counter is atomic under the GIL —
# two engines constructed concurrently can no longer mint one name
# (round-17 fix; the bare `global n; n += 1` read-modify-write raced)
_ENGINE_IDS = itertools.count()

#: round 14: the engine owns its executables via the AOT path
#: (jitted.lower().compile()) instead of jax.jit's implicit cache —
#: the compiled object carries XLA cost_analysis()/memory_analysis()
#: for free (obs/costs.py), the compile wall is measured exactly (not
#: smeared into the first execution), and dispatch overhead is within
#: noise of the jit fast path (measured ~2.6us vs ~2.4us per call).
#: key -> (compiled_executable, obs.costs.ProgramCost entry).
# thread-safe: GIL-atomic dict get/set; a duplicate compile under a
# concurrent-engines race wastes one compile, last-write-wins on insert
_SERVING_EXECUTABLES: dict = {}


class _StackedPrograms:
    """The dense side of `ServingEngine` (llama / gpt: layers stacked by
    `_stacked_params*`; one pool array `[L, N, H_kv, bs, D]`, which the
    step programs see as `[L * N, ...]`, carry through their layer scan
    and address by `l * N + block id`: `_scan_layers`): for `_run_chunk`
    and `_decode` each site's step function with its operands, and what
    its result means. `inference/layered.LayeredPrograms` is its
    counterpart for a model that declares its layers one by one, method
    for method."""

    whole_prompt_prefill = True

    def __init__(self, eng):
        self.eng = eng

    def full_pool(self):
        """One layer's pool (shape and dtype): [N, H_kv, rows, D]."""
        k = self.eng.cache.k
        return jax.ShapeDtypeStruct(k.shape[1:], k.dtype)

    def chunk_buckets(self, n, ctx_need):
        """(chunk-length bucket, context-pages bucket) of a chunk of `n`
        tokens whose context ends in page `ctx_need`."""
        from ..jit.api import default_buckets

        e = self.eng
        c_bucket = max(8, default_buckets(n))
        return c_bucket, min(e.pages, max(
            default_buckets(ctx_need),
            blocks_for(c_bucket, e.block_size) + 1))

    def chunk(self, slot, req, start, n, c_bucket, is_last, ctx_pages, cow):
        """(step, number of static operands, operands)."""
        e, c = self.eng, self.eng.cache
        sample = req.do_sample and is_last
        ints = np.zeros(5 + e.pages + c_bucket, np.int32)
        ints[:5] = (start, start + n, req.prompt.size - 1 - start, *cow)
        ints[5:5 + e.pages] = e._tables[slot]
        ints[5 + e.pages:5 + e.pages + n] = req.prompt[start:start + n]
        return _chunk_prefill_step, 7, (
            e.spec, e.block_size, e.kv_mode, sample, is_last, ctx_pages,
            e.pages, e.params, e._put(ints), c.k, c.v, c.k_scale,
            c.v_scale, e._samp([req], 0, sample), e._key)

    def chunk_done(self, out, n, is_last, run):
        """Take the program's result: swap the pools in, fetch the token
        (None unless the prompt's last chunk)."""
        c = self.eng.cache
        tok_arr, ck, cv, cks, cvs, self.eng._key = out
        c.swap(ck, cv, cks, cvs)
        if is_last:
            return int(jax.device_get(tok_arr)[0])
        # non-final chunks fetch no token, so without an explicit barrier
        # the span would end at async dispatch's enqueue time — block on
        # the written cache so the observed wall (roofline utilization +
        # the chunk's span) is the program's
        jax.block_until_ready(c.k)
        return None

    def decode(self, active, reqs, bucket, any_sample):
        e, c = self.eng, self.eng.cache
        n = len(active)
        # padded rows: token 0 at position 0 through the trash block
        ints = np.zeros((bucket, 2 + e.pages), np.int32)
        ints[n:, 2:] = TRASH_BLOCK
        ints[:n, 0] = [r.tokens[-1] for r in reqs]
        ints[:n, 1] = e._slot_pos[active]
        ints[:n, 2:] = e._tables[active]
        return _decode_step, 4, (
            e.spec, e.block_size, e.kv_mode, any_sample, e.params,
            e._put(ints), c.k, c.v, c.k_scale, c.v_scale,
            e._samp(reqs, bucket - n, any_sample), e._key)

    def decode_done(self, out, n_active, run):
        nxt, ck, cv, cks, cvs, self.eng._key = out
        self.eng.cache.swap(ck, cv, cks, cvs)
        return np.asarray(jax.device_get(nxt))

    def decode_jaxpr(self, bucket, samp):
        e, c = self.eng, self.eng.cache
        fn = functools.partial(_decode_step_impl, e.spec, e.block_size,
                               e.kv_mode, False)
        return jax.make_jaxpr(fn)(
            e.params, jnp.zeros((bucket, 2 + e.pages), jnp.int32),
            c.k, c.v, c.k_scale, c.v_scale, samp, e._key)

    def kv_steps(self, bucket):
        return self.eng._kv_steps(bucket)

    def update_gauges(self):
        """No gauge of its own: one kind of layer state."""


class Request:
    """One generation request riding the engine."""

    __slots__ = ("rid", "prompt", "max_new_tokens", "do_sample",
                 "temperature", "top_k", "top_p", "eos_token_id",
                 "tokens", "arrival_s", "admitted_s", "first_token_s",
                 "finished", "max_time_ms", "deadline_s", "finish_reason",
                 "cached_len", "prefill_pos", "prefill_done",
                 "speculative", "_hashes", "_hash_ns", "_flight")

    def __init__(self, rid, prompt, max_new_tokens, do_sample, temperature,
                 top_k, top_p, eos_token_id, max_time_ms=None,
                 speculative=None, arrival_s=None):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.do_sample = bool(do_sample)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_token_id = -1 if eos_token_id is None else int(eos_token_id)
        self.tokens: list[int] = []
        # when the request reached the SYSTEM (a perf_counter time): the
        # caller's, where it kept one (a router's mailbox, an open-loop
        # generator's due time), else now
        self.arrival_s = time.perf_counter() if arrival_s is None \
            else float(arrival_s)
        self.admitted_s = None      # set when a slot + block budget land
        self.first_token_s = None
        self.finished = False
        # per-request deadline (robustness round 12): a wall-clock budget
        # from ARRIVAL; an expired request finishes with reason "timeout"
        # and releases its blocks — a stuck-long request can't hold a
        # slot + pool budget forever
        self.max_time_ms = None if max_time_ms is None else float(max_time_ms)
        self.deadline_s = None if max_time_ms is None \
            else self.arrival_s + float(max_time_ms) / 1e3
        self.finish_reason = None   # "eos" | "length" | "timeout"
        # per-request speculative opt-out (round 18): None follows the
        # engine config; False decodes normally even on a spec engine
        self.speculative = speculative
        # prefix-cache / chunked-prefill progress (set at admission):
        # positions [0, cached_len) are served from cached blocks, the
        # suffix [cached_len, prompt) is computed chunk by chunk —
        # prefill_pos is the next position to compute
        self.cached_len = 0
        self.prefill_pos = 0
        self.prefill_done = False
        # memoized prefix-block hashes (a pool-blocked head-of-line
        # request is re-examined every scheduler tick; the sha256 chain
        # over an 8k prompt must not recompute per tick)
        self._hashes = None
        self._hash_ns = None
        # flight-recorder timeline (obs/flight.py), set at add_request
        self._flight = None

    def expired(self, now=None) -> bool:
        if self.deadline_s is None:
            return False
        return (time.perf_counter() if now is None else now) \
            >= self.deadline_s

    @property
    def ttft_s(self):
        if self.first_token_s is None:
            return None
        return self.first_token_s - self.arrival_s

    @property
    def queue_wait_s(self):
        """Host wall spent WAITING for admission (slot + block budget).
        Split out of TTFT so the prefill span measures prefill — a pool
        blocking on releases used to inflate 'prefill' p95s."""
        if self.admitted_s is None:
            return None
        return self.admitted_s - self.arrival_s

    @property
    def prefill_s(self):
        """Admission → first token: the actual prefill program span.
        ttft_s == queue_wait_s + prefill_s."""
        if self.first_token_s is None or self.admitted_s is None:
            return None
        return self.first_token_s - self.admitted_s


class ServingEngine:
    """Continuous-batching scheduler over a fixed slot array + paged KV
    pool. `admission="continuous"` (default) refills freed slots
    mid-flight; `admission="static"` only admits into an EMPTY engine
    (whole-batch waves) — the baseline the serving bench compares
    utilization against.

    THREAD CONTRACT (round 17, D15): the engine is deliberately
    single-threaded — one owner thread drives ``add_request``/``step``/
    ``run``/``finish_warmup`` (the scheduler state, slot arrays, block
    pool and prefix cache are mutated without locks by design). The
    contract binds to the first driving thread; under
    ``FLAGS_debug_thread_checks`` a call from any other thread raises
    ``ConcurrencyContractError``. A future router over N engine replicas
    must serialize each engine's calls onto one thread (or hand off
    ownership explicitly via ``engine.contract.rebind()`` after
    draining). Read-only surfaces (``stats()``, ``metrics()``, the
    /metrics endpoint, ``close()``) stay thread-safe."""

    #: D15 static marker: methods the single-owner contract guards
    _thread_contract = ("add_request", "step", "run", "finish_warmup",
                        "drain")

    def __init__(self, model, max_slots=None, kv_block_size=None,
                 num_kv_blocks=None, kv_cache_dtype=None,
                 max_model_len=None, seed=0, admission="continuous",
                 prefix_cache=None, chunked_prefill_tokens=None,
                 prefix_cache_max_blocks=None, spec_decode=None,
                 weight_quant=None):
        from ..core.flags import flag

        if weight_quant in (None, "none"):
            # serving-wide default; per-engine weight_quant= overrides
            weight_quant = str(flag("FLAGS_weight_only_dtype"))
        if weight_quant not in ("none", "int8", "int4"):
            raise ValueError(
                f"weight_quant must be 'none', 'int8' or 'int4', got "
                f"{weight_quant!r}")
        self.weight_quant = str(weight_quant)
        cfg = model.config
        arch = getattr(model, "_gen_arch", "llama")
        #: a model that declares its layers one by one (`serving_arrays()`:
        #: its own buffers, a layer each; `config.block_spec()`: each
        #: layer's cache kind) is served by inference/layered.py's
        #: programs. `self.layered` is their static key (None for the
        #: dense architectures' stacked programs), `self.ring` the window
        #: layers' state; `self.programs` answers for either kind
        self.layered = self.ring = None
        per_layer = hasattr(model, "serving_arrays")
        if per_layer:
            blk = cfg.block_spec()
            self.spec = _GenSpec(
                num_layers=len(blk.layer_types), num_heads=blk.num_heads,
                num_kv_heads=blk.num_kv_heads, head_dim=blk.head_dim,
                rope_theta=blk.rope_theta, rms_eps=blk.eps,
                max_new_tokens=0, do_sample=False, top_k=0, top_p=1.0,
                temperature=1.0, eos_token_id=-1, tie_embeddings=True,
                arch=arch, weight_quant="none")
            # the model's own buffers, by reference: no second copy
            self.params = model.serving_arrays()
        elif arch == "gpt":
            nh = cfg.num_attention_heads
            self.spec = _GenSpec(
                num_layers=cfg.num_hidden_layers, num_heads=nh,
                num_kv_heads=nh, head_dim=cfg.hidden_size // nh,
                rope_theta=0.0, rms_eps=cfg.layer_norm_eps,
                max_new_tokens=0, do_sample=False, top_k=0, top_p=1.0,
                temperature=1.0, eos_token_id=-1, tie_embeddings=False,
                arch="gpt", weight_quant=self.weight_quant)
            self.params = _stacked_params_gpt(
                model, weight_quant=self.weight_quant)
        else:
            self.spec = _GenSpec(
                num_layers=cfg.num_hidden_layers,
                num_heads=cfg.num_attention_heads,
                num_kv_heads=cfg.num_key_value_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                rms_eps=cfg.rms_norm_eps, max_new_tokens=0,
                do_sample=False, top_k=0, top_p=1.0, temperature=1.0,
                eos_token_id=-1,
                tie_embeddings=bool(cfg.tie_word_embeddings),
                weight_quant=self.weight_quant)
            self.params = _stacked_params(
                model, weight_quant=self.weight_quant)
        self.block_size = int(kv_block_size or flag("FLAGS_kv_block_size"))
        self.max_slots = int(max_slots or flag("FLAGS_serving_slots"))
        if self.max_slots < 1:
            raise ValueError("need at least one serving slot")
        mode = str(kv_cache_dtype or flag("FLAGS_kv_cache_dtype"))
        if mode not in ("model", "int8", "int4"):
            raise ValueError(f"kv_cache_dtype must be 'model', 'int8' or "
                             f"'int4', got {mode!r}")
        self.kv_mode = mode
        self.quantized = mode != "model"
        dtype = self.params["embed"].dtype
        # usable context rounds DOWN to whole pages (prompt + decode both
        # address the cache through page-granular tables)
        max_pos = int(cfg.max_position_embeddings)
        mml = min(int(max_model_len or max_pos), max_pos)
        self.max_model_len = (mml // self.block_size) * self.block_size
        if self.max_model_len < self.block_size:
            raise ValueError(
                f"max_model_len {mml} below one kv block ({self.block_size})")
        self.pages = self.max_model_len // self.block_size
        # default pool: every slot can hold a full-context sequence (+the
        # trash block); size it down to exercise admission control
        if num_kv_blocks is None:
            num_kv_blocks = 1 + self.max_slots * self.pages
        self.chunk_tokens = int(
            flag("FLAGS_chunked_prefill_tokens")
            if chunked_prefill_tokens is None else chunked_prefill_tokens)
        if per_layer:
            # layer state by kind in one manager: the allocator's pages
            # are the full-history layers' (K and V, or a latent row); a
            # window layer keeps a static ring of the last window + chunk
            # positions a slot
            self._refuse_for_layered(arch, mode, spec_decode, prefix_cache,
                                     layered.refusals(blk))
            prefix_cache = False
            self.programs = layered.LayeredPrograms(
                self, blk, int(num_kv_blocks), dtype)
            self.layered, self.ring, self.cache = (
                self.programs.spec, self.programs.ring, self.programs.cache)
        else:
            self.cache = PagedKVCache(
                self.spec.num_layers, int(num_kv_blocks),
                self.spec.num_kv_heads, self.block_size,
                self.spec.head_dim, mode if self.quantized else dtype)
            self.programs = _StackedPrograms(self)
        kv_dtype = str(self.programs.full_pool().dtype)
        self.allocator = BlockAllocator(int(num_kv_blocks))
        if admission not in ("continuous", "static"):
            raise ValueError(f"unknown admission mode {admission!r}")
        self.admission = admission
        # ---- prefix cache + chunked prefill (round 13). The PrefixCache
        # wraps the allocator for EVERY alloc/release, cache enabled or
        # not: with the flag off nothing is ever hash-registered, so
        # release degenerates to the free list and behavior is identical
        # to the round-10 engine.
        self.prefix_cache_enabled = bool(
            flag("FLAGS_prefix_cache") if prefix_cache is None
            else prefix_cache)
        self.prefix_cache = PrefixCache(
            self.allocator,
            max_cached_blocks=int(
                flag("FLAGS_prefix_cache_max_blocks")
                if prefix_cache_max_blocks is None
                else prefix_cache_max_blocks))
        #: seeds the content-hash chain: KV blocks are only interchangeable
        #: within one (arch, layer geometry, block size, cache MODE).
        #: kv_mode (not the storage dtype) disambiguates int4 from int8 —
        #: both store int8 arrays, but their block bytes mean different
        #: things, so cached blocks must never alias across modes. The
        #: spec carries weight_quant, so differently-quantized weights
        #: (different K/V numerics) never alias either.
        self._prefix_namespace = hash(
            (self.spec, self.block_size, self.kv_mode, kv_dtype))
        self._slot_chunk: dict[int, dict] = {}   # slot -> chunk progress
        self._slot_extra_refs: list[list[int]] = [[] for _ in
                                                  range(self.max_slots)]
        # D7 (cache-defeated) bookkeeping: identical prompts re-admitted
        # while the cache is on should be hitting. LRU-capped — a
        # long-lived engine over mostly-unique prompts must not grow an
        # unbounded host-side set for a diagnostic
        self._prompt_fingerprints: OrderedDict = OrderedDict()
        self._prompt_fingerprints_cap = 4096
        self.prefix_repeat_admissions = 0
        self._tables = np.zeros((self.max_slots, self.pages), np.int32)
        self._slot_req: list[Request | None] = [None] * self.max_slots
        self._slot_pos = np.zeros(self.max_slots, np.int64)
        self._slot_blocks: list[list[int]] = [[] for _ in
                                              range(self.max_slots)]
        self._waiting: deque[Request] = deque()
        self._key = jax.random.PRNGKey(int(seed))
        self._next_id = 0
        # what a call's build need not do again: host arrays handed to
        # the device so far (`_put`; a `.build` span's `h2d` is its
        # share), a greedy call's sampling operands by bucket size
        # (`_samp`), the decode kernel's grid by slot bucket
        self._h2d = 0
        self._greedy_samp: dict[int, dict] = {}
        self._kv_steps_of: dict[int, int] = {}
        # scheduler bookkeeping the step logic itself reads
        self.steps = 0
        self.active_slot_steps = 0
        self.completed: dict[int, np.ndarray] = {}
        self.finish_reasons: dict[int, str] = {}
        # the newest samples only, as many as the histograms' exact ring
        # keeps: a long-lived server does not grow, stats() copies a
        # bounded list
        self.ttfts: deque[float] = deque(maxlen=DEFAULT_EXACT_CAP)
        self.queue_waits: deque[float] = deque(maxlen=DEFAULT_EXACT_CAP)
        # ---- telemetry (obs): the serving stats ARE a metrics registry
        # now — stats() is a thin view over it. Per-ENGINE registry so
        # concurrent engines/tests never share counters; always on (the
        # per-tick cost is a handful of attribute updates — PERF.md
        # round 11 measures the overhead under 2% tok/s).
        from .. import obs

        self.registry = obs.Registry()
        reg = self.registry
        self._m_ttft = reg.histogram(
            "serving_ttft_seconds", "arrival -> first token (= queue wait "
            "+ prefill)")
        self._m_queue_wait = reg.histogram(
            "serving_queue_wait_seconds",
            "arrival -> admission (slot + full block budget)")
        self._m_prefill = reg.histogram(
            "serving_prefill_seconds", "admission -> first token (the "
            "prefill program span, queue wait excluded)")
        self._m_decode_step = reg.histogram(
            "serving_decode_step_seconds", "one decode tick (all active "
            "slots advance one token)")
        self._m_tpot = reg.histogram(
            "serving_tpot_seconds", "time per output token as the "
            "request's user sees it, observed ONCE PER EMITTED TOKEN: "
            "the gap since that request's previous token (a speculative "
            "verify window that emits n tokens observes gap / n, n "
            "times — multi-token ticks report real TPOT, not a fake "
            "per-tick win)")
        self._m_decode_tokens = reg.counter(
            "serving_decode_tokens_total", "tokens emitted by decode ticks")
        self._m_prefill_tokens = reg.counter(
            "serving_prefill_tokens_total", "prompt tokens prefilled")
        self._m_completed = reg.counter(
            "serving_requests_completed_total", "requests finished (eos, "
            "length or timeout)")
        self._m_timeout = reg.counter(
            "serving_requests_timeout_total", "requests finished by their "
            "per-request deadline (max_time_ms) — slots/blocks reclaimed")
        self._m_rejects = reg.counter(
            "serving_admission_rejects_total", "requests rejected outright "
            "(could never be served)", ("reason",))
        self._m_drained = reg.counter(
            "serving_drained_requests_total", "requests that finished "
            "while the engine was draining (router drain/handoff — each "
            "one completed or timed out in place instead of being "
            "dropped by the deploy)")
        self._m_blocked = reg.counter(
            "serving_admission_blocked_total", "admission attempts that "
            "waited: head-of-line request's block budget did not fit the "
            "free pool")
        self._m_prefix_hit = reg.counter(
            "serving_prefix_blocks_hit_total", "prompt KV blocks served "
            "from the prefix cache (zero prefill paid for them)")
        self._m_prefix_miss = reg.counter(
            "serving_prefix_blocks_missed_total", "full prompt KV blocks "
            "that had to be computed (no cached prefix covered them)")
        self._m_chunks = reg.counter(
            "serving_prefill_chunks_total", "chunk-prefill program "
            "invocations (chunked + cache-hit-suffix prefills)")
        self._m_prefix_evict = reg.counter(
            "serving_prefix_cache_evictions_total", "cached blocks "
            "evicted (LRU, refcount-0 only) to satisfy allocations")
        self._m_cache_blocks = reg.gauge(
            "serving_prefix_cache_blocks", "blocks addressable by "
            "content hash (cached prefixes)")
        self._m_cache_refed = reg.gauge(
            "serving_prefix_cache_referenced_blocks", "hash-mapped blocks "
            "live requests still reference (refcount > 0)")
        self._m_queue_depth = reg.gauge(
            "serving_queue_depth", "requests waiting for admission")
        self._m_active = reg.gauge(
            "serving_active_slots", "slots currently decoding")
        self._m_pool_free = reg.gauge(
            "serving_block_pool_free_blocks", "free KV blocks")
        self._m_pool_used = reg.gauge(
            "serving_block_pool_used_blocks", "allocated KV blocks")
        # ---- flight recorder (round 14): every request gets a span
        # timeline; anomalies (timeout / TTFT SLO breach / post-warmup
        # compile) auto-dump a Chrome-trace postmortem
        self._m_flight_anomalies = reg.counter(
            "serving_flight_anomalies_total", "flight-recorder anomaly "
            "triggers observed (request timeout, TTFT SLO breach, "
            "post-warmup compile)", ("trigger",))
        self._m_flight_dumps = reg.counter(
            "serving_flight_dumps_total", "flight-recorder postmortem "
            "trace files written to FLAGS_obs_flight_dir", ("trigger",))
        self._m_flight_requests = reg.gauge(
            "serving_flight_requests", "request timelines held in the "
            "flight-recorder ring (active + finished)")
        # ---- speculative decoding (round 18): metrics exist whether or
        # not the engine speculates — the catalog contract is
        # unconditional, a non-spec engine just never observes them
        self._m_spec_windows = reg.counter(
            "serving_spec_windows_total", "speculative verify windows "
            "executed (one K+1-candidate batched scoring pass per "
            "speculating slot per tick)")
        self._m_spec_proposed = reg.counter(
            "serving_spec_proposed_tokens_total", "draft tokens proposed "
            "into verify windows")
        self._m_spec_accepted = reg.counter(
            "serving_spec_accepted_tokens_total", "proposed tokens the "
            "verify oracle accepted (emitted without their own decode "
            "tick — the speculative goodput)")
        self._m_spec_accept_rate = reg.histogram(
            "serving_spec_accept_rate", "per-window acceptance fraction "
            "(accepted / proposed)")
        self._m_spec_emitted = reg.histogram(
            "serving_spec_accepted_per_window", "tokens emitted per "
            "verify window: accepted prefix + the correction/bonus "
            "token (1..K+1)")
        # ---- expert layers and the two-kind cache (PR 31): like the
        # speculative rows, they exist on every engine; a dense model
        # with one kind of layer state never moves them
        self._m_moe_picks = reg.counter(
            "serving_moe_local_picks_total", "(token, held expert) picks "
            "the step programs computed, summed over expert layers")
        self._m_moe_tokens = reg.counter(
            "serving_moe_routed_tokens_total", "tokens routed, counted "
            "once an expert layer: the held experts' share of the picks "
            "is local_picks / (routed_tokens x experts per token)")
        self._m_moe_read = reg.counter(
            "serving_moe_experts_read_total", "held experts whose weights "
            "a layer read (those with a live pick on the grouped kernel, "
            "every one on the scan), summed over expert layers and step "
            "programs")
        self._m_kv_window = reg.gauge(
            "serving_kv_window_bytes_held", "bytes of window-layer state "
            "(the rings of occupied slots, all window layers)")
        self._m_kv_full = reg.gauge(
            "serving_kv_full_blocks_used", "full-history cache blocks "
            "allocated to live requests")
        # ---- the latent (MLA) cache (PR 37): one row a position a layer
        self._m_kv_latent = reg.gauge(
            "serving_kv_latent_bytes_held", "bytes of latent rows (as "
            "stored, padding included) in the blocks allocated to live "
            "requests, all latent layers")
        self._m_latent_ctx = reg.counter(
            "serving_latent_ctx_tokens_total", "cached positions the step "
            "programs attended, once a latent layer: each decode token's "
            "context and each chunk token's positions up to its own")
        self._m_latent_kernel_blocks = reg.counter(
            "serving_latent_chunk_kernel_blocks_total", "context blocks the "
            "chunk programs attended through the latent chunk kernel, once "
            "a latent layer (PR 38); zero where the composition ran")
        # ---- recurrent state beside the pages (PR 40): a linear layer's
        # state a slot, reset by the first chunk of each request
        self._m_state_slots = reg.counter(
            "serving_state_slot_steps_total", "slot states the decode "
            "programs advanced one token, once a linear-attention layer")
        self._m_state_tokens = reg.counter(
            "serving_state_tokens_total", "prompt tokens the chunk programs "
            "ran through the recurrence, once a linear-attention layer")
        self._m_state_bytes = reg.gauge(
            "serving_recurrent_state_bytes_held", "bytes of recurrent state "
            "(a float32 matrix a value head and the conv's last inputs) "
            "the occupied slots hold, all linear-attention layers")
        # config: explicit arg wins; the FLAGS_spec_decode string is the
        # flag-surface shorthand ("off" | "ngram" | "draft")
        from .speculative import SpecConfig, make_proposer

        if spec_decode is None:
            m = str(flag("FLAGS_spec_decode"))
            spec_decode = None if m == "off" else SpecConfig(method=m)
        elif isinstance(spec_decode, str):
            spec_decode = None if spec_decode == "off" \
                else SpecConfig(method=spec_decode)
        self.spec_config = spec_decode
        self.proposer = (make_proposer(spec_decode)
                         if spec_decode is not None else None)
        reg.gauge("serving_slots", "engine slot count").set(self.max_slots)
        reg.gauge("serving_kv_pool_blocks",
                  "total KV blocks (incl. trash)").set(
                      self.allocator.num_blocks)
        self._m_pool_free.set(self.allocator.available)
        # compile watchdog + executable-cache state: after
        # finish_warmup() any NEW program key is a steady-state retrace
        # (warm=True -> lint finding). The static key prefix is
        # prehashed ONCE — _program runs every tick and a frozen
        # dataclass rehashes per lookup. Round 14: the key now also
        # fingerprints the param avals — the key addresses REAL
        # executables (_SERVING_EXECUTABLES), so two models sharing a
        # _GenSpec but differing in vocab/intermediate width must not
        # collide onto one compiled program.
        params_fp = tuple((tuple(p.shape), str(p.dtype))
                          for p in jax.tree_util.tree_leaves(self.params))
        self._prog_key_base = hash(
            (self.spec, self.layered, self.block_size, self.kv_mode,
             self.pages, self.allocator.num_blocks, kv_dtype, params_fp))
        self._warmed = False
        self._draining = False
        # D15 owner-thread contract (binds on the first driving call,
        # NOT here — construction may happen on a loader thread)
        from ..core import lockdep as _lockdep

        self.contract = _lockdep.ThreadContract("ServingEngine")
        self.cache.contract = self.contract
        self.prefix_cache.contract = self.contract
        self.allocator.contract = self.contract
        self.flight = obs.FlightRecorder()
        slo_ms = float(flag("FLAGS_obs_slo_ttft_ms"))
        self._slo_ttft_s = slo_ms / 1e3 if slo_ms > 0 else None
        self._log = obs.get_logger(__name__)
        self._metrics_server = None
        self._engine_name = None
        port = int(flag("FLAGS_obs_http_port"))
        if port > 0:
            # round 16: engines share ONE endpoint per port — each
            # registers its registry (exported with an engine="..."
            # label) and a readiness probe (/healthz flips to 200 only
            # once every registered engine passed finish_warmup); the
            # pre-round-16 behavior left every engine after the first
            # unscraped on a bind failure
            try:
                self._engine_name = f"engine{next(_ENGINE_IDS)}"
                self._metrics_server = obs.shared_server(port)
                self._metrics_server.register_engine(
                    self._engine_name, reg, ready=lambda: self._warmed)
            except OSError as e:
                self._metrics_server = None
                self._log.warning(
                    f"obs metrics endpoint :{port} not started ({e}) — "
                    "this engine goes unscraped; use "
                    "obs.serve_metrics(port, engine.registry) to expose "
                    "it elsewhere", key="obs-http-bind")

    def _refuse_for_layered(self, arch, kv_mode, spec_decode, prefix_cache,
                            why):
        """What a model served by the per-layer programs does not get
        yet, each refused by name at construction (no fallback). `why`:
        the reason by option, true of the model's kinds of layer state
        (`layered.refusals`). `kv_mode`, `self.weight_quant` and
        `self.chunk_tokens` are the resolved values (argument, else
        flag)."""
        from ..core.flags import flag

        def refuse(option, key):
            raise ValueError(
                f"{option} is not supported for {arch}: {why[key]}")

        if self.weight_quant != "none":
            refuse(f"weight_quant={self.weight_quant!r}", "weight_quant")
        if kv_mode != "model":
            refuse(f"kv_cache_dtype={kv_mode!r}", "kv_cache_dtype")
        spec = (str(flag("FLAGS_spec_decode")) if spec_decode is None
                else spec_decode)
        if spec != "off":
            refuse(f"spec_decode={spec!r}", "spec_decode")
        if prefix_cache:
            refuse("prefix_cache=True", "prefix_cache")
        if self.chunk_tokens <= 0:
            refuse(f"chunked_prefill_tokens={self.chunk_tokens}",
                   "chunked_prefill_tokens")

    # ------------------------------------------------------------- API
    def add_request(self, prompt, max_new_tokens=32, do_sample=False,
                    temperature=1.0, top_k=0, top_p=1.0,
                    eos_token_id=None, max_time_ms=None,
                    speculative=None, arrival_s=None) -> int:
        """Queue a request. Raises when it could NEVER be served (context
        or pool too small); otherwise it waits for admission.
        `arrival_s` is the `time.perf_counter()` time at which the
        request reached the system, for a caller that held it before this
        call (a router's mailbox, an open-loop generator's due time):
        the deadline, the flight's enqueue mark and the TTFT / queue-wait
        histograms run from it. None: now.
        `max_time_ms` is a per-request wall-clock deadline from arrival:
        when it expires the request finishes with reason ``"timeout"``
        (whatever tokens it produced so far are its result) and its
        blocks return to the free list. `speculative=False` opts this
        request out of speculative decoding on a spec-enabled engine
        (it decodes one token per tick, coexisting with speculating
        slots in the same tick); None follows the engine config."""
        self.contract.check("add_request")
        if self._draining:
            self._reject("draining",
                         "engine is draining: no new admissions until "
                         "teardown (route to another replica)")
        prompt = np.asarray(
            prompt._data if hasattr(prompt, "_data") else prompt,
            np.int64).reshape(-1).astype(np.int32)
        if prompt.size < 1:
            self._reject("empty_prompt", "empty prompt")
        if int(max_new_tokens) < 1:
            self._reject("bad_max_new_tokens",
                         "max_new_tokens must be positive")
        total = prompt.size + int(max_new_tokens)
        if total > self.max_model_len:
            self._reject(
                "context_overflow",
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) = {total} exceeds the engine context "
                f"({self.max_model_len} = max_position_embeddings rounded "
                f"down to whole {self.block_size}-token kv blocks)")
        need = blocks_for(total, self.block_size)
        if need > self.allocator.num_blocks - 1:
            self._reject(
                "pool_too_small",
                f"request needs {need} kv blocks but the pool only has "
                f"{self.allocator.num_blocks - 1}")
        if max_time_ms is not None and float(max_time_ms) <= 0:
            self._reject("bad_max_time_ms", "max_time_ms must be positive")
        rid = self._next_id
        self._next_id += 1
        req = Request(rid, prompt, max_new_tokens, do_sample, temperature,
                      top_k, top_p, eos_token_id, max_time_ms=max_time_ms,
                      speculative=speculative, arrival_s=arrival_s)
        req._flight = self.flight.begin(rid, prompt.size,
                                        int(max_new_tokens),
                                        req.arrival_s)
        self._m_flight_requests.set(len(self.flight._flights))
        self._waiting.append(req)
        self._m_queue_depth.set(len(self._waiting))
        return rid

    def _reject(self, reason: str, msg: str):
        """Admission reject: count it, log it (rate-limited), raise."""
        self._m_rejects.labels(reason).inc()
        self._log.warning(f"admission reject ({reason}): {msg}",
                          key=f"reject:{reason}")
        raise ValueError(msg)

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @property
    def num_waiting(self) -> int:
        return len(self._waiting)

    def has_work(self) -> bool:
        return bool(self._waiting) or self.num_active > 0

    def step(self):
        """One scheduler tick: expire deadlined requests, admit joining
        requests (small cache-cold prompts prefill whole, long or
        cache-hit prompts enter the chunk ladder), advance every
        PREFILLING slot by one chunk, then advance every DECODING slot
        one token — chunked prefill interleaves with decode instead of
        head-of-line blocking it. Returns a list of (request_id, token,
        finished) for tokens emitted this tick; a request finished by
        its deadline emits a terminal ``(request_id, None, True)`` —
        streaming consumers see every completion, timeout included."""
        self.contract.check("step")
        # the spans of a tick (obs/trace.py): `serving.step` bounds it;
        # inside it a site's host preparation is `.build`, its dispatch up
        # to the result on the host `.run`, the bookkeeping after `.emit`
        with _span("serving.step", active=self.num_active,
                   waiting=len(self._waiting)):
            with _span("serving.expire"):
                emitted = self._expire()
            waiting = len(self._waiting)
            with _span("serving.admit") as sp:
                emitted.extend(self._admit())
                sp.attrs["admitted"] = waiting - len(self._waiting)
            emitted.extend(self._chunk_phase())
            active = [i for i, r in enumerate(self._slot_req)
                      if r is not None and r.prefill_done]
            if active:
                # partition: speculating slots ride the verify window, the
                # rest (opt-outs, empty proposals, non-spec engine) take
                # the ordinary one-token decode — both in the same tick
                spec_slots, props = self._spec_proposals(active)
                if spec_slots:
                    in_spec = set(spec_slots)
                    plain = [i for i in active if i not in in_spec]
                else:
                    plain = active
                if plain:
                    emitted.extend(self._decode(plain))
                if spec_slots:
                    emitted.extend(self._spec_decode(spec_slots, props))
                self.steps += 1
                self.active_slot_steps += len(active)
                self._m_active.set(len(active))
            if self._draining:
                done = sum(1 for _rid, _tok, fin in emitted if fin)
                if done:
                    self._m_drained.inc(done)
        return emitted

    def run(self, max_steps=100000):
        """Drive the engine until every queued request completes; returns
        {request_id: np.ndarray of generated tokens}."""
        for _ in range(max_steps):
            if not self.has_work():
                break
            self.step()
        else:
            raise RuntimeError("serving engine did not drain (max_steps)")
        return dict(self.completed)

    def stats(self) -> dict:
        """Thin view over the metrics registry (plus the scheduler's own
        counters) — the pre-obs ad-hoc stats dict, same keys, now derived
        from the same numbers /metrics exports. New in round 11:
        `queue_wait_s` / the TTFT decomposition (ttft = queue_wait +
        prefill, satellite-6 fix)."""
        util = (self.active_slot_steps / (self.steps * self.max_slots)
                if self.steps else 0.0)
        return {"steps": self.steps,
                "decode_tokens": int(self._m_decode_tokens.value),
                "prefill_tokens": int(self._m_prefill_tokens.value),
                "decode_time_s": self._m_decode_step.sum,
                "prefill_time_s": self._m_prefill.sum,
                "queue_wait_time_s": self._m_queue_wait.sum,
                "slot_utilization": round(util, 4),
                "ttft_s": list(self.ttfts),
                "queue_wait_s": list(self.queue_waits),
                "admission_blocked": int(self._m_blocked.value),
                "requests_completed": int(self._m_completed.value),
                # round 20: drain/handoff (router rolling restarts)
                "draining": self._draining,
                "drained_requests": int(self._m_drained.value),
                "kv_pool_blocks": self.allocator.num_blocks,
                "kv_pool_free": self.allocator.available,
                "kv_hbm_bytes": self.cache.hbm_bytes,
                # round 20: quantization config (bench/D20 read these)
                "kv_cache_mode": self.kv_mode,
                "weight_quant": self.weight_quant,
                "param_bytes": self.param_bytes,
                # round 13: prefix cache + chunked prefill
                "prefix_blocks_hit": int(self._m_prefix_hit.value),
                "prefix_blocks_missed": int(self._m_prefix_miss.value),
                "prefix_cached_blocks": self.prefix_cache.cached_blocks,
                "prefix_evictions": self.prefix_cache.evictions,
                "prefill_chunks": int(self._m_chunks.value),
                # round 18: speculative decoding
                "spec_windows": int(self._m_spec_windows.value),
                "spec_proposed_tokens": int(self._m_spec_proposed.value),
                "spec_accepted_tokens": int(self._m_spec_accepted.value),
                "spec_accept_rate": round(
                    int(self._m_spec_accepted.value)
                    / max(int(self._m_spec_proposed.value), 1), 4)}

    def spec_stats(self) -> dict:
        """Speculative-decoding summary for D16 (audit_spec_decode):
        overall acceptance across every verify window this engine ran."""
        proposed = int(self._m_spec_proposed.value)
        return {"enabled": self.proposer is not None,
                "k": int(getattr(self.proposer, "k", 0) or 0),
                "windows": int(self._m_spec_windows.value),
                "proposed_tokens": proposed,
                "accepted_tokens": int(self._m_spec_accepted.value),
                "accept_rate": (int(self._m_spec_accepted.value)
                                / proposed if proposed else 0.0)}

    def metrics(self) -> dict:
        """Registry snapshot (counters/gauges + histogram quantiles) —
        the machine-readable serving telemetry; render_prometheus() is
        the scrape body of the same registry."""
        return self.registry.to_dict()

    def render_prometheus(self) -> str:
        return self.registry.render_prometheus()

    def finish_warmup(self):
        """Declare the program ladder warm: every (prefill-bucket,
        decode-bucket, sampling) program this workload needs has
        compiled. Any compile recorded after this is tagged warm=True —
        a steady-state retrace — and fails the obs lint smoke
        (obs.audit_recompiles post-warmup-compile warning)."""
        self.contract.check("finish_warmup")
        self._warmed = True
        return self

    @property
    def warmed(self) -> bool:
        return self._warmed

    def drain(self, deadline_ms=None):
        """Stop admission for handoff (round 20): every add_request from
        now on rejects with reason ``"draining"``; requests already
        queued or in flight keep running until they finish. With a
        ``deadline_ms`` budget each surviving request's per-request
        deadline (the round-12 timeout path) is CLAMPED to now+budget,
        so a stuck-long request cannot hold the replica open forever —
        it timeout-finishes with whatever tokens it produced, blocks
        reclaimed. ``drained`` flips True once queue+slots are empty;
        the router then ``contract.rebind()``s the engine for teardown.
        Completions observed while draining count into the
        ``serving_drained_requests_total`` metric. Idempotent — a
        second drain() only tightens the deadline."""
        self.contract.check("drain")
        self._draining = True
        if deadline_ms is not None and float(deadline_ms) > 0:
            now = time.perf_counter()
            deadline_s = now + float(deadline_ms) / 1e3
            live = list(self._waiting) + [r for r in self._slot_req
                                          if r is not None]
            for req in live:
                if req.deadline_s is None or req.deadline_s > deadline_s:
                    req.deadline_s = deadline_s
                    # keep the timeout log's ms figure meaningful
                    req.max_time_ms = (deadline_s - req.arrival_s) * 1e3
        return self

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        """True once a draining engine has no queued or active work —
        the router's signal that teardown (rebind + close) is safe."""
        return self._draining and not self.has_work()

    def close(self):
        """Detach from the shared /metrics endpoint (no-op otherwise).
        The endpoint itself stays up — other engines may still be
        registered on it; obs.shared_server(port).close() stops it.

        Idempotent under concurrent callers (round-17 satellite) and
        deliberately OUTSIDE the owner-thread contract: teardown comes
        from whoever is shutting the process down. The swap-to-local
        below means a double close can at worst unregister twice (an
        idempotent pop), never call through None."""
        srv, self._metrics_server = self._metrics_server, None
        if srv is not None:
            srv.unregister_engine(self._engine_name)

    def _program(self, site: str, jitted, n_static: int, bucket: int,
                 any_sample: bool, extra, args):
        """AOT program cache: the engine's step programs compile through
        ``jitted.lower(*args).compile()`` into a MODULE-level executable
        cache (shared across engines — same spec + shapes genuinely
        reuse the compiled program). The compiled object hands XLA
        cost_analysis()/memory_analysis() to the cost ledger for free
        (obs/costs.py), and the compile wall is the measured
        lower+compile time, not the first execution smeared in.

        Returns ``(callable, ProgramCost entry)``; invoke the callable
        with ``args[n_static:]`` (AOT calls exclude static args).
        ``_SEEN_SERVING_PROGRAMS`` stays the separate event mirror:
        clearing it (tests) re-records compile events without forcing a
        real recompile, exactly the old jit-cache semantics."""
        key = (site, self._prog_key_base, bool(any_sample), int(bucket),
               tuple(extra))

        def keystr():       # a miss's or a first sighting's, never a hit's
            return (f"bucket{bucket}/sample{int(any_sample)}/"
                    f"kv{self.kv_mode}/w{self.weight_quant}"
                    + "".join(f"/{x}" for x in extra))

        cached = _SERVING_EXECUTABLES.get(key)
        compile_wall = None
        if cached is None:
            from ..obs import costs as _costs

            with _span("serving.compile", site=site,
                       bucket=int(bucket)) as sp:
                compiled = jitted.lower(*args).compile()
            compile_wall = sp.end - sp.start
            entry = _costs.record_program(
                site, self._prog_group(site), keystr(),
                compiled=compiled, wall_s=compile_wall, bucket=int(bucket))
            cached = (compiled, entry)
            _SERVING_EXECUTABLES[key] = cached
        else:
            # cache hit: the executable (and its ProgramCost) outlived a
            # clear_ledger() — re-surface the row or this engine's decode
            # traffic is invisible to the ledger
            from ..obs import costs as _costs

            _costs.reregister(cached[1])
        if key not in _SEEN_SERVING_PROGRAMS:
            _SEEN_SERVING_PROGRAMS.add(key)
            from ..obs.watchdog import record_compile

            entry = cached[1]
            record_compile(
                site, self._prog_group(site), keystr(), bucket=int(bucket),
                wall_s=compile_wall or 0.0, donated=True,
                warm=self._warmed,
                cost=({"flops": entry.flops,
                       "bytes_accessed": entry.bytes_accessed,
                       "peak_hbm_bytes": entry.peak_hbm_bytes}
                      if entry.analyzed else None))
            if self._warmed:
                self._log.warning(
                    f"post-warmup compile: {site} bucket {bucket} traced "
                    "after finish_warmup() — steady-state ticks must not "
                    "compile", key=f"warm-compile:{site}")
                self._anomaly("post_warmup_compile")
        return cached

    def _prog_group(self, site: str) -> str:
        return (f"{site}/L{self.spec.num_layers}"
                f"h{self.spec.num_heads}d{self.spec.head_dim}")

    def _anomaly(self, trigger: str):
        """One flight-recorder anomaly: count it and (when
        FLAGS_obs_flight_dir is set) write the postmortem trace."""
        self._m_flight_anomalies.labels(trigger).inc()
        path = self.flight.anomaly_dump(trigger)
        if path is not None:
            self._m_flight_dumps.labels(trigger).inc()
            self._log.warning(
                f"flight recorder postmortem ({trigger}) dumped to "
                f"{path}", key=f"flight-dump:{trigger}")

    def dump_trace(self, path: str) -> str:
        """Export the flight-recorder ring as Chrome-trace/Perfetto JSON
        (load it at ui.perfetto.dev or chrome://tracing). Asserts the
        TTFT tiling invariant — every finished request's queue_wait +
        prefill spans sum bitwise to its recorded TTFT — before
        writing; obs.validate_trace(path) re-checks the dumped file."""
        return self.flight.dump(path)

    # ------------------------------------------------------- scheduling
    def _expire(self):
        """Per-request deadline enforcement: active slots past their
        `max_time_ms` finish NOW with reason "timeout" (blocks back to
        the free list — a stuck-long request can't starve the pool), and
        queued requests whose deadline lapsed before admission finish
        empty without ever taking a slot.  Returns the terminal
        ``(rid, None, True)`` events so step() consumers observe every
        completion."""
        now = time.perf_counter()
        emitted = []
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.expired(now):
                req.finish_reason = "timeout"
                self._m_timeout.inc()
                self._log.warning(
                    f"request {req.rid} hit its {req.max_time_ms:.0f}ms "
                    f"deadline after {len(req.tokens)} token(s); slot "
                    "and blocks reclaimed", key="request-timeout")
                self._finish(slot)
                self._anomaly("timeout")
                emitted.append((req.rid, None, True))
        expired_waiting = [r for r in self._waiting if r.expired(now)]
        if expired_waiting:
            self._waiting = deque(r for r in self._waiting
                                  if not r.expired(now))
            self._m_queue_depth.set(len(self._waiting))
            for req in expired_waiting:
                req.finished = True
                req.finish_reason = "timeout"
                self.completed[req.rid] = np.asarray(req.tokens, np.int64)
                self.finish_reasons[req.rid] = "timeout"
                self._m_timeout.inc()
                self._m_completed.inc()
                self.flight.finish(req.rid, now, "timeout")
                self._anomaly("timeout")
                emitted.append((req.rid, None, True))
        return emitted

    def _admit(self):
        """Admission control: head-of-line requests enter freed slots only
        when the pool covers their block budget NET OF cached prefix
        blocks (a 95%-cached request admits with a tiny budget; evictable
        refcount-0 cached blocks count as capacity) — admitted requests
        can never OOM mid-flight. Small cache-cold prompts prefill whole
        right here (the round-10 fast path); long or cache-hit prompts
        enter the chunk ladder and emit their first token from a later
        chunk phase. Static mode additionally waits for the whole engine
        to drain (the wave baseline)."""
        if self.admission == "static" and self.num_active:
            return
        for slot in range(self.max_slots):
            if not self._waiting or self._slot_req[slot] is not None:
                continue
            req = self._waiting[0]
            s = req.prompt.size
            if not self.prefix_cache_enabled:
                hashes = []
            else:
                # memoized per request, keyed on the namespace so drift
                # (the D7 fixture) still rehashes
                if (req._hashes is None
                        or req._hash_ns != self._prefix_namespace):
                    req._hashes = hash_blocks(req.prompt, self.block_size,
                                              self._prefix_namespace)
                    req._hash_ns = self._prefix_namespace
                hashes = req._hashes
            hit = self.prefix_cache.lookup(hashes)
            hit_blocks = len(hit)
            # the LAST real prompt position is never served from cache:
            # its hidden state seeds the first token, so a whole-prompt
            # hit recomputes the final token into a COPY-ON-WRITE
            # duplicate of the shared last block
            cow_src = None
            cached_len = len(hit) * self.block_size
            if cached_len > s - 1:
                cow_src = hit.pop()
                cached_len = s - 1
            need = blocks_for(s + req.max_new_tokens,
                              self.block_size) - len(hit)
            ids = self.prefix_cache.allocate(need)
            if ids is None:
                # pool full: wait for releases — and UNDO the lookup so
                # blocked retries neither leak refcounts nor inflate the
                # hit counters. The head-of-line request keeps QUEUEING
                # (its clock runs in queue_wait, not prefill — the
                # satellite-6 TTFT decomposition fix)
                undo = hit + ([cow_src] if cow_src is not None else [])
                self.prefix_cache.cancel_lookup(undo, len(hashes))
                self._m_blocked.inc()
                fl = req._flight
                if fl.blocked_ticks == 0:
                    fl.add_mark("admission_blocked", time.perf_counter(),
                                {"need_blocks": int(need),
                                 "available":
                                     int(self.prefix_cache.available)})
                fl.blocked_ticks += 1
                self._log.vlog(
                    2, f"admission blocked: request {req.rid} needs "
                    f"{need} blocks, {self.prefix_cache.available} "
                    "available", key="admission-blocked")
                break
            self._waiting.popleft()
            req.admitted_s = time.perf_counter()
            req.cached_len = cached_len
            req.prefill_pos = cached_len
            fl = req._flight
            fl.admitted_s = req.admitted_s
            fl.cached_blocks = hit_blocks
            fl.cow = cow_src is not None
            fl.add_mark("admitted", req.admitted_s,
                        {"slot": slot, "cached_blocks": hit_blocks,
                         "cached_len": int(cached_len),
                         "need_blocks": int(need)})
            self.queue_waits.append(req.queue_wait_s)
            self._m_queue_wait.observe(req.queue_wait_s)
            self._m_queue_depth.set(len(self._waiting))
            self._m_prefix_hit.inc(hit_blocks)
            self._m_prefix_miss.inc(len(hashes) - hit_blocks)
            if hashes:
                # deliberately independent of the cache's hash chain so a
                # broken chain / namespace drift can't hide from D7
                fp = hash(tuple(int(t) for t in req.prompt))
                if fp in self._prompt_fingerprints:
                    self.prefix_repeat_admissions += 1
                    self._prompt_fingerprints.move_to_end(fp)
                self._prompt_fingerprints[fp] = True
                while (len(self._prompt_fingerprints)
                       > self._prompt_fingerprints_cap):
                    self._prompt_fingerprints.popitem(last=False)
            self._slot_req[slot] = req
            blocks = hit + ids
            self._slot_blocks[slot] = blocks
            row = np.zeros(self.pages, np.int32)
            row[:len(blocks)] = blocks
            self._tables[slot] = row
            self._update_pool_gauges()
            if self.programs.whole_prompt_prefill and cached_len == 0 and (
                    self.chunk_tokens <= 0 or s <= self.chunk_tokens):
                tok, done = self._prefill(slot, req)
                self._register_full_blocks(slot)
                yield (req.rid, tok, done)
                if done:
                    self._finish(slot)
                continue
            # chunk ladder: one chunk per tick from cached_len. The COW
            # source ref is held until the first chunk's copy executed
            state = {"cow": None}
            if cow_src is not None:
                # ids[0] occupies page cached_len // block_size — exactly
                # the page the shared block served
                state["cow"] = (cow_src, ids[0])
                self._slot_extra_refs[slot].append(cow_src)
            self._slot_chunk[slot] = state
            if self.admission == "static":
                # waves admit slot-by-slot; chunked members join the same
                # wave (prefill ticks run before the first decode tick)
                continue

    def _update_pool_gauges(self):
        self._m_pool_free.set(self.allocator.available)
        self._m_pool_used.set(self.allocator.num_blocks - 1
                              - self.allocator.available)
        self.programs.update_gauges()
        self._m_cache_blocks.set(self.prefix_cache.cached_blocks)
        self._m_cache_refed.set(self.prefix_cache.referenced_blocks)
        ev = self.prefix_cache.evictions - self._m_prefix_evict.value
        if ev > 0:
            self._m_prefix_evict.inc(ev)
            # eviction pressure on the flight recorder's engine track:
            # the LRU gave up warm blocks to satisfy an allocation
            self.flight.tick_mark("prefix_evictions", time.perf_counter(),
                                  evicted=int(ev))

    def _register_full_blocks(self, slot):
        """Publish this slot's FULLY-WRITTEN blocks into the prefix cache
        under their content hashes. Written watermark: the whole prompt
        once prefill finished (plus appended generation tokens — the
        last sampled token was never consumed, so its K/V is absent),
        else the chunk ladder's progress."""
        if not self.prefix_cache_enabled:
            return
        req = self._slot_req[slot]
        if req.prefill_done:
            content = np.concatenate(
                [req.prompt, np.asarray(req.tokens[:-1] if req.tokens
                                        else [], np.int32)])
        else:
            content = req.prompt[:req.prefill_pos]
        hashes = hash_blocks(content, self.block_size,
                             self._prefix_namespace)
        self.prefix_cache.register(hashes,
                                   self._slot_blocks[slot][:len(hashes)])

    def _prefill(self, slot, req):
        from ..jit.api import default_buckets

        s = req.prompt.size
        bucket = min(_ceil_to(default_buckets(s), self.block_size),
                     self.max_model_len)
        bucket = max(bucket, _ceil_to(s, self.block_size))
        at = {"rid": req.rid, "tokens": int(s), "bucket": int(bucket)}
        c = self.cache
        with _span("serving.prefill.build", **at) as build:
            h2d = self._h2d
            ints = np.zeros(1 + self.pages + bucket, np.int32)
            ints[0] = s
            ints[1:1 + self.pages] = self._tables[slot]
            ints[1 + self.pages:1 + self.pages + s] = req.prompt
            args = (self.spec, self.block_size, self.kv_mode,
                    req.do_sample, self.pages, self.params,
                    self._put(ints), c.k, c.v, c.k_scale, c.v_scale,
                    self._samp([req], 0, req.do_sample), self._key)
            prog, entry = self._program("serving.prefill", _prefill_step,
                                        5, bucket, req.do_sample, (), args)
            build.attrs["h2d"] = self._h2d - h2d
        with _span("serving.prefill.run", **at) as run:
            out = prog(*args[5:])
            tok_arr, ck, cv, cks, cvs, self._key = out
            c.swap(ck, cv, cks, cvs)
            tok = int(jax.device_get(tok_arr)[0])
        # the span's own clock reads: the flight recorder, the cost ledger
        # and the lifecycle mark time this interval through them
        t_run, req.first_token_s = run.start, run.end
        entry.observe(req.first_token_s - t_run)
        req.prefill_pos = s
        req.prefill_done = True
        self._m_prefill.observe(req.prefill_s)
        self._m_ttft.observe(req.ttft_s)
        self.ttfts.append(req.ttft_s)
        self._m_prefill_tokens.inc(s)
        req.tokens.append(tok)
        self._slot_pos[slot] = s
        req._flight.add_span(
            "prefill_program", t_run, req.first_token_s,
            {"bucket": bucket, "program": entry.program, "tokens": int(s)})
        self._first_token(req)
        return tok, self._check_done(req, tok)

    def _first_token(self, req):
        """Flight-recorder bookkeeping at a request's first token, plus
        the TTFT SLO anomaly trigger (FLAGS_obs_slo_ttft_ms)."""
        fl = req._flight
        fl.first_token_s = req.first_token_s
        fl.last_token_s = req.first_token_s
        fl.ttft_s = req.ttft_s
        fl.tokens += 1
        if self._slo_ttft_s is not None and req.ttft_s > self._slo_ttft_s:
            fl.add_mark("slo_breach", req.first_token_s,
                        {"ttft_s": req.ttft_s, "slo_s": self._slo_ttft_s})
            self._anomaly("slo_breach")

    def _chunk_phase(self):
        """Advance every prefilling slot by ONE chunk. A slot whose final
        chunk completes emits its first token and joins the decode set
        next tick — chunks and decode ticks share the scheduler loop, so
        a long prompt costs in-flight decodes one chunk per tick, never
        its whole prefill."""
        emitted = []
        for slot in sorted(self._slot_chunk):
            req = self._slot_req[slot]
            tok, t_end = self._run_chunk(slot, req, self._slot_chunk[slot])
            if tok is None:
                continue
            del self._slot_chunk[slot]
            s = req.prompt.size
            req.prefill_done = True
            req.first_token_s = t_end       # the token reached the host
            self._m_prefill.observe(req.prefill_s)
            self._m_ttft.observe(req.ttft_s)
            self.ttfts.append(req.ttft_s)
            req.tokens.append(tok)
            self._slot_pos[slot] = s
            self._first_token(req)
            self._register_full_blocks(slot)
            done = self._check_done(req, tok)
            emitted.append((req.rid, tok, done))
            if done:
                self._finish(slot)
        return emitted

    def _run_chunk(self, slot, req, state):
        """One chunk-prefill program invocation for one slot. Returns
        (the first token when this was the prompt's final chunk, else
        None; the end of the chunk's `.run` span). The chunk program is
        keyed by (chunk-length bucket,
        context-pages bucket, emit_token): chunk lengths bucket like
        prompt lengths, context pages like slot counts, so a stream
        compiles O(log S * log pages) chunk programs."""
        s = req.prompt.size
        start = req.prefill_pos
        n = s - start if self.chunk_tokens <= 0 \
            else min(s - start, self.chunk_tokens)
        is_last = start + n >= s
        c_bucket, ctx_pages = self.programs.chunk_buckets(
            n, blocks_for(start + n, self.block_size))
        cow = state.pop("cow", None)
        cow_src, cow_dst = cow if cow is not None else (TRASH_BLOCK,
                                                        TRASH_BLOCK)
        at = {"rid": req.rid, "tokens": int(n), "start": int(start),
              "last": bool(is_last), "bucket": int(c_bucket)}
        with _span("serving.chunk.build", **at) as build:
            h2d = self._h2d
            step, n_static, args = self.programs.chunk(
                slot, req, start, n, c_bucket, is_last, ctx_pages,
                (cow_src, cow_dst))
            prog, entry = self._program(
                "serving.chunk_prefill", step, n_static, c_bucket,
                req.do_sample and is_last, (ctx_pages, bool(is_last)),
                args)
            build.attrs["h2d"] = self._h2d - h2d
        with _span("serving.chunk.run", **at) as run:
            tok = self.programs.chunk_done(prog(*args[n_static:]), n,
                                           is_last, run)
        t_run, t_end = run.start, run.end
        entry.observe(t_end - t_run)
        fl = req._flight
        fl.chunks += 1
        fl.add_span("prefill_chunk", t_run, t_end,
                    {"start": int(start), "tokens": int(n),
                     "last": bool(is_last), "cow": cow is not None,
                     "program": entry.program})
        if cow is not None:
            # the copy executed (device order is program order): drop the
            # admission-time ref that kept the source from being evicted
            self.prefix_cache.release([cow_src])
            self._slot_extra_refs[slot].remove(cow_src)
            self._update_pool_gauges()
        req.prefill_pos = start + n
        self._m_chunks.inc()
        self._m_prefill_tokens.inc(n)
        return tok, t_end

    def _kv_steps(self, bucket):
        """Grid steps a layer of the `paged_decode` kernel over K and V
        pools at this slot bucket; 0 where the router keeps the XLA
        composition."""
        from ..ops import pallas_decode as pd

        full = self.programs.full_pool()     # [N, H_kv, rows, D]
        pool, kv_dt = full.shape, full.dtype
        int4 = self.kv_mode == "int4"
        if not pd.use_pallas_decode(
                jax.ShapeDtypeStruct((bucket, self.spec.num_heads,
                                      self.spec.head_dim),
                                     self.params["embed"].dtype),
                jax.ShapeDtypeStruct(pool, kv_dt),
                jax.ShapeDtypeStruct((bucket, self.pages), jnp.int32), int4):
            return 0
        return pd.kv_steps(bucket, self.pages, pool[2], pool[1], pool[3],
                           kv_dt.itemsize)

    def _decode(self, active):
        from ..jit.api import default_buckets

        bucket = min(default_buckets(len(active)), self.max_slots)
        if bucket not in self._kv_steps_of:
            self._kv_steps_of[bucket] = self.programs.kv_steps(bucket)
        at = {"active": len(active), "bucket": int(bucket),
              "live_pages": int(
                  (self._slot_pos[active] // self.block_size + 1).sum()),
              "kv_steps": self._kv_steps_of[bucket]}
        with _span("serving.decode.build", **at) as build:
            h2d = self._h2d
            reqs = [self._slot_req[i] for i in active]
            any_sample = any(r.do_sample for r in reqs)
            step, n_static, args = self.programs.decode(
                active, reqs, bucket, any_sample)
            prog, entry = self._program("serving.decode", step, n_static,
                                        bucket, any_sample, (), args)
            build.attrs["h2d"] = self._h2d - h2d
        with _span("serving.decode.run", **at) as run:
            nxt = self.programs.decode_done(prog(*args[n_static:]),
                                            len(active), run)
        with _span("serving.decode.emit", **at):
            t_run, t_end = run.start, run.end
            entry.observe(t_end - t_run)
            self.flight.tick_span("decode_tick", t_run, t_end,
                                  active=len(active), bucket=int(bucket),
                                  program=entry.program)
            # the tick as its two spans time it (the span machinery's own
            # microsecond between them left out)
            self._m_decode_step.observe(
                (build.end - build.start) + (t_end - t_run))
            emitted = []
            for j, slot in enumerate(active):
                req = self._slot_req[slot]
                t = int(nxt[j])
                req.tokens.append(t)
                fl = req._flight
                fl.tokens += 1
                # TPOT as this request's user sees it: the gap since its
                # previous token, whatever ran in between (a prefill chunk
                # of another slot, the scheduler), one observation a token
                self._m_tpot.observe(t_end - fl.last_token_s)
                fl.last_token_s = t_end
                self._slot_pos[slot] += 1
                done = self._check_done(req, t)
                emitted.append((req.rid, t, done))
                if done:
                    self._finish(slot)
            self._m_decode_tokens.inc(len(active))
        return emitted

    def _spec_proposals(self, active):
        """Ask the proposer for candidate continuations of every
        opted-in active slot. Returns (spec_slots, proposals) — only
        slots with a NON-EMPTY proposal speculate this tick; the rest
        fall back to the ordinary decode (an n-gram miss costs
        nothing, it just decodes normally)."""
        if self.proposer is None:
            return [], []
        cand = [i for i in active
                if self._slot_req[i].speculative is not False]
        if not cand:
            return [], []
        reqs = [self._slot_req[i] for i in cand]
        props = self.proposer.proposals(self, cand, reqs)
        spec_slots, out = [], []
        for slot, p in zip(cand, props):
            p = np.asarray(p, np.int64).reshape(-1)
            if p.size:
                spec_slots.append(slot)
                out.append(p)
        return spec_slots, out

    def _spec_decode(self, slots, proposals):
        """One verify window for every speculating slot: score each
        slot's K+1 candidate positions in ONE batched paged-attention
        pass, then emit its accepted prefix + the correction/bonus
        token. Rollback is pure bookkeeping — `_slot_pos` only advances
        past what was emitted, so rejected candidates' K/V is stale
        data the length masks never expose and the next window
        overwrites. eos/length finish honors mid-window acceptance
        (tokens after an accepted eos are dropped), and the per-request
        deadline path is untouched (_expire runs at tick start)."""
        from ..jit.api import default_buckets

        t0 = time.perf_counter()
        k = self.proposer.k
        width = k + 1
        bucket = min(default_buckets(len(slots)), self.max_slots)
        reqs = [self._slot_req[i] for i in slots]
        n_live = len(slots)
        # a row a slot: pos, limit, its block table row, its candidates
        # (padded rows: zeros through the trash block, limit 0)
        ints = np.zeros((bucket, 2 + self.pages + width), np.int32)
        ints[n_live:, 2:2 + self.pages] = TRASH_BLOCK
        ints[:n_live, 0] = self._slot_pos[slots]
        ints[:n_live, 2:2 + self.pages] = self._tables[slots]
        toks = ints[:, 2 + self.pages:]
        for j, (slot, req, prop) in enumerate(zip(slots, reqs,
                                                  proposals)):
            toks[j, 0] = req.tokens[-1]
            n = min(len(prop), k)
            toks[j, 1:1 + n] = prop[:n]
            if n < k:    # short proposal: pad by repeating (auto-reject)
                toks[j, 1 + n:] = toks[j, n]
            ints[j, 1] = len(self._slot_blocks[slot]) * self.block_size
        any_sample = any(r.do_sample for r in reqs)
        c = self.cache
        args = (self.spec, self.block_size, self.kv_mode, any_sample,
                self.pages, self.params, self._put(ints), c.k, c.v,
                c.k_scale, c.v_scale,
                self._samp(reqs, bucket - n_live, any_sample), self._key)
        prog, entry = self._program("serving.spec_verify",
                                    _spec_verify_step, 5, bucket,
                                    any_sample, (k,), args)
        # a span only so that the flight recorder's `verify_window` and
        # the cost ledger share its clock reads; no metric reads it
        with _span("serving.verify.run", active=len(slots),
                   k=int(k)) as run:
            out = prog(*args[5:])
            acc, tgt, ck, cv, cks, cvs, self._key = out
            c.swap(ck, cv, cks, cvs)
            acc = np.asarray(jax.device_get(acc))
            tgt = np.asarray(jax.device_get(tgt))
        t_run, t_end = run.start, run.end
        step_wall = t_end - t0
        entry.observe(t_end - t_run)
        self._m_decode_step.observe(step_wall)
        emitted = []
        n_windows = len(slots)
        n_tokens = 0
        n_accepted = 0
        for j, slot in enumerate(slots):
            req = self._slot_req[slot]
            prop = proposals[j]
            plen = min(len(prop), k)
            a = 0
            while a < plen and acc[j, a]:
                a += 1
            new = [int(t) for t in prop[:a]] + [int(tgt[j, a])]
            new = new[: req.max_new_tokens - len(req.tokens)]
            if req.eos_token_id >= 0:
                for i, t in enumerate(new):
                    if t == req.eos_token_id:
                        new = new[: i + 1]
                        break
            fl = req._flight
            done = False
            for t in new:
                req.tokens.append(t)
                done = self._check_done(req, t)
            self._slot_pos[slot] += len(new)
            fl.tokens += len(new)
            # the window's tokens reach the user together: each takes its
            # share of the gap since the request's previous token
            for _ in new:
                self._m_tpot.observe((t_end - fl.last_token_s) / len(new))
            fl.last_token_s = t_end
            n_tokens += len(new)
            n_accepted += a
            self._m_spec_accept_rate.observe(a / plen if plen else 0.0)
            self._m_spec_emitted.observe(len(new))
            emitted.extend((req.rid, t, done and i == len(new) - 1)
                           for i, t in enumerate(new))
            if done:
                self._finish(slot)
        self._m_spec_windows.inc(n_windows)
        self._m_spec_proposed.inc(sum(min(len(p), k)
                                      for p in proposals))
        self._m_spec_accepted.inc(n_accepted)
        self._m_decode_tokens.inc(n_tokens)
        self.flight.tick_span("verify_window", t_run, t_end,
                              active=n_windows, k=int(k),
                              accepted=int(n_accepted),
                              emitted=int(n_tokens), bucket=int(bucket),
                              program=entry.program)
        return emitted

    def _put(self, host):
        """Hand one host array to the device. Every operand a step
        program is given from the host goes through here, so that a
        `.build` span can say how many its call made (`h2d`)."""
        self._h2d += 1
        return jnp.asarray(host)

    def _samp_arrays(self, reqs, pad=0):
        """Per-slot sampling params as batched device arrays (padded rows
        greedy — their tokens are discarded)."""
        return {
            "do_sample": self._put(
                np.array([r.do_sample for r in reqs] + [False] * pad,
                         bool)),
            "temperature": self._put(
                np.array([r.temperature for r in reqs] + [1.0] * pad,
                         np.float32)),
            "top_k": self._put(
                np.array([r.top_k for r in reqs] + [0] * pad, np.int32)),
            "top_p": self._put(
                np.array([r.top_p for r in reqs] + [1.0] * pad,
                         np.float32)),
        }

    def _samp(self, reqs, pad, any_sample):
        """A call's sampling operands. A greedy program reads none of them
        (`any_sample` is static), so a greedy call is handed one device
        copy a bucket size, made once, and transfers nothing. No program
        donates them."""
        if any_sample:
            return self._samp_arrays(reqs, pad)
        n = len(reqs) + pad
        if n not in self._greedy_samp:
            self._greedy_samp[n] = self._samp_arrays([], n)
        return self._greedy_samp[n]

    def _check_done(self, req, tok) -> bool:
        if req.eos_token_id >= 0 and tok == req.eos_token_id:
            req.finish_reason = "eos"
            return True
        if len(req.tokens) >= req.max_new_tokens:
            req.finish_reason = "length"
            return True
        return False

    def _finish(self, slot):
        """Copy-free release THROUGH THE PREFIX CACHE: every fully-written
        block is first published under its content hash (the next request
        sharing this prompt — or this prompt plus this completion, the
        multi-turn shape — hits it), then the slot's blocks are decref'd.
        Shared blocks other requests still reference survive; hash-mapped
        blocks at refcount 0 park in the LRU; unmapped blocks free-list.
        The round-12 timeout path comes through here too — an
        unconditional allocator.free() would have corrupted any prefix
        shared with a live request."""
        req = self._slot_req[slot]
        req.finished = True
        self.completed[req.rid] = np.asarray(req.tokens, np.int64)
        self.finish_reasons[req.rid] = req.finish_reason or "length"
        self.flight.finish(req.rid, time.perf_counter(),
                           self.finish_reasons[req.rid])
        self._m_flight_requests.set(len(self.flight._flights))
        self._register_full_blocks(slot)
        self.prefix_cache.release(self._slot_blocks[slot]
                                  + self._slot_extra_refs[slot])
        self._slot_extra_refs[slot] = []
        self._slot_chunk.pop(slot, None)
        self._slot_blocks[slot] = []
        self._slot_req[slot] = None
        self._slot_pos[slot] = 0
        self._tables[slot] = TRASH_BLOCK
        self._m_completed.inc()
        if self.proposer is not None:
            self.proposer.finish(slot)
        self._update_pool_gauges()

    # ------------------------------------------------------- introspection
    @property
    def param_bytes(self) -> int:
        """Total bytes of the stacked serving params AS STORED — packed
        int4 counts its nibbles-per-byte bytes, int8 its bytes, scales
        included. The D20 (audit_quantized_bytes) declaration side: a
        quantized engine claiming a bandwidth win must show this number
        (and the D8 ledger's measured bytes) actually dropped vs its
        full-precision twin."""
        return int(sum(p.nbytes for p in
                       jax.tree_util.tree_leaves(self.params)))

    def decode_program_jaxpr(self, bucket=2):
        """The decode step program's jaxpr at a given slot bucket — the
        serving analogue of CompiledFunction.program_jaxpr(), consumed by
        tools/graft_lint.py's paged smoke audit."""
        bucket = min(bucket, self.max_slots)
        samp = {"do_sample": jnp.zeros(bucket, bool),
                "temperature": jnp.ones(bucket, jnp.float32),
                "top_k": jnp.zeros(bucket, jnp.int32),
                "top_p": jnp.ones(bucket, jnp.float32)}
        return self.programs.decode_jaxpr(bucket, samp)

    def verify_program_jaxpr(self, bucket=2, k=4):
        """The speculative verify program's jaxpr at a given (slot
        bucket, K) — same D4/D5/dtype-stream audit surface as
        decode_program_jaxpr, for the verify half of spec decoding."""
        bucket = min(bucket, self.max_slots)
        c = self.cache
        samp = {"do_sample": jnp.zeros(bucket, bool),
                "temperature": jnp.ones(bucket, jnp.float32),
                "top_k": jnp.zeros(bucket, jnp.int32),
                "top_p": jnp.ones(bucket, jnp.float32)}
        fn = functools.partial(_spec_verify_impl, self.spec,
                               self.block_size, self.kv_mode, False,
                               self.pages)
        return jax.make_jaxpr(fn)(
            self.params,
            jnp.zeros((bucket, 2 + self.pages + int(k) + 1), jnp.int32),
            c.k, c.v, c.k_scale, c.v_scale, samp, self._key)


def generate_paged(model, ids, max_new_tokens, do_sample=False,
                   temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                   seed=None, **engine_kwargs):
    """Model.generate(..., engine="paged") entry: run a rectangular batch
    through a ServingEngine and return tokens [B, max_new_tokens] int64
    (rows that hit eos early are padded with eos, matching the
    single-program engine's emit-eos-forever semantics so the shared trim
    logic applies unchanged). seed=None draws a FRESH seed from the
    framework rng stream — same semantics as the static engine, so
    repeated unseeded sampling calls differ."""
    ids = np.asarray(ids, np.int64)
    b = ids.shape[0]
    if seed is None:
        from ..core.rng import next_key

        seed = int(np.asarray(jax.device_get(next_key()))[-1])
    eng = ServingEngine(model, max_slots=max(1, b), seed=seed,
                        **engine_kwargs)
    order = [eng.add_request(
        ids[i], max_new_tokens=max_new_tokens, do_sample=do_sample,
        temperature=temperature, top_k=top_k, top_p=top_p,
        eos_token_id=eos_token_id) for i in range(b)]
    done = eng.run()
    pad = -1 if eos_token_id is None else int(eos_token_id)
    out = np.full((b, int(max_new_tokens)), pad, np.int64)
    for i, rid in enumerate(order):
        toks = done[rid]
        out[i, :len(toks)] = toks
    return out
