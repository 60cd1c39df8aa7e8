"""The serving engine's step programs for a model that declares its
layers one by one (`model.serving_arrays()`: its own buffers, a layer
each; `config.block_spec()`: each layer's cache kind): full-attention
layers keep the whole history in pages the `BlockAllocator` hands out,
sliding-window layers keep a `WindowRing` of the last `window + chunk`
positions a slot.

The block's mathematics is `text/models/parallel_block.block`, the same
function the Layer's `forward` calls; what these programs add is WHERE
the keys and values live (`attend`: scatter the step's K/V into the
layer's pool through its table, attend over the pool), the head, the
sampling, and the expert layer's counts. Layers are unrolled, each with
its own pool array, and the parameters are the model's own buffers: no
stacked second copy of either exists.

Two programs, as for the other architectures: `decode_step` (a compacted
slot bucket advances one token) and `chunk_step` (one chunk of one
prompt; EVERY prompt of this architecture is prefilled by chunks, so the
whole-prompt prefill program has no variant here). Window layers are
handed the slot's ring rotated into logical order (`wtable`, `wbase`:
entry i is absolute page `wbase + i`) and address it by position less
`wbase * block_size`; the window mask needs differences of positions
only, so it is the same in either frame. Everything the host decides a
step (tokens, positions, tables, ring views) reaches a program as ONE
int32 array: one transfer a program call, not one a value.

`LayeredPrograms` is what `ServingEngine._run_chunk` and `_decode` ask
for these programs and their operands (`engine._StackedPrograms` answers
for the dense architectures' stacked ones).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas_decode import paged_decode_attention
from ..text.models import parallel_block as pb
from ..text.paged_cache import (TRASH_BLOCK, LayeredKVCache, WindowRing,
                                append_rows, blocks_for, gather_context,
                                scatter_chunk_rows)


@dataclass(frozen=True)
class LayeredSpec:
    """Static key of the programs."""
    block: pb.BlockSpec
    block_size: int
    window_pages: int


def _sample(lg, any_sample, samp, key):
    from .engine import _sample_batched

    if any_sample:
        key, sub = jax.random.split(key)
        return _sample_batched(lg, sub, samp["do_sample"],
                               samp["temperature"], samp["top_k"],
                               samp["top_p"]), key
    return jnp.argmax(lg, axis=-1).astype(jnp.int32), key


def _decode_impl(spec: LayeredSpec, any_sample: bool, params, ints, ks,
                 vs, samp, key):
    """ONE decode step for a compacted slot bucket. `ints` [B, 4 + pages
    + R], a row a slot (`LayeredPrograms.decode` packs it): its token,
    its position, 1 for a live row (0: padding, whose tables are the
    trash block), its ring view's base page, the full layers' block table
    [pages] and the ring view [R]. ks/vs: one pool a layer. Returns (next
    tokens [B], local picks [L], largest expert load [L], ks, vs, key)."""
    blk, bs = spec.block, spec.block_size
    tok, pos, valid, wbase = (ints[:, i] for i in range(4))
    ftables = ints[:, 4:ints.shape[1] - spec.window_pages]
    wtables = ints[:, ints.shape[1] - spec.window_pages:]
    rows = jnp.arange(ints.shape[0])
    x = params["embed"][tok]
    rope = (params["rope_cos"][pos], params["rope_sin"][pos])
    valid = valid > 0
    wpos = pos - wbase * bs
    ks, vs = list(ks), list(vs)
    picks, loads = [], []
    for li, kind in enumerate(blk.layer_types):
        sliding = kind == pb.SLIDING
        tables, p = (wtables, wpos) if sliding else (ftables, pos)

        def attend(q, k, v):
            bid = tables[rows, p // bs]
            off = (p % bs).astype(jnp.int32)
            ks[li] = append_rows(ks[li], k, bid, off)
            vs[li] = append_rows(vs[li], v, bid, off)
            if sliding:
                return paged_decode_attention(
                    q, ks[li], vs[li], tables, p + 1,
                    kv_start=jnp.maximum(p + 1 - blk.window, 0),
                    name="paged_window_decode")
            return paged_decode_attention(q, ks[li], vs[li], tables, p + 1)

        x, n, m = pb.block(x, params["layers"][li], blk, kind, attend,
                           rope=rope, valid=valid)
        picks.append(n)
        loads.append(m)
    lg = pb.logits(x, params["final_ln"], params["embed"], blk)
    nxt, key = _sample(lg, any_sample, samp, key)
    return (nxt, jnp.stack(picks), jnp.stack(loads), tuple(ks), tuple(vs),
            key)


def _chunk_impl(spec: LayeredSpec, any_sample: bool, emit_token: bool,
                ctx_pages: int, params, ids, ints, ks, vs, samp, key):
    """Prefill ONE chunk of one prompt: positions [start, true_end) of
    ids [1, C] (the rest is padding). `ints` [4 + pages + R]
    (`LayeredPrograms.chunk` packs it): start, true_end, last_idx, the
    ring view's base page, the full layers' block table and the ring
    view. Each layer scatters the chunk's K/V
    through its table and attends every chunk position over what its kind
    sees: a full layer the first `ctx_pages` (static, bucketed) pages of
    `ftable` under `kv <= q`, a window layer the whole ring view under
    `0 <= q - kv < window`. Scores are computed one KV head's group at a
    time (`parallel_block.grouped_attention`), so the largest temporary
    is [heads a KV head, C, context] in float32. `emit_token` (static):
    the prompt's final chunk samples the first token from chunk row
    `last_idx`."""
    blk, bs = spec.block, spec.block_size
    start, true_end, last_idx, wbase = (ints[i] for i in range(4))
    ftable = ints[4:ints.shape[0] - spec.window_pages]
    wtable = ints[ints.shape[0] - spec.window_pages:]
    c = ids.shape[1]
    pos = start + jnp.arange(c)
    x = params["embed"][ids[0]]
    at = jnp.clip(pos, 0, params["rope_cos"].shape[0] - 1)
    rope = (params["rope_cos"][at], params["rope_sin"][at])
    valid = pos < true_end
    ks, vs = list(ks), list(vs)
    picks, loads = [], []
    for li, kind in enumerate(blk.layer_types):
        sliding = kind == pb.SLIDING
        table, shift, pages = ((wtable, wbase * bs, spec.window_pages)
                               if sliding else (ftable, 0, ctx_pages))

        def attend(q, k, v):
            ks[li] = scatter_chunk_rows(ks[li], k, start - shift,
                                        true_end - shift, table, bs)
            vs[li] = scatter_chunk_rows(vs[li], v, start - shift,
                                        true_end - shift, table, bs)
            kx = gather_context(ks[li], None, table, pages)
            vx = gather_context(vs[li], None, table, pages)
            seen = pb.visible(pos - shift, jnp.arange(pages * bs), kind,
                              blk.window)
            return pb.grouped_attention(q, kx.astype(q.dtype),
                                        vx.astype(q.dtype), seen)

        x, n, m = pb.block(x, params["layers"][li], blk, kind, attend,
                           rope=rope, valid=valid)
        picks.append(n)
        loads.append(m)
    if emit_token:
        x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=0)
        lg = pb.logits(x_last, params["final_ln"], params["embed"], blk)
        tok, key = _sample(lg, any_sample, samp, key)
    else:
        tok = jnp.zeros((1,), jnp.int32)
    return (tok, jnp.stack(picks), jnp.stack(loads), tuple(ks), tuple(vs),
            key)


decode_step = functools.partial(
    jax.jit, static_argnums=(0, 1), donate_argnums=(4, 5))(_decode_impl)
chunk_step = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3),
    donate_argnums=(7, 8))(_chunk_impl)


class LayeredPrograms:
    """The two-kind side of `ServingEngine`: the window layers' ring, the
    pools, and for `_run_chunk` / `_decode` each site's step function
    with its operands and what its result means. The counterpart of
    `engine._StackedPrograms`, method for method."""

    #: every prompt goes through the chunk program (no whole-prompt one)
    whole_prompt_prefill = False

    def __init__(self, eng, block: pb.BlockSpec, full_blocks: int, dtype):
        self.eng = eng
        self.ring = WindowRing(eng.max_slots, block.window,
                               eng.chunk_tokens, eng.block_size)
        self.spec = LayeredSpec(block=block, block_size=eng.block_size,
                                window_pages=self.ring.pages)
        cos, sin = pb.rope_tables(eng.max_model_len, block.head_dim,
                                  block.rope_theta)
        eng.params.update(rope_cos=jnp.asarray(cos), rope_sin=jnp.asarray(sin))
        self.cache = LayeredKVCache(
            [k == pb.SLIDING for k in block.layer_types], full_blocks,
            self.ring.num_blocks, block.num_kv_heads, eng.block_size,
            block.head_dim, dtype)

    def full_pool(self):
        """One full-history layer's pool (shape and dtype)."""
        return self.cache.k[self.cache.sliding.index(False)]

    def chunk_buckets(self, n, ctx_need):
        """One chunk shape, and full-layer contexts in powers of two
        from the window up: a handful of programs, where attention over
        the padding is a small part of a chunk's work."""
        e = self.eng
        floor = blocks_for(self.spec.block.window, e.block_size)
        return e.chunk_tokens, min(e.pages, max(
            floor, 1 << (ctx_need - 1).bit_length()))

    def chunk(self, slot, req, start, n, c_bucket, is_last, ctx_pages, cow):
        """(step, number of static operands, operands)."""
        e, c = self.eng, self.cache
        sample = req.do_sample and is_last
        ids = np.zeros((1, c_bucket), np.int32)
        ids[0, :n] = req.prompt[start:start + n]
        wrow, wbase = self.ring.view(slot, start + n - 1)
        ints = np.concatenate([
            np.array([start, start + n, req.prompt.size - 1 - start, wbase],
                     np.int32), e._tables[slot], wrow])
        return chunk_step, 4, (
            self.spec, sample, is_last, ctx_pages, e.params,
            e._put(ids), e._put(ints), c.k, c.v,
            e._samp([req], 0, sample), e._key)

    def chunk_done(self, out, n, is_last, run):
        """Take the program's result: swap the pools in, fetch the token
        (None unless the prompt's last chunk). The counts come with the
        token: one fetch, which is also the barrier a non-final chunk's
        span needs."""
        tok, picks, loads, ck, cv, self.eng._key = out
        self.cache.swap(ck, cv)
        tok, picks, loads = jax.device_get((tok, picks, loads))
        run.attrs.update(self._moe_attrs(n, picks, loads))
        return int(tok[0]) if is_last else None

    def decode(self, active, reqs, bucket, any_sample):
        e, c = self.eng, self.cache
        n = len(active)
        ints = np.zeros((bucket, 4 + e.pages + self.ring.pages), np.int32)
        ints[:, 4:] = TRASH_BLOCK
        ints[:n, 0] = [r.tokens[-1] for r in reqs]
        ints[:n, 1], ints[:n, 2] = e._slot_pos[active], 1
        ints[:n, 4:4 + e.pages] = e._tables[active]
        for j, slot in enumerate(active):
            ints[j, 4 + e.pages:], ints[j, 3] = self.ring.view(
                slot, e._slot_pos[slot])
        return decode_step, 2, (
            self.spec, any_sample, e.params, e._put(ints), c.k, c.v,
            e._samp(reqs, bucket - n, any_sample), e._key)

    def decode_done(self, out, n_active, run):
        nxt, picks, loads, ck, cv, self.eng._key = out
        self.cache.swap(ck, cv)
        nxt, picks, loads = jax.device_get((nxt, picks, loads))
        run.attrs.update(self._moe_attrs(n_active, picks, loads))
        return np.asarray(nxt)

    def decode_jaxpr(self, bucket, samp):
        e, c = self.eng, self.cache
        ints = jnp.zeros((bucket, 4 + e.pages + self.ring.pages), jnp.int32)
        fn = functools.partial(_decode_impl, self.spec, False)
        return jax.make_jaxpr(fn)(e.params, ints, c.k, c.v, samp, e._key)

    def kv_held(self):
        """(full-layer blocks allocated to live requests, bytes of the
        occupied slots' window rings over all window layers)."""
        e = self.eng
        return (sum(len(b) for b in e._slot_blocks),
                e.num_active * self.ring.tokens_reserved()
                * self.cache.bytes_per_token(True))

    def update_gauges(self):
        full_blocks, window_bytes = self.kv_held()
        self.eng._m_kv_full.set(full_blocks)
        self.eng._m_kv_window.set(window_bytes)

    def _moe_attrs(self, tokens, picks, loads):
        """Span attributes of one step, and the registry's share:
        `picks`/`loads` [L] are the program's counts a layer (local picks,
        the largest held expert's), `tokens` the step's real tokens."""
        e = self.eng
        routed = int(tokens) * len(picks)
        e._m_moe_picks.inc(int(picks.sum()))
        e._m_moe_tokens.inc(routed)
        full_blocks, window_bytes = self.kv_held()
        held = (full_blocks * e.block_size
                * self.cache.bytes_per_token(False) + window_bytes)
        return {"moe_tokens": routed, "moe_local_picks": int(picks.sum()),
                "moe_max_load": int(loads.sum()),
                "kv_bytes_held": int(held),
                "live_tokens": int(sum(
                    e._slot_pos[i] if r.prefill_done else r.prefill_pos
                    for i, r in enumerate(e._slot_req)
                    if r is not None))}
