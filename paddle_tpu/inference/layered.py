"""The serving engine's step programs for a model that declares its
layers one by one (`model.serving_arrays()`: its own buffers, a layer
each; `config.block_spec()`: the block's sizes and each layer's kind):
full-attention layers keep the whole history in pages the
`BlockAllocator` hands out, sliding-window layers keep a `WindowRing` of
the last `window + chunk` positions a slot, latent-attention layers keep
ONE row a position (no heads, no V) in pages of the same tables, and
linear-attention layers a fixed-size state a SLOT (a float32 matrix a
value head and the conv's last inputs), addressed by the slot's index.

The block's mathematics is the model's block module's
(`text/models/parallel_block.py`, `latent_block.py`,
`gated_delta_block.py`; `_BLOCKS` finds it by the spec's type), the same
`block` function the Layer's `forward` calls;
what these programs add is WHERE the cached state lives (`attend`:
scatter the step's rows into the layer's pool through its table, attend
over the pool), the head, the sampling, and the expert layer's counts.
Layers are unrolled, each with its own pool array, and the parameters are
the model's own buffers: no stacked second copy of either exists.

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

A hybrid's full layers over K/V pages (`gated_attention_block`) attend a
chunk in the same blocks of the context, with an online softmax
(`gated_attention_block.blocked_attention`: a traced count of blocks, ONE
chunk program), and decode through `paged_decode` as any full layer.

A latent layer's decode is the absorbed form over its pages
(`ops/pallas_decode.paged_latent_decode`); its chunk attention is the
expanded form over blocks of the context, as many as reach the chunk's
end, so one chunk program serves every context length: on the chip ONE
kernel a layer (`ops/pallas_latent_chunk`), elsewhere the composition.

A linear layer's chunk reads its slot's state (zero where the chunk
starts the prompt: a slot's state is reset by the first chunk of every
request it admits), runs the chunk in the WY form, sub-chunks of
`wy_chunk` (jnp; padded rows take beta = 0, g = 0 and leave the state as
they found it), and writes the state and the conv's last real inputs
back. Its decode advances every row of the bucket one token through
`ops/pallas_gated_delta.gated_delta_decode`, in place, through the
slot's index; rows that hold no request name the trash slot.

Every model's packed operand ends in the slot's index (the chunk's and
each decode row's), because every step keeps the engine's last-token
column: a decode row whose token is a tick still in flight reads it
there by its slot, the step writes the new ones back, and a prompt's
final chunk writes its first token.

`LayeredPrograms` is what `ServingEngine._launch_chunk` and
`_launch_decode` ask for these programs and their operands, and what
`_take_chunks` and `_take_ticks` ask of their results
(`engine._StackedPrograms` answers for the dense architectures' stacked
ones).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.compile_cache import frameless_locations
from ..ops import pallas_decode as pd
from ..ops import pallas_gated_delta as pgd
from ..ops import pallas_latent_chunk as plc
from ..ops.pallas_decode import paged_decode_attention
from ..text.models import gated_attention_block as ga
from ..text.models import gated_delta_block as gd
from ..text.models import latent_block as lb
from ..text.models import parallel_block as pb
from ..text.paged_cache import (TRASH_BLOCK, LayeredKVCache, WindowRing,
                                append_rows, blocks_for, gather_context,
                                latent_row_width, scatter_chunk_rows)

#: the block module of a spec: `block`, `head`, `rope_for`, `cache_kind`,
#: `expert_layer`
_BLOCKS = {pb.BlockSpec: pb, lb.BlockSpec: lb, gd.BlockSpec: gd}


def _full(block):
    """The spec a full layer's attention reads (a hybrid model's
    `full`)."""
    return getattr(block, "full", block)


def _kinds(block) -> list:
    mod = _BLOCKS[type(block)]
    return [mod.cache_kind(k, block) for k in block.layer_types]


def _blocked_full(block) -> bool:
    """A hybrid's full layers over K/V pages attend a chunk in context
    blocks (one chunk program for every context length); the
    parallel-block model's take a bucketed context whole."""
    return isinstance(block, gd.BlockSpec)


@dataclass(frozen=True)
class LayeredSpec:
    """Static key of the programs."""
    block: pb.BlockSpec | lb.BlockSpec
    block_size: int
    window_pages: int


#: why a per-layer model is refused an option, by what its layers keep
_REFUSALS = {
    pb.SLIDING: {
        "kv_cache_dtype": "the window layers' ring has no per-block scales",
        "spec_decode": "there is no verify program for two-kind layers",
        "prefix_cache": "a cached prefix holds no window-layer state to "
                        "resume from",
        "chunked_prefill_tokens": "every prompt is prefilled by chunks (the "
                                  "window layers' ring is sized by the "
                                  "chunk)"},
    lb.LATENT: {
        "kv_cache_dtype": "a latent pool has no per-block scales, and the "
                          "absorbed decode kernel reads its rows as they "
                          "are stored",
        "spec_decode": "there is no verify program over a latent cache "
                       "(the config's multi-token-prediction module, its "
                       "drafter, is not built either)",
        "prefix_cache": "latent pages are not registered under content "
                        "hashes, and the per-layer chunk program has no "
                        "copy-on-write of a shared page",
        "chunked_prefill_tokens": "every prompt is prefilled by chunks (the "
                                  "per-layer programs have no whole-prompt "
                                  "one)"},
    gd.RECURRENT: {
        "kv_cache_dtype": "a linear layer's recurrent state is a float32 "
                          "matrix a slot with no quantised form",
        "spec_decode": "a recurrent state cannot be rolled back past a "
                       "rejected draft token (the config's multi-token-"
                       "prediction modules, its drafters, are not built "
                       "either)",
        "prefix_cache": "a cached prefix holds no recurrent state: a hit "
                        "would need each linear layer's state after the "
                        "prefix, which pages do not keep",
        "chunked_prefill_tokens": "every prompt is prefilled by chunks (the "
                                  "chunk program carries the slot's "
                                  "recurrent state)"}}


def refusals(block) -> dict:
    """{option: why `ServingEngine` refuses it} for a model of `block`'s
    kinds of layer state: the reasons of every kind it keeps, joined."""
    why = {"weight_quant": "its step programs read the model's own "
                           "buffers and have no dequantising matmul"}
    for kind in dict.fromkeys(_kinds(block)):
        for option, reason in _REFUSALS.get(kind, {}).items():
            why[option] = f"{why[option]}; {reason}" if option in why \
                else reason
    return why


def _latent_row(c, kr, pool):
    """(c, kr) of some positions as rows of `pool` [N, 1, bs, W]: side by
    side, zeros up to the stored width. [T, 1, W]."""
    pad = pool.shape[-1] - c.shape[-1] - kr.shape[-1]
    return jnp.pad(jnp.concatenate([c, kr], axis=-1),
                   ((0, 0), (0, pad)))[:, None, :].astype(pool.dtype)


def _latent_decode_attend(blk, pools, li, tables, pos, rows, bs):
    """`attend` of a latent layer in a decode step: append each slot's
    row through its table, then the absorbed form over the slot's pages
    (`paged_latent_decode`: every head against the one cached row)."""
    blk = _full(blk)

    def attend(q_nope, q_rope, c, kr, w_kvb):
        pools[li] = append_rows(pools[li], _latent_row(c, kr, pools[li]),
                                tables[rows, pos // bs],
                                (pos % bs).astype(jnp.int32))
        q = lb.absorb_query(q_nope, w_kvb, blk)
        width = pools[li].shape[-1]
        q = jnp.pad(jnp.concatenate([q, q_rope], axis=-1),
                    ((0, 0), (0, 0), (0, width - blk.latent_width)))
        olat = pd.paged_latent_decode(q, pools[li], tables, pos + 1,
                                      blk.kv_rank, blk.scale)
        return lb.unabsorb(olat, w_kvb, blk)
    return attend


def _chunk_kernel_block(blk, dtype, q_rows, ctx_rows, width) -> int:
    """Context positions a grid step of the chunk kernel takes for a chunk
    of `q_rows` over a table of `ctx_rows` positions cached `width` wide,
    0 where the composition runs instead (`plc.chunk_gate_reason` says
    why). The program and the engine's `attn_kernel_blocks` both ask
    here."""
    blk = _full(blk)
    if not plc.use_latent_chunk_kernel(
            dtype, q_rows, ctx_rows, blk.num_heads, blk.qk_nope_dim,
            blk.qk_rope_dim, blk.v_dim, blk.kv_rank, width):
        return 0
    return plc.context_block(q_rows, ctx_rows)


def _latent_chunk_attend(blk, pools, li, table, start, true_end, pos, bs):
    """`attend` of a latent layer in a chunk step: scatter the chunk's
    rows through the table, then attend over the pages that hold
    positions [0, start + C) in the EXPANDED form (the absorbed one took
    1.47 x as long as a composition: PERF.md section 6, PR 37), a block
    of the context at a time with an online softmax: the steps run to the
    chunk's end and no further (a traced count: ONE program for every
    context length), and no [heads, C, context] tensor exists. On the
    chip that is `plc.latent_chunk_attention_raw` over the table's rows
    (a step's scores never leave VMEM; 0.26 ms a 512 positions a layer
    where the composition takes 1.1: PERF.md section 6, PR 38); where
    `_chunk_kernel_block` says 0, the composition
    `lb.expanded_attention`, `lb.CTX_BLOCK` positions a step (the whole
    table where it holds fewer), which is also the kernel's oracle."""
    block = min(lb.CTX_BLOCK, table.shape[0] * bs)
    per = block // bs
    blk = _full(blk)

    def attend(q_nope, q_rope, c, kr, w_kvb):
        pools[li] = scatter_chunk_rows(
            pools[li], _latent_row(c, kr, pools[li]), start, true_end,
            table, bs)
        if _chunk_kernel_block(blk, q_nope.dtype, q_nope.shape[0],
                               table.shape[0] * bs, pools[li].shape[-1]):
            rows = pools[li][table].reshape(table.shape[0] * bs, -1)
            return plc.latent_chunk_attention_raw(
                q_nope, q_rope, rows, start, w_kvb, blk.kv_rank, blk.scale)
        n_blocks = jnp.minimum(
            (start + q_nope.shape[0] + block - 1) // block,
            table.shape[0] // per)

        def rows_of(j):
            ids = jax.lax.dynamic_slice_in_dim(table, j * per, per)
            return pools[li][ids].reshape(block, -1)

        return lb.expanded_attention(q_nope, q_rope, rows_of, n_blocks,
                                     block, w_kvb, pos, blk)
    return attend


def _full_chunk_attend(ks, vs, li, table, start, true_end, pos, bs):
    """`attend` of a hybrid's full layer over K/V pages in a chunk step:
    scatter the chunk's K and V through the table, then attend over the
    pages that hold positions [0, start + C), `ga.CTX_BLOCK` positions a
    step with an online softmax (a traced count of steps: ONE program for
    every context length; the float32 scores held are [heads, C,
    block])."""
    block = min(ga.CTX_BLOCK, table.shape[0] * bs)
    per = block // bs

    def attend(q, k, v):
        ks[li] = scatter_chunk_rows(ks[li], k, start, true_end, table, bs)
        vs[li] = scatter_chunk_rows(vs[li], v, start, true_end, table, bs)
        n_blocks = jnp.minimum((start + q.shape[0] + block - 1) // block,
                               table.shape[0] // per)

        def kv_of(j):
            ids = jax.lax.dynamic_slice_in_dim(table, j * per, per)
            return tuple(p[ids].transpose(0, 2, 1, 3).reshape(
                block, p.shape[1], p.shape[3]) for p in (ks[li], vs[li]))

        return ga.blocked_attention(q, kv_of, n_blocks, block, pos,
                                    k.shape[1])
    return attend


def _recurrent_decode_attend(blk, ks, vs, li, slots):
    """`attend` of a linear layer in a decode step: row b's conv runs
    over its slot's last K - 1 inputs and its token's (which then join
    the slot's), and `gated_delta_decode` advances the slot's state one
    token in place. [B, nv, dv]."""
    def attend(mixed, b, a, lw):
        x_ext = jnp.concatenate([vs[li][slots], mixed[:, None, :]], axis=1)
        vs[li] = vs[li].at[slots].set(x_ext[:, 1:])
        q, k, v, beta, g = (y[:, 0] for y in gd.delta_inputs(
            x_ext, b[:, None], a[:, None], lw, blk))
        o, ks[li] = pgd.gated_delta_decode(ks[li], slots, q, k, v, beta,
                                           jnp.exp(g))
        return o
    return attend


def _slot_state(pool, slot, fresh):
    """A slot's state as a chunk starts from it: zero where the chunk
    starts the prompt (`fresh`), whatever the slot's last request left."""
    return jnp.where(fresh, 0.0, pool[slot])


def _recurrent_chunk_attend(blk, ks, vs, li, slot, start, true_end,
                            valid):
    """`attend` of a linear layer in a chunk step: the slot's two states,
    zero where the chunk starts the prompt; the conv over them and the
    chunk's inputs, the WY form over the chunk, both states written back
    (the conv's: the last K - 1 REAL inputs). Padded rows take beta = 0
    and g = 0: the state passes them unchanged."""
    keep = blk.conv_kernel - 1

    def attend(mixed, b, a, lw):
        fresh = start == 0
        x_ext = jnp.concatenate([_slot_state(vs[li], slot, fresh), mixed])
        vs[li] = vs[li].at[slot].set(jax.lax.dynamic_slice_in_dim(
            x_ext, true_end - start, keep))
        q, k, v, beta, g = gd.delta_inputs(x_ext, b, a, lw, blk)
        beta = jnp.where(valid[:, None], beta, 0.0)
        g = jnp.where(valid[:, None], g, 0.0)
        s0 = _slot_state(ks[li], slot, fresh)
        o, s = gd.chunked(s0, q, k, v, beta, g, blk.wy_chunk)
        ks[li] = ks[li].at[slot].set(s)
        return o
    return attend


def _sample(lg, any_sample, samp, key):
    from .engine import _sample_batched

    if any_sample:
        key, sub = jax.random.split(key)
        return _sample_batched(lg, sub, samp["do_sample"],
                               samp["temperature"], samp["top_k"],
                               samp["top_p"]), key
    return jnp.argmax(lg, axis=-1).astype(jnp.int32), key


def _decode_impl(spec: LayeredSpec, any_sample: bool, params, ints, ks,
                 vs, last, samp, key):
    """ONE decode step for a compacted slot bucket. `ints` [B, 4 + pages
    + R + 1], a row a slot (`LayeredPrograms.decode` packs it): its
    token (-1: read it from `last`), its position, 1 for a live row (0:
    padding, whose tables are the trash block), its ring view's base
    page, the full layers' block table [pages], the ring view [R] (R = 0
    for a model without window layers) and its slot (padding: the trash
    slot). ks/vs: a layer's arrays (`LayeredKVCache`; None where the kind
    has no second). `last` [slots + 1]: each slot's last token on the
    device, read where the row says -1 and written with the new ones.
    Returns (next tokens [B], the expert counts [3, L] (local picks, the
    largest held expert's, held experts picked; a layer), ks, vs, last,
    key)."""
    from .engine import _row_tokens

    blk, bs = spec.block, spec.block_size
    mod = _BLOCKS[type(blk)]
    kinds = _kinds(blk)
    end = ints.shape[1] - 1
    slots = ints[:, -1]
    tok = _row_tokens(ints[:, 0], slots, last)
    pos, valid, wbase = (ints[:, i] for i in range(1, 4))
    ftables = ints[:, 4:end - spec.window_pages]
    wtables = ints[:, end - spec.window_pages:end]
    rows = jnp.arange(ints.shape[0])
    x = params["embed"][tok]
    rope = (params["rope_cos"][pos], params["rope_sin"][pos])
    valid = valid > 0
    wpos = pos - wbase * bs
    ks, vs = list(ks), list(vs)
    counts = []
    for li, kind in enumerate(blk.layer_types):
        sliding = kind == pb.SLIDING
        tables, p = (wtables, wpos) if sliding else (ftables, pos)

        if kinds[li] == lb.LATENT:
            attend = _latent_decode_attend(blk, ks, li, ftables, pos, rows,
                                           bs)
        elif kinds[li] == gd.RECURRENT:
            attend = _recurrent_decode_attend(blk, ks, vs, li, slots)
        else:
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
                return paged_decode_attention(q, ks[li], vs[li], tables,
                                              p + 1)

        x, *layer_counts = mod.block(x, params["layers"][li], blk, kind,
                                     attend, rope=rope, valid=valid)
        counts.append(jnp.stack(layer_counts))
    lg = mod.head(x, params, blk)
    nxt, key = _sample(lg, any_sample, samp, key)
    return (nxt, jnp.stack(counts, axis=1), tuple(ks), tuple(vs),
            last.at[slots].set(nxt), key)


def _chunk_impl(spec: LayeredSpec, any_sample: bool, emit_token: bool,
                ctx_pages: int, params, ids, ints, ks, vs, last, samp, key):
    """Prefill ONE chunk of one prompt: positions [start, true_end) of
    ids [1, C] (the rest is padding). `ints` [4 + pages + R + 1]
    (`LayeredPrograms.chunk` packs it): start, true_end, last_idx, the
    ring view's base page, the full layers' block table, the ring view
    and the slot. Each layer scatters the chunk's K/V through its table
    and attends every chunk position over what its kind sees: a full
    layer the first `ctx_pages` (static, bucketed) pages of `ftable`
    under `kv <= q`, a window layer the whole ring view under
    `0 <= q - kv < window`. Scores are computed one KV head's group at a
    time (`parallel_block.grouped_attention`), so the largest temporary
    is [heads a KV head, C, context] in float32. A latent layer:
    `_latent_chunk_attend` (`ctx_pages` is then the whole table and not
    read); a hybrid's full layer: `_full_chunk_attend` (likewise); a
    linear layer: `_recurrent_chunk_attend`. `emit_token`
    (static): the prompt's final chunk samples the first token from chunk
    row `last_idx` and writes it into the slot's row of `last`."""
    blk, bs = spec.block, spec.block_size
    mod = _BLOCKS[type(blk)]
    kinds = _kinds(blk)
    end = ints.shape[0] - 1
    start, true_end, last_idx, wbase = (ints[i] for i in range(4))
    ftable = ints[4:end - spec.window_pages]
    wtable = ints[end - spec.window_pages:end]
    c = ids.shape[1]
    pos = start + jnp.arange(c)
    x = params["embed"][ids[0]]
    at = jnp.clip(pos, 0, params["rope_cos"].shape[0] - 1)
    rope = (params["rope_cos"][at], params["rope_sin"][at])
    valid = pos < true_end
    ks, vs = list(ks), list(vs)
    counts = []
    for li, kind in enumerate(blk.layer_types):
        sliding = kind == pb.SLIDING
        table, shift, pages = ((wtable, wbase * bs, spec.window_pages)
                               if sliding else (ftable, 0, ctx_pages))

        if kinds[li] == lb.LATENT:
            attend = _latent_chunk_attend(blk, ks, li, ftable, start,
                                          true_end, pos, bs)
        elif kinds[li] == pb.FULL and _blocked_full(blk):
            attend = _full_chunk_attend(ks, vs, li, ftable, start, true_end,
                                        pos, bs)
        elif kinds[li] == gd.RECURRENT:
            attend = _recurrent_chunk_attend(blk, ks, vs, li, ints[-1],
                                             start, true_end, valid)
        else:
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

        x, *layer_counts = mod.block(x, params["layers"][li], blk, kind,
                                     attend, rope=rope, valid=valid)
        counts.append(jnp.stack(layer_counts))
    if emit_token:
        x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=0)
        lg = mod.head(x_last, params, blk)
        tok, key = _sample(lg, any_sample, samp, key)
        last = last.at[ints[-1]].set(tok[0])
    else:
        tok = jnp.zeros((1,), jnp.int32)
    return tok, jnp.stack(counts, axis=1), tuple(ks), tuple(vs), last, key


class _Step:
    """A step program's `jax.jit`, lowered under
    `compile_cache.frameless_locations`: these programs hold Pallas
    kernels (the grouped experts' and the attention's), whose serialized
    bodies would otherwise key the persistent cache by the trace history
    of the process, so that a fresh process missed."""

    def __init__(self, jitted):
        self.jitted = jitted

    def lower(self, *args):
        with frameless_locations():
            return self.jitted.lower(*args)


decode_step = _Step(functools.partial(
    jax.jit, static_argnums=(0, 1), donate_argnums=(4, 5, 6))(_decode_impl))
chunk_step = _Step(functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3),
    donate_argnums=(7, 8, 9))(_chunk_impl))


class LayeredPrograms:
    """The per-layer side of `ServingEngine`: the window layers' ring
    (where the model has window layers), the pools, and for
    `_launch_chunk` / `_launch_decode` each site's step function with its
    operands, what a dispatched call hands the engine and what its result
    means once fetched. The counterpart of `engine._StackedPrograms`,
    method for method."""

    #: every prompt goes through the chunk program (no whole-prompt one)
    whole_prompt_prefill = False

    def __init__(self, eng, block, full_blocks: int, dtype):
        self.eng = eng
        mod = _BLOCKS[type(block)]
        kinds = _kinds(block)
        self.latent = lb.LATENT in kinds
        self.latent_layers = kinds.count(lb.LATENT)
        self.linear_layers = kinds.count(gd.RECURRENT)
        #: a hybrid's full layers over K/V pages (`_full_chunk_attend`)
        self.blocked_full_layers = kinds.count(pb.FULL) \
            if _blocked_full(block) else 0
        self.expert_layers = sum(map(mod.expert_layer, block.layer_types))
        #: held experts over the expert layers: what a step could read
        self.experts_held = (self.expert_layers
                             * _full(block).num_local_experts)
        sliding = [k == pb.SLIDING for k in kinds]
        self.ring = WindowRing(eng.max_slots, block.window,
                               eng.chunk_tokens, eng.block_size) \
            if any(sliding) else None
        self.spec = LayeredSpec(
            block=block, block_size=eng.block_size,
            window_pages=self.ring.pages if self.ring else 0)
        cos, sin = mod.rope_for(block, eng.max_model_len)
        eng.params.update(rope_cos=jnp.asarray(cos), rope_sin=jnp.asarray(sin))
        heads, width = block.num_kv_heads, block.head_dim
        if self.latent or self.blocked_full_layers:
            step = min(lb.CTX_BLOCK if self.latent else ga.CTX_BLOCK,
                       eng.max_model_len)
            if step % eng.block_size or eng.max_model_len % step:
                raise ValueError(
                    f"a chunk attends its context {step} positions a "
                    f"step: kv_block_size {eng.block_size} must divide "
                    f"that, and that max_model_len {eng.max_model_len}")
        if self.latent:
            heads, width = 1, latent_row_width(_full(block).latent_width)
        recurrent = {}
        if self.linear_layers:
            # the engine's slots and the trash slot (index max_slots)
            recurrent = dict(slots=eng.max_slots + 1,
                             state_shape=block.state_shape,
                             conv_shape=block.conv_shape)
        self.cache = LayeredKVCache(
            kinds, full_blocks, self.ring.num_blocks if self.ring else 0,
            heads, eng.block_size, width, dtype, **recurrent)

    def full_pool(self):
        """One full-history layer's pool (shape and dtype)."""
        first = next(i for i, k in enumerate(self.cache.kinds)
                     if k in (pb.FULL, lb.LATENT))
        return self.cache.k[first]

    def kv_steps(self, bucket):
        """Grid steps a layer of the decode kernel over a full-history
        pool at this slot bucket (0: the XLA composition)."""
        e, pool = self.eng, self.full_pool()
        if not self.latent:
            return e._kv_steps(bucket)
        blk = _full(self.spec.block)
        q = jax.ShapeDtypeStruct((bucket, blk.num_heads, pool.shape[-1]),
                                 pool.dtype)
        tables = jax.ShapeDtypeStruct((bucket, e.pages), jnp.int32)
        if not pd.use_pallas_latent_decode(q, pool, tables, blk.kv_rank):
            return 0
        return pd.kv_steps(bucket, e.pages, pool.shape[2], 1,
                           pool.shape[3], pool.dtype.itemsize)

    def chunk_buckets(self, n, ctx_need):
        """One chunk shape. Window and full layers: full-layer contexts
        in powers of two from the window up, a handful of programs, where
        attention over the padding is a small part of a chunk's work. A
        latent cache: ONE program, its attention's steps counted in the
        program from the chunk's own end; so too a hybrid's full layers
        over K/V pages."""
        e = self.eng
        if self.latent or self.blocked_full_layers:
            return e.chunk_tokens, e.pages
        floor = blocks_for(self.spec.block.window, e.block_size)
        return e.chunk_tokens, min(e.pages, max(
            floor, 1 << (ctx_need - 1).bit_length()))

    def _ring_view(self, slot, last_pos):
        """(table row, base page) of the slot's ring; a model with no
        window layer has neither."""
        if self.ring is None:
            return np.zeros(0, np.int32), 0
        return self.ring.view(slot, last_pos)

    def chunk(self, slot, req, start, n, c_bucket, is_last, ctx_pages, cow):
        """(step, number of static operands, operands)."""
        e, c = self.eng, self.cache
        sample = req.do_sample and is_last
        ids = np.zeros((1, c_bucket), np.int32)
        ids[0, :n] = req.prompt[start:start + n]
        wrow, wbase = self._ring_view(slot, start + n - 1)
        ints = np.concatenate([
            np.array([start, start + n, req.prompt.size - 1 - start, wbase],
                     np.int32), e._tables[slot], wrow,
            np.array([slot], np.int32)])
        return chunk_step, 4, (
            self.spec, sample, is_last, ctx_pages, e.params,
            e._put(ids), e._put(ints), c.k, c.v, e._last,
            e._samp([req], 0, sample), e._key)

    def chunk_sent(self, out):
        """Take the dispatched call's state (the pools, the key, the
        last-token column) into the engine; returns what is left to fetch
        and the span attributes read from the host's state as the call
        was made."""
        tok, counts, ck, cv, self.eng._last, self.eng._key = out
        self.cache.swap(ck, cv)
        return tok, counts, self._held_attrs()

    def chunk_done(self, sent, n, is_last, run):
        """The token (None unless the prompt's last chunk) and the
        chunk's counts, in one fetch, onto the span."""
        tok, counts, held = sent
        tok, counts = jax.device_get((tok, counts))
        run.attrs.update(self._moe_attrs(n, counts), **held)
        if self.latent:
            # every chunk position attends the positions up to its own
            start = run.attrs["start"]
            run.attrs["attn_pairs"] = self._latent_ctx(
                n * start + n * (n + 1) // 2)
            run.attrs["attn_kernel_blocks"] = self._chunk_kernel_blocks(
                start, run.attrs["bucket"])
        elif self.blocked_full_layers:
            start = run.attrs["start"]
            run.attrs["attn_pairs"] = self.blocked_full_layers * (
                n * start + n * (n + 1) // 2)
        if self.linear_layers:
            run.attrs["state_tokens"] = n * self.linear_layers
            self.eng._m_state_tokens.inc(run.attrs["state_tokens"])
        return int(tok[0]) if is_last else None

    def _ints_width(self):
        """Columns of a decode row: token, position, live, ring base,
        the table, the ring view, the slot."""
        return 5 + self.eng.pages + self.spec.window_pages

    def decode(self, active, reqs, bucket, any_sample):
        e, c = self.eng, self.cache
        n = len(active)
        ints = np.zeros((bucket, self._ints_width()), np.int32)
        ints[:, 4:] = TRASH_BLOCK
        ints[:n, 0] = e._packed_tokens(reqs)
        ints[:n, 1], ints[:n, 2] = e._slot_pos[active], 1
        ints[:n, 4:4 + e.pages] = e._tables[active]
        for j, slot in enumerate(active):
            ints[j, 4 + e.pages:4 + e.pages + self.spec.window_pages], \
                ints[j, 3] = self._ring_view(slot, e._slot_pos[slot])
        ints[:, -1] = e.max_slots                     # the trash slot
        ints[:n, -1] = active
        return decode_step, 2, (
            self.spec, any_sample, e.params, e._put(ints), c.k, c.v,
            e._last, e._samp(reqs, bucket - n, any_sample), e._key)

    def decode_sent(self, out, active):
        """As `chunk_sent`, before the tick's positions advance."""
        nxt, counts, ck, cv, self.eng._last, self.eng._key = out
        self.cache.swap(ck, cv)
        held = self._held_attrs()
        if self.latent:
            held["ctx_tokens"] = self._latent_ctx(
                int(self.eng._slot_pos[active].sum()) + len(active))
        elif self.blocked_full_layers:
            held["ctx_tokens"] = self.blocked_full_layers * (
                int(self.eng._slot_pos[active].sum()) + len(active))
        if self.linear_layers:
            held["state_bytes_held"] = self._state_bytes()
        return nxt, counts, held

    def decode_done(self, sent, n_active, run):
        nxt, counts, held = sent
        nxt, counts = jax.device_get((nxt, counts))
        run.attrs.update(self._moe_attrs(n_active, counts), **held)
        if self.linear_layers:
            run.attrs["state_slots"] = n_active * self.linear_layers
            self.eng._m_state_slots.inc(run.attrs["state_slots"])
        return np.asarray(nxt)

    def decode_jaxpr(self, bucket, samp):
        e, c = self.eng, self.cache
        ints = jnp.zeros((bucket, self._ints_width()), jnp.int32)
        fn = functools.partial(_decode_impl, self.spec, False)
        return jax.make_jaxpr(fn)(e.params, ints, c.k, c.v, e._last, samp,
                                  e._key)

    def kv_held(self):
        """(full-history blocks allocated to live requests, bytes of the
        occupied slots' window rings over all window layers)."""
        e = self.eng
        window = 0 if self.ring is None else (
            e.num_active * self.ring.tokens_reserved()
            * self.cache.bytes_per_token(True))
        return sum(len(b) for b in e._slot_blocks), window

    def _paged_bytes(self, blocks):
        """Bytes `blocks` allocated blocks hold over the full-history
        layers (K and V, or the latent rows as stored)."""
        return blocks * self.eng.block_size * self.cache.bytes_per_token(
            False)

    def update_gauges(self):
        full_blocks, window_bytes = self.kv_held()
        self.eng._m_kv_full.set(full_blocks)
        self.eng._m_kv_window.set(window_bytes)
        if self.latent:
            self.eng._m_kv_latent.set(self._paged_bytes(full_blocks))
        if self.linear_layers:
            self.eng._m_state_bytes.set(self._state_bytes())

    def _state_bytes(self):
        """Bytes of recurrent state the occupied slots hold, all linear
        layers (a slot's is held whole from admission to its end)."""
        return self.eng.num_active * self.cache.state_bytes_per_slot()

    def _chunk_kernel_blocks(self, start, c_bucket):
        """Context blocks x latent layers the chunk kernel computed in a
        chunk of `c_bucket` rows at `start` (its steps reach the padded
        chunk's end, as the program's do); 0 where the composition ran."""
        pool, ctx_rows = self.full_pool(), self.eng.pages * self.eng.block_size
        block = _chunk_kernel_block(self.spec.block, pool.dtype, c_bucket,
                                    ctx_rows, pool.shape[-1])
        n = self.latent_layers * plc.live_blocks(
            int(start), int(c_bucket), block, ctx_rows) if block else 0
        self.eng._m_latent_kernel_blocks.inc(n)
        return n

    def _latent_ctx(self, positions):
        """`positions` attended a layer, over the latent layers: the
        span attribute's value, counted in the registry too."""
        n = int(positions) * self.latent_layers
        self.eng._m_latent_ctx.inc(n)
        return n

    def _moe_attrs(self, tokens, counts):
        """Span attributes of one step's expert layers, and the
        registry's share: `counts` [3, L] are the program's counts a layer
        (local picks, the largest held expert's, held experts with a
        pick; 0 on a layer without experts), `tokens` the step's real
        tokens."""
        e = self.eng
        routed = int(tokens) * self.expert_layers
        picks, loads, read = (int(c) for c in counts.sum(axis=1))
        e._m_moe_picks.inc(picks)
        e._m_moe_tokens.inc(routed)
        e._m_moe_read.inc(read)
        return {"moe_tokens": routed, "moe_local_picks": picks,
                "moe_max_load": loads, "moe_experts_read": read,
                "moe_experts_held": self.experts_held}

    def _held_attrs(self):
        """Span attributes of the cache the live requests hold, read as
        a call is made (its `.run` span, which carries them, may lie in a
        later step)."""
        e = self.eng
        full_blocks, window_bytes = self.kv_held()
        return {"kv_bytes_held": int(self._paged_bytes(full_blocks)
                                     + window_bytes),
                "live_tokens": int(sum(
                    e._slot_pos[i] if r.prefill_done else r.prefill_pos
                    for i, r in enumerate(e._slot_req)
                    if r is not None))}
