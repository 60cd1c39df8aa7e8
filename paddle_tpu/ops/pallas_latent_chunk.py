"""Pallas TPU attention of a prefill chunk over a latent (MLA) context, in
the published EXPANDED form: keys `[c Wuk^h | kr]`, values `c Wuv^h`.

The composition (`text/models/latent_block.expanded_attention`) walks the
context in blocks and, a block, writes a float32 `[heads, Q, block]` score
tensor to HBM and passes over it five or six times (two score products,
the mask, the maximum, the exponential, the sum, `P V`): 134 MB a block a
layer at 128 heads x 512 x 512, 0.96 ms where the block's FLOPs are 0.2 ms
of a v5e's peak (PERF.md section 5, PR 37). Here the score tile and the
softmax state never leave VMEM.

TPU-native design:
  - Grid `(head group, context block)`, the context innermost: a group's
    running maximum, sum and accumulator (float32 VMEM scratch) persist
    over its whole walk of the context, so no carry goes through HBM and
    the output is written once, already normalised, in the query's dtype.
  - The context arrives as its cached ROWS `[T, W]` (position i at row i:
    the latent, the rotary key, padding), one block of them a step by the
    grid's own pipeline; a block is expanded through the group's slice of
    `Wkvb` INSIDE the kernel (one `[block, rkv] x [rkv, dn + dv]` product a
    head into VMEM scratch), so no expanded K/V exists in HBM either. The
    rows are read once a head group: `heads / group` times a chunk.
  - The number of live blocks follows from a scalar-prefetch operand (the
    chunk's first position), not from a shape: ONE program serves every
    context length. Steps past the chunk's end do nothing and fetch
    nothing (their index map is clamped to the last live block, and the
    pipeline copies a block only when its index changes).
  - Big tiles, because the MXU's passes set the time and the scheduler
    overlaps them with the softmax only inside one basic block: at 512
    queries a context block of 1024 with two heads a loop step ran at 0.26
    ms a 512 positions a layer on a v5e where a block of 512 took 0.40
    (PERF.md section 6, PR 38; 38.7 GFLOP: 77% against 49% of the peak).
  - ONE body, masked everywhere: beside the products the iota, compare and
    select cost 0.7% (measured), and a second, lean copy of the body for
    the blocks the mask cannot touch cost 3.1 ms a call at this size.
  - The composition's precisions to the letter: both score products and
    `P V` accumulate in float32, the scale, maximum, exponential and sum
    are float32, the probabilities are rounded to the values' dtype for
    `P V` alone, and an expanded key or value is rounded to the rows' dtype
    as XLA's `br,rhd->bhd` rounds it.

Same layering as pallas_decode.py: `interpret` mode off-TPU (how the
parity tests run on the CPU), routing by `use_latent_chunk_kernel` with the
composition as the everywhere-else path, the gate's reasons by name in
`chunk_gate_reason`.
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

# every kernel scalar is an explicitly-typed np.float32 (x64 is on
# globally: a bare float would trace as f64, which Mosaic cannot legalize)
_NEG_INF = np.float32(-1e30)
_F32 = jnp.float32

NAME = "latent_chunk_attn"
#: context positions a grid step takes, the largest that fits first
_CTX_BLOCKS = (1024, 512, 256, 128)
#: the score tile `[Q, block]` in float32, with its exponentials, stays in
#: VMEM: 2 MiB of scores (512 x 1024) is the most a step is given
_MAX_TILE_ELEMS = 512 * 1024
#: heads a step of the kernel's loop over its group attends: independent
#: work the scheduler overlaps (the products of one with the softmax of
#: the other); 4 measured 1% better than 2 and compiles a tenth slower
_HEADS_A_LOOP_STEP = 2
#: the scoped VMEM the kernel may ask for (a v5e core has 128 MiB; the
#: default scope of 16 MiB does not hold a group's state at 512 x 1024)
_VMEM_CAP_BYTES = 100 << 20
#: the share of it a head group's blocks and scratch may fill: the rest is
#: the compiler's, for the score tile and its temporaries
_GROUP_VMEM_BYTES = 40 << 20


# ------------------------------------------------------------------ sizing

def context_block(q_rows, ctx_rows):
    """Context positions one grid step takes: the largest of `_CTX_BLOCKS`
    that divides the context's rows and keeps the score tile within
    `_MAX_TILE_ELEMS`; 0 where none does."""
    for block in _CTX_BLOCKS:
        if ctx_rows % block == 0 and q_rows * block <= _MAX_TILE_ELEMS:
            return block
    return 0


def live_blocks(start, q_rows, block, ctx_rows):
    """Blocks of `block` positions that hold positions [0, start + q_rows)
    of a context of `ctx_rows`: the steps a head group computes. Python
    ints or traced int32 alike (the program and the engine's
    `attn_kernel_blocks` count read the same arithmetic)."""
    n = (start + q_rows + block - 1) // block
    whole = ctx_rows // block
    return min(n, whole) if isinstance(n, int) else jnp.minimum(n, whole)


def _group_bytes(group, q_rows, block, dn, dr, dv, rkv, width, itemsize):
    """VMEM one step of a `group`-head kernel holds: the pipeline's blocks
    (double-buffered) and the scratch."""
    qo_lanes = sum(_ceil_to(d, 128) for d in (dn, dr, dv))
    blocks = (group * q_rows * qo_lanes + rkv * group * (dn + dv)
              + block * _ceil_to(width, 128)) * itemsize
    scratch = (group * block * (dn + dv) * itemsize
               + group * q_rows * (_ceil_to(dv, 128) + 2 * 128) * 4)
    return 2 * blocks + scratch


def heads_per_step(heads, q_rows, block, dn, dr, dv, rkv, width, itemsize):
    """Heads one grid step attends: the largest divisor of `heads` that is
    a multiple of 8 (or all of them) and whose blocks and scratch fit
    `_GROUP_VMEM_BYTES`; 0 where none does. More heads a step: fewer steps
    and fewer passes over the context's rows (8 and 16 measured alike)."""
    for g in range(heads, 0, -1):
        if heads % g or (g % 8 and g != heads):
            continue
        if _group_bytes(g, q_rows, block, dn, dr, dv, rkv, width,
                        itemsize) <= _GROUP_VMEM_BYTES:
            return g
    return 0


# ------------------------------------------------------------------ kernel

def _chunk_kernel(start_ref, qn_ref, qr_ref, w_ref, rows_ref, o_ref, kv_s,
                  acc, m_s, l_s, *, scale, block, rkv, dn, dr, ctx_rows):
    """One (head group, context block) step. start_ref (scalar prefetch):
    [position of query row 0]. qn/qr [G, Q, dn|dr] and w [rkv, G * (dn +
    dv)] are the group's (fetched once a group), rows [block, W] the
    step's context block; o [G, Q, dv] is written by the group's last
    step. kv_s [G, block, dn + dv]: the block expanded for the group's
    heads; acc [G, Q, dv], m_s/l_s [G, Q, 128] (a row's value on every
    lane): the running softmax state."""
    j, n_j = pl.program_id(1), pl.num_programs(1)
    start = start_ref[0]
    group, q_rows, _ = acc.shape
    dkv = kv_s.shape[-1]
    cdt = kv_s.dtype
    unroll = _HEADS_A_LOOP_STEP if group % _HEADS_A_LOOP_STEP == 0 else 1

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(j < live_blocks(start, q_rows, block, ctx_rows))
    def _block():
        c = rows_ref[:, :rkv]
        kr = rows_ref[:, rkv:rkv + dr]
        # static slices of the group's weights: a lane offset cannot be a
        # loop variable; the attention itself is a loop over the scratch
        for h in range(group):
            kv_s[h] = jnp.dot(c, w_ref[:, h * dkv:(h + 1) * dkv],
                              preferred_element_type=_F32).astype(cdt)
        q_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (q_rows, block), 0)
        kv_pos = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (q_rows, block), 1)
        seen = q_pos >= kv_pos

        def head(h):
            kv = kv_s[h]
            s = (jax.lax.dot_general(qn_ref[h], kv[:, :dn],
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=_F32)
                 + jax.lax.dot_general(qr_ref[h], kr,
                                       (((1,), (1,)), ((), ())),
                                       preferred_element_type=_F32)) \
                * np.float32(scale)
            # block 0 holds position 0, which every row sees: a row's
            # maximum is finite from its first step on, and a row that a
            # later block hides whole adds exp(-1e30 - m) = 0
            s = jnp.where(seen, s, _NEG_INF)
            m_prev = m_s[h][:, :1]
            l_prev = l_s[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc[h] = acc[h] * alpha + jnp.dot(
                p.astype(cdt), kv[:, dn:], preferred_element_type=_F32)
            m_s[h] = jnp.broadcast_to(m_new, m_s.shape[1:])
            l_s[h] = jnp.broadcast_to(l_new, l_s.shape[1:])

        def heads(i, carry):
            for u in range(unroll):
                head(i * unroll + u)
            return carry
        jax.lax.fori_loop(0, group // unroll, heads, None)

    @pl.when(j == n_j - 1)
    def _finish():
        o_ref[...] = (acc[...] / l_s[...][:, :, :1]).astype(o_ref.dtype)


def latent_chunk_attention_raw(q_nope, q_rope, rows, start, w_kvb, kv_rank,
                               scale, block_=None, heads_per_step_=None):
    """The Pallas kernel path. q_nope [Q, nh, dn], q_rope [Q, nh, dr]: the
    chunk's queries, row i at position `start + i` (an int32 scalar, may
    be traced: no shape follows from it); rows [T, W]: the context's
    cached rows, position i at row i (the first `kv_rank` columns the
    latent, the next dr the rotary key); row i attends positions
    [0, start + i] of them; w_kvb [kv_rank, nh * (dn + dv)]. Returns
    [Q, nh, dv] in q_nope's dtype. `block_` and `heads_per_step_` override
    the derived context block and head group (tests and sweeps; nothing
    in the library passes them)."""
    with _x64_guard():
        return _latent_chunk_x32(q_nope, q_rope, rows, start, w_kvb,
                                 kv_rank, scale, block_, heads_per_step_)


def _latent_chunk_x32(q_nope, q_rope, rows, start, w_kvb, rkv, scale, block,
                      group):
    q_rows, heads, dn = q_nope.shape
    dr = q_rope.shape[-1]
    dv = w_kvb.shape[1] // heads - dn
    t, width = rows.shape
    dtype = q_nope.dtype
    block = block or context_block(q_rows, t)
    if not block or t % block:
        raise ValueError(f"context of {t} rows in no whole blocks "
                         f"({block or _CTX_BLOCKS})")
    size = (q_rows, block, dn, dr, dv, rkv, width, dtype.itemsize)
    group = group or heads_per_step(heads, *size)
    if not group or heads % group:
        raise ValueError(f"no head group of {heads} heads fits the kernel "
                         f"(Q {q_rows}, block {block})")
    kernel = functools.partial(_chunk_kernel, scale=scale, block=block,
                               rkv=rkv, dn=dn, dr=dr, ctx_rows=t)

    def of_group(width_):
        return pl.BlockSpec((group, q_rows, width_),
                            lambda g, j, start: (g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(heads // group, t // block),
        in_specs=[
            of_group(dn), of_group(dr),
            pl.BlockSpec((rkv, group * (dn + dv)),
                         lambda g, j, start: (0, g)),
            # dead steps name the last live block again: nothing is copied
            pl.BlockSpec((block, width), lambda g, j, start: (jnp.minimum(
                j, live_blocks(start[0], q_rows, block, t) - 1), 0)),
        ],
        out_specs=[of_group(dv)],
        scratch_shapes=[
            pltpu.VMEM((group, block, dn + dv), dtype),
            pltpu.VMEM((group, q_rows, dv), _F32),
            pltpu.VMEM((group, q_rows, 128), _F32),
            pltpu.VMEM((group, q_rows, 128), _F32),
        ],
    )
    vmem = min(_VMEM_CAP_BYTES, max(
        32 << 20, _group_bytes(group, *size) + 12 * q_rows * block * 4))
    out, = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((heads, q_rows, dv), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=_interpret(), name=NAME,
    )(jnp.maximum(jnp.asarray(start, jnp.int32), 0).reshape(1),
      q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2), w_kvb,
      rows.astype(dtype))
    return out.transpose(1, 0, 2)


# --------------------------------------------------------------- routing

def chunk_gate_reason(platform, dtype, q_rows, ctx_rows, heads, dn, dr, dv,
                      rkv, width):
    """Why a chunk's latent attention would take the composition and not
    this kernel — ONE definition, consulted by the router and by the
    engine's `attn_kernel_blocks` count. `q_rows` queries over a context
    of `ctx_rows` cached rows of `width` columns. Returns (reason,
    severity) as `pallas_decode.decode_gate_reason` does: a legitimate
    gate is a note, no reason is the should-have-routed warning."""
    if platform != "tpu":
        return ("not on TPU — the composition is the intended fallback "
                "path here"), "note"
    if dtype not in ("bfloat16", "float32"):
        return f"dtype {dtype} unsupported by the chunk kernel", "note"
    if dn % 128 or dv % 128 or rkv % 128:
        return (f"head widths {dn} (keys), {dv} (values) or the latent's "
                f"{rkv} not lane-aligned (128): a head's slice of Wkvb and "
                "of its expanded block would start inside a tile"), "note"
    if dr % 64 or rkv + dr > width:
        return (f"rotary key of {dr} columns at {rkv} of a row of {width}: "
                "the kernel slices it off the row's tile at a half-lane "
                "boundary (64)"), "note"
    block = context_block(q_rows, ctx_rows)
    if q_rows % 128 or not block:
        return (f"chunk of {q_rows} rows over a context of {ctx_rows}: the "
                f"score tile wants whole lanes (128) of queries and one of "
                f"{_CTX_BLOCKS} context positions a step, at most "
                f"{_MAX_TILE_ELEMS} scores"), "note"
    if not heads_per_step(heads, q_rows, block, dn, dr, dv, rkv, width,
                          jnp.dtype(dtype).itemsize):
        return (f"no group of the {heads} heads fits the kernel's VMEM "
                f"share at {q_rows} x {block}"), "note"
    return ("no gating reason — this chunk attention should have routed "
            "to the Pallas kernel"), "warning"


def use_latent_chunk_kernel(dtype, q_rows, ctx_rows, heads, dn, dr, dv, rkv,
                            width) -> bool:
    """True when a chunk's latent attention should ride the kernel here
    (arguments as `chunk_gate_reason`'s, the platform the default one)."""
    _, sev = chunk_gate_reason(jax.default_backend(), str(dtype), q_rows,
                               ctx_rows, heads, dn, dr, dv, rkv, width)
    return sev == "warning"
