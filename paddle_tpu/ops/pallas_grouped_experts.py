"""Pallas TPU kernel for the experts one chip holds in a dropless expert
layer: ONE grouped matmul over the picks, expert by expert, that reads only
the experts some row picked and computes only the rows that picked each.

    out[t] = sum_e gate[t, e] * Wd_e(silu(Wg_e h_t) * (Wu_e h_t))   float32

`dropless_moe.local_experts`' contract (`gate` [T, n] the token's weight
for held expert e, 0 where it did not pick it). Its scan applies every
held expert to every row, so a call reads all n experts' weights and
computes n x T rows where the picks need about k x n / E of that.

TPU-native design:
  - The picks are the nonzero entries of `gate` in the rows that are
    `valid`. Read expert-major, `gate.T` [n, T], they are already sorted by
    expert, stably (a token's picks stay in expert order): a cumulative sum
    a row of it gives each pick its place in its expert's group, and no
    sort runs.
  - Each expert's group takes whole tiles of `tm` rows in a padded buffer,
    group after group; XLA gathers the picked rows into it. The tile ->
    expert table and the number of live tiles are scalar-prefetch
    operands.
  - Grid (tile, block of the FFN width). The weights' index maps read the
    tile's expert from that table, so an expert with no pick is never
    named and never fetched. Steps past the live tiles name the last live
    step's blocks again: the pipeline copies nothing and the body computes
    nothing.
  - A step: gate and up [tm, tf] in the weights' dtype with float32
    accumulation, the clamp and SiLU as `clamp_gate`/`clamp_up` do them, their
    product, and down accumulated over the FFN width in float32 VMEM; the
    last block writes the tile's rows rounded to the weights' dtype, as the
    scan's `@ Wd` rounds them.
  - XLA combines: each pick's row weighted by its float32 gate, summed
    over a token's picks in float32.
  - The body's size depends on no count of experts, rows or picks: the
    grid and the prefetched table carry them.

Same layering as pallas_gated_delta.py: `interpret` mode off-TPU (the
parity tests), routing by `use_grouped_experts` with the scan as the
everywhere-else path, the gate's reasons by name in `grouped_gate_reason`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._pallas_common import ceil_to as _ceil_to
from ._pallas_common import interpret as _interpret
from ._pallas_common import pltpu
from ._pallas_common import x64_guard as _x64_guard

NAME = "grouped_experts"
_F32 = jnp.float32
#: rows a tile takes once a call has more rows than that (a chunk); a
#: decode bucket's rows fit one tile, so each expert's picks take one
_CHUNK_TILE = 128
#: FFN columns a grid step takes, the largest that divides the width first
_FFN_BLOCKS = (512, 256, 128)
#: the three weight blocks of a step, double-buffered, may fill this
_WEIGHT_VMEM_BYTES = 32 << 20
#: the scoped VMEM the kernel may ask for (a v5e core has 128 MiB)
_VMEM_CAP_BYTES = 100 << 20


def clamp_gate(y, limit):
    """A SwiGLU's gate projection capped at `limit` (0: as it is)."""
    return jnp.minimum(y, limit) if limit else y


def clamp_up(y, limit):
    """A SwiGLU's up projection clipped to [-limit, limit] (0: as it is)."""
    return jnp.clip(y, -limit, limit) if limit else y


# ------------------------------------------------------------------ sizing

def tiles(rows, hidden, ffn, itemsize):
    """(tm, tf): rows a tile takes (a call's rows rounded up to the
    sublanes where they fit `_CHUNK_TILE`, else that) and FFN columns a
    step takes (0 where no block of `_FFN_BLOCKS` divides the width within
    `_WEIGHT_VMEM_BYTES`)."""
    sub = 32 // itemsize                    # 8 rows of float32, 16 of bf16
    tm = _ceil_to(rows, sub) if rows <= _CHUNK_TILE else _CHUNK_TILE
    for tf in _FFN_BLOCKS:
        if ffn % tf == 0 and 6 * hidden * tf * itemsize <= _WEIGHT_VMEM_BYTES:
            return tm, tf
    return tm, 0


def vmem_bytes(tm, tf, hidden, itemsize):
    """VMEM a step holds: the row tile in and out and the weight blocks
    (double-buffered), the float32 accumulator and the [tm, tf] products."""
    return (2 * (2 * tm * hidden + 3 * hidden * tf) * itemsize
            + tm * hidden * 4 + 4 * tm * tf * 4)


def num_tiles(rows, n, tm):
    """Tiles the padded buffer holds: every expert may take all `rows`."""
    return n * -(-rows // tm)


# ------------------------------------------------------------------ kernel

def _kernel(expert_ref, live_ref, x_ref, wg_ref, wu_ref, wd_ref, y_ref,
            acc_ref, *, limit):
    """One (tile, FFN block) step. x [tm, H] the tile's rows, wg/wu
    [H, tf] and wd [tf, H] its expert's blocks, y [tm, H] written by the
    tile's last step; acc [tm, H] float32 scratch."""
    del expert_ref                          # read by the index maps alone
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < live_ref[0])
    def _tile():
        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        dt = x.dtype
        g = jnp.dot(x, wg_ref[...], preferred_element_type=_F32).astype(dt)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=_F32).astype(dt)
        # the clamps are exact in float32 and the rounding is monotone: as
        # the scan's on values of the weights' dtype
        a = jax.nn.silu(
            clamp_gate(g.astype(_F32), limit).astype(dt).astype(_F32))
        s = (a.astype(dt).astype(_F32)
             * clamp_up(u.astype(_F32), limit).astype(dt).astype(_F32))
        acc_ref[...] += jnp.dot(s.astype(dt), wd_ref[...],
                                preferred_element_type=_F32)

        @pl.when(j == pl.num_programs(1) - 1)
        def _write():
            y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _grouped_matmul(expert, live, xs, w_gate, w_up, w_down, tm, tf, limit):
    """y [tiles x tm, H]: each live tile's rows through its expert's
    SwiGLU (rows of dead tiles are left unwritten)."""
    n_tiles = xs.shape[0] // tm
    _, hidden, ffn = w_gate.shape
    nf = ffn // tf

    def tile(i, live):          # past the live tiles: the last live one
        return jnp.maximum(jnp.minimum(i, live[0] - 1), 0)

    def block(i, j, live):      # ... at its last block
        return jnp.where(i < live[0], j, nf - 1)

    rows = pl.BlockSpec((tm, hidden), lambda i, j, ex, lv: (tile(i, lv), 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, nf),
        in_specs=[
            rows,
            pl.BlockSpec((None, hidden, tf), lambda i, j, ex, lv: (
                ex[tile(i, lv)], 0, block(i, j, lv))),
            pl.BlockSpec((None, hidden, tf), lambda i, j, ex, lv: (
                ex[tile(i, lv)], 0, block(i, j, lv))),
            pl.BlockSpec((None, tf, hidden), lambda i, j, ex, lv: (
                ex[tile(i, lv)], block(i, j, lv), 0)),
        ],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((tm, hidden), _F32)],
    )
    vmem = min(_VMEM_CAP_BYTES, max(32 << 20, vmem_bytes(
        tm, tf, hidden, xs.dtype.itemsize) + (8 << 20)))
    return pl.pallas_call(
        functools.partial(_kernel, limit=limit),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(xs.shape, xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=_interpret(), name=NAME,
    )(expert, live, xs, w_gate, w_up, w_down)


def grouped_experts(h, gate, w_gate, w_up, w_down, valid=None, limit=0.0):
    """The kernel path of `dropless_moe.local_experts`: float32 [T, H].
    h [T, H]; gate [T, n] float32 (0: not picked); weights stacked [n, H,
    F], [n, H, F], [n, F, H] in h's dtype; `valid` [T] bool: rows that are
    False pick nothing (their rows of the result are 0); `limit`: the
    SwiGLU clamp.

    The work runs through ONE module-level `jax.jit` function (the
    weights its arguments, the clamp its one static), so the expert
    layers of a program trace it once a process and lower it once a
    program: one private function, called a layer. Rows that fit one
    tile are padded to it first (padding rows pick nothing): every decode
    bucket up to a tile is the same trace."""
    t = h.shape[0]
    if valid is None:
        valid = jnp.ones((t,), bool)
    pad = ((0, max(tiles(t, h.shape[1], w_gate.shape[2],
                         h.dtype.itemsize)[0] - t, 0)),)
    h, gate = (jnp.pad(a, pad + ((0, 0),)) for a in (h, gate))
    valid = jnp.pad(valid, pad)
    return _grouped_call(h, gate, w_gate, w_up, w_down, valid,
                         limit=float(limit))[:t]


@functools.partial(jax.jit, static_argnames=("limit",))
def _grouped_call(h, gate, w_gate, w_up, w_down, valid, limit):
    with _x64_guard():
        return _grouped(h, gate, w_gate, w_up, w_down, valid, limit)


def _grouped(h, gate, w_gate, w_up, w_down, valid, limit):
    i32 = jnp.int32
    t, hidden = h.shape
    n, _, ffn = w_gate.shape
    tm, tf = tiles(t, hidden, ffn, h.dtype.itemsize)
    n_tiles = num_tiles(t, n, tm)
    size = n_tiles * tm
    picked = (gate > 0) & valid[:, None]
    by_expert = picked.T.astype(i32)                         # [n, T]
    counts = jnp.sum(by_expert, axis=1, dtype=i32)
    tiles_of = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_of, dtype=i32)
    # each pick's row in the padded buffer; `size` (out of range) if none
    slot = jnp.where(
        by_expert > 0,
        ((tile_end - tiles_of) * tm)[:, None]
        + jnp.cumsum(by_expert, axis=1, dtype=i32) - 1, size)
    token = jnp.broadcast_to(jnp.arange(t, dtype=i32), (n, t))
    src = jnp.full((size,), t, i32).at[slot.reshape(-1)].set(
        token.reshape(-1), mode="drop")
    xs = jnp.take(h, src, axis=0, mode="fill", fill_value=0)
    expert = jnp.minimum(jnp.sum(
        tile_end[None, :] <= jnp.arange(n_tiles, dtype=i32)[:, None],
        axis=1, dtype=i32), n - 1)
    y = _grouped_matmul(expert, tile_end[-1:], xs, w_gate, w_up, w_down,
                        tm, tf, limit)
    rows = jnp.take(y, slot.T, axis=0, mode="fill", fill_value=0)  # [T, n, H]
    return jnp.sum(gate[:, :, None] * rows.astype(_F32), axis=1)


# --------------------------------------------------------------- routing

def grouped_gate_reason(h, w_gate, platform=None):
    """Why a layer's held experts `w_gate` [n, H, F] over rows `h` [T, H]
    would take the scan and not this kernel. Returns (reason, severity)
    as `pallas_decode.decode_gate_reason` does: a legitimate gate is a
    note, no reason is the should-have-routed warning."""
    platform = platform or jax.default_backend()
    if platform != "tpu":
        return ("not on TPU — the scan is the intended fallback path "
                "here"), "note"
    if h.dtype != jnp.bfloat16 or w_gate.dtype != h.dtype:
        return (f"rows {h.dtype}, weights {w_gate.dtype}: the kernel takes "
                "bfloat16 rows and weights"), "note"
    _, hidden, ffn = w_gate.shape
    if hidden % 128 or ffn % 128:
        return (f"widths {hidden} (hidden) or {ffn} (FFN) off the 128 "
                "lanes"), "note"
    tm, tf = tiles(h.shape[0], hidden, ffn, h.dtype.itemsize)
    if not tf or vmem_bytes(tm, tf, hidden, 2) > _VMEM_CAP_BYTES:
        return (f"no FFN block of {_FFN_BLOCKS} keeps a step of "
                f"{tm} x {hidden} within VMEM"), "note"
    return ("no gating reason — these held experts should have routed to "
            "the grouped kernel"), "warning"


def use_grouped_experts(h, w_gate) -> bool:
    """True when a layer's held experts should ride the kernel here."""
    return grouped_gate_reason(h, w_gate)[1] == "warning"
