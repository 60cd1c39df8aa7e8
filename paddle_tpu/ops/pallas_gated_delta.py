"""One decode token of the gated delta rule (Gated DeltaNet: Yang et al.,
arXiv:2412.06464) for a bucket of slots, the state of each slot advanced
IN PLACE in the pool of every slot's state.

    S'  = alpha S                                  S [dk, dv] a value head, float32
    u   = beta (v - S'^T k)
    S'' = S' + k u^T
    o   = S''^T q

The state is addressed by SLOT, not by page: `state [slots, H, dk, dv]`
holds one matrix a value head for every slot of the engine (the last one
is the trash slot that the bucket's rows without a request point at).
`slots [B]` says which slot row b advances; q and k come already
normalised, scaled and repeated to the value heads.

`gated_delta_decode_raw` is the Pallas kernel: grid (head group, row);
the slot ids and the per-head scalars (beta, alpha) are scalar-prefetch
operands in SMEM, the state block `[1, hg, dk, dv]` of slot `slots[b]`
is fetched by the grid's pipeline through the slot id and written back
to the same place (`input_output_aliases`): each slot's state is read
once and written once a step, and nothing else of the pool moves. Rows
that point at the trash slot sit at the bucket's end and share one block
index, so the pipeline fetches it once. Inside a step the heads are
unrolled and the arithmetic is the vector unit's: k and q are columns of
one `[dk, 2 hg]` operand (dk on the sublanes), so `S'^T k` and `S''^T q`
are sublane reductions and `k u^T` a broadcast product — no matmul with
one row. `gated_delta_decode_xla` is the same step as a jnp composition
(gather, step, scatter): the off-chip route and the kernel's oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._pallas_common import interpret as _interpret
from ._pallas_common import pltpu
from ._pallas_common import x64_guard as _x64_guard

_F32 = jnp.float32
#: bytes of state a grid step takes in (and as many out), double-buffered
#: each: 4 x 2 MiB of the 16 MiB scoped-VMEM default at dk = dv = 128
_STEP_BYTES = 2 << 20


def delta_step(s, q, k, v, beta, alpha):
    """One token of the rule for any leading batch: s [..., dk, dv], q, k
    [..., dk], v [..., dv], beta, alpha [...] -> (o [..., dv], s'), all
    float32 (what the kernel computes, in the kernel's order)."""
    s = s * alpha[..., None, None]
    kv = jnp.sum(s * k[..., :, None], axis=-2)
    u = beta[..., None] * (v - kv)
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


def head_group(heads: int, dk: int, dv: int) -> int:
    """Value heads a grid step takes: the most that divide `heads`, keep
    a step's state within `_STEP_BYTES` and fit k and q side by side in
    one 128-lane tile."""
    best = 1
    for hg in range(1, heads + 1):
        if heads % hg == 0 and hg * dk * dv * 4 <= _STEP_BYTES \
                and 2 * hg <= 128:
            best = hg
    return best


def gate_reason(state_shape, platform):
    """(reason, severity): why the router declines the kernel for a state
    pool of `state_shape` [slots, H, dk, dv] on `platform`, "warning" when
    nothing declines it (the kernel should run)."""
    _, _, dk, dv = state_shape
    if platform != "tpu":
        return ("not on TPU — the jnp composition is the intended route "
                "here"), "note"
    if dv % 128 or dk % 8:
        return (f"state [{dk}, {dv}] off the (8, 128) tile: a head's "
                "matrix would need repacking"), "note"
    return "no gating reason — the kernel should run", "warning"


def _kernel(slot_ref, beta_ref, alpha_ref, kq_ref, v_ref, s_ref, o_ref,
            s_out_ref, *, hg, heads):
    del slot_ref                 # read by the index maps alone
    g, b = pl.program_id(0), pl.program_id(1)
    base = b * heads + g * hg
    kq = kq_ref[0, 0]                                     # [dk, 2 hg]
    for h in range(hg):
        s = s_ref[0, h] * alpha_ref[base + h]             # [dk, dv]
        kc, qc = kq[:, h:h + 1], kq[:, hg + h:hg + h + 1]  # [dk, 1]
        kv = jnp.sum(s * kc, axis=0, keepdims=True)       # [1, dv]
        u = beta_ref[base + h] * (v_ref[0, h:h + 1, :] - kv)
        s = s + kc * u
        s_out_ref[0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(s * qc, axis=0, keepdims=True)


def gated_delta_decode_raw(state, slots, q, k, v, beta, alpha):
    """The Pallas kernel. state [N, H, dk, dv] float32 (donated: written
    in place); slots [B] int32; q, k [B, H, dk]; v [B, H, dv]; beta,
    alpha [B, H]. Returns (o [B, H, dv] float32, state)."""
    with _x64_guard():
        return _decode(state, slots, q, k, v, beta, alpha)


def _decode(state, slots, q, k, v, beta, alpha):
    _, heads, dk, dv = state.shape
    rows = slots.shape[0]
    hg = head_group(heads, dk, dv)
    groups = heads // hg
    # k and q of a head group side by side as columns: [B, G, dk, 2 hg]
    kq = jnp.concatenate([k.reshape(rows, groups, hg, dk),
                          q.reshape(rows, groups, hg, dk)], axis=2)
    kq = kq.astype(_F32).transpose(0, 1, 3, 2)
    kernel = functools.partial(_kernel, hg=hg, heads=heads)
    state_spec = pl.BlockSpec((1, hg, dk, dv),
                              lambda g, b, slot, *_: (slot[b], g, 0, 0))
    row_spec = pl.BlockSpec((1, hg, dv), lambda g, b, *_: (b, g, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(groups, rows),
        in_specs=[pl.BlockSpec((1, 1, dk, 2 * hg),
                               lambda g, b, *_: (b, g, 0, 0)),
                  row_spec, state_spec],
        out_specs=[row_spec, state_spec])
    o, state = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, heads, dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: slots, beta, alpha, kq, v, state -> the state is 5
        input_output_aliases={5: 1},
        interpret=_interpret(), name="gated_delta_decode",
    )(slots.astype(jnp.int32), beta.astype(_F32).reshape(-1),
      alpha.astype(_F32).reshape(-1), kq, v.astype(_F32), state)
    return o, state


def gated_delta_decode_xla(state, slots, q, k, v, beta, alpha):
    """The composition: gather the rows' states, `delta_step`, scatter
    them back (a trash slot named by several rows takes one of them)."""
    o, s = delta_step(state[slots], q.astype(_F32), k.astype(_F32),
                      v.astype(_F32), beta.astype(_F32),
                      alpha.astype(_F32))
    return o, state.at[slots].set(s)


def use_kernel(state_shape) -> bool:
    return gate_reason(state_shape, jax.default_backend())[1] == "warning"


def gated_delta_decode(state, slots, q, k, v, beta, alpha):
    """Routed: the kernel on the chip, the composition elsewhere (or
    where `gate_reason` names a reason). Same contract as `_raw`."""
    if use_kernel(state.shape):
        return gated_delta_decode_raw(state, slots, q, k, v, beta, alpha)
    return gated_delta_decode_xla(state, slots, q, k, v, beta, alpha)
