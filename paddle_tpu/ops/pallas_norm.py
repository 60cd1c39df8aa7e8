"""Pallas TPU fused elementwise/norm kernels — the bandwidth-bound chains.

Reference parity: the phi fusion library's hand-fused CUDA kernels for the
NON-attention chains (fused_rms_norm / fused_layer_norm /
fused_rotary_position_embedding / swiglu / fused_dropout_add,
/root/reference/paddle/phi/kernels/fusion/) — the Apex/Megatron-LM fused
kernel playbook applied to the bandwidth-bound part of a train step: every
byte the elementwise chains move between matmuls crosses HBM (819 GB/s on a
v5e, obs/peaks.py) and does no MXU work.

Kernel inventory (each: one HBM pass forward, one backward):

  rms_norm_fused / add_rms_norm_fused     y = w * rmsnorm(x [+ residual])
  layer_norm_fused / add_layer_norm_fused y = w * ln(x [+ residual]) + b
  rope_qk_fused                           rotary embedding on Q AND K in one
                                          kernel (no materialized rotated
                                          copies; bwd reuses the same rotation
                                          structure with the sign folded)
  swiglu_fused                            silu(gate) * up
  dropout_add_fused                       mask * x * (1/keep) + y

All kernels flatten leading dims to rows and tile (block_rows, 128k lanes);
inputs/outputs stay in the caller's dtype (bf16 on the flagship path) while
EVERY reduction/normalization accumulates in f32 inside VMEM — the
bf16-residual-stream policy (FLAGS_residual_dtype) relies on this: the
stream crosses HBM in bf16, f32 exists only inside kernels. The norm
backward saves only rstd (and mean for LN) per row and recomputes the
normalized activation in the backward kernel — no [rows, H] f32 residual.

Layering (same shape as pallas_attention.py):
  Pallas kernel on TPU when the tensor clears _MIN_ELEMS
  -> the existing XLA composition everywhere else (CPU tests, tiny shapes,
     and partition() steps over several devices: GSPMD cannot split a
     Mosaic kernel, _pallas_common.auto_partitioned).
nn/functional + incubate/nn/functional route through use_pallas(); tests
force the kernels on CPU via FORCE_PALLAS (interpreter mode).

Like pallas_attention.py: paddle_tpu enables jax x64 globally, so scalar
literals are explicitly np.float32 and real-TPU traces run with x64 OFF
(Mosaic cannot legalize stray i64/f64). Interpret-mode traces keep the
caller's x64 setting — toggling x64 inside an outer x64 jit breaks jnp
internal jitted helpers on CPU (the round-8 sdpa triage).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ._pallas_common import auto_partitioned as _auto_partitioned
from ._pallas_common import ceil_to as _ceil_to
from ._pallas_common import interpret as _interpret
from ._pallas_common import pltpu
from ._pallas_common import x64_guard as _x64_guard

#: most rows per grid step; block_rows() takes fewer where the width asks
DEFAULT_BLOCK_ROWS = 256
#: elementwise kernels additionally tile the lane axis
DEFAULT_BLOCK_COLS = 2048
#: Mosaic's scoped-VMEM limit on v5e is 16 MiB per kernel and the compiler
#: refuses a kernel over it (at 256 rows: add_rms_norm fwd 16.25 MiB at
#: H=4096, rms_norm bwd 18.25 MiB, swiglu bwd 18.96 MiB at 256x2048). Blocks
#: are sized to three quarters of it; tests/test_chip_compile.py holds the
#: LLaMA-7B widths to the chip's compiler.
VMEM_BUDGET = 12 << 20
#: f32 working copies of a block Mosaic keeps beside the streamed blocks
#: (fitted to the compiler's own reports above: 1.4-1.7 per kernel)
_F32_COPIES = 2

#: below this many elements the kernel launch overhead beats the bandwidth
#: saving (never calibrated on this installation — ROADMAP C.6)
_MIN_ELEMS = 1 << 18

#: tests set True to run the kernels in interpreter mode on CPU; None = auto
#: (TPU + size threshold), False = always the XLA composition
FORCE_PALLAS: bool | None = None


_SUPPORTED_DTYPES = ("float32", "bfloat16", "float16")


def use_pallas(x) -> bool:
    """Gate for the framework-level routing: Pallas on TPU above the size
    threshold, XLA composition everywhere else. `x` is a jax array (or
    anything with .shape/.dtype/.size)."""
    if FORCE_PALLAS is not None:
        return FORCE_PALLAS
    if _interpret():
        return False
    from ..core.flags import flag

    if not flag("FLAGS_pallas_fused_ops") or _auto_partitioned():
        return False
    try:
        size = int(np.prod(x.shape))
    except TypeError:  # dynamic dims: stay on the composition
        return False
    return size >= _MIN_ELEMS and str(x.dtype) in _SUPPORTED_DTYPES


def block_rows(rows: int, cols: int, n_streams: int, itemsize: int) -> int:
    """Rows per grid step for a kernel that streams `n_streams` [rows, cols]
    arrays of `itemsize` bytes: the largest power of two, at most
    DEFAULT_BLOCK_ROWS, whose double-buffered blocks plus f32 working
    copies fit VMEM_BUDGET. A power of two so that it divides the row
    counts models produce (batch x seq) and nothing is padded in HBM."""
    per_row = cols * (2 * n_streams * itemsize + 4 * _F32_COPIES)
    fit = max(8, min(DEFAULT_BLOCK_ROWS, VMEM_BUDGET // per_row))
    return min(1 << (fit.bit_length() - 1), _ceil_to(rows, 8))


def _rows_of(shape) -> int:
    r = 1
    for s in shape[:-1]:
        r *= int(s)
    return r


def _pad2(x2, rp, cp):
    r, c = x2.shape
    if r == rp and c == cp:
        return x2
    return jnp.pad(x2, ((0, rp - r), (0, cp - c)))


def _lanes8(vec, hp):
    """[H] param vector -> zero-padded, sublane-replicated [8, Hp] block
    (Mosaic wants (8, 128)-aligned last-two block dims)."""
    v = jnp.pad(vec, (0, hp - vec.shape[0]))
    return jnp.broadcast_to(v[None, :], (8, hp))


# ------------------------------------------------------------------- norms

def _norm_fwd_kernel(x_ref, *refs, eps, h, kind, has_res, has_w, has_b,
                     emit_sum):
    """One pass: read x (+residual), write normalized y (+the summed
    stream) + per-row stats. Padded lanes hold zeros on input and w/b, so
    the E[x^2]-mean^2 variance needs no lane masking; padded rows are
    sliced away by the caller."""
    it = iter(refs)
    res_ref = next(it) if has_res else None
    w_ref = next(it) if has_w else None
    b_ref = next(it) if has_b else None
    o_ref = next(it)
    s_ref = next(it) if emit_sum else None
    rstd_ref = next(it)
    mean_ref = next(it) if kind == "layer" else None

    xf = x_ref[...].astype(jnp.float32)                     # [br, Hp]
    if has_res:
        xf = xf + res_ref[...].astype(jnp.float32)
    if emit_sum:
        s_ref[...] = xf.astype(s_ref.dtype)
    inv_h = np.float32(1.0 / h)
    if kind == "layer":
        mean = jnp.sum(xf, axis=-1, keepdims=True) * inv_h   # [br, 1]
        var = jnp.maximum(
            jnp.sum(xf * xf, axis=-1, keepdims=True) * inv_h - mean * mean,
            np.float32(0.0))
        centered = xf - mean
    else:
        var = jnp.sum(xf * xf, axis=-1, keepdims=True) * inv_h
        centered = xf
    rstd = jax.lax.rsqrt(var + np.float32(eps))
    y = centered * rstd
    if has_w:
        y = y * w_ref[...][0:1, :]
    if has_b:
        y = y + b_ref[...][0:1, :]
    o_ref[...] = y.astype(o_ref.dtype)
    rstd_ref[...] = jnp.broadcast_to(rstd, rstd_ref.shape)
    if kind == "layer":
        mean_ref[...] = jnp.broadcast_to(mean, mean_ref.shape)


def _norm_bwd_kernel(s_ref, w_ref, rstd_ref, *refs, h, kind, has_w, emit_db):
    """Backward in one pass over the rows: recompute xhat = (s - mean)*rstd
    from the saved stats (the f32 normalized activation is never stored),
    produce dx and accumulate dw/db in VMEM scratch across the sequential
    row grid."""
    it = iter(refs)
    mean_ref = next(it) if kind == "layer" else None
    dy_ref = next(it)
    dx_ref = next(it)
    dw_ref = next(it)
    db_ref = next(it) if emit_db else None
    dw_acc = next(it)
    db_acc = next(it) if emit_db else None

    ri = pl.program_id(0)
    nr = pl.num_programs(0)

    @pl.when(ri == 0)
    def _init():
        dw_acc[...] = jnp.zeros_like(dw_acc)
        if emit_db:
            db_acc[...] = jnp.zeros_like(db_acc)

    xf = s_ref[...].astype(jnp.float32)                     # [br, Hp]
    rstd = rstd_ref[...][:, :1]                             # [br, 1]
    if kind == "layer":
        xhat = (xf - mean_ref[...][:, :1]) * rstd
    else:
        xhat = xf * rstd
    dyf = dy_ref[...].astype(jnp.float32)
    wdy = dyf * w_ref[...][0:1, :] if has_w else dyf
    inv_h = np.float32(1.0 / h)
    c2 = jnp.sum(wdy * xhat, axis=-1, keepdims=True) * inv_h
    if kind == "layer":
        c1 = jnp.sum(wdy, axis=-1, keepdims=True) * inv_h
        dx = rstd * (wdy - c1 - xhat * c2)
    else:
        dx = rstd * (wdy - xhat * c2)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dw_acc[...] = dw_acc[...] + jnp.broadcast_to(
        jnp.sum(dyf * xhat, axis=0, keepdims=True), dw_acc.shape)
    if emit_db:
        db_acc[...] = db_acc[...] + jnp.broadcast_to(
            jnp.sum(dyf, axis=0, keepdims=True), db_acc.shape)

    @pl.when(ri == nr - 1)
    def _finish():
        dw_ref[...] = dw_acc[...]
        if emit_db:
            db_ref[...] = db_acc[...]


def _norm_forward(x, res, w, b, eps, kind):
    """x [.., H] (+res same shape); w/b [H] or None. Returns
    (y, s_or_None, rstd [rows,1] f32, mean_or_None) with y/s in x.dtype."""
    with _x64_guard():
        h = int(x.shape[-1])
        rows = _rows_of(x.shape)
        x2 = x.reshape(rows, h)
        has_res, has_w, has_b = res is not None, w is not None, b is not None
        emit_sum = has_res
        hp = _ceil_to(h, 128)
        block_r = block_rows(rows, hp, 4 if has_res else 2,
                             x.dtype.itemsize)
        rp = _ceil_to(rows, block_r)
        nrb = rp // block_r

        args = [_pad2(x2, rp, hp)]
        row_spec = pl.BlockSpec((block_r, hp), lambda ri: (ri, 0))
        par_spec = pl.BlockSpec((8, hp), lambda ri: (0, 0))
        stat_spec = pl.BlockSpec((block_r, 128), lambda ri: (ri, 0))
        in_specs = [row_spec]
        if has_res:
            args.append(_pad2(res.reshape(rows, h), rp, hp))
            in_specs.append(row_spec)
        if has_w:
            args.append(_lanes8(w, hp))
            in_specs.append(par_spec)
        if has_b:
            args.append(_lanes8(b, hp))
            in_specs.append(par_spec)

        out_specs = [row_spec] + ([row_spec] if emit_sum else []) \
            + [stat_spec] + ([stat_spec] if kind == "layer" else [])
        out_shape = [jax.ShapeDtypeStruct((rp, hp), x.dtype)]
        if emit_sum:
            out_shape.append(jax.ShapeDtypeStruct((rp, hp), x.dtype))
        out_shape.append(jax.ShapeDtypeStruct((rp, 128), jnp.float32))
        if kind == "layer":
            out_shape.append(jax.ShapeDtypeStruct((rp, 128), jnp.float32))

        kernel = functools.partial(
            _norm_fwd_kernel, eps=float(eps), h=h, kind=kind,
            has_res=has_res, has_w=has_w, has_b=has_b, emit_sum=emit_sum)
        outs = pl.pallas_call(
            kernel, grid=(nrb,), in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, interpret=_interpret(),
            name=("add_" if has_res else "") + f"{kind}_norm_fwd")(*args)
        it = iter(outs)
        y = next(it)[:rows, :h].reshape(x.shape)
        s = next(it)[:rows, :h].reshape(x.shape) if emit_sum else None
        rstd = next(it)[:rows, :1]
        mean = next(it)[:rows, :1] if kind == "layer" else None
        return y, s, rstd, mean


def _norm_backward(s, w, rstd, mean, dy, kind, want_db):
    """dy [.., H] -> (dx [.., H], dw [H] f32, db [H] f32 or None). `s` is
    the PRE-norm activation (the saved input, or the summed stream for the
    add-fused variants)."""
    with _x64_guard():
        h = int(s.shape[-1])
        rows = _rows_of(s.shape)
        hp = _ceil_to(h, 128)
        block_r = block_rows(rows, hp, 3, s.dtype.itemsize)  # s, dy -> dx
        rp = _ceil_to(rows, block_r)
        nrb = rp // block_r
        has_w = w is not None

        row_spec = pl.BlockSpec((block_r, hp), lambda ri: (ri, 0))
        par_spec = pl.BlockSpec((8, hp), lambda ri: (0, 0))
        stat_spec = pl.BlockSpec((block_r, 128), lambda ri: (ri, 0))
        stat_pad = jnp.pad(jnp.broadcast_to(rstd, (rows, 128)),
                           ((0, rp - rows), (0, 0)))
        args = [_pad2(s.reshape(rows, h), rp, hp),
                _lanes8(w if has_w else jnp.ones((h,), s.dtype), hp),
                stat_pad]
        in_specs = [row_spec, par_spec, stat_spec]
        if kind == "layer":
            args.append(jnp.pad(jnp.broadcast_to(mean, (rows, 128)),
                                ((0, rp - rows), (0, 0))))
            in_specs.append(stat_spec)
        args.append(_pad2(dy.reshape(rows, h), rp, hp))
        in_specs.append(row_spec)

        out_specs = [row_spec, par_spec] + ([par_spec] if want_db else [])
        out_shape = [jax.ShapeDtypeStruct((rp, hp), s.dtype),
                     jax.ShapeDtypeStruct((8, hp), jnp.float32)]
        scratch = [pltpu.VMEM((8, hp), jnp.float32)]
        if want_db:
            out_shape.append(jax.ShapeDtypeStruct((8, hp), jnp.float32))
            scratch.append(pltpu.VMEM((8, hp), jnp.float32))

        kernel = functools.partial(
            _norm_bwd_kernel, h=h, kind=kind, has_w=has_w, emit_db=want_db)
        outs = pl.pallas_call(
            kernel, grid=(nrb,), in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            interpret=_interpret(), name=f"{kind}_norm_bwd")(*args)
        dx = outs[0][:rows, :h].reshape(s.shape)
        dw = outs[1][0, :h]
        db = outs[2][0, :h] if want_db else None
        return dx, dw, db


# rms ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm_fused(x, w, eps):
    y, _, _, _ = _norm_forward(x, None, w, None, eps, "rms")
    return y


# vjp-saves: x, w, rstd
def _rms_fwd(x, w, eps):
    y, _, rstd, _ = _norm_forward(x, None, w, None, eps, "rms")
    return y, (x, w, rstd)


def _rms_bwd(eps, resids, dy):
    x, w, rstd = resids
    dx, dw, _ = _norm_backward(x, w, rstd, None, dy, "rms", False)
    return dx, dw.astype(w.dtype)


rms_norm_fused.defvjp(_rms_fwd, _rms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def add_rms_norm_fused(x, res, w, eps):
    """(normed, summed): normed = w * rmsnorm(x + res); summed = x + res —
    the pre-norm residual-add fused INTO the norm kernel (the summed stream
    is this kernel's second output, so the residual chain costs one HBM
    round-trip instead of three)."""
    y, s, _, _ = _norm_forward(x, res, w, None, eps, "rms")
    return y, s


# vjp-saves: s, w, rstd
def _add_rms_fwd(x, res, w, eps):
    y, s, rstd, _ = _norm_forward(x, res, w, None, eps, "rms")
    return (y, s), (s, w, rstd)


def _add_rms_bwd(eps, resids, grads):
    s, w, rstd = resids
    dy, ds = grads
    dx, dw, _ = _norm_backward(s, w, rstd, None, dy, "rms", False)
    dsum = dx + ds
    return dsum, dsum, dw.astype(w.dtype)


add_rms_norm_fused.defvjp(_add_rms_fwd, _add_rms_bwd)


# layer norm ---------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm_fused(x, w, b, eps):
    y, _, _, _ = _norm_forward(x, None, w, b, eps, "layer")
    return y


# vjp-saves: x, w, rstd, mean
def _ln_fwd(x, w, b, eps):
    y, _, rstd, mean = _norm_forward(x, None, w, b, eps, "layer")
    return y, (x, w, rstd, mean)


def _ln_bwd(eps, resids, dy):
    x, w, rstd, mean = resids
    dx, dw, db = _norm_backward(x, w, rstd, mean, dy, "layer", True)
    return dx, dw.astype(w.dtype), db.astype(w.dtype)


layer_norm_fused.defvjp(_ln_fwd, _ln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def add_layer_norm_fused(x, res, w, b, eps):
    y, s, _, _ = _norm_forward(x, res, w, b, eps, "layer")
    return y, s


# vjp-saves: s, w, rstd, mean
def _add_ln_fwd(x, res, w, b, eps):
    y, s, rstd, mean = _norm_forward(x, res, w, b, eps, "layer")
    return (y, s), (s, w, rstd, mean)


def _add_ln_bwd(eps, resids, grads):
    s, w, rstd, mean = resids
    dy, ds = grads
    dx, dw, db = _norm_backward(s, w, rstd, mean, dy, "layer", True)
    dsum = dx + ds
    return dsum, dsum, dw.astype(w.dtype), db.astype(w.dtype)


add_layer_norm_fused.defvjp(_add_ln_fwd, _add_ln_bwd)


# ------------------------------------------------------------------ rotary

def _rope_kernel(q_ref, k_ref, c_ref, s_ref, qo_ref, ko_ref, *, dh, dp,
                 backward):
    """Neox-style rotation on Q and K in one pass. With swap(a) =
    concat(a2, a1) over the first 2*dh lanes and ss = concat(-sin, sin) the
    sign-folded sin table, forward is out = a*cos + swap(a)*ss and backward
    (cotangent g) is da = g*cos + swap(g*ss) — swap is its own transpose, so
    ONE kernel body serves both directions. swap is lane ROTATION (the XLU),
    never a lane slice: Mosaic refuses a slice or concat at dh lanes inside
    a 128-lane tile. Lanes beyond d come out as garbage in the backward and
    are sliced away by the caller."""
    c = c_ref[...].astype(jnp.float32)[:, None, :]           # [bs, 1, Dp]
    ss = s_ref[...].astype(jnp.float32)[:, None, :]

    def swap(a):
        up = pltpu.roll(a, dh, a.ndim - 1)                   # a[j - dh]
        if 2 * dh == dp:         # a full tile: rotating by half swaps halves
            return up
        lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, a.ndim - 1)
        return jnp.where(lane < dh, pltpu.roll(a, dp - dh, a.ndim - 1), up)

    for a_ref, o_ref in ((q_ref, qo_ref), (k_ref, ko_ref)):
        a = a_ref[0].astype(jnp.float32)                     # [bs, H, Dp]
        out = a * c + (swap(a * ss) if backward else swap(a) * ss)
        o_ref[0] = out.astype(o_ref.dtype)


def _rope_apply(q, k, cos2, sin2, backward):
    """q,k [B, S, H, D]; cos2/sin2 [S, D]. One pallas_call for both."""
    with _x64_guard():
        bsz, sq, heads, d = q.shape
        dh = d // 2
        dp = _ceil_to(d, 128)
        bs = block_rows(sq, heads * dp, 4, q.dtype.itemsize)  # q,k -> qo,ko
        sp = _ceil_to(sq, bs)
        ns = sp // bs
        pad4 = lambda a: jnp.pad(
            a, ((0, 0), (0, sp - sq), (0, 0), (0, dp - d)))
        pad2 = lambda a: jnp.pad(a, ((0, sp - sq), (0, dp - d)))
        sign = jnp.where(jnp.arange(d) < dh, -1, 1).astype(sin2.dtype)
        qk_spec = pl.BlockSpec((1, bs, heads, dp), lambda b, si: (b, si, 0, 0))
        cs_spec = pl.BlockSpec((bs, dp), lambda b, si: (si, 0))
        kernel = functools.partial(_rope_kernel, dh=dh, dp=dp,
                                   backward=backward)
        qo, ko = pl.pallas_call(
            kernel, grid=(bsz, ns),
            in_specs=[qk_spec, qk_spec, cs_spec, cs_spec],
            out_specs=[qk_spec, qk_spec],
            out_shape=[jax.ShapeDtypeStruct((bsz, sp, heads, dp), q.dtype),
                       jax.ShapeDtypeStruct((bsz, sp, heads, dp), k.dtype)],
            interpret=_interpret(),
            name="rope_qk_bwd" if backward else "rope_qk_fwd",
        )(pad4(q), pad4(k), pad2(cos2), pad2(sin2 * sign))
        return qo[:, :sq, :, :d], ko[:, :sq, :, :d]


def _tables2(cos, sq, d):
    """[1, S, 1, D] (or any broadcastable) rope table -> [S, D]."""
    c = jnp.reshape(cos, (-1, cos.shape[-1]))
    if c.shape[0] == 1 and sq > 1:
        c = jnp.broadcast_to(c, (sq, d))
    return c


@jax.custom_vjp
def rope_qk_fused(q, k, cos, sin):
    qo, ko = _rope_apply(q, k, _tables2(cos, q.shape[1], q.shape[3]),
                         _tables2(sin, q.shape[1], q.shape[3]), False)
    return qo, ko


# vjp-saves: c2, s2, cos, sin
def _rope_fwd(q, k, cos, sin):
    c2 = _tables2(cos, q.shape[1], q.shape[3])
    s2 = _tables2(sin, q.shape[1], q.shape[3])
    qo, ko = _rope_apply(q, k, c2, s2, False)
    return (qo, ko), (c2, s2, cos, sin)


def _rope_bwd(resids, grads):
    c2, s2, cos, sin = resids
    dqo, dko = grads
    dq, dk = _rope_apply(dqo, dko, c2, s2, True)
    # rope tables are non-trainable buffers; their cotangent is never
    # consumed — emit plain zeros instead of a [S, D] reduction
    return dq, dk, jnp.zeros_like(cos), jnp.zeros_like(sin)


rope_qk_fused.defvjp(_rope_fwd, _rope_bwd)


# ------------------------------------------------------------------ swiglu

def _ew_grid(x, n_streams):
    """(grid, spec, padded shape) for a 2-D elementwise kernel that streams
    `n_streams` arrays shaped like the flattened [rows, cols] view `x`."""
    rows, cols = x.shape
    bc = min(DEFAULT_BLOCK_COLS, _ceil_to(cols, 128))
    br = block_rows(rows, bc, n_streams, x.dtype.itemsize)
    rp, cp = _ceil_to(rows, br), _ceil_to(cols, bc)
    spec = pl.BlockSpec((br, bc), lambda ri, ci: (ri, ci))
    return (rp // br, cp // bc), spec, (rp, cp)


def _swiglu_fwd_kernel(g_ref, u_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    o_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)


def _swiglu_bwd_kernel(g_ref, u_ref, do_ref, dg_ref, du_ref):
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    sig = jax.nn.sigmoid(g)
    silu = g * sig
    dg_ref[...] = (do * u * (sig + silu * (np.float32(1.0) - sig))
                   ).astype(dg_ref.dtype)
    du_ref[...] = (do * silu).astype(du_ref.dtype)


@jax.custom_vjp
def swiglu_fused(gate, up):
    return _swiglu_call(gate, up, None)


def _swiglu_call(gate, up, do):
    with _x64_guard():
        shape = gate.shape
        cols = int(shape[-1])
        rows = _rows_of(shape)
        g2 = gate.reshape(rows, cols)
        u2 = up.reshape(rows, cols)
        grid, spec, (rp, cp) = _ew_grid(g2, 3 if do is None else 5)
        if do is None:
            out = pl.pallas_call(
                _swiglu_fwd_kernel, grid=grid, in_specs=[spec, spec],
                out_specs=[spec],
                out_shape=[jax.ShapeDtypeStruct((rp, cp), gate.dtype)],
                interpret=_interpret(), name="swiglu_fwd",
            )(_pad2(g2, rp, cp), _pad2(u2, rp, cp))
            return out[0][:rows, :cols].reshape(shape)
        dg, du = pl.pallas_call(
            _swiglu_bwd_kernel, grid=grid, in_specs=[spec, spec, spec],
            out_specs=[spec, spec],
            out_shape=[jax.ShapeDtypeStruct((rp, cp), gate.dtype),
                       jax.ShapeDtypeStruct((rp, cp), up.dtype)],
            interpret=_interpret(), name="swiglu_bwd",
        )(_pad2(g2, rp, cp), _pad2(u2, rp, cp),
          _pad2(do.reshape(rows, cols), rp, cp))
        return (dg[:rows, :cols].reshape(shape),
                du[:rows, :cols].reshape(shape))


# vjp-saves: gate, up
def _swiglu_vjp_fwd(gate, up):
    return _swiglu_call(gate, up, None), (gate, up)


def _swiglu_vjp_bwd(resids, do):
    gate, up = resids
    return _swiglu_call(gate, up, do)


swiglu_fused.defvjp(_swiglu_vjp_fwd, _swiglu_vjp_bwd)


# ------------------------------------------------------------ dropout + add

def _dropout_add_fwd_kernel(x_ref, y_ref, m_ref, o_ref, *, scale):
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    o_ref[...] = (x * m * np.float32(scale) + y).astype(o_ref.dtype)


def _dropout_add_bwd_kernel(g_ref, m_ref, dx_ref, *, scale):
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    dx_ref[...] = (g * m * np.float32(scale)).astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dropout_add_fused(x, y, mask, scale):
    """mask*x*scale + y in one pass. `mask` is a 0/1 array in x.dtype
    (generated by the caller — pltpu's in-kernel PRNG has no interpreter
    lowering on this jax, and the mask is what the backward needs anyway,
    exactly like the CUDA fused_dropout_add saves its mask tensor)."""
    with _x64_guard():
        shape = x.shape
        cols = int(shape[-1])
        rows = _rows_of(shape)
        grid, spec, (rp, cp) = _ew_grid(x.reshape(rows, cols), 4)
        out = pl.pallas_call(
            functools.partial(_dropout_add_fwd_kernel, scale=float(scale)),
            grid=grid, in_specs=[spec, spec, spec], out_specs=[spec],
            out_shape=[jax.ShapeDtypeStruct((rp, cp), x.dtype)],
            interpret=_interpret(), name="dropout_add_fwd",
        )(_pad2(x.reshape(rows, cols), rp, cp),
          _pad2(y.reshape(rows, cols), rp, cp),
          _pad2(mask.reshape(rows, cols), rp, cp))
        return out[0][:rows, :cols].reshape(shape)


# vjp-saves: mask
def _dropout_add_vjp_fwd(x, y, mask, scale):
    return dropout_add_fused(x, y, mask, scale), (mask,)


def _dropout_add_vjp_bwd(scale, resids, g):
    (mask,) = resids
    with _x64_guard():
        shape = g.shape
        cols = int(shape[-1])
        rows = _rows_of(shape)
        grid, spec, (rp, cp) = _ew_grid(g.reshape(rows, cols), 3)
        dx = pl.pallas_call(
            functools.partial(_dropout_add_bwd_kernel, scale=float(scale)),
            grid=grid, in_specs=[spec, spec], out_specs=[spec],
            out_shape=[jax.ShapeDtypeStruct((rp, cp), g.dtype)],
            interpret=_interpret(), name="dropout_add_bwd",
        )(_pad2(g.reshape(rows, cols), rp, cp),
          _pad2(mask.reshape(rows, cols), rp, cp))[0]
        return (dx[:rows, :cols].reshape(shape), g,
                jnp.zeros_like(mask))


dropout_add_fused.defvjp(_dropout_add_vjp_fwd, _dropout_add_vjp_bwd)


# ------------------------------------------------- raw convenience wrappers
#
# The wrappers make the fused paths DTYPE-PROMOTION-EQUIVALENT to the XLA
# compositions: mixed-dtype operands (bf16 stream + f32 params without
# amp) are harmonized with ordinary jnp casts OUTSIDE the custom_vjp, so
# the kernels see uniform dtypes, outputs promote like the composition
# would, and autodiff routes each cotangent back through the cast to its
# primal's dtype (the round-8 review-drive catch: a custom_vjp bwd that
# returns one dsum for differently-typed x/res inputs is a dtype error).

def _cast_to(a, dt):
    return a if a.dtype == dt else a.astype(dt)


def rms_norm_raw(x, w=None, eps=1e-6):
    if w is None:
        w = jnp.ones((x.shape[-1],), x.dtype)
    y = rms_norm_fused(x, w, float(eps))
    return _cast_to(y, jnp.result_type(x.dtype, w.dtype))


def add_rms_norm_raw(x, res, w=None, eps=1e-6):
    ct = jnp.result_type(x.dtype, res.dtype)
    x, res = _cast_to(x, ct), _cast_to(res, ct)
    if w is None:
        w = jnp.ones((x.shape[-1],), ct)
    y, s = add_rms_norm_fused(x, res, w, float(eps))
    return _cast_to(y, jnp.result_type(ct, w.dtype)), s


def layer_norm_raw(x, w=None, b=None, eps=1e-5):
    out_dt = jnp.result_type(x.dtype, *(p.dtype for p in (w, b)
                                        if p is not None))
    if w is None:
        w = jnp.ones((x.shape[-1],), x.dtype)
    if b is None:
        b = jnp.zeros((x.shape[-1],), x.dtype)
    return _cast_to(layer_norm_fused(x, w, b, float(eps)), out_dt)


def add_layer_norm_raw(x, res, w=None, b=None, eps=1e-5):
    ct = jnp.result_type(x.dtype, res.dtype)
    x, res = _cast_to(x, ct), _cast_to(res, ct)
    out_dt = jnp.result_type(ct, *(p.dtype for p in (w, b)
                                   if p is not None))
    if w is None:
        w = jnp.ones((x.shape[-1],), ct)
    if b is None:
        b = jnp.zeros((x.shape[-1],), ct)
    y, s = add_layer_norm_fused(x, res, w, b, float(eps))
    return _cast_to(y, out_dt), s


def rope_qk_raw(q, k, cos, sin):
    ct_q = jnp.result_type(q.dtype, cos.dtype, sin.dtype)
    ct_k = jnp.result_type(k.dtype, cos.dtype, sin.dtype)
    return rope_qk_fused(_cast_to(q, ct_q), _cast_to(k, ct_k), cos, sin)


def swiglu_raw(gate, up):
    ct = jnp.result_type(gate.dtype, up.dtype)
    return swiglu_fused(_cast_to(gate, ct), _cast_to(up, ct))


def dropout_add_raw(x, y, mask, scale):
    ct = jnp.result_type(x.dtype, y.dtype)
    return dropout_add_fused(_cast_to(x, ct), _cast_to(y, ct),
                             _cast_to(mask, ct), scale)
