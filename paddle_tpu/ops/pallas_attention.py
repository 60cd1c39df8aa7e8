"""Pallas TPU flash attention (fwd + bwd), the fusion-library equivalent.

Reference parity: paddle's flash attention surface
(python/paddle/nn/functional/flash_attention.py:358 `flash_attention`,
:1139 `scaled_dot_product_attention`) backed by the CUDA fusion library
(paddle/phi/kernels/fusion/gpu). Here the kernel is written directly for the
TPU memory hierarchy: Q/K/V tiles are streamed HBM->VMEM by the Pallas grid
pipeline, the online-softmax running state (m, l, acc) lives in VMEM scratch
that persists across the innermost (kv) grid steps, and every matmul hits the
MXU in f32 accumulation.

Layout convention at this level is [batch, heads, seq, head_dim]; the public
wrapper accepts paddle's [batch, seq, heads, head_dim] and transposes.

On non-TPU backends the same kernels run in Pallas interpreter mode, which is
how tests/test_pallas_attention.py checks numerics against the XLA softmax
composition on the CPU mesh.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# ONE copy of the platform/x64 rules shared with pallas_norm.py — the
# x64-toggle behavior is subtle (real-TPU-only; see _pallas_common)
from ._pallas_common import ceil_to as _ceil_to
from ._pallas_common import interpret as _interpret
from ._pallas_common import pltpu
from ._pallas_common import x64_guard as _x64_guard

# measured on v5e (b8 h16 s1024 d64): 128x128 blocks ran at 3.0 TFLOP/s —
# grid-overhead/VPU-bound; 512x1024 reached 5.9 before mask specialization
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
# paddle_tpu enables jax x64 globally, so bare python floats would trace as
# STRONG f64 constants inside the kernels — Mosaic cannot legalize the
# resulting f64->f32 truncf on real TPUs. Every scalar here must therefore
# be an explicitly-typed np.float32.
_NEG_INF = np.float32(-1e30)
_ZERO = np.float32(0.0)
_ONE = np.float32(1.0)


def _block_dispatch(compute, *, causal, qi, ki, nk, sq, sk,
                    block_q, block_k, force_masked=False):
    """Shared interior/boundary dispatch for the three flash kernels.

    compute(masked): masked=False runs the lean path (no iota/compare/
    where — most causal blocks sit strictly below the diagonal and need no
    masking; the VPU softmax chain is the kernel's cost). Blocks entirely
    above the diagonal are skipped. `qi`/`ki` are the q-block / kv-block
    program ids; causal visibility is `col <= row + (sk - sq)` (last q row
    aligned with last kv col). force_masked (varlen): the kv bound is a
    runtime value — every surviving block masks."""
    if force_masked:
        if causal:
            row1_off = qi * block_q + block_q - 1 + (sk - sq)

            @pl.when(ki * block_k <= row1_off)
            def _fm():
                compute(True)
        else:
            compute(True)
        return
    sk_aligned = (sk % block_k) == 0
    if causal:
        row0_off = qi * block_q + (sk - sq)
        row1_off = qi * block_q + block_q - 1 + (sk - sq)
        col0 = ki * block_k
        col1 = col0 + block_k - 1
        # interior: every column visible from every row AND fully in range
        interior = (col1 <= row0_off) & \
            ((col1 < sk) if not sk_aligned else (col0 >= 0))

        @pl.when(col0 <= row1_off)
        def _():  # not entirely above the diagonal
            @pl.when(interior)
            def _i():
                compute(False)

            @pl.when(~interior)
            def _b():
                compute(True)
    else:
        if sk_aligned:
            compute(False)
        else:
            @pl.when(ki < nk - 1)
            def _i():
                compute(False)

            @pl.when(ki == nk - 1)
            def _b():
                compute(True)


# ----------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, *refs,
                scale, causal, sq, sk, block_q, block_k, has_lens=False):
    # NOTE: program_id(2) is only materialized under `causal` — Mosaic on
    # real TPUs fails to legalize kernels carrying unused program-id-derived
    # values ('tpu.truncf'/'func.return'), so nothing dead may be traced.
    # has_lens (varlen): an extra [1,128] lens_ref input carries this
    # batch's kv length; every block takes the masked path with the dynamic
    # bound (the flash-varlen kernel the reference ships as a CUDA variant,
    # flash_attention.py:358).
    if has_lens:
        lens_ref, o_ref, lse_ref, acc, m_s, l_s = refs
    else:
        o_ref, lse_ref, acc, m_s, l_s = refs
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    # only bound under causal (used in mask + block-skip predicate): an
    # unused program_id value fails Mosaic legalization, and program_id
    # cannot be called inside a pl.when body in interpreter mode
    qi = pl.program_id(2) if causal else None

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def compute(masked):
        """masked=False → interior block: no iota/compare/where — the VPU
        cost of flash attention is the softmax chain, and on a causal
        S=1024 run ~80% of blocks need no masking at all (the FlashAttention
        block-specialization; the reference fusion library does the same on
        CUDA)."""
        q = q_ref[0, 0].astype(jnp.float32) * np.float32(scale)  # [bq, d]
        k = k_ref[0, 0]                                      # [bk, d]
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bq, bk]
        if masked:
            cols = ki * block_k + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if has_lens:
                mask = cols < lens_ref[0, 0, 0]
            else:
                mask = cols < sk
            if causal:
                # causal offset aligns the last q row with the last kv col
                rows = qi * block_q + \
                    jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                mask = mask & (cols <= rows + (sk - sq))
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_s[:, :1]                                  # [bq, 1]
        l_prev = l_s[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                               # [bq, bk]
        if masked:
            # a FULLY-masked row has m_new == -1e30, which cancels in
            # exp(s - m_new) → p = 1; zero it explicitly (empty rows must
            # produce l == 0 → output 0). Interior blocks can't be empty.
            p = jnp.where(mask, p, _ZERO)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0]                                      # [bk, d]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bq, d]
        acc[:] = acc[:] * alpha + pv
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    _block_dispatch(compute, causal=causal, qi=qi, ki=ki, nk=nk,
                    sq=sq, sk=sk, block_q=block_q, block_k=block_k,
                    force_masked=has_lens)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l == _ZERO, _ONE, l)
        o_ref[0, 0] = (acc[:] / safe_l).astype(o_ref.dtype)
        # lse is lane-replicated [bq, 128]: TPU block tiling requires the
        # last two block dims be (8k, 128)-aligned, so per-row stats ride a
        # full lane dim (the standard TPU flash-kernel layout)
        lse_ref[0, 0] = jnp.broadcast_to(
            m_s[:, :1] + jnp.log(safe_l), lse_ref[0, 0].shape)


def _lens_lanes(lens, b):
    """[B] int32 kv lengths -> [B, 8, 128] tile-replicated block input
    (Mosaic requires the last two block dims be (8, 128)-aligned)."""
    return jnp.broadcast_to(lens.astype(jnp.int32)[:, None, None],
                            (b, 8, 128))


def _flash_forward(q, k, v, causal, block_q, block_k, lens=None):
    """q,k,v: [B, H, S, D] (same H — GQA expanded by caller).

    Returns (o [B,H,S,D], lse_lanes [B,H,Sq_padded,1]) — per-row softmax
    stats (lane-replication for the TPU tiling happens inside the kernel
    and is sliced away here to keep residuals small). lens: optional [B]
    per-batch kv length (varlen)."""
    # paddle_tpu runs jax with x64 enabled; trace the pallas program with
    # x64 OFF so index-map/kernel literals stay i32/f32 (Mosaic cannot
    # legalize stray i64/f64 values on real TPUs)
    with _x64_guard():
        return _flash_forward_x32(q, k, v, causal, block_q, block_k, lens)


def _flash_forward_x32(q, k, v, causal, block_q, block_k, lens=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    sq_p = _ceil_to(sq, block_q)
    sk_p = _ceil_to(sk, block_k)
    d_p = _ceil_to(d, 128)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, d_p - d)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, d_p - d)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, d_p - d)))
    nq, nk = sq_p // block_q, sk_p // block_k
    has_lens = lens is not None

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, sq=sq, sk=sk,
        block_q=block_q, block_k=block_k, has_lens=has_lens)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d_p), lambda b, h, qi, ki: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, d_p), lambda b, h, qi, ki: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_k, d_p), lambda b, h, qi, ki: (b, h, ki, 0)),
    ]
    args = [qp, kp, vp]
    if has_lens:
        in_specs.append(
            pl.BlockSpec((1, 8, 128), lambda b, h, qi, ki: (b, 0, 0)))
        args.append(_lens_lanes(lens, b))
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d_p), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 128), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, d_p), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_p, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d_p), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_interpret(), name="flash_fwd",
    )(*args)
    # keep one lane in the residuals (128x smaller); backward re-broadcasts
    return o[:, :, :sq, :d], lse[:, :, :, :1]


# ----------------------------------------------------------------- backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                   scale, causal, sq, sk, block_q, block_k, has_lens=False):
    if has_lens:
        lens_ref, dq_ref, dq_acc = refs
    else:
        dq_ref, dq_acc = refs
    # like _fwd_kernel: nothing dead may be traced (Mosaic legalization)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2) if causal else None

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute(masked):
        q = q_ref[0, 0].astype(jnp.float32) * np.float32(scale)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        lse = lse_ref[0, 0][:, :1]                            # [bq, 1] of lanes
        if masked:
            cols = ki * block_k + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = (cols < lens_ref[0, 0, 0]) if has_lens else (cols < sk)
            if causal:
                rows = qi * block_q + \
                    jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                mask = mask & (cols <= rows + (sk - sq))
        p = jnp.exp(s - lse)                                  # [bq, bk]
        if masked:
            # empty rows have lse == -1e30 (cancels the mask value): zero p
            p = jnp.where(mask, p, _ZERO)
        do = do_ref[0, 0].astype(jnp.float32)                 # [bq, d]
        v = v_ref[0, 0].astype(jnp.float32)                   # [bk, d]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[0, 0][:, :1]
        ds = p * (dp - delta) * np.float32(scale)             # [bq, bk]
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _block_dispatch(compute, causal=causal, qi=qi, ki=ki, nk=nk,
                    sq=sq, sk=sk, block_q=block_q, block_k=block_k,
                    force_masked=has_lens)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                    scale, causal, sq, sk, block_q, block_k, has_lens=False):
    if has_lens:
        lens_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    # grid here is (b, h, ki, qi): kv blocks outer, q blocks inner
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    k_start = ki * block_k
    nk = pl.num_programs(2)

    def compute(masked):
        q = q_ref[0, 0].astype(jnp.float32) * np.float32(scale)  # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                   # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        if masked:
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = (cols < lens_ref[0, 0, 0]) if has_lens else (cols < sk)
            if causal:
                rows = qi * block_q + \
                    jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                mask = mask & (cols <= rows + (sk - sq))
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)                                  # [bq, bk]
        if masked:
            # empty q rows have lse == -1e30 (cancels the mask value): p
            # must be zeroed or they pollute dk/dv accumulations
            p = jnp.where(mask, p, _ZERO)
        do = do_ref[0, 0].astype(jnp.float32)                 # [bq, d]
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[0, 0][:, :1]
        # `q` here is pre-scaled by 1/sqrt(d), which is exactly dk's scale
        # factor — so ds must NOT be scaled again
        ds = p * (dp - delta)                                 # [bq, bk]
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _block_dispatch(compute, causal=causal, qi=qi, ki=ki, nk=nk,
                    sq=sq, sk=sk, block_q=block_q, block_k=block_k,
                    force_masked=has_lens)

    @pl.when(qi == nq - 1)
    def _finish():
        # dk picked up the q-side 1/sqrt(d) scale through `q`; already applied
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse_lanes, do, causal, block_q, block_k,
                    lens=None):
    with _x64_guard():  # see _flash_forward
        return _flash_backward_x32(q, k, v, o, lse_lanes, do, causal,
                                   block_q, block_k, lens)


def _flash_backward_x32(q, k, v, o, lse_lanes, do, causal, block_q, block_k,
                        lens=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    sq_p = _ceil_to(sq, block_q)
    sk_p = _ceil_to(sk, block_k)
    d_p = _ceil_to(d, 128)
    pad4 = lambda x, s: jnp.pad(x, ((0, 0), (0, 0), (0, s - x.shape[2]), (0, d_p - d)))
    qp, kp, vp = pad4(q, sq_p), pad4(k, sk_p), pad4(v, sk_p)
    dop = pad4(do, sq_p)
    lsep = jnp.broadcast_to(lse_lanes, (b, h, lse_lanes.shape[2], 128))
    deltap = jnp.broadcast_to(
        jnp.pad(delta, ((0, 0), (0, 0), (0, sq_p - sq)))[..., None],
        (b, h, sq_p, 128))
    nq, nk = sq_p // block_q, sk_p // block_k

    has_lens = lens is not None
    common = dict(scale=scale, causal=causal, sq=sq, sk=sk,
                  block_q=block_q, block_k=block_k, has_lens=has_lens)
    q_spec = pl.BlockSpec((1, 1, block_q, d_p), lambda b, h, qi, ki: (b, h, qi, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, d_p), lambda b, h, qi, ki: (b, h, ki, 0))
    r_spec = pl.BlockSpec((1, 1, block_q, 128), lambda b, h, qi, ki: (b, h, qi, 0))
    lens_spec = pl.BlockSpec((1, 8, 128), lambda b, h, qi, ki: (b, 0, 0))
    extra = [_lens_lanes(lens, b)] if has_lens else []

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec]
        + ([lens_spec] if has_lens else []),
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq_p, d_p), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d_p), jnp.float32)],
        interpret=_interpret(), name="flash_bwd_dq",
    )(qp, kp, vp, dop, lsep, deltap, *extra)[0]

    # dkv kernel: kv blocks outer, q blocks inner
    q_spec2 = pl.BlockSpec((1, 1, block_q, d_p), lambda b, h, ki, qi: (b, h, qi, 0))
    k_spec2 = pl.BlockSpec((1, 1, block_k, d_p), lambda b, h, ki, qi: (b, h, ki, 0))
    r_spec2 = pl.BlockSpec((1, 1, block_q, 128), lambda b, h, ki, qi: (b, h, qi, 0))
    lens_spec2 = pl.BlockSpec((1, 8, 128), lambda b, h, ki, qi: (b, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(b, h, nk, nq),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2]
        + ([lens_spec2] if has_lens else []),
        out_specs=[k_spec2, k_spec2],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk_p, d_p), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk_p, d_p), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d_p), jnp.float32),
                        pltpu.VMEM((block_k, d_p), jnp.float32)],
        interpret=_interpret(), name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lsep, deltap, *extra)
    return (dq[:, :, :sq, :d], dk[:, :, :sk, :d], dv[:, :, :sk, :d])


# ----------------------------------------------------------- differentiable op

#: residual names consulted by the attention-resident remat policy
#: (fleet recompute(policy="flash_resident")): under
#: jax.checkpoint(save_only_these_names(*FLASH_RESIDUAL_NAMES)) the flash
#: outputs + softmax stats are SAVED across fwd/bwd, so the rematerialized
#: backward never re-runs the forward flash kernel — only the cheap
#: surrounding GEMM/pointwise chains are recomputed (q/k/v regenerate from
#: the qkv projections). Outside a checkpoint context checkpoint_name is
#: the identity, so naming costs nothing on the normal path.
FLASH_RESIDUAL_NAMES = ("flash_attn_out", "flash_attn_lse")


def _name_flash_residuals(o, lse):
    from jax.ad_checkpoint import checkpoint_name

    return (checkpoint_name(o, FLASH_RESIDUAL_NAMES[0]),
            checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, bwd_block_q=None,
           bwd_block_k=None):
    # bwd_block_q/bwd_block_k: block sizes for the dq/dkv kernels — the
    # backward's best block shape differs from the forward's at long
    # sequence (round-6 autotune), defaulting to the forward's choice
    o, _ = _flash_forward(q, k, v, causal, block_q, block_k)
    return o


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, bwd_block_q=None,
                    bwd_block_k=None):
    o, lse = _flash_forward(q, k, v, causal, block_q, block_k)
    o, lse = _name_flash_residuals(o, lse)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, block_q, block_k, bwd_block_q, bwd_block_k,
                    res, g):
    q, k, v, o, lse = res
    return _flash_backward(q, k, v, o, lse, g, causal,
                           bwd_block_q or block_q, bwd_block_k or block_k)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_varlen(q, k, v, lens, causal, block_q, block_k):
    o, _ = _flash_forward(q, k, v, causal, block_q, block_k, lens=lens)
    return o


def _flash_varlen_fwd(q, k, v, lens, causal, block_q, block_k):
    o, lse = _flash_forward(q, k, v, causal, block_q, block_k, lens=lens)
    o, lse = _name_flash_residuals(o, lse)
    return o, (q, k, v, o, lse, lens)


def _flash_varlen_bwd(causal, block_q, block_k, res, g):
    q, k, v, o, lse, lens = res
    dq, dk, dv = _flash_backward(q, k, v, o, lse, g, causal, block_q,
                                 block_k, lens=lens)
    return dq, dk, dv, jnp.zeros(lens.shape, jax.dtypes.float0)


_flash_varlen.defvjp(_flash_varlen_fwd, _flash_varlen_bwd)


_TUNE_CACHE: dict = {}
#: candidate (block_q, block_k) pairs, ordered by prior; the autotuner
#: measures each on the first sighting of a shape family and pins the best
#: (≙ reference conv/attention runtime autotuning,
#: /root/reference/paddle/phi/kernels/autotune/auto_tune_base.h)
_TUNE_CANDIDATES = ((512, 1024), (256, 1024), (512, 512), (1024, 1024),
                    (256, 512))
#: long-sequence candidates (sq or sk >= 4096): the 512x1024 default was
#: tuned at s1024 and is wrong at s4096/s8192 — longer kv blocks amortize
#: the per-grid-step overhead over the much larger kv axis, and the probe
#: machinery discards anything that overflows VMEM on this chip
_TUNE_CANDIDATES_LONG = ((512, 1024), (1024, 1024), (512, 2048),
                         (1024, 2048), (256, 2048), (2048, 1024),
                         (512, 512))
#: ceiling accepted from the DISK cache: a poisoned/corrupt entry may not
#: force Mosaic failures (ADVICE round 5) — anything outside
#: [128, _TUNE_BLOCK_MAX] multiples of 128 is dropped on load
_TUNE_BLOCK_MAX = 4096


def _tune_candidates(sq, sk):
    return _TUNE_CANDIDATES_LONG if max(sq, sk) >= 4096 else _TUNE_CANDIDATES


def _valid_blocks(vv):
    """True iff vv is a loadable tune-cache value: a (block_q, block_k) or
    (fwd_q, fwd_k, bwd_q, bwd_k) sequence of positive multiples of 128 no
    larger than _TUNE_BLOCK_MAX (the validated shape of every candidate the
    tuner itself can emit)."""
    if not isinstance(vv, (list, tuple)) or len(vv) not in (2, 4):
        return False
    return all(isinstance(x, int) and not isinstance(x, bool)
               and 0 < x <= _TUNE_BLOCK_MAX and x % 128 == 0 for x in vv)


def _norm4(hit):
    """Normalize a tune-cache value to the 4-tuple (fwd_q, fwd_k, bwd_q,
    bwd_k) contract — legacy 2-element entries reuse the fwd pair for the
    backward. None passes through (caller falls back to defaults)."""
    if hit is None:
        return None
    return tuple(hit) if len(hit) == 4 else (*hit, *hit)


#: probe failures that mean "this candidate doesn't compile/fit here"
#: (Mosaic lowering rejections, VMEM overflow) — anything else propagates
_PROBE_ERRORS = (ValueError, NotImplementedError, jax.errors.JaxRuntimeError)


def _tune_cache_path():
    """Disk location of the tune cache: PADDLE_TPU_TUNE_CACHE_DIR, else
    beside the compile cache (core/compile_cache.py: where
    JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache) — the two
    are made on the same machine for the same chip and travel together.
    Never a world-writable temp directory: a poisoned entry must not be
    able to pin bad block shapes (ADVICE round 5)."""
    import os

    from ..core.compile_cache import cache_dir

    base = os.environ.get("PADDLE_TPU_TUNE_CACHE_DIR") or cache_dir()
    return os.path.join(base, "flash_tune_cache_v2.json")


_TUNE_DISK_LOADED = False


def _parse_tune_entries(payload):
    """{key-string: blocks} pairs -> validated {key-tuple: blocks-tuple}.
    Keys are 'kind|sq|sk|d|dtype|causal'; values must pass _valid_blocks
    (positive multiples of 128) — anything else is dropped, never raised:
    a poisoned disk entry costs at most a re-tune."""
    out = {}
    if not isinstance(payload, dict):
        return out
    for ks, vv in payload.items():
        try:
            kind, sq, sk, d, dt, causal = ks.split("|")
            key = (kind, int(sq), int(sk), int(d), dt, causal == "True")
        except (ValueError, AttributeError):
            continue
        if _valid_blocks(vv):
            out[key] = tuple(vv)
    return out


def _tune_cache_load():
    global _TUNE_DISK_LOADED
    if _TUNE_DISK_LOADED:
        return
    _TUNE_DISK_LOADED = True
    import json

    try:
        with open(_tune_cache_path()) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return  # missing or corrupt/concurrent write: re-tune
    for key, vv in _parse_tune_entries(payload).items():
        _TUNE_CACHE.setdefault(key, vv)


def _tune_cache_store():
    import json
    import os
    import tempfile

    path = _tune_cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # merge-on-store: re-load and union so concurrent tuners working on
        # different shape families stop dropping each other's entries
        # (ADVICE round 5); in-process results win on conflict
        merged = {}
        try:
            with open(path) as f:
                merged.update(_parse_tune_entries(json.load(f)))
        except (OSError, ValueError):
            pass
        merged.update(_TUNE_CACHE)
        payload = {"|".join(map(str, k)): list(v) for k, v in merged.items()}
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path))
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)  # atomic vs concurrent processes
    except OSError:  # read-only fs: cache stays per-process
        pass


def _probe_time(fn, *args):
    """Median-of-groups timing of a compiled probe. Returns inf when the
    candidate doesn't compile/fit (Mosaic rejection, VMEM overflow)."""
    import statistics
    import time as _time

    try:
        jax.block_until_ready(fn(*args))  # compile + warm
        groups = []
        for _ in range(3):
            t0 = _time.perf_counter()
            for _ in range(2):
                out = fn(*args)
            jax.block_until_ready(out)
            groups.append(_time.perf_counter() - t0)
        return statistics.median(groups)
    except _PROBE_ERRORS:
        return float("inf")


def _rank_candidates(sq, sk, probe):
    """Measure every (clamped, deduped) candidate pair with `probe(bq, bk)`
    and return the fastest. The default pair is among the candidates, so
    "none compiled" means the kernel cannot run at this shape: an error,
    never a quiet return to untested defaults."""
    cands = _tune_candidates(sq, sk)
    seen = set()
    best, best_t = None, float("inf")
    for bq_c, bk_c in cands:
        bq = min(bq_c, _ceil_to(sq, 128))
        bk = min(bk_c, _ceil_to(sk, 128))
        if (bq, bk) in seen:
            continue  # clamping collapsed this candidate into an earlier one
        seen.add((bq, bk))
        dt = probe(bq, bk)
        if dt < best_t:
            best, best_t = (bq, bk), dt
    if best is None:
        raise RuntimeError(
            f"flash autotune: none of the block candidates {sorted(seen)} "
            f"compiled for sq={sq} sk={sk} on this device")
    return best


def _autotune_blocks(q, k, v, causal):
    """Pick (fwd_block_q, fwd_block_k, bwd_block_q, bwd_block_k) for this
    (sq, sk, d, dtype, causal) family. Off the TPU (interpret mode) or when
    FLAGS_flash_autotune is off, the measured v5e default is used.

    Round-6 shape: candidates are SEQ-LENGTH-KEYED (the 512x1024 default
    was tuned at s1024 and loses at s4096/s8192 where longer kv blocks
    amortize grid overhead), and with FLAGS_flash_tune_bwd_split the
    backward dq/dkv kernels are tuned separately — stage 1 ranks
    forward-only probes, stage 2 ranks fwd+bwd probes with the forward
    pinned to its winner (the bwd kernels' arithmetic-intensity profile
    differs: 5 matmuls per block pair vs the forward's 2). Winners are
    cached in-process AND on disk beside the compile cache."""
    from ..core.flags import flag

    sq, sk, d = q.shape[2], k.shape[2], q.shape[3]
    key = ("flash", sq, sk, d, str(q.dtype), causal)
    hit = _norm4(_TUNE_CACHE.get(key))
    if hit is not None:
        return hit
    if _interpret() or isinstance(q, jax.core.Tracer) \
            or not flag("FLAGS_flash_autotune"):
        return (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    _tune_cache_load()
    hit = _norm4(_TUNE_CACHE.get(key))
    if hit is not None:
        return hit

    def probe_fwd(bq, bk):
        fn = jax.jit(lambda a, b, c2: _flash(a, b, c2, causal, bq, bk))
        return _probe_time(fn, q, k, v)

    fwd = _rank_candidates(sq, sk, probe_fwd)
    bwd = fwd
    if flag("FLAGS_flash_tune_bwd_split"):
        def probe_bwd(bq, bk):
            fn = jax.jit(lambda a, b, c2: jax.grad(
                lambda aa: jnp.sum(
                    _flash(aa, b, c2, causal, fwd[0], fwd[1], bq, bk)
                    .astype(jnp.float32)))(a))
            return _probe_time(fn, q, k, v)

        bwd = _rank_candidates(sq, sk, probe_bwd)
    best = (*fwd, *bwd)
    _TUNE_CACHE[key] = best
    _tune_cache_store()
    return best


def flash_attention_raw(q, k, v, causal=False,
                        block_q=None, block_k=None):
    """jax-level flash attention on [B, H, S, D] arrays (GQA expanded here).
    block_q/block_k default to the per-shape autotuned choice — the
    autotuner keys candidates by sequence length and tunes the backward
    dq/dkv block pair separately from the forward's (explicit block_q/
    block_k pin BOTH directions, the pre-round-6 behavior)."""
    hq, hk = q.shape[1], k.shape[1]
    if hq != hk:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    cap_q = _ceil_to(q.shape[2], 128)
    cap_k = _ceil_to(k.shape[2], 128)
    if block_q is None or block_k is None:
        tq, tk, tbq, tbk = _autotune_blocks(q, k, v, causal)
        return _flash(q, k, v, causal,
                      min(block_q or tq, cap_q), min(block_k or tk, cap_k),
                      min(block_q or tbq, cap_q), min(block_k or tbk, cap_k))
    bq = min(block_q, cap_q)
    bk = min(block_k, cap_k)
    return _flash(q, k, v, causal, bq, bk)


def flash_attention_varlen_raw(q, k, v, kv_lens, causal=False,
                               block_q=DEFAULT_BLOCK_Q,
                               block_k=DEFAULT_BLOCK_K):
    """Varlen flash: [B, H, S, D] padded batch + [B] int32 kv lengths —
    key columns >= kv_lens[b] are masked INSIDE the kernel (the flash-varlen
    path the reference ships as a CUDA variant, flash_attention.py:358).
    Query rows beyond a sequence's length produce zeros; callers drop them.
    """
    hq, hk = q.shape[1], k.shape[1]
    if hq != hk:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    bq = min(block_q, _ceil_to(q.shape[2], 128))
    bk = min(block_k, _ceil_to(k.shape[2], 128))
    return _flash_varlen(q, k, v, jnp.asarray(kv_lens, jnp.int32), causal,
                         bq, bk)


def ensure_tuned(b, h, sq, sk, d, dtype, causal):
    """Eagerly autotune the block choice for a shape family using synthetic
    operands. Called from framework code BEFORE entering any trace (jit
    traces can only consult the cache); a no-op off-TPU, on repeat shapes,
    or with FLAGS_flash_autotune off."""
    from ..core.flags import flag

    key = ("flash", sq, sk, d, str(jnp.dtype(dtype)), causal)
    if key in _TUNE_CACHE or _interpret() or not flag("FLAGS_flash_autotune"):
        hit = _norm4(_TUNE_CACHE.get(key))
        if hit is not None:
            return hit
        return (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    kk = jax.random.PRNGKey(0)
    # one head is enough to rank block choices; keeps probe cost tiny
    q = jax.random.normal(kk, (1, 1, sq, d), jnp.dtype(dtype))
    k = jax.random.normal(kk, (1, 1, sk, d), jnp.dtype(dtype))
    v = jax.random.normal(kk, (1, 1, sk, d), jnp.dtype(dtype))
    return _autotune_blocks(q, k, v, causal)


def flash_attention_op(query, key, value, is_causal=False):
    """Framework-level op on paddle-layout [B, S, H, D] Tensors; tape-recorded."""
    from ..core.dispatch import op_call

    qd = query._data if hasattr(query, "_data") else query
    if not isinstance(qd, jax.core.Tracer) and not _interpret():
        kd = key._data if hasattr(key, "_data") else key
        ensure_tuned(int(qd.shape[0]), int(qd.shape[2]), int(qd.shape[1]),
                     int(kd.shape[1]), int(qd.shape[3]), qd.dtype, is_causal)

    def f(q, k, v):
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        out = flash_attention_raw(qt, kt, vt, causal=is_causal)
        return jnp.swapaxes(out, 1, 2)

    return op_call(f, query, key, value, name="flash_attention", n_diff=3)


# ------------------------------------------------- flashmask (block-sparse)

def _fm_block_dispatch(compute, *, causal, row0, row1, col0, col1,
                       smin, smax, sq, sk, block_k):
    """Shared fwd/dq/dkv FlashMask block dispatch: skip kv blocks whose
    max start row precedes the q block entirely; run the lean no-mask path
    when the whole block is visible (its LAST row precedes every start);
    only straddling blocks pay the iota/where chain. ONE definition so the
    forward's visibility can never desynchronize from the backward's."""
    run = row0 < smax
    if causal:
        run = run & (col0 <= row1 + (sk - sq))
    sk_aligned = (sk % block_k) == 0
    interior = (row1 < smin) & ((col1 < sk) if not sk_aligned else
                                (col0 >= 0))
    if causal:
        interior = interior & (col1 <= row0 + (sk - sq))

    @pl.when(run)
    def _run():
        @pl.when(interior)
        def _i():
            compute(False)

        @pl.when(~interior)
        def _b():
            compute(True)


def _fm_mask(start_ref, shape, row0, col0, causal, sq, sk):
    """Per-element FlashMask visibility for a straddling block: key column
    j visible to query row i iff i < start[j] (and in range / causal)."""
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    starts = start_ref[0, 0, 0:1, :]
    mask = (cols < sk) & (rows < starts)
    if causal:
        mask = mask & (cols <= rows + (sk - sq))
    return mask


def _fm_fwd_kernel(q_ref, k_ref, v_ref, start_ref, smin_ref, smax_ref,
                   o_ref, lse_ref, acc, m_s, l_s, *,
                   scale, causal, sq, sk, block_q, block_k):
    """FlashMask forward: per-COLUMN start rows (causal LTS form — key col
    j is blocked for query rows i >= start[j]) consulted at BLOCK
    granularity: kv blocks whose max start row is <= the block's first
    query row are skipped outright (no MXU work, the splash/FlashMask
    block-skip idea); blocks fully visible take the lean no-mask path;
    only straddling blocks pay the iota/where chain."""
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    row0 = qi * block_q
    row1 = row0 + block_q - 1
    col0 = ki * block_k
    col1 = col0 + block_k - 1
    smax = smax_ref[0, 0, 0, 0, 0]
    smin = smin_ref[0, 0, 0, 0, 0]

    def compute(masked):
        q = q_ref[0, 0].astype(jnp.float32) * np.float32(scale)
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            mask = _fm_mask(start_ref, s.shape, row0, col0, causal, sq, sk)
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, _ZERO)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[:] = acc[:] * alpha + pv
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    _fm_block_dispatch(compute, causal=causal, row0=row0, row1=row1,
                       col0=col0, col1=col1, smin=smin, smax=smax,
                       sq=sq, sk=sk, block_k=block_k)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l == _ZERO, _ONE, l)
        o_ref[0, 0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(
            m_s[:, :1] + jnp.log(safe_l), lse_ref[0, 0].shape)


def _fm_starts_prep(start_rows, b, h, sk, sk_p, nk, block_k):
    """Shared fwd/bwd prep of the per-column start rows: tile-replicated
    per-column starts [B,H,8,Sk_p] plus per-kv-block min/max start
    [B,H,nk,8,128] driving the block-skip / lean-path predicates."""
    sr = start_rows.astype(jnp.int32)                  # [B, H, Sk]
    # padded key columns get start 0 => visible to no row (blocked)
    sr_p = jnp.pad(sr, ((0, 0), (0, 0), (0, sk_p - sk)))
    # per-column starts, sublane-replicated: [B, H, 8, Sk_p]
    sr_lanes = jnp.broadcast_to(sr_p[:, :, None, :], (b, h, 8, sk_p))
    # per-kv-block min/max start: [B, H, nk] -> tile-replicated
    blk = sr_p.reshape(b, h, nk, block_k)
    smin = jnp.min(jnp.where(jnp.arange(block_k)[None, None, None, :]
                             + jnp.arange(nk)[None, None, :, None]
                             * block_k < sk, blk, jnp.int32(2**30)), axis=-1)
    smax = jnp.max(blk, axis=-1)
    smin_l = jnp.broadcast_to(smin[:, :, :, None, None], (b, h, nk, 8, 128))
    smax_l = jnp.broadcast_to(smax[:, :, :, None, None], (b, h, nk, 8, 128))
    return sr_lanes, smin_l, smax_l


def _fm_forward_x32(q, k, v, start_rows, causal, block_q, block_k):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    sq_p = _ceil_to(sq, block_q)
    sk_p = _ceil_to(sk, block_k)
    d_p = _ceil_to(d, 128)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, d_p - d)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, d_p - d)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, d_p - d)))
    nq, nk = sq_p // block_q, sk_p // block_k
    sr_lanes, smin_l, smax_l = _fm_starts_prep(start_rows, b, h, sk, sk_p,
                                               nk, block_k)

    kernel = functools.partial(
        _fm_fwd_kernel, scale=scale, causal=causal, sq=sq, sk=sk,
        block_q=block_q, block_k=block_k)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d_p),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d_p),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d_p),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, 8, block_k),
                         lambda b, h, qi, ki: (b, h, 0, ki)),
            pl.BlockSpec((1, 1, 1, 8, 128),
                         lambda b, h, qi, ki: (b, h, ki, 0, 0)),
            pl.BlockSpec((1, 1, 1, 8, 128),
                         lambda b, h, qi, ki: (b, h, ki, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d_p),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, d_p), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_p, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d_p), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_interpret(), name="flashmask_fwd",
    )(qp, kp, vp, sr_lanes, smin_l, smax_l)
    # keep one lane of the softmax stats for the backward (see _flash_forward)
    return o[:, :, :sq, :d], lse[:, :, :, :1]


def _fm_dense_ref(q, k, v, start_rows, causal):
    """Dense O(S^2) reference of the flashmask semantics. NOT on any
    production path — kept as the numerics oracle for
    tests/test_pallas_attention.py; fwd AND bwd run the block-skipping
    Pallas kernels."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    rows = jnp.arange(sq)[None, None, :, None]
    mask = rows < start_rows[:, :, None, :]
    if causal:
        cols = jnp.arange(sk)[None, None, None, :]
        mask = mask & (cols <= rows + (sk - sq))
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    empty = ~jnp.any(mask, axis=-1, keepdims=True)
    p = jnp.where(empty, jnp.zeros_like(p), p)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _fm_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      start_ref, smin_ref, smax_ref, dq_ref, dq_acc, *,
                      scale, causal, sq, sk, block_q, block_k):
    """dq with the SAME block-skip predicates as the flashmask forward:
    kv blocks fully blocked for this q block contribute nothing and are
    skipped before touching the MXU; fully-visible blocks take the lean
    no-iota path; only straddling blocks pay the mask chain. The fwd LSE
    is reused — no dense [Sq,Sk] softmax is ever materialized."""
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    row0 = qi * block_q
    row1 = row0 + block_q - 1
    col0 = ki * block_k
    col1 = col0 + block_k - 1
    smax = smax_ref[0, 0, 0, 0, 0]
    smin = smin_ref[0, 0, 0, 0, 0]

    def compute(masked):
        q = q_ref[0, 0].astype(jnp.float32) * np.float32(scale)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        if masked:
            mask = _fm_mask(start_ref, s.shape, row0, col0, causal, sq, sk)
        p = jnp.exp(s - lse)
        if masked:
            # fully-blocked rows carry lse == -1e30 which cancels in the
            # exp; zero them (and padded/blocked columns) explicitly
            p = jnp.where(mask, p, _ZERO)
        do = do_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[0, 0][:, :1]
        ds = p * (dp - delta) * np.float32(scale)
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _fm_block_dispatch(compute, causal=causal, row0=row0, row1=row1,
                       col0=col0, col1=col1, smin=smin, smax=smax,
                       sq=sq, sk=sk, block_k=block_k)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _fm_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       start_ref, smin_ref, smax_ref, dk_ref, dv_ref,
                       dk_acc, dv_acc, *,
                       scale, causal, sq, sk, block_q, block_k):
    # grid is (b, h, ki, qi): kv blocks outer, q blocks inner
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    row0 = qi * block_q
    row1 = row0 + block_q - 1
    col0 = ki * block_k
    col1 = col0 + block_k - 1
    smax = smax_ref[0, 0, 0, 0, 0]
    smin = smin_ref[0, 0, 0, 0, 0]

    def compute(masked):
        q = q_ref[0, 0].astype(jnp.float32) * np.float32(scale)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        if masked:
            mask = _fm_mask(start_ref, s.shape, row0, col0, causal, sq, sk)
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)
        if masked:
            # blocked/padded rows have lse == -1e30 (cancels the mask
            # value): p must be zeroed or they pollute dk/dv
            p = jnp.where(mask, p, _ZERO)
        do = do_ref[0, 0].astype(jnp.float32)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[0, 0][:, :1]
        # `q` is pre-scaled by 1/sqrt(d) = dk's scale; ds NOT scaled again
        ds = p * (dp - delta)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _fm_block_dispatch(compute, causal=causal, row0=row0, row1=row1,
                       col0=col0, col1=col1, smin=smin, smax=smax,
                       sq=sq, sk=sk, block_k=block_k)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _fm_backward_x32(q, k, v, o, lse_lanes, do, start_rows, causal,
                     block_q, block_k):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    sq_p = _ceil_to(sq, block_q)
    sk_p = _ceil_to(sk, block_k)
    d_p = _ceil_to(d, 128)
    pad4 = lambda x, s: jnp.pad(
        x, ((0, 0), (0, 0), (0, s - x.shape[2]), (0, d_p - d)))
    qp, kp, vp = pad4(q, sq_p), pad4(k, sk_p), pad4(v, sk_p)
    dop = pad4(do, sq_p)
    lsep = jnp.broadcast_to(lse_lanes, (b, h, lse_lanes.shape[2], 128))
    deltap = jnp.broadcast_to(
        jnp.pad(delta, ((0, 0), (0, 0), (0, sq_p - sq)))[..., None],
        (b, h, sq_p, 128))
    nq, nk = sq_p // block_q, sk_p // block_k
    sr_lanes, smin_l, smax_l = _fm_starts_prep(start_rows, b, h, sk, sk_p,
                                               nk, block_k)

    common = dict(scale=scale, causal=causal, sq=sq, sk=sk,
                  block_q=block_q, block_k=block_k)
    q_spec = pl.BlockSpec((1, 1, block_q, d_p),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, d_p),
                          lambda b, h, qi, ki: (b, h, ki, 0))
    r_spec = pl.BlockSpec((1, 1, block_q, 128),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    sr_spec = pl.BlockSpec((1, 1, 8, block_k),
                           lambda b, h, qi, ki: (b, h, 0, ki))
    mm_spec = pl.BlockSpec((1, 1, 1, 8, 128),
                           lambda b, h, qi, ki: (b, h, ki, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_fm_bwd_dq_kernel, **common),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec,
                  sr_spec, mm_spec, mm_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq_p, d_p), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d_p), jnp.float32)],
        interpret=_interpret(), name="flashmask_bwd_dq",
    )(qp, kp, vp, dop, lsep, deltap, sr_lanes, smin_l, smax_l)[0]

    # dkv kernel: kv blocks outer, q blocks inner
    q_spec2 = pl.BlockSpec((1, 1, block_q, d_p),
                           lambda b, h, ki, qi: (b, h, qi, 0))
    k_spec2 = pl.BlockSpec((1, 1, block_k, d_p),
                           lambda b, h, ki, qi: (b, h, ki, 0))
    r_spec2 = pl.BlockSpec((1, 1, block_q, 128),
                           lambda b, h, ki, qi: (b, h, qi, 0))
    sr_spec2 = pl.BlockSpec((1, 1, 8, block_k),
                            lambda b, h, ki, qi: (b, h, 0, ki))
    mm_spec2 = pl.BlockSpec((1, 1, 1, 8, 128),
                            lambda b, h, ki, qi: (b, h, ki, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_fm_bwd_dkv_kernel, **common),
        grid=(b, h, nk, nq),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2,
                  sr_spec2, mm_spec2, mm_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk_p, d_p), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk_p, d_p), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d_p), jnp.float32),
                        pltpu.VMEM((block_k, d_p), jnp.float32)],
        interpret=_interpret(), name="flashmask_bwd_dkv",
    )(qp, kp, vp, dop, lsep, deltap, sr_lanes, smin_l, smax_l)
    return (dq[:, :, :sq, :d], dk[:, :, :sk, :d], dv[:, :, :sk, :d])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flashmask(q, k, v, start_rows, causal, block_q, block_k,
               bwd_block_q=None, bwd_block_k=None):
    with _x64_guard():
        o, _ = _fm_forward_x32(q, k, v, start_rows, causal, block_q, block_k)
    return o


def _flashmask_fwd(q, k, v, start_rows, causal, block_q, block_k,
                   bwd_block_q=None, bwd_block_k=None):
    with _x64_guard():
        o, lse = _fm_forward_x32(q, k, v, start_rows, causal,
                                 block_q, block_k)
    o, lse = _name_flash_residuals(o, lse)
    return o, (q, k, v, o, lse, start_rows)


def _flashmask_bwd(causal, block_q, block_k, bwd_block_q, bwd_block_k,
                   res, g):
    q, k, v, o, lse, start_rows = res
    with _x64_guard():
        dq, dk, dv = _fm_backward_x32(q, k, v, o, lse, g, start_rows,
                                      causal, bwd_block_q or block_q,
                                      bwd_block_k or block_k)
    return dq, dk, dv, jnp.zeros(start_rows.shape, jax.dtypes.float0)


_flashmask.defvjp(_flashmask_fwd, _flashmask_bwd)


def _autotune_blocks_fm(q, k, v, start_rows, causal):
    """FlashMask twin of _autotune_blocks (cache kind 'flashmask'): the
    block-sparse kernels' best shape depends on the mask's blocked fraction
    as well as seq length, so they get their own probe family. Defaults
    (512, 512) off-TPU/in-trace — smaller kv blocks keep skippable
    granularity fine for sliding-window patterns."""
    from ..core.flags import flag

    sq, sk, d = q.shape[2], k.shape[2], q.shape[3]
    key = ("flashmask", sq, sk, d, str(q.dtype), causal)
    hit = _norm4(_TUNE_CACHE.get(key))
    if hit is not None:
        return hit
    if _interpret() or isinstance(q, jax.core.Tracer) \
            or isinstance(start_rows, jax.core.Tracer) \
            or not flag("FLAGS_flash_autotune"):
        return (DEFAULT_BLOCK_Q, 512, DEFAULT_BLOCK_Q, 512)
    _tune_cache_load()
    hit = _norm4(_TUNE_CACHE.get(key))
    if hit is not None:
        return hit

    def probe_fwd(bq, bk):
        fn = jax.jit(lambda a, b, c2, sr: _flashmask(a, b, c2, sr, causal,
                                                     bq, bk))
        return _probe_time(fn, q, k, v, start_rows)

    fwd = _rank_candidates(sq, sk, probe_fwd)
    bwd = fwd
    if flag("FLAGS_flash_tune_bwd_split"):
        def probe_bwd(bq, bk):
            fn = jax.jit(lambda a, b, c2, sr: jax.grad(
                lambda aa: jnp.sum(
                    _flashmask(aa, b, c2, sr, causal, fwd[0], fwd[1],
                               bq, bk).astype(jnp.float32)))(a))
            return _probe_time(fn, q, k, v, start_rows)

        bwd = _rank_candidates(sq, sk, probe_bwd)
    best = (*fwd, *bwd)
    _TUNE_CACHE[key] = best
    _tune_cache_store()
    return best


def ensure_tuned_flashmask(sq, sk, d, dtype, causal, start_rows):
    """Eagerly autotune the FlashMask block choice for a shape family
    BEFORE entering a trace (the functional flashmask_attention path runs
    the kernel under jit, where only the cache can be consulted). Probes
    one head with the caller's actual start rows so the blocked fraction
    the tuner sees matches the workload; no-op off-TPU / on repeat shapes /
    with FLAGS_flash_autotune off."""
    from ..core.flags import flag

    key = ("flashmask", sq, sk, d, str(jnp.dtype(dtype)), causal)
    if key in _TUNE_CACHE or _interpret() or not flag("FLAGS_flash_autotune"):
        hit = _norm4(_TUNE_CACHE.get(key))
        if hit is not None:
            return hit
        return (DEFAULT_BLOCK_Q, 512, DEFAULT_BLOCK_Q, 512)
    kk = jax.random.PRNGKey(0)
    q = jax.random.normal(kk, (1, 1, sq, d), jnp.dtype(dtype))
    k = jax.random.normal(kk, (1, 1, sk, d), jnp.dtype(dtype))
    v = jax.random.normal(kk, (1, 1, sk, d), jnp.dtype(dtype))
    sr = jnp.asarray(start_rows, jnp.int32)[:1, :1, :]
    return _autotune_blocks_fm(q, k, v, sr, causal)


def flashmask_attention_raw(q, k, v, start_rows, causal=False,
                            block_q=None, block_k=None):
    """Block-sparse FlashMask attention on [B, H, S, D] arrays with
    per-column start rows [B, H, S_k] (causal LTS form). Forward AND
    backward skip fully-blocked kv blocks in Pallas kernels; the backward
    reuses the forward's LSE so no [Sq,Sk] softmax is ever materialized
    (≙ the reference's fused fwd+bwd flashmask CUDA family,
    nn/functional/flash_attention.py flashmask_attention). Block sizes
    default to the per-shape autotuned choice (cache kind 'flashmask',
    fwd and bwd tuned separately); explicit block_q/block_k pin both."""
    cap_q = _ceil_to(q.shape[2], 128)
    cap_k = _ceil_to(k.shape[2], 128)
    if block_q is None or block_k is None:
        tq, tk, tbq, tbk = _autotune_blocks_fm(q, k, v, start_rows, causal)
        return _flashmask(q, k, v, start_rows, causal,
                          min(block_q or tq, cap_q),
                          min(block_k or tk, cap_k),
                          min(block_q or tbq, cap_q),
                          min(block_k or tbk, cap_k))
    bq = min(block_q, cap_q)
    bk = min(block_k, cap_k)
    return _flashmask(q, k, v, start_rows, causal, bq, bk)
