"""Fused norm/rotary/SwiGLU/dropout-add Pallas kernels + the bf16
residual-stream policy.

The kernels (ops/pallas_norm.py) run in interpreter mode on the CPU mesh;
numerics are checked against the unfused XLA compositions with the same
tolerance tiers as tests/test_pallas_attention.py (f32 tight, bf16 at bf16
resolution), gradients via jax.grad against the composition's grads, and
the framework routing (nn.functional / incubate / the LLaMA-GPT-BERT
blocks) is exercised end-to-end with the kernels forced on.

The FLAGS_residual_dtype=bfloat16 policy is proven at the jaxpr level: the
compiled LLaMA forward contains ZERO f32 values of residual-stream size
once the policy is on (the f32 casts the AMP blacklist used to insert at
every norm disappear), and a 5-step train loss parity run bounds the drift
vs the f32 stream.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops import pallas_norm as pn

TOL = {"float32": 5e-5, "bfloat16": 2e-2}


@pytest.fixture
def force_pallas():
    pn.FORCE_PALLAS = True
    yield
    pn.FORCE_PALLAS = None


def _tol(dtype):
    return TOL[str(jnp.dtype(dtype))]


def _rand(rs, shape, dtype):
    return jnp.asarray(rs.randn(*shape).astype("float32")).astype(dtype)


def _ref_rms(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), -1, keepdims=True)
    out = (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return out * w if w is not None else out


def _ref_ln(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, -1, keepdims=True)
    v = jnp.var(xf, -1, keepdims=True)
    out = ((xf - m) * jax.lax.rsqrt(v + eps)).astype(x.dtype)
    if w is not None:
        out = out * w
    if b is not None:
        out = out + b
    return out


def _ref_rot(a, c, s):
    a1, a2 = jnp.split(a, 2, axis=-1)
    return a * c + jnp.concatenate([-a2, a1], -1) * s


def _close(a, b, tol, msg=""):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=tol, atol=tol, err_msg=msg)


# ------------------------------------------------------------- raw kernels

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape,with_w", [((4, 33, 100), True),
                                          ((2, 16, 64), False),
                                          ((3, 300), True)])
def test_rms_norm_parity_and_grads(shape, with_w, dtype):
    rs = np.random.RandomState(0)
    x = _rand(rs, shape, dtype)
    w = _rand(rs, shape[-1:], dtype) if with_w else None
    tol = _tol(dtype)
    _close(pn.rms_norm_raw(x, w), _ref_rms(x, w), tol)

    if dtype == "float32":  # grads in f32 (bf16 grads checked for finiteness)
        gf = jax.grad(lambda a: jnp.sum(jnp.sin(pn.rms_norm_raw(a, w))))(x)
        gr = jax.grad(lambda a: jnp.sum(jnp.sin(_ref_rms(a, w))))(x)
        _close(gf, gr, tol, "dx")
        if with_w:
            gf = jax.grad(lambda ww: jnp.sum(jnp.sin(pn.rms_norm_raw(x, ww))))(w)
            gr = jax.grad(lambda ww: jnp.sum(jnp.sin(_ref_rms(x, ww))))(w)
            _close(gf, gr, tol, "dw")
    else:
        g = jax.grad(lambda a: jnp.sum(
            pn.rms_norm_raw(a, w).astype(jnp.float32) ** 2))(x)
        assert np.isfinite(np.asarray(g, np.float32)).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_add_rms_norm_parity_and_grads(dtype):
    rs = np.random.RandomState(1)
    x = _rand(rs, (2, 24, 96), dtype)
    res = _rand(rs, (2, 24, 96), dtype)
    w = _rand(rs, (96,), dtype)
    tol = _tol(dtype)
    y, s = pn.add_rms_norm_raw(x, res, w)
    _close(s, x + res, tol, "summed stream")
    _close(y, _ref_rms((x + res).astype(jnp.dtype(dtype)), w), tol)

    if dtype == "float32":
        # both outputs carry cotangents: y through sin, s through cos
        def lf(a, r, ww):
            yy, ss = pn.add_rms_norm_raw(a, r, ww)
            return jnp.sum(jnp.sin(yy)) + jnp.sum(jnp.cos(ss))

        def lr(a, r, ww):
            ss = a + r
            return jnp.sum(jnp.sin(_ref_rms(ss, ww))) + jnp.sum(jnp.cos(ss))

        gf = jax.grad(lf, argnums=(0, 1, 2))(x, res, w)
        gr = jax.grad(lr, argnums=(0, 1, 2))(x, res, w)
        for a, b, nm in zip(gf, gr, ("dx", "dres", "dw")):
            _close(a, b, tol, nm)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("with_w,with_b", [(True, True), (True, False),
                                           (False, False)])
def test_layer_norm_parity_and_grads(with_w, with_b, dtype):
    rs = np.random.RandomState(2)
    # nonzero mean exercises the E[x^2]-mean^2 lane-padding-safe variance
    x = _rand(rs, (2, 17, 100), dtype) * 2.0 + 3.0
    w = _rand(rs, (100,), dtype) if with_w else None
    b = _rand(rs, (100,), dtype) if with_b else None
    tol = _tol(dtype)
    _close(pn.layer_norm_raw(x, w, b), _ref_ln(x, w, b), tol)

    if dtype == "float32" and with_w and with_b:
        gf = jax.grad(lambda a, ww, bb: jnp.sum(jnp.sin(
            pn.layer_norm_raw(a, ww, bb))), argnums=(0, 1, 2))(x, w, b)
        gr = jax.grad(lambda a, ww, bb: jnp.sum(jnp.sin(
            _ref_ln(a, ww, bb))), argnums=(0, 1, 2))(x, w, b)
        for a, bb, nm in zip(gf, gr, ("dx", "dw", "db")):
            _close(a, bb, tol, nm)


def test_add_layer_norm_parity_and_grads():
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(2, 16, 64).astype("float32"))
    res = jnp.asarray(rs.randn(2, 16, 64).astype("float32"))
    w = jnp.asarray(rs.randn(64).astype("float32"))
    b = jnp.asarray(rs.randn(64).astype("float32"))
    y, s = pn.add_layer_norm_raw(x, res, w, b)
    _close(s, x + res, 5e-5)
    _close(y, _ref_ln(x + res, w, b), 5e-5)

    def lf(a, r):
        yy, ss = pn.add_layer_norm_raw(a, r, w, b)
        return jnp.sum(jnp.sin(yy)) + jnp.sum(jnp.cos(ss))

    def lr(a, r):
        ss = a + r
        return jnp.sum(jnp.sin(_ref_ln(ss, w, b))) + jnp.sum(jnp.cos(ss))

    gf = jax.grad(lf, argnums=(0, 1))(x, res)
    gr = jax.grad(lr, argnums=(0, 1))(x, res)
    for a, bb, nm in zip(gf, gr, ("dx", "dres")):
        _close(a, bb, 5e-5, nm)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("B,S,H,D", [(2, 32, 4, 16), (1, 40, 2, 64),
                                     (1, 24, 2, 128)])
def test_rope_qk_parity_and_grads(B, S, H, D, dtype):
    rs = np.random.RandomState(4)
    q = _rand(rs, (B, S, H, D), dtype)
    k = _rand(rs, (B, S, H, D), dtype)
    inv = 1.0 / (10000.0 ** (np.arange(0, D, 2) / D))
    fr = np.outer(np.arange(S), inv)
    emb = np.concatenate([fr, fr], -1)
    cos = jnp.asarray(np.cos(emb)[None, :, None, :].astype("float32")).astype(dtype)
    sin = jnp.asarray(np.sin(emb)[None, :, None, :].astype("float32")).astype(dtype)
    tol = _tol(dtype)
    qo, ko = pn.rope_qk_fused(q, k, cos, sin)
    _close(qo, _ref_rot(q, cos, sin), tol, "q")
    _close(ko, _ref_rot(k, cos, sin), tol, "k")

    if dtype == "float32":
        def lf(a, bq):
            qq, kk = pn.rope_qk_fused(a, bq, cos, sin)
            return jnp.sum(jnp.sin(qq)) + jnp.sum(jnp.cos(kk))

        def lr(a, bq):
            return jnp.sum(jnp.sin(_ref_rot(a, cos, sin))) + \
                jnp.sum(jnp.cos(_ref_rot(bq, cos, sin)))

        gf = jax.grad(lf, argnums=(0, 1))(q, k)
        gr = jax.grad(lr, argnums=(0, 1))(q, k)
        _close(gf[0], gr[0], tol, "dq")
        _close(gf[1], gr[1], tol, "dk")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_swiglu_parity_and_grads(dtype):
    rs = np.random.RandomState(5)
    g = _rand(rs, (6, 40, 130), dtype)
    u = _rand(rs, (6, 40, 130), dtype)
    tol = _tol(dtype)
    _close(pn.swiglu_fused(g, u), jax.nn.silu(g.astype(jnp.float32))
           * u.astype(jnp.float32), tol)

    if dtype == "float32":
        gf = jax.grad(lambda a, bq: jnp.sum(jnp.sin(pn.swiglu_fused(a, bq))),
                      argnums=(0, 1))(g, u)
        gr = jax.grad(lambda a, bq: jnp.sum(jnp.sin(jax.nn.silu(a) * bq)),
                      argnums=(0, 1))(g, u)
        _close(gf[0], gr[0], tol, "dgate")
        _close(gf[1], gr[1], tol, "dup")


def test_dropout_add_mask_semantics_and_grads():
    rs = np.random.RandomState(6)
    x = jnp.asarray(rs.randn(4, 30, 70).astype("float32"))
    y = jnp.asarray(rs.randn(4, 30, 70).astype("float32"))
    m = jnp.asarray((rs.rand(4, 30, 70) > 0.25).astype("float32"))
    scale = 1.0 / 0.75
    _close(pn.dropout_add_fused(x, y, m, scale), x * m * scale + y, 5e-6)

    gf = jax.grad(lambda a, bq: jnp.sum(jnp.sin(
        pn.dropout_add_fused(a, bq, m, scale))), argnums=(0, 1))(x, y)
    gr = jax.grad(lambda a, bq: jnp.sum(jnp.sin(a * m * scale + bq)),
                  argnums=(0, 1))(x, y)
    _close(gf[0], gr[0], 5e-6, "dx carries the mask*scale")
    _close(gf[1], gr[1], 5e-6, "dy is identity")


# --------------------------------------------------- framework-level routing

def test_use_pallas_gates_off_tpu():
    # CPU backend, no FORCE: the composition path (tier-1 stays pallas-free)
    assert pn.FORCE_PALLAS is None
    assert not pn.use_pallas(jnp.ones((1024, 1024), jnp.float32))
    # the flag kills the fast path even where it would apply
    assert paddle.get_flags("FLAGS_pallas_fused_ops")[
        "FLAGS_pallas_fused_ops"] is True


def test_functional_parity_forced_vs_composition(force_pallas):
    rs = np.random.RandomState(7)
    xn = rs.randn(2, 24, 96).astype("float32")
    rn = rs.randn(2, 24, 96).astype("float32")
    wn = rs.randn(96).astype("float32")
    bn = rs.randn(96).astype("float32")

    def both(fn):
        pn.FORCE_PALLAS = True
        fast = fn()
        pn.FORCE_PALLAS = False
        slow = fn()
        pn.FORCE_PALLAS = True
        return fast, slow

    def t(a):
        tt = paddle.to_tensor(a)
        tt.stop_gradient = False
        return tt

    # rms_norm fwd + Tensor-tape backward
    def run_rms():
        x = t(xn)
        w = t(wn)
        out = F.rms_norm(x, w)
        (out * out).sum().backward()
        return (np.asarray(out._data), np.asarray(x.grad._data),
                np.asarray(w.grad._data))

    fast, slow = both(run_rms)
    for a, b in zip(fast, slow):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    # fused add+rms: (y, s) and grads through BOTH outputs
    def run_add_rms():
        x = t(xn)
        r = t(rn)
        w = t(wn)
        y, s = F.fused_add_rms_norm(x, r, w)
        ((y * y).sum() + (s * s).sum()).backward()
        return (np.asarray(y._data), np.asarray(s._data),
                np.asarray(x.grad._data), np.asarray(r.grad._data))

    fast, slow = both(run_add_rms)
    for a, b in zip(fast, slow):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    # fused add+LN
    def run_add_ln():
        x = t(xn)
        r = t(rn)
        w = t(wn)
        b = t(bn)
        y, s = F.fused_add_layer_norm(x, r, w, b)
        ((y * y).sum() + (s * s).sum()).backward()
        return (np.asarray(y._data), np.asarray(s._data),
                np.asarray(x.grad._data))

    fast, slow = both(run_add_ln)
    for a, b in zip(fast, slow):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    # swiglu
    def run_swiglu():
        g = t(xn)
        u = t(rn)
        out = F.swiglu(g, u)
        (out * out).sum().backward()
        return (np.asarray(out._data), np.asarray(g.grad._data),
                np.asarray(u.grad._data))

    fast, slow = both(run_swiglu)
    for a, b in zip(fast, slow):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_rotary_functional_parity(force_pallas):
    rs = np.random.RandomState(8)
    B, S, H, D = 2, 20, 4, 32
    qn = rs.randn(B, S, H, D).astype("float32")
    kn = rs.randn(B, S, H, D).astype("float32")
    inv = 1.0 / (10000.0 ** (np.arange(0, D, 2) / D))
    fr = np.outer(np.arange(S), inv)
    emb = np.concatenate([fr, fr], -1)
    cosn = np.cos(emb)[None, :, None, :].astype("float32")
    sinn = np.sin(emb)[None, :, None, :].astype("float32")

    def run():
        q = paddle.to_tensor(qn)
        k = paddle.to_tensor(kn)
        q.stop_gradient = False
        k.stop_gradient = False
        qo, ko = F.rotary_position_embedding(
            q, k, paddle.to_tensor(cosn), paddle.to_tensor(sinn))
        ((qo * qo).sum() + (ko * ko).sum()).backward()
        return (np.asarray(qo._data), np.asarray(ko._data),
                np.asarray(q.grad._data), np.asarray(k.grad._data))

    pn.FORCE_PALLAS = True
    fast = run()
    pn.FORCE_PALLAS = False
    slow = run()
    for a, b in zip(fast, slow):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_mixed_dtype_promotion_matches_composition(force_pallas):
    """bf16 stream + f32 params WITHOUT amp (the bf16 policy flipped on a
    plain-f32 model): the fused paths must promote like the compositions
    do and grads must come back in each primal's dtype — the round-8
    verify-drive catch (an f32 cotangent used to hit a bf16-primal vjp)."""
    rs = np.random.RandomState(13)
    x = _rand(rs, (2, 16, 64), "float32")          # branch output (f32)
    res = _rand(rs, (2, 16, 64), "bfloat16")       # bf16 residual stream
    w = _rand(rs, (64,), "float32")                # f32 param

    def lf(a, r, ww):
        y, s = pn.add_rms_norm_raw(a, r, ww)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(
            s.astype(jnp.float32))

    y, s = pn.add_rms_norm_raw(x, res, w)
    assert s.dtype == jnp.float32                  # result_type(f32, bf16)
    ga = jax.grad(lf, argnums=(0, 1, 2))(x, res, w)
    assert ga[0].dtype == jnp.float32
    assert ga[1].dtype == jnp.bfloat16             # grad in primal dtype
    assert ga[2].dtype == jnp.float32
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in ga)

    # end-to-end: policy ON, f32 params, NO amp — eager backward must run
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny_config

    paddle.set_flags({"FLAGS_residual_dtype": "bfloat16"})
    try:
        paddle.seed(0)
        m = LlamaForCausalLM(llama_tiny_config(num_key_value_heads=2))
        ids = paddle.to_tensor(np.random.RandomState(0).randint(
            0, 256, (2, 32)).astype("int64"))
        loss = m(ids, ids)
        loss.backward()
        g = m.model.layers[0].self_attn.q_proj.weight.grad
        assert np.isfinite(np.asarray(g._data, np.float32)).all()
    finally:
        paddle.set_flags({"FLAGS_residual_dtype": "float32"})


def test_rotary_gqa_takes_composition_path(force_pallas):
    """GQA (fewer kv heads): the fused kernel processes q and k through
    the same block shapes, so mismatched head counts must fall back to the
    composition — and stay CORRECT (the round-8 review catch: the fused
    path returned ko with q's head count)."""
    rs = np.random.RandomState(12)
    B, S, HQ, HK, D = 2, 16, 4, 2, 32
    qn = rs.randn(B, S, HQ, D).astype("float32")
    kn = rs.randn(B, S, HK, D).astype("float32")
    inv = 1.0 / (10000.0 ** (np.arange(0, D, 2) / D))
    fr = np.outer(np.arange(S), inv)
    emb = np.concatenate([fr, fr], -1)
    cos = np.cos(emb)[None, :, None, :].astype("float32")
    sin = np.sin(emb)[None, :, None, :].astype("float32")
    qo, ko = F.rotary_position_embedding(
        paddle.to_tensor(qn), paddle.to_tensor(kn),
        paddle.to_tensor(cos), paddle.to_tensor(sin))
    assert tuple(ko.shape) == (B, S, HK, D), ko.shape
    np.testing.assert_allclose(
        np.asarray(ko._data),
        np.asarray(_ref_rot(jnp.asarray(kn), jnp.asarray(cos),
                            jnp.asarray(sin))), rtol=1e-5, atol=1e-5)


def test_fused_dropout_add_functional(force_pallas):
    rs = np.random.RandomState(9)
    xn = rs.randn(2, 16, 64).astype("float32")
    yn = rs.randn(2, 16, 64).astype("float32")
    x = paddle.to_tensor(xn)
    y = paddle.to_tensor(yn)
    # p=0 / eval: exact add, no kernel
    out = F.fused_dropout_add(x, y, p=0.0, training=True)
    np.testing.assert_allclose(np.asarray(out._data), xn + yn, rtol=1e-6)
    out = F.fused_dropout_add(x, y, p=0.5, training=False)
    np.testing.assert_allclose(np.asarray(out._data), xn + yn, rtol=1e-6)
    # training: mask semantics — surviving entries are x/keep + y, dropped
    # entries are exactly y
    paddle.seed(123)
    x2 = paddle.to_tensor(xn)
    x2.stop_gradient = False
    out = F.fused_dropout_add(x2, y, p=0.5, training=True)
    o = np.asarray(out._data)
    kept = np.abs(o - yn) > 1e-12
    np.testing.assert_allclose(o[kept], (xn * 2.0 + yn)[kept], rtol=1e-5)
    assert 0.2 < kept.mean() < 0.8  # mask is actually random
    out.sum().backward()
    g = np.asarray(x2.grad._data)
    np.testing.assert_allclose(g[kept], 2.0, rtol=1e-6)
    np.testing.assert_allclose(g[~kept], 0.0, atol=1e-12)


def test_incubate_surface(force_pallas):
    from paddle_tpu.incubate.nn import functional as IF

    rs = np.random.RandomState(10)
    x = paddle.to_tensor(rs.randn(2, 16, 64).astype("float32"))
    r = paddle.to_tensor(rs.randn(2, 16, 64).astype("float32"))
    w = paddle.to_tensor(rs.randn(64).astype("float32"))
    out, invvar = IF.fused_rms_norm(x, w)
    assert invvar is None
    np.testing.assert_allclose(
        np.asarray(out._data),
        np.asarray(_ref_rms(jnp.asarray(x._data), jnp.asarray(w._data))),
        rtol=5e-5, atol=5e-5)
    out2, summed = IF.fused_rms_norm(x, w, residual=r)
    np.testing.assert_allclose(np.asarray(summed._data),
                               np.asarray(x._data) + np.asarray(r._data),
                               rtol=1e-6)
    # rotary: neox style only; v rides through
    with pytest.raises(NotImplementedError):
        IF.fused_rotary_position_embedding(x, use_neox_rotary_style=False)
    got = IF.fused_dropout_add(x, r, p=0.0)
    np.testing.assert_allclose(np.asarray(got._data),
                               np.asarray(x._data) + np.asarray(r._data),
                               rtol=1e-6)


# ------------------------------------------------------------- model level

def test_llama_block_parity_forced_vs_composition():
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny_config

    rs = np.random.RandomState(11)
    ids_np = rs.randint(0, 256, (2, 32)).astype("int64")

    def run():
        paddle.seed(0)
        m = LlamaForCausalLM(llama_tiny_config())
        ids = paddle.to_tensor(ids_np)
        loss = m(ids, ids)
        loss.backward()
        g = np.asarray(m.model.layers[0].self_attn.q_proj.weight.grad._data)
        return float(loss), g

    pn.FORCE_PALLAS = True
    try:
        l1, g1 = run()
    finally:
        pn.FORCE_PALLAS = None
    l0, g0 = run()
    assert abs(l0 - l1) < 5e-5, (l0, l1)
    np.testing.assert_allclose(g0, g1, rtol=1e-4, atol=1e-5)
    assert np.isfinite(g1).all()


# ------------------------------------------- bf16 residual stream policy
#
# Round-9: the hand-written jaxpr string scan this test used through round 8
# became the D1 dtype-stream detector (paddle_tpu.analysis), which
# tools/graft_lint.py runs over ANY captured program — this test drives the
# SAME detector on the same LLaMA program, so the test and the CI lint
# cannot diverge.


class TestResidualDtypePolicy:
    B, S = 2, 32

    def _program(self, policy):
        from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny_config

        cfg = llama_tiny_config()
        paddle.set_flags({"FLAGS_residual_dtype": policy,
                          "FLAGS_jit_debug_program": True})
        pn.FORCE_PALLAS = True
        try:
            paddle.seed(0)
            model = LlamaForCausalLM(cfg)
            model = paddle.amp.decorate(model, level="O2", dtype="bfloat16",
                                        master_weight=False)

            @paddle.jit.to_static
            def fwd(x):
                with paddle.amp.auto_cast(enable=True, dtype="bfloat16",
                                          level="O2"):
                    return model(x)

            ids = paddle.to_tensor(
                np.random.RandomState(0).randint(
                    0, 256, (self.B, self.S)).astype("int64"))
            fwd(ids)
            fwd(ids)
            fwd(ids)  # warm-up -> discovery -> compile
            return fwd.program_jaxpr(), cfg
        finally:
            pn.FORCE_PALLAS = None
            paddle.set_flags({"FLAGS_residual_dtype": "float32",
                              "FLAGS_jit_debug_program": False})

    def test_jaxpr_no_f32_stream_under_bf16_policy(self):
        """The round-6-remat-style jaxpr proof, now through the D1
        dtype-stream detector: with the policy on, the compiled LLaMA
        forward carries NO f32 tensor of residual-stream size — every
        norm/rope/residual value crossing HBM is bf16 (f32 lives only
        inside the Pallas kernels' VMEM accumulation, which the detector
        deliberately does not descend into)."""
        from paddle_tpu.analysis import audit_dtype_stream

        jx_off, cfg = self._program("float32")
        shapes = [(self.B, self.S, cfg.hidden_size),
                  (self.B, self.S, cfg.num_attention_heads, cfg.head_dim)]
        off_hits = audit_dtype_stream(jx_off, policy="bfloat16",
                                      stream_shapes=shapes)
        assert off_hits, \
            "detector sanity: the f32 stream should be visible with the " \
            "policy off (AMP blacklist casts at every norm)"
        jx_on, _ = self._program("bfloat16")
        on_hits = audit_dtype_stream(jx_on, policy="bfloat16",
                                     stream_shapes=shapes)
        assert not on_hits, "f32 residual-stream tensors survived the " \
            "bf16 policy:\n" + "\n".join(repr(f) for f in on_hits[:8])

    def test_loss_parity_bf16_vs_f32_stream(self):
        """5 optimizer steps under amp O2: the bf16 residual stream tracks
        the f32 stream within 5e-3 relative per step (measured ~1e-4)."""
        from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny_config

        ids_np = np.random.RandomState(0).randint(
            0, 256, (2, 64)).astype("int64")

        def run(policy):
            paddle.set_flags({"FLAGS_residual_dtype": policy})
            try:
                paddle.seed(0)
                m = LlamaForCausalLM(llama_tiny_config())
                opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                             parameters=m.parameters())
                m, opt = paddle.amp.decorate(m, opt, level="O2",
                                             dtype="bfloat16",
                                             master_weight=False)
                ids = paddle.to_tensor(ids_np)
                out = []
                for _ in range(5):
                    with paddle.amp.auto_cast(enable=True, dtype="bfloat16",
                                              level="O2"):
                        loss = m(ids, ids)
                    loss.backward()
                    opt.step()
                    opt.clear_grad()
                    out.append(float(loss))
                return out
            finally:
                paddle.set_flags({"FLAGS_residual_dtype": "float32"})

        l32 = run("float32")
        l16 = run("bfloat16")
        assert all(np.isfinite(l16))
        assert l16[-1] < l16[0], "bf16 stream must still train"
        for a, b in zip(l32, l16):
            assert abs(a - b) / max(1.0, abs(a)) < 5e-3, (l32, l16)

    def test_flag_defaults(self):
        flags = paddle.get_flags(["FLAGS_residual_dtype",
                                  "FLAGS_pallas_fused_ops"])
        assert flags["FLAGS_residual_dtype"] == "float32"
        assert flags["FLAGS_pallas_fused_ops"] is True


def test_registered_in_quick_tier():
    import os

    src = open(os.path.join(os.path.dirname(__file__), "conftest.py")).read()
    assert '"test_pallas_norm.py"' in src.split("QUICK_MODULES")[1], \
        "tests/test_pallas_norm.py must be registered in QUICK_MODULES"
