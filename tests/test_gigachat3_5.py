"""`GigaChat35ForCausalLM` against the plain float32 reference of its
benchmark family (`benchmark/families/gigachat3_5.py`, which imports
nothing of the program and runs the Gated DeltaNet token by token), on
seeded weights at a small size with the norm gains and the SwiGLU clamp
made to bind: the Layer's forward, the chunked (WY) form against the
recurrence, padded rows, `gated_delta_decode` in the Pallas interpreter
against its composition, planted faults the comparison must see, the
expert layer's shares adding up to the whole layer, and
`paddle.LazyGuard`."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark import weights
from benchmark.families import gigachat3_5 as fam
from paddle_tpu.incubate.nn.functional import dropless_moe as moe
from paddle_tpu.ops import pallas_gated_delta as pgd
from paddle_tpu.text.models import gated_delta_block as gd
from paddle_tpu.text.models import latent_block as lb

#: a dense linear layer, two linear expert layers, one MLA expert layer
#: (published layers 0, 4-6 and 7 in the cell's cut, one linear fewer);
#: linear: 2 key and 4 value heads of 16; MLA: 4 heads of 16 + 8 (values
#: 16), ranks 24 and 32, YaRN over 64 original positions; 16 experts of
#: which rank 1 of 4 holds 4; the SwiGLU clamp at 2 so that it binds
TINY = {"name": "tiny", "family": "gigachat3_5", "hidden_size": 64,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "vocab_size": 256, "num_attention_heads": 4,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "q_lora_rank": 24, "kv_lora_rank": 32, "first_k_dense_replace": 1,
        "full_attention_layers": [3], "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_key_head_dim": 16,
        "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
        "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-6,
        "n_routed_experts": 4, "n_shared_experts": 1,
        "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
        "norm_topk_prob": True, "swiglu_limit": 2.0,
        "rope_theta": 100000,
        "rope_scaling": {"type": "yarn", "factor": 8, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 64},
        "expert_parallel": {"chips": 4, "rank": 1, "experts_total": 16},
        "rms_norm_eps": 1e-6, "num_hidden_layers": {"serve": 4},
        "max_position_embeddings": 256, "tie_word_embeddings": False,
        "dtype": "float32"}
LAYERS = 4


def tiny_model(seed, cfg=TINY, layers=LAYERS, scale=8.0, dt_bias=0.0):
    """(model, weights): the family's model with the seed's weights, the
    matrices `scale` times the benchmark's 0.02 (the layers move the
    logits as much as the residual does, and the clamp binds), every norm
    gain's w 10 times (0.2: (1 + w) is read), A_log drawn (A in 0.6-1.7)
    and `dt_bias` given (-4: slow decays, a state remembers)."""
    model = fam.build_model(cfg, layers, "serve")
    w = weights.make(fam.weight_spec(cfg, layers), seed, "float32")
    rng = np.random.default_rng(seed)
    w = {k: (v * scale if v.ndim > 1 else v * 10.0) for k, v in w.items()}
    for k in w:
        if k.endswith("A_log"):
            w[k] = jnp.asarray(0.3 * rng.standard_normal(w[k].shape),
                               jnp.float32)
        if k.endswith("dt_bias"):
            w[k] = jnp.full(w[k].shape, dt_bias, jnp.float32)
    weights.assign(model, w)
    return model, w


def _params(model):
    return jax.tree_util.tree_map(jnp.asarray, model.serving_arrays())


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 3])
def test_forward_agrees_with_the_reference(seed):
    """Logits of every position, float32. Tolerance 2e-4 on logits of
    size ~5: both sides are float32 at "highest" and differ in summation
    order and in the recurrence's form (the program's WY form, the
    reference's token by token; measured 5e-5); a planted fault (below)
    moves them by 1e-1 and more."""
    model, w = tiny_model(seed)
    ids = np.random.default_rng(seed).integers(0, 256, (2, 80))
    out = np.asarray(model(paddle.to_tensor(ids))._data)
    assert out.shape == (2, 80, 256) and out.dtype == np.float32
    for b in range(2):
        ref = fam.reference_rows(TINY, LAYERS, w, ids[b], np.arange(80))
        assert np.abs(ref).max() > 1.0
        np.testing.assert_allclose(out[b], ref, atol=2e-4, rtol=0)


def test_the_gains_and_the_clamp_bind():
    """What the comparison above can see: w of the gains is far from 0,
    and the clamp cuts a share of the dense layer's gate products."""
    model, w = tiny_model(11)
    assert np.abs(np.asarray(w["model.layers.0.input_layernorm.weight"])
                  ).max() > 0.3
    ids = np.random.default_rng(11).integers(0, 256, 64)
    x = np.asarray(w["model.embed_tokens.weight"])[ids]
    gate = x @ np.asarray(w["model.layers.0.mlp.gate_proj"])
    assert gate.std() > 0 and (np.abs(x @ np.asarray(
        w["model.layers.0.mlp.up_proj"])) > 0).all()
    params = _params(model)
    spec = model.config.block_spec()
    h = {}

    def spy(y, limit):
        h["share"] = float(jnp.mean(y > limit))
        return jnp.minimum(y, limit)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(lb, "clamp_gate", spy)
        gd.forward_sequence(params, jnp.asarray(ids), spec)
    assert 0.02 < h["share"] < 0.5


def _delta_case(t, seed=0, h=3, dk=16, dv=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    unit = lambda y: y / jnp.linalg.norm(y, axis=-1, keepdims=True)
    return (f(h, dk, dv), unit(f(t, h, dk)) / 4.0, unit(f(t, h, dk)),
            f(t, h, dv), jax.nn.sigmoid(f(t, h)),
            -jax.nn.softplus(f(t, h) - 2.0))


@pytest.mark.parametrize("t,chunk", [(150, 64), (64, 64), (40, 8)])
def test_the_chunked_form_agrees_with_the_recurrence(t, chunk):
    """The WY form a sub-chunk at a time against the rule token by token,
    from a state that is not zero, over lengths that end inside a
    sub-chunk (150 = 2 x 64 + 22) and on one: outputs and the final
    state to 2e-5 (float32 at HIGHEST either way, another order of
    sums)."""
    s, q, k, v, beta, g = _delta_case(t)
    o1, s1 = gd.recurrent(s, q, k, v, beta, g)
    o2, s2 = gd.chunked(s, q, k, v, beta, g, chunk)
    assert float(jnp.abs(o1).max()) > 0.3
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=2e-5)


def test_padded_rows_leave_the_state_bit_equal():
    """Rows with beta = g = 0 carry whatever q, k, v a bucket's padding
    holds; the state after them is the state without them's, bit for
    bit (their u is exactly 0 and their decay exactly 1)."""
    s, q, k, v, beta, g = _delta_case(40, seed=3)
    garbage = _delta_case(24, seed=4)
    pad = lambda a, b: jnp.concatenate([a, b])
    zeros = lambda a: jnp.zeros_like(a)
    _, s_zero = gd.chunked(s, pad(q, zeros(garbage[1])),
                           pad(k, zeros(garbage[2])),
                           pad(v, zeros(garbage[3])),
                           pad(beta, zeros(garbage[4])),
                           pad(g, zeros(garbage[5])), 64)
    _, s_junk = gd.chunked(s, pad(q, garbage[1]), pad(k, garbage[2]),
                           pad(v, garbage[3]), pad(beta, zeros(garbage[4])),
                           pad(g, zeros(garbage[5])), 64)
    np.testing.assert_array_equal(np.asarray(s_zero), np.asarray(s_junk))
    _, s_real = gd.chunked(s, q, k, v, beta, g, 64)
    np.testing.assert_allclose(np.asarray(s_zero), np.asarray(s_real),
                               atol=2e-6)


def test_the_decode_kernel_matches_its_composition():
    """`gated_delta_decode` in the Pallas interpreter against the jnp
    composition: 5 rows over a pool of 7 slots, two rows on the trash
    slot (6). Outputs and the rows' states to 1e-5 (float32, the same
    products in the same order); the slots no row names bit-equal."""
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    state = f(7, 8, 16, 128)
    slots = jnp.asarray([3, 0, 5, 6, 6], jnp.int32)
    args = (f(5, 8, 16), f(5, 8, 16), f(5, 8, 128),
            jax.nn.sigmoid(f(5, 8)), jnp.exp(-jax.nn.softplus(f(5, 8))))
    o1, s1 = pgd.gated_delta_decode_raw(state, slots, *args)
    o2, s2 = pgd.gated_delta_decode_xla(state, slots, *args)
    np.testing.assert_allclose(np.asarray(o1[:3]), np.asarray(o2[:3]),
                               atol=1e-5)
    for slot in (0, 3, 5):
        np.testing.assert_allclose(np.asarray(s1[slot]),
                                   np.asarray(s2[slot]), atol=1e-5)
    for slot in (1, 2, 4):
        np.testing.assert_array_equal(np.asarray(s1[slot]),
                                      np.asarray(state[slot]))
    assert pgd.head_group(64, 128, 128) == 32
    assert pgd.gate_reason((65, 64, 128, 128), "tpu")[1] == "warning"
    assert "not on TPU" in pgd.gate_reason((65, 64, 128, 128), "cpu")[0]


@pytest.mark.parametrize("fault", ["decay", "beta", "interleave", "gate"])
def test_a_planted_fault_is_seen_at_this_size(monkeypatch, fault):
    """The comparison above can see what it guards: the decay g left out
    (alpha = 1), beta left out (= 1), RoPE's pairs taken rotate-half, or
    the attention's output gate left out: the logits move by far more
    than its tolerance."""
    model, w = tiny_model(5)
    ids = np.random.default_rng(5).integers(0, 256, (1, 48))
    ref = fam.reference_rows(TINY, LAYERS, w, ids[0], np.arange(48))
    real = gd.delta_inputs
    if fault in ("decay", "beta"):
        def planted(*a, **k):
            q, kk, v, beta, g = real(*a, **k)
            if fault == "decay":
                return q, kk, v, beta, jnp.zeros_like(g)
            return q, kk, v, jnp.ones_like(beta), g
        monkeypatch.setattr(gd, "delta_inputs", planted)
    elif fault == "interleave":
        monkeypatch.setattr(lb, "apply_rope", lambda x, cos, sin, spec:
                            lb.rope_half(x, cos, sin))
    else:
        spec = model.config.block_spec()
        monkeypatch.setattr(
            type(model.config), "block_spec",
            lambda self: dataclasses.replace(spec, mla=dataclasses.replace(
                spec.mla, gated_attention=False)))
    jax.clear_caches()
    out = np.asarray(model(paddle.to_tensor(ids))._data)[0]
    jax.clear_caches()
    assert np.abs(out - ref).max() > 1e-2


def test_yarn_and_the_softmax_factor():
    """YaRN keeps the fast pairs and divides the slow ones by the factor;
    the softmax scale is (0.1 ln 8 + 1)^2 / sqrt(192) at the cell's
    widths."""
    from paddle_tpu.text.models import GigaChat35Config

    c = GigaChat35Config(num_hidden_layers=4, full_attention_layers=(3,))
    spec = c.block_spec().mla
    assert spec.rope_yarn == (8.0, 32768, 32.0, 1.0)
    assert spec.scale == pytest.approx((0.1 * np.log(8) + 1) ** 2
                                       / np.sqrt(192))
    base = 1.0 / (1e5 ** (np.arange(0, 64, 2) / 64))
    inv = lb.yarn_frequencies(base, 64, 1e5, *spec.rope_yarn)
    assert inv[0] == base[0] and inv[-1] == pytest.approx(base[-1] / 8)
    assert (np.diff(inv) < 0).all()
    cos, _ = lb.rope_for(spec, 4)
    ref_cos, _ = fam._rope_tables(
        {"qk_rope_head_dim": 64, "rope_theta": 1e5,
         "rope_scaling": {"factor": 8, "beta_fast": 32, "beta_slow": 1,
                          "original_max_position_embeddings": 32768}}, 4)
    np.testing.assert_allclose(cos[:, :32], ref_cos, atol=1e-6)


# ------------------------------------------------------------- the share

def _uncut_layer(h, lw, k, scale, limit):
    """The whole expert layer written out in numpy float64: every routed
    expert, gates scaled, the one shared expert unscaled, every SwiGLU
    clamped at `limit`."""
    f64 = lambda a: np.asarray(a, np.float64)
    silu = lambda a: a / (1.0 + np.exp(-a))

    def swiglu(x, wg, wu, wd):
        return (silu(np.minimum(x @ f64(wg), limit))
                * np.clip(x @ f64(wu), -limit, limit)) @ f64(wd)

    h = f64(h)
    s = 1.0 / (1.0 + np.exp(-(h @ f64(lw["router"]))))
    idx = np.argsort(-s, axis=-1)[:, :k]
    top = np.take_along_axis(s, idx, axis=-1)
    g = scale * top / top.sum(-1, keepdims=True)
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        for j in range(k):
            e = idx[t, j]
            out[t] += g[t, j] * swiglu(h[t], lw["experts_gate"][e],
                                       lw["experts_up"][e],
                                       lw["experts_down"][e])
    shared = swiglu(h, lw["shared_gate"], lw["shared_up"], lw["shared_down"])
    return out + shared, shared


def test_the_shares_of_4_ranks_add_up_to_the_uncut_layer():
    """4 ranks of 4 experts each, the clamp binding: the routed parts of
    all ranks, with the shared expert every rank computes alike counted
    ONCE, are the whole layer; the local picks add up to tokens x k."""
    rng = np.random.default_rng(3)
    h_dim, f, e, k, t, limit = 16, 8, 16, 4, 24, 1.0
    mk = lambda *shape: jnp.asarray(0.5 * rng.standard_normal(shape),
                                    jnp.float32)
    lw = {"router": mk(h_dim, e), "experts_gate": mk(e, h_dim, f),
          "experts_up": mk(e, h_dim, f), "experts_down": mk(e, f, h_dim),
          "shared_gate": mk(h_dim, f), "shared_up": mk(h_dim, f),
          "shared_down": mk(f, h_dim)}
    h = mk(t, h_dim)
    whole, shared = _uncut_layer(h, lw, k, 2.5, limit)
    assert (np.abs(np.asarray(h) @ np.asarray(lw["shared_up"])) > limit
            ).mean() > 0.1
    total, picks = np.zeros_like(whole), 0
    for rank in range(4):
        cut = slice(4 * rank, 4 * rank + 4)
        mine = dict(lw, **{n: lw[n][cut] for n in (
            "experts_gate", "experts_up", "experts_down")})
        out, n, _ = moe.moe_forward(h, mine, top_k=k, first_expert=4 * rank,
                                    num_local=4, num_shared=1,
                                    routed_scale=2.5, swiglu_limit=limit)
        total += np.asarray(out, np.float64) - shared
        picks += int(n)
    assert picks == t * k
    np.testing.assert_allclose(total + shared, whole, atol=2e-5, rtol=0)


def test_the_model_is_built_lazily_and_names_its_parameters():
    live = lambda: sum(a.nbytes for a in jax.live_arrays())
    before = live()
    model = fam.build_model(TINY, LAYERS, "serve")
    params = dict(model.named_parameters())
    assert sum(p.size for p in params.values()) > 100000
    assert live() - before < 1024        # shapes and no buffers
    assert set(params) == {n for n, _, _ in fam.weight_spec(TINY, LAYERS)}
    spec = model.config.block_spec()
    assert spec.layer_types == (("linear_attention", "dense"),
                                ("linear_attention", "experts"),
                                ("linear_attention", "experts"),
                                ("full_attention", "experts"))
    assert [gd.cache_kind(k) for k in spec.layer_types] == [
        "recurrent"] * 3 + ["latent"]
    assert spec.state_shape == (4, 16, 16) and spec.conv_shape == (3, 128)
    assert params["model.layers.0.linear_attn.in_proj_qkvz"].shape == [
        64, 2 * 2 * 16 + 2 * 4 * 16]
    assert params["model.layers.3.self_attn.gate_proj"].shape == [64, 64]
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        type(model.config)(tie_word_embeddings=True)
    # the Layer's own initialiser: A_log = log U(1, 16), dt_bias 0
    paddle.seed(1)
    eager = type(model)(model.config)
    a = np.asarray(eager.model.layers[0].linear_attn.A_log._data)
    assert (a >= 0).all() and (a <= np.log(16)).all() and a.std() > 0
    assert not np.asarray(
        eager.model.layers[0].linear_attn.dt_bias._data).any()
