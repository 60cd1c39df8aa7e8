"""`PanguUltraMoEForCausalLM` against the plain float32 reference of its
benchmark family (`benchmark/families/pangu_ultra_moe.py`, which imports
nothing of the program), on seeded weights at a small size: the Layer's
forward, the absorbed form against the expanded one, planted faults the
comparison must see, the expert layer's shares of a 32-chip deployment
adding up to the whole layer, and `paddle.LazyGuard`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark import weights
from benchmark.families import pangu_ultra_moe as fam
from paddle_tpu.incubate.nn.functional import dropless_moe as moe
from paddle_tpu.text.models import latent_block as lb

#: one dense layer then two expert layers; 4 heads of 16 + 8 (values 16),
#: ranks 24 and 32; 16 experts of which rank 1 of 4 holds 4
TINY = {"name": "tiny", "family": "pangu_ultra_moe", "hidden_size": 64,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "vocab_size": 256, "num_attention_heads": 4,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "q_lora_rank": 24, "kv_lora_rank": 32, "first_k_dense_replace": 1,
        "n_routed_experts": 4, "n_shared_experts": 1,
        "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
        "norm_topk_prob": True, "sandwich_norm": True,
        "expert_parallel": {"chips": 4, "rank": 1, "experts_total": 16},
        "rms_norm_eps": 1e-5, "rope_theta": 25600000,
        "num_hidden_layers": {"serve": 3}, "max_position_embeddings": 128,
        "tie_word_embeddings": False, "dtype": "float32"}
LAYERS = 3


def tiny_model(seed, cfg=TINY, layers=LAYERS, scale=8.0):
    """(model, weights): the family's model with the seed's weights, the
    matrices `scale` times the benchmark's 0.02 so that attention and the
    experts move the logits as much as the residual does."""
    model = fam.build_model(cfg, layers, "serve")
    w = weights.make(fam.weight_spec(cfg, layers), seed, "float32")
    w = {k: (v * scale if v.ndim > 1 else v) for k, v in w.items()}
    weights.assign(model, w)
    return model, w


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 3])
def test_forward_agrees_with_the_reference(seed, built):
    """Logits of every position, float32. Tolerance 5e-5 on logits of
    size ~5: both sides are float32 at "highest" and differ in summation
    order alone (measured 5e-6); a planted fault (below) moves them by
    1e-1."""
    model, w = built(seed)
    ids = np.random.default_rng(seed).integers(0, 256, (2, 48))
    out = np.asarray(model(paddle.to_tensor(ids))._data)
    assert out.shape == (2, 48, 256) and out.dtype == np.float32
    for b in range(2):
        ref = fam.reference_rows(TINY, LAYERS, w, ids[b], np.arange(48))
        assert np.abs(ref).max() > 1.0
        np.testing.assert_allclose(out[b], ref, atol=5e-5, rtol=0)


def _params(model):
    return jax.tree_util.tree_map(jnp.asarray, model.serving_arrays())


def test_the_absorbed_form_agrees_with_the_expanded_one():
    """Wuk folded into the query and Wuv applied after the sum: the same
    logits up to float32 summation order, over a sequence of two context
    blocks (patched to 32 positions) so that the online softmax merges."""
    model, _ = tiny_model(7)
    spec, params = model.config.block_spec(), _params(model)
    ids = jnp.asarray(np.random.default_rng(7).integers(0, 256, 48))
    with pytest.MonkeyPatch.context() as m:
        m.setattr(lb, "CTX_BLOCK", 32)
        exp = lb.forward_sequence(params, ids, spec, lb.expanded_attention)
        ab = lb.forward_sequence(params, ids, spec, lb.absorbed_attention)
    whole = lb.forward_sequence(params, ids, spec)      # one block
    assert float(jnp.abs(exp).max()) > 1.0
    np.testing.assert_allclose(np.asarray(ab), np.asarray(exp), atol=5e-5,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(exp),
                               atol=5e-5, rtol=0)


@pytest.mark.parametrize("fault", ["rotary", "sandwich", "routed_scale"])
def test_a_planted_fault_is_seen_at_this_size(monkeypatch, fault,
                                              fresh_programs, built):
    """The comparison above can see what it guards: with the rotary part
    of the score left out (q_rope . kr = 0), with the sandwich norms
    skipped (x + y in place of x + RMS(y)), or with the routed scale 2.5
    left out, the same logits are off by far more than its tolerance."""
    model, w = built(5)
    ids = np.random.default_rng(5).integers(0, 256, (1, 48))
    ref = fam.reference_rows(TINY, LAYERS, w, ids[0], np.arange(48))
    if fault == "rotary":
        monkeypatch.setattr(lb, "rope_half",
                            lambda x, cos, sin: jnp.zeros_like(x))
    elif fault == "sandwich":
        monkeypatch.setattr(
            lb, "sandwich_add",
            lambda x, y, gain, eps: (x + y.astype(x.dtype)))
    else:
        real = moe.route_sigmoid_topk
        monkeypatch.setattr(
            moe, "route_sigmoid_topk",
            lambda h, w_r, k, routed_scale=1.0: real(h, w_r, k))
    fresh_programs()                # the planted fault is traced anew
    out = np.asarray(model(paddle.to_tensor(ids))._data)[0]
    assert np.abs(out - ref).max() > 1e-2


# ------------------------------------------------------------- the share

def _uncut_layer(h, lw, k, scale):
    """The whole expert layer written out in numpy float64: every routed
    expert, gates scaled, the one shared expert unscaled."""
    f64 = lambda a: np.asarray(a, np.float64)
    silu = lambda a: a / (1.0 + np.exp(-a))
    h = f64(h)
    s = 1.0 / (1.0 + np.exp(-(h @ f64(lw["router"]))))
    idx = np.argsort(-s, axis=-1)[:, :k]
    top = np.take_along_axis(s, idx, axis=-1)
    g = scale * top / (top.sum(-1, keepdims=True) + 1e-20)
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        for j in range(k):
            e = idx[t, j]
            y = (silu(h[t] @ f64(lw["experts_gate"][e]))
                 * (h[t] @ f64(lw["experts_up"][e]))) \
                @ f64(lw["experts_down"][e])
            out[t] += g[t, j] * y
    shared = (silu(h @ f64(lw["shared_gate"])) * (h @ f64(lw["shared_up"]))) \
        @ f64(lw["shared_down"])
    return out + shared, shared


def test_the_shares_of_all_32_ranks_add_up_to_the_uncut_layer():
    """32 ranks of one expert each (the deployment's width, an expert a
    rank at this size): the routed parts of all ranks, with the shared
    expert every rank computes alike counted ONCE, are the whole layer;
    each rank's local picks add up to tokens x k."""
    rng = np.random.default_rng(3)
    h_dim, f, e, k, t = 16, 8, 32, 8, 24
    mk = lambda *shape: jnp.asarray(0.3 * rng.standard_normal(shape),
                                    jnp.float32)
    lw = {"router": mk(h_dim, e), "experts_gate": mk(e, h_dim, f),
          "experts_up": mk(e, h_dim, f), "experts_down": mk(e, f, h_dim),
          "shared_gate": mk(h_dim, f), "shared_up": mk(h_dim, f),
          "shared_down": mk(f, h_dim)}
    h = mk(t, h_dim)
    whole, shared = _uncut_layer(h, lw, k, 2.5)
    total, picks = np.zeros_like(whole), 0
    for rank in range(32):
        mine = dict(lw, **{n: lw[n][rank:rank + 1] for n in (
            "experts_gate", "experts_up", "experts_down")})
        out, n, *_ = moe.moe_forward(h, mine, top_k=k, first_expert=rank,
                                    num_local=1, num_shared=1,
                                    routed_scale=2.5)
        total += np.asarray(out, np.float64) - shared
        picks += int(n)
    assert picks == t * k
    np.testing.assert_allclose(total + shared, whole, atol=2e-5, rtol=0)
    # the scale is on the routed gates alone, and 1.0 is the old router
    idx, g = moe.route_sigmoid_topk(h, lw["router"], k, 2.5)
    idx1, g1 = moe.route_sigmoid_topk(h, lw["router"], k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx1))
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 2.5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g1.sum(-1)), 1.0, atol=1e-6)


def test_program_and_reference_hold_the_same_share():
    """Another rank (3 of 4, experts 12-15) through model and reference
    alike; and the reference of rank 1 is NOT rank 3's (the share is
    read, not ignored)."""
    cfg3 = dict(TINY, expert_parallel={"chips": 4, "rank": 3,
                                       "experts_total": 16})
    model, w = tiny_model(9, cfg3)
    assert model.config.first_expert == 12
    ids = np.random.default_rng(9).integers(0, 256, (1, 32))
    out = np.asarray(model(paddle.to_tensor(ids))._data)[0]
    ref3 = fam.reference_rows(cfg3, LAYERS, w, ids[0], np.arange(32))
    ref1 = fam.reference_rows(TINY, LAYERS, w, ids[0], np.arange(32))
    np.testing.assert_allclose(out, ref3, atol=5e-5, rtol=0)
    assert np.abs(ref1 - ref3).max() > 1e-2


def test_the_model_is_built_lazily_and_names_its_parameters():
    live = lambda: sum(a.nbytes for a in jax.live_arrays())
    before = live()
    model = fam.build_model(TINY, LAYERS, "serve")
    params = dict(model.named_parameters())
    assert sum(p.size for p in params.values()) > 100000
    assert live() - before < 1024        # shapes and no buffers (400 KB)
    assert set(params) == {n for n, _, _ in fam.weight_spec(TINY, LAYERS)}
    spec = model.config.block_spec()
    assert spec.layer_types == ("dense", "experts", "experts")
    assert (spec.latent_width, spec.head_dim, spec.num_kv_heads) == (40, 24, 1)
    assert "model.layers.0.mlp.gate_proj" in params
    assert "model.layers.1.mlp.experts.gate_proj" in params
    assert params["lm_head.weight"].shape == [256, 64]
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        type(model.config)(tie_word_embeddings=True)
