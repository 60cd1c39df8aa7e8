"""`Qwen3NextForCausalLM` against the plain float32 reference of its
benchmark family (`benchmark/families/qwen3_next.py`, which imports
nothing of the program, splits the projections index by index and runs
the Gated DeltaNet token by token), on seeded weights at a small size
with every gain drawn at random (so that a zero-centred gain read as a
plain one, or the reverse, moves the logits): the Layer's forward,
planted faults the comparison must see, the grouped projections against
the published ordering, the blocked attention against the whole one, the
expert shares adding up to the uncut layer, and `paddle.LazyGuard`."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark import weights
from benchmark.families import qwen3_next as fam
from paddle_tpu.incubate.nn.functional import dropless_moe as moe
from paddle_tpu.text.models import gated_attention_block as ga
from paddle_tpu.text.models import gated_delta_block as gd
from paddle_tpu.text.models import latent_block as lb
from paddle_tpu.text.models import parallel_block as pb

#: three linear layers and one full layer, all with experts; linear: 2
#: key and 4 value heads of 16; full: 4 heads of 32 on 2 KV heads, 8 of
#: the 32 dims rotated; 16 experts of which rank 1 of 4 holds 4, top-3,
#: a shared expert 48 wide
TINY = {"name": "tiny", "family": "qwen3_next", "hidden_size": 64,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 48,
        "vocab_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32,
        "partial_rotary_factor": 0.25, "full_attention_interval": 4,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 16, "linear_value_head_dim": 16,
        "linear_conv_kernel_dim": 4, "num_experts": 4,
        "num_experts_per_tok": 3, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "rope_theta": 10000,
        "expert_parallel": {"chips": 4, "rank": 1, "experts_total": 16},
        "rms_norm_eps": 1e-6, "num_hidden_layers": {"serve": 4},
        "max_position_embeddings": 256, "tie_word_embeddings": False,
        "dtype": "float32"}
LAYERS = 4


def tiny_model(seed, cfg=TINY, layers=LAYERS, scale=8.0, dt_bias=None):
    """(model, weights): the family's model with the seed's weights, the
    matrices `scale` times the benchmark's 0.02, every 1-D parameter
    (each gain's w, A_log, dt_bias) drawn 0.3 normal, or `dt_bias` given
    (-4: slow decays, a state remembers far)."""
    model = fam.build_model(cfg, layers, "serve")
    w = weights.make(fam.weight_spec(cfg, layers), seed, "float32")
    rng = np.random.default_rng(seed)
    w = {k: (v * scale if v.ndim > 1 else jnp.asarray(
        0.3 * rng.standard_normal(v.shape), jnp.float32))
        for k, v in w.items()}
    if dt_bias is not None:
        for k in w:
            if k.endswith("dt_bias"):
                w[k] = jnp.full(w[k].shape, dt_bias, jnp.float32)
    weights.assign(model, w)
    return model, w


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 3])
def test_forward_agrees_with_the_reference(seed, built):
    """Logits of every position, float32. Tolerance 2e-4 on logits of
    size ~5: both sides are float32 at "highest" and differ in summation
    order and in the recurrence's form (the program's WY form, the
    reference's token by token; measured 6e-5); a planted fault (below)
    moves them by 1e-2 and more."""
    model, w = built(seed)
    ids = np.random.default_rng(seed).integers(0, 256, (2, 80))
    out = np.asarray(model(paddle.to_tensor(ids))._data)
    assert out.shape == (2, 80, 256) and out.dtype == np.float32
    for b in range(2):
        ref = fam.reference_rows(TINY, LAYERS, w, ids[b], np.arange(80))
        assert np.abs(ref).max() > 1.0
        np.testing.assert_allclose(out[b], ref, atol=2e-4, rtol=0)


def _patch_spec(monkeypatch, full=False, **kw):
    """The model's block spec with `kw` replaced (in its `full` spec with
    `full`)."""
    cls = paddle.text.models.Qwen3NextConfig
    real = cls.block_spec

    def block_spec(self):
        spec = real(self)
        if full:
            return dataclasses.replace(
                spec, full=dataclasses.replace(spec.full, **kw))
        return dataclasses.replace(spec, **kw)
    monkeypatch.setattr(cls, "block_spec", block_spec)


def _plant(fault, monkeypatch):
    """Patch the program with one fault of the kind a port makes."""
    if fault == "rope_all_dims":
        _patch_spec(monkeypatch, full=True, rope_dim=TINY["head_dim"])
    elif fault == "no_shared_gate":
        monkeypatch.setattr(moe, "shared_expert_gate",
                            lambda h, w: jnp.ones((h.shape[0], 1)))
    elif fault == "qk_norm_plain_gain":
        real = lb.gain
        monkeypatch.setattr(lb, "gain", lambda w, spec: (
            w if w.shape[-1] == TINY["head_dim"] else real(w, spec)))
    elif fault == "sigmoid_router":
        monkeypatch.setattr(moe, "route_softmax_topk",
                            lambda h, w, k: moe.route_sigmoid_topk(h, w, k))
    elif fault == "zero_centred_out_gate":
        monkeypatch.setattr(gd, "gate_silu", gd.gate_sigmoid)
    elif fault == "flat_projections":
        monkeypatch.setattr(gd, "split_grouped", gd.split_plain)


FAULTS = ("rope_all_dims", "no_shared_gate", "qk_norm_plain_gain",
          "sigmoid_router", "zero_centred_out_gate", "flat_projections")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(fault, built, monkeypatch,
                                              fresh_programs):
    """Each fault moves the logits far past the 2e-4 the program keeps:
    RoPE on every dim of a head, the shared expert's gate left out, the
    q/k norms' (1 + w) read as w, routing by sigmoid, the linear layers'
    output norm as the latent hybrid has it ((1 + w) sigmoid(z) for the
    plain gain and silu(z)), the grouped projections read as plain
    concatenations."""
    model, w = built(11)
    ids = np.random.default_rng(3).integers(0, 256, (1, 48))
    ref = fam.reference_rows(TINY, LAYERS, w, ids[0], np.arange(48))
    _plant(fault, monkeypatch)
    fresh_programs()                # the planted fault is traced anew
    out = np.asarray(model(paddle.to_tensor(ids))._data)[0]
    assert np.abs(out - ref).max() > 1e-2


def test_the_grouped_split_follows_the_published_ordering():
    """`gated_delta_block.split_grouped` against an index-by-index reading
    of transformers' `fix_query_key_value_ordering` (numpy, loops): key
    head g's block is [q | k | v of its r value heads | z of them], and
    [b | a] of them; the conv's input is [q | k | v], key-head-major."""
    spec = paddle.text.models.Qwen3NextConfig(
        num_hidden_layers=4, linear_num_key_heads=3,
        linear_num_value_heads=6, linear_key_head_dim=4,
        linear_value_head_dim=5).block_spec()
    nk, nv, dk, dv, r = 3, 6, 4, 5, 2
    rng = np.random.default_rng(0)
    qkvz = rng.standard_normal((7, nk * (2 * dk + 2 * r * dv)))
    ba = rng.standard_normal((7, nk * 2 * r))
    mixed, z, b, a = (np.asarray(y) for y in gd.split_grouped(
        jnp.asarray(qkvz), jnp.asarray(ba), spec))
    width = 2 * dk + 2 * r * dv
    for t in range(7):
        for g in range(nk):
            blk = qkvz[t, g * width:(g + 1) * width]
            np.testing.assert_array_equal(mixed[t, g * dk:(g + 1) * dk],
                                          blk[:dk])
            np.testing.assert_array_equal(
                mixed[t, nk * dk + g * dk:nk * dk + (g + 1) * dk],
                blk[dk:2 * dk])
            for i in range(r):
                head = g * r + i
                v_at = 2 * nk * dk + head * dv
                np.testing.assert_array_equal(
                    mixed[t, v_at:v_at + dv],
                    blk[2 * dk + i * dv:2 * dk + (i + 1) * dv])
                z0 = 2 * dk + r * dv
                np.testing.assert_array_equal(
                    z[t, head * dv:(head + 1) * dv],
                    blk[z0 + i * dv:z0 + (i + 1) * dv])
                assert b[t, head] == ba[t, g * 2 * r + i]
                assert a[t, head] == ba[t, g * 2 * r + r + i]
    # the family's reading of the same numbers, head by head
    cfg = dict(TINY, linear_num_key_heads=nk, linear_num_value_heads=nv,
               linear_key_head_dim=dk, linear_value_head_dim=dv)
    q, k, v, zz, bb, aa = (np.asarray(y) for y in
                           fam.fix_query_key_value_ordering(
                               jnp.asarray(qkvz), jnp.asarray(ba), cfg))
    np.testing.assert_array_equal(mixed[:, :nk * dk], q.reshape(7, -1))
    np.testing.assert_array_equal(mixed[:, 2 * nk * dk:], v.reshape(7, -1))
    np.testing.assert_array_equal(z, zz.reshape(7, -1))
    np.testing.assert_array_equal(b, bb)
    np.testing.assert_array_equal(a, aa)
    del k


@pytest.mark.parametrize("start,n_blocks", [(0, 1), (40, 4), (57, 5)])
def test_blocked_attention_equals_the_whole_one(start, n_blocks):
    """`gated_attention_block.blocked_attention` a block of 16 positions
    at a time with an online softmax, against the whole scores of
    `parallel_block.grouped_attention`, for queries at positions
    [start, start + 23) of a context that runs past them (a later row
    holds garbage the causal mask must hide): to 1e-5 in float32."""
    rng = np.random.default_rng(start)
    nq, nh, nkv, d, block = 23, 8, 2, 32, 16
    ctx = n_blocks * block
    q = jnp.asarray(rng.standard_normal((nq, nh, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((ctx, nkv, d)), jnp.float32)
            for _ in range(2))
    pos = start + jnp.arange(nq)
    need = min(-(-(start + nq) // block), n_blocks)
    got = ga.blocked_attention(
        q, lambda j: (jax.lax.dynamic_slice_in_dim(k, j * block, block),
                      jax.lax.dynamic_slice_in_dim(v, j * block, block)),
        jnp.int32(need), block, pos, nkv)
    rows = pos < ctx
    want = pb.grouped_attention(q, k, v,
                                pos[:, None] >= jnp.arange(ctx)[None, :])
    np.testing.assert_allclose(np.asarray(got)[np.asarray(rows)],
                               np.asarray(want)[np.asarray(rows)],
                               atol=1e-5, rtol=0)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Two ranks of 8 experts each (the cell's cut: 256 of 512, rank 0 of
    2) against the layer holding all 16: the routed parts add up, and
    the gated shared expert, which every rank computes alike, is counted
    once. The program's layer and the reference's, each."""
    rng = np.random.default_rng(5)
    h, f, e, k = 32, 16, 16, 4
    lw = {"router": rng.standard_normal((h, e)),
          "experts_gate": rng.standard_normal((e, h, f)) / 4,
          "experts_up": rng.standard_normal((e, h, f)) / 4,
          "experts_down": rng.standard_normal((e, f, h)) / 4,
          "shared_gate": rng.standard_normal((h, 24)) / 4,
          "shared_up": rng.standard_normal((h, 24)) / 4,
          "shared_down": rng.standard_normal((24, h)) / 4,
          "shared_expert_gate": rng.standard_normal((h, 1))}
    lw = {n: jnp.asarray(a, jnp.float32) for n, a in lw.items()}
    x = jnp.asarray(rng.standard_normal((10, h)), jnp.float32)

    def part(first, n):
        sub = dict(lw, experts_gate=lw["experts_gate"][first:first + n],
                   experts_up=lw["experts_up"][first:first + n],
                   experts_down=lw["experts_down"][first:first + n])
        return np.asarray(moe.moe_forward(
            x, sub, top_k=k, first_expert=first, num_local=n, num_shared=1,
            router="softmax", shared_gate=True)[0])

    shared = np.asarray(moe.shared_expert_gate(x, lw["shared_expert_gate"])
                        * moe.shared_experts_mean(
                            x, lw["shared_gate"], lw["shared_up"],
                            lw["shared_down"], 1))
    whole = part(0, 16)
    np.testing.assert_allclose(part(0, 8) + part(8, 8) - shared, whole,
                               atol=1e-4, rtol=0)
    assert np.abs(part(8, 8) - shared).max() > 0.1

    # the reference's expert layer, its rows through one FFN block
    cfg = dict(TINY, hidden_size=h, moe_intermediate_size=f,
               num_experts_per_tok=k, rms_norm_eps=1e-6)

    def ref(rank, held):
        c = dict(cfg, num_experts=held,
                 expert_parallel={"chips": 16 // held, "rank": rank,
                                  "experts_total": 16})
        fw = {"router": lw["router"], "sgate": lw["shared_gate"],
              "sup": lw["shared_up"], "sdown": lw["shared_down"],
              "sg": lw["shared_expert_gate"],
              "gate": lw["experts_gate"][rank * held:(rank + 1) * held],
              "up": lw["experts_up"][rank * held:(rank + 1) * held],
              "down": lw["experts_down"][rank * held:(rank + 1) * held],
              "ln_post": jnp.zeros((h,), jnp.float32)}
        with jax.default_matmul_precision("highest"):
            return np.asarray(fam._ffn(c, fam._mm("f32"))(x, fw)) \
                - np.asarray(x)

    xn = np.asarray(x) / np.sqrt(np.mean(np.asarray(x) ** 2, -1,
                                         keepdims=True) + 1e-6)
    ref_shared = np.asarray(moe.shared_expert_gate(
        jnp.asarray(xn), lw["shared_expert_gate"]) * moe.shared_experts_mean(
            jnp.asarray(xn), lw["shared_gate"], lw["shared_up"],
            lw["shared_down"], 1))
    np.testing.assert_allclose(ref(0, 8) + ref(1, 8) - ref_shared,
                               ref(0, 16), atol=1e-4, rtol=0)


def test_the_model_is_built_lazily_and_names_its_parameters():
    live = lambda: sum(a.nbytes for a in jax.live_arrays())
    before = live()
    model = fam.build_model(TINY, LAYERS, "serve")
    params = dict(model.named_parameters())
    assert sum(p.size for p in params.values()) > 100000
    assert live() - before < 1024        # shapes and no buffers
    assert set(params) == {n for n, _, _ in fam.weight_spec(TINY, LAYERS)}
    spec = model.config.block_spec()
    assert spec.layer_types == (("linear_attention", "experts"),) * 3 + (
        ("full_attention", "experts"),)
    assert [gd.cache_kind(k, spec) for k in spec.layer_types] == [
        "recurrent"] * 3 + ["full_attention"]
    assert (spec.num_kv_heads, spec.head_dim, spec.full.rope_dim) == (
        2, 32, 8)
    assert params["model.layers.3.self_attn.q_proj"].shape == [64, 4 * 64]
    assert params["model.layers.0.mlp.shared_expert_gate.weight"].shape \
        == [64, 1]
    with pytest.raises(ValueError, match="mlp_only_layers"):
        type(model.config)(mlp_only_layers=(1,))
    # the Layer's own initialiser: A_log = log U(1, 16), dt_bias and the
    # output norm's plain gain 1, every zero-centred gain 0
    paddle.seed(1)
    cfg = type(model.config)(
        hidden_size=16, num_hidden_layers=4, num_attention_heads=2,
        num_key_value_heads=1, head_dim=8, linear_num_key_heads=1,
        linear_num_value_heads=2, linear_key_head_dim=8,
        linear_value_head_dim=8, num_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=8, shared_expert_intermediate_size=8,
        vocab_size=32)
    eager = type(model)(cfg)
    lin = eager.model.layers[0].linear_attn
    a = np.asarray(lin.A_log._data)
    assert (a >= 0).all() and (a <= np.log(16)).all() and a.std() > 0
    assert (np.asarray(lin.dt_bias._data) == 1).all()
    assert (np.asarray(lin.norm._data) == 1).all()
    assert not np.asarray(eager.model.layers[3].self_attn.q_norm._data).any()
    assert not np.asarray(eager.model.norm.weight._data).any()
