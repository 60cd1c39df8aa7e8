"""`Cohere2MoeForCausalLM` against the plain float32 reference of its
benchmark family (`benchmark/families/cohere2_moe.py`, which imports
nothing of the program), on seeded weights at a small size; the expert
layer's share of an expert-parallel deployment adding up to the whole
layer; and `paddle.LazyGuard`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from benchmark import weights
from benchmark.families import cohere2_moe as fam
from paddle_tpu.incubate.nn.functional import dropless_moe as moe
from paddle_tpu.text.models import parallel_block as pb

#: two periods, window 16, 16 experts of which rank 1 of 4 holds 4
TINY = {"name": "tiny", "family": "cohere2_moe", "hidden_size": 64,
        "intermediate_size": 32, "vocab_size": 256, "head_dim": 16,
        "num_attention_heads": 8, "num_key_value_heads": 2,
        "num_experts": 4, "num_experts_per_tok": 4, "num_shared_experts": 2,
        "expert_parallel": {"chips": 4, "rank": 1, "experts_total": 16},
        "sliding_window": 16, "layer_norm_eps": 1e-5, "rope_theta": 50000,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
        "num_hidden_layers": {"serve": 8}, "max_position_embeddings": 128,
        "logit_scale": 1, "tie_word_embeddings": True, "dtype": "float32"}
LAYERS = 8


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
def test_forward_agrees_with_the_reference(seed):
    """(a) Logits of every position, float32, sequences of 3 x the window
    over two periods. Tolerance 5e-5 on logits of size ~5: both sides are
    float32 at "highest" and differ in summation order alone (measured
    6e-6); a dropped window (below) moves them by 1e-1."""
    model, w = tiny_model(seed)
    ids = np.random.default_rng(seed).integers(0, 256, (2, 48))
    out = np.asarray(model(paddle.to_tensor(ids))._data)
    assert out.shape == (2, 48, 256) and out.dtype == np.float32
    for b in range(2):
        ref = fam.reference_rows(TINY, LAYERS, w, ids[b], np.arange(48))
        assert np.abs(ref).max() > 1.0
        np.testing.assert_allclose(out[b], ref, atol=5e-5, rtol=0)


def test_the_window_and_the_rotation_matter_at_this_size(monkeypatch):
    """The comparison above can see what it guards: without the window
    mask, or with half-split in place of interleaved rotation, the same
    logits are off by far more than its tolerance."""
    model, w = tiny_model(5)
    ids = np.random.default_rng(5).integers(0, 256, (1, 48))
    ref = fam.reference_rows(TINY, LAYERS, w, ids[0], np.arange(48))

    def off_by(patch, **kw):
        with monkeypatch.context() as m:
            m.setattr(pb, patch, kw["fn"])
            jax.clear_caches()
            out = np.asarray(model(paddle.to_tensor(ids))._data)[0]
        jax.clear_caches()
        return np.abs(out - ref).max()

    causal_only = lambda q, k, kind, window: q[:, None] >= k[None, :]
    assert off_by("visible", fn=causal_only) > 1e-2

    def half_split(x, cos, sin):
        x1, x2 = jnp.split(x, 2, axis=-1)
        c, s = cos[:, None, 0::2], sin[:, None, 0::2]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    assert off_by("rope_interleaved", fn=half_split) > 1e-2


# ------------------------------------------------------------- the share

def _layer_arrays(w, i=0):
    names = {"ln": "input_layernorm.weight", "router": "mlp.router.weight",
             "experts_gate": "mlp.experts.gate_proj",
             "experts_up": "mlp.experts.up_proj",
             "experts_down": "mlp.experts.down_proj",
             "shared_gate": "mlp.shared_experts.gate_proj",
             "shared_up": "mlp.shared_experts.up_proj",
             "shared_down": "mlp.shared_experts.down_proj"}
    return {k: w[f"model.layers.{i}.{n}"] for k, n in names.items()}


def _uncut_layer(h, lw, k, n_shared):
    """The whole expert layer written out in numpy float64: every routed
    expert, the shared experts averaged."""
    f64 = lambda a: np.asarray(a, np.float64)
    silu = lambda a: a / (1.0 + np.exp(-a))
    h = f64(h)
    s = 1.0 / (1.0 + np.exp(-(h @ f64(lw["router"]))))
    idx = np.argsort(-s, axis=-1)[:, :k]
    top = np.take_along_axis(s, idx, axis=-1)
    g = top / top.sum(-1, keepdims=True)
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        for j in range(k):
            e = idx[t, j]
            y = (silu(h[t] @ f64(lw["experts_gate"][e]))
                 * (h[t] @ f64(lw["experts_up"][e]))) \
                @ f64(lw["experts_down"][e])
            out[t] += g[t, j] * y
    f = lw["shared_gate"].shape[1] // n_shared
    shared = np.zeros_like(h)
    for j in range(n_shared):
        c = slice(j * f, (j + 1) * f)
        shared += (silu(h @ f64(lw["shared_gate"][:, c]))
                   * (h @ f64(lw["shared_up"][:, c]))) \
            @ f64(lw["shared_down"][c])
    return out, shared / n_shared, idx


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """(c) 4 ranks x 4 experts: the routed parts of all ranks plus the
    shared experts ONCE are the whole layer; the float32 router picks
    what the float64 one picks (0 differing picks)."""
    whole = dict(TINY, num_experts=16,
                 expert_parallel={"chips": 1, "rank": 0,
                                  "experts_total": 16})
    w = weights.make(fam.weight_spec(whole, 1), 77, "float32")
    lw = {k: v * 8.0 if v.ndim > 1 else v
          for k, v in _layer_arrays(w).items()}
    h = jnp.asarray(np.random.default_rng(0).standard_normal((40, 64)),
                    jnp.float32)
    routed_all, shared_mean, idx64 = _uncut_layer(h, lw, 4, 2)

    idx, gates = moe.route_sigmoid_topk(h, lw["router"], 4)
    differing = int((np.sort(np.asarray(idx), -1)
                     != np.sort(idx64, -1)).sum())
    print(f"router picks differing from float64: {differing}")
    assert differing == 0

    total = np.zeros_like(routed_all)
    for rank in range(4):
        held = slice(4 * rank, 4 * rank + 4)
        part = dict(lw, experts_gate=lw["experts_gate"][held],
                    experts_up=lw["experts_up"][held],
                    experts_down=lw["experts_down"][held])
        out, picks, max_load = moe.moe_forward(
            h, part, top_k=4, first_expert=4 * rank, num_local=4,
            num_shared=2)
        mine = np.isin(idx64, np.arange(4 * rank, 4 * rank + 4))
        assert int(picks) == mine.sum()
        assert int(max_load) == max(
            (idx64 == e).sum() for e in range(4 * rank, 4 * rank + 4))
        total += np.asarray(out, np.float64) - shared_mean
    np.testing.assert_allclose(total, routed_all, atol=2e-5, rtol=0)
    # the shared experts are averaged (a sum would be twice this) ...
    np.testing.assert_allclose(
        np.asarray(moe.shared_experts_mean(
            h, lw["shared_gate"], lw["shared_up"], lw["shared_down"], 2)),
        shared_mean, atol=2e-5, rtol=0)
    assert np.abs(shared_mean).max() > 1e-2
    # ... and the weights are normalised over all k picks, not over the
    # picks that land on one rank: a rank's gates sum to less than 1
    local = np.asarray(moe.local_gates(idx, gates, 4, 4)).sum(-1)
    assert (local < 1.0 - 1e-6).any() and (local <= 1.0 + 1e-6).all()


def test_the_program_and_the_reference_hold_the_same_share():
    """The model at rank 1 of 4 equals the reference given rank 1's
    experts, and differs from the reference given rank 0's."""
    model, w = tiny_model(3, layers=4)
    ids = np.random.default_rng(3).integers(0, 256, (1, 24))
    out = np.asarray(model(paddle.to_tensor(ids))._data)[0]
    ref = fam.reference_rows(TINY, 4, w, ids[0], np.arange(24))
    np.testing.assert_allclose(out, ref, atol=5e-5, rtol=0)
    rank0 = dict(TINY, expert_parallel=dict(TINY["expert_parallel"], rank=0))
    other = fam.reference_rows(rank0, 4, w, ids[0], np.arange(24))
    assert np.abs(out - other).max() > 1e-2


# -------------------------------------------------------------- LazyGuard

def _live_bytes():
    return sum(a.nbytes for a in jax.live_arrays())


class _Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.a = nn.Linear(64, 256)
        self.b = nn.Linear(256, 8)

    def forward(self, x):
        return self.b(self.a(x))


def test_lazy_guard_allocates_nothing_until_first_use():
    """(g) Built under the guard a Layer holds shapes, dtypes and no
    parameter buffer; first use materialises by the initializer with the
    values an eager build of the same seed gives; the RNG moves on as the
    eager build moves it."""
    paddle.seed(41)
    eager = _Net()
    want = {n: np.asarray(p._data) for n, p in eager.named_parameters()}
    after_eager = np.asarray(paddle.get_rng_state()[0])

    paddle.seed(41)
    before = _live_bytes()
    with paddle.LazyGuard():
        lazy = _Net()
    params = dict(lazy.named_parameters())
    # nothing but the RNG's own 16-byte key was made
    assert _live_bytes() - before < 64
    assert sum(p.size * 4 for p in params.values()) > 70000
    np.testing.assert_array_equal(paddle.get_rng_state()[0], after_eager)
    for n, p in params.items():
        assert p.shape == list(want[n].shape) and p.dtype == np.float32
    assert _live_bytes() - before < 64        # shape and dtype made nothing

    x = paddle.to_tensor(np.ones((2, 64), "float32"))
    out = lazy(x)                               # first use
    for n, p in params.items():
        np.testing.assert_array_equal(np.asarray(p._data), want[n])
    np.testing.assert_array_equal(np.asarray(out._data),
                                  np.asarray(eager(x)._data))
    np.testing.assert_array_equal(paddle.get_rng_state()[0], after_eager)


def test_lazy_guard_parameter_takes_assigned_weights():
    with paddle.LazyGuard():
        lazy = _Net()
    w = jnp.full((64, 256), 0.5, jnp.float32)
    before = _live_bytes()
    lazy.a.weight._data = w                     # as weights.assign does
    lazy.b.weight.set_value(np.full((256, 8), 0.25, "float32"))
    assert _live_bytes() - before < 256 * 8 * 4 + 64   # no initial value
    assert np.asarray(lazy.a.weight._data)[0, 0] == 0.5
    assert np.asarray(lazy.b.weight._data)[0, 0] == 0.25
    sd = {n: np.zeros(p.shape, "float32")
          for n, p in lazy.named_parameters()}
    lazy.set_state_dict(sd)
    assert float(np.abs(np.asarray(lazy.b.bias._data)).max()) == 0.0


def test_outside_the_guard_parameters_are_eager():
    with paddle.LazyGuard():
        pass
    net = _Net()
    assert isinstance(net.a.weight._data_buf, jax.Array)


def test_family_builds_the_model_without_buffers():
    before = _live_bytes()
    model = fam.build_model(TINY, 4, "serve")
    n = sum(p.size for p in model.parameters())
    assert n > 100000 and _live_bytes() - before < 4096   # keys, no buffer
    w = weights.make(fam.weight_spec(TINY, 4), 1, "float32")
    assert weights.assign(model, w) == n
