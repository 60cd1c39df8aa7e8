"""The grouped kernel of the held experts (`ops/pallas_grouped_experts.py`)
against the scan it replaces on the chip (`dropless_moe.local_experts`),
in the Pallas interpreter at float32; and the count of held experts a
block reads (`dropless_moe.local_load`) against numpy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.nn.functional import dropless_moe as moe
from paddle_tpu.ops import pallas_grouped_experts as pge

#: (rows, hidden, FFN width, held experts, experts in all, top-k, clamp,
#: routed scale, how the picks are made): the three served models' width
#: ratios scaled down (Cohere 4096/4096 with 16 held, Pangu 7680/2048 and
#: GigaChat 7168/2048 with 8 held, each a rank of an expert-parallel
#: deployment), decode buckets and a chunk, and an FFN width that takes
#: three grid steps
CASES = {
    "one_row": (1, 128, 128, 16, 128, 8, 0.0, 1.0, "routed"),
    "three_rows_most_experts_unpicked": (3, 256, 128, 8, 64, 8, 0.0, 2.5,
                                         "routed"),
    "cohere_decode16": (16, 128, 128, 16, 128, 8, 0.0, 1.0, "routed"),
    "gigachat_decode64_clamped": (64, 448, 128, 8, 256, 8, 10.0, 2.5,
                                  "routed"),
    "pangu_chunk512": (512, 480, 128, 8, 32, 8, 0.0, 2.5, "routed"),
    "cohere_chunk512_clamped": (512, 128, 128, 16, 32, 8, 10.0, 1.0,
                                "routed"),
    "all_picks_on_one_expert": (64, 128, 256, 8, 8, 1, 0.0, 1.0, "one"),
    "all_picks_on_one_expert_chunk": (512, 128, 128, 4, 4, 1, 0.0, 1.0,
                                      "one"),
    "ffn_in_three_blocks": (16, 128, 1536, 8, 128, 8, 10.0, 1.0, "routed"),
    "ffn_in_three_blocks_chunk": (256, 128, 1536, 8, 32, 8, 0.0, 1.0,
                                  "routed"),
    "padding_rows_unread": (16, 256, 128, 8, 16, 4, 10.0, 2.5, "padded"),
    "padding_rows_unread_chunk": (512, 128, 128, 8, 16, 4, 0.0, 1.0,
                                  "padded"),
}


def _case(rows, hidden, ffn, n, experts, k, scale, picks, seed):
    rng = np.random.default_rng(seed)

    def mk(*shape, s=1.0):
        return jnp.asarray(rng.standard_normal(shape) * s, jnp.float32)

    h = mk(rows, hidden)
    w = (mk(n, hidden, ffn, s=hidden ** -0.5), mk(n, hidden, ffn, s=2.0),
         mk(n, ffn, hidden, s=ffn ** -0.5))
    idx, gates = moe.route_sigmoid_topk(h, mk(hidden, experts), k, scale)
    gate = moe.local_gates(idx, gates, 0, n)
    valid = np.ones(rows, bool)
    if picks == "one":
        gate = jnp.zeros((rows, n), jnp.float32).at[:, 2].set(
            jnp.asarray(rng.uniform(0.1, 1.0, rows), jnp.float32))
    elif picks == "padded":
        valid[rows // 2 + 1:] = False
    return h, gate, w, jnp.asarray(valid)


@pytest.mark.parametrize("name", list(CASES))
def test_grouped_kernel_matches_the_scan(name):
    rows, hidden, ffn, n, experts, k, limit, scale, picks = CASES[name]
    h, gate, w, valid = _case(rows, hidden, ffn, n, experts, k, scale,
                              picks, seed=len(name))
    keep = np.asarray(valid)
    # the scan computes every row: its oracle leaves the padding's picks
    # out; the kernel is handed NaN there, which no valid row may see
    want = moe.local_experts(h, gate * valid[:, None], *w, limit)
    h_in = jnp.where(valid[:, None], h, jnp.nan)
    got = jax.jit(pge.grouped_experts, static_argnames="limit")(
        h_in, gate, *w, valid, limit=limit)
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    assert (got[~keep] == 0).all()
    size = np.abs(want).max()
    assert size > 0.1
    np.testing.assert_allclose(got[keep], want[keep], rtol=1e-5,
                               atol=1e-5 * size)
    picked = np.asarray((gate > 0) & valid[:, None]).any(0)
    if picks == "routed":
        # a call skips experts no row picked (in a decode tick most)
        assert rows > 16 or not picked.all()
    if limit:
        # the clamp binds somewhere, or the case would not test it
        up = np.einsum("th,nhf->ntf", np.asarray(h)[keep], np.asarray(w[1]))
        assert np.abs(up).max() > limit


def test_tiles_and_the_gate_follow_the_shapes():
    """A decode bucket's rows fit one tile (each expert's picks take one);
    a chunk's take tiles of 128; the FFN block keeps the three weight
    blocks of a step, double-buffered, within 32 MiB at the served
    widths; off the chip the scan runs."""
    assert pge.tiles(1, 4096, 4096, 2) == (16, 512)
    assert pge.tiles(64, 7168, 2048, 2) == (64, 256)
    assert pge.tiles(512, 7680, 2048, 2) == (128, 256)
    assert pge.tiles(3, 128, 128, 4) == (8, 128)
    assert pge.num_tiles(16, 16, 16) == 16
    assert pge.num_tiles(512, 8, 128) == 32
    h = jax.ShapeDtypeStruct((16, 4096), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((16, 4096, 4096), jnp.bfloat16)
    assert pge.grouped_gate_reason(h, w, "tpu")[1] == "warning"
    assert pge.grouped_gate_reason(h, w, "cpu")[1] == "note"
    assert not pge.use_grouped_experts(h, w)             # the CPU here
    odd = jax.ShapeDtypeStruct((16, 4096, 4000), jnp.bfloat16)
    assert "128 lanes" in pge.grouped_gate_reason(h, odd, "tpu")[0]
    f32 = jax.ShapeDtypeStruct((16, 4096), jnp.float32)
    assert "bfloat16" in pge.grouped_gate_reason(f32, w, "tpu")[0]


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 5])
def test_experts_read_counts_distinct_held_experts_picked(seed):
    """`read` is the number of held experts that some valid row picked:
    padding rows, whatever they picked, add none."""
    rng = np.random.default_rng(seed)
    rows, n, experts, k = 24, 8, 64, 8
    h = jnp.asarray(rng.standard_normal((rows, 32)), jnp.float32)
    idx, gates = moe.route_sigmoid_topk(
        h, jnp.asarray(rng.standard_normal((32, experts)), jnp.float32), k)
    gate = moe.local_gates(idx, gates, 8, n)
    valid = np.arange(rows) < 5
    picks, max_load, read = moe.local_load(gate, jnp.asarray(valid))
    local = np.asarray(idx)[valid]
    local = local[(local >= 8) & (local < 8 + n)]
    assert int(read) == len(np.unique(local))
    assert int(picks) == local.size
    assert int(max_load) == (np.bincount(local - 8).max() if local.size
                             else 0)
    # the padding's picks alone would have added experts
    _, _, every = moe.local_load(gate)
    assert int(every) >= int(read)
    assert int(moe.local_load(gate, jnp.zeros(rows, bool))[2]) == 0


def _rounded_as_the_scan(h, gate, w, limit):
    """The held experts written out in float32, rounded to bfloat16 where
    the scan's products and SwiGLU round on the chip: gate and up, the
    SiLU, their product, and down once a row."""
    def bf(v):
        return np.asarray(jnp.asarray(v, jnp.bfloat16), np.float32)

    x = np.asarray(h, np.float32)
    wg, wu, wd = (np.asarray(a, np.float32) for a in w)
    out = np.zeros((x.shape[0], wd.shape[-1]), np.float32)
    for e in range(wg.shape[0]):
        g, u = bf(x @ wg[e]), bf(x @ wu[e])
        a = bf(np.asarray(jax.nn.silu(
            np.minimum(g, limit) if limit else g)))
        s = bf(a * (np.clip(u, -limit, limit) if limit else u))
        out += np.asarray(gate)[:, e:e + 1] * bf(s @ wd[e])
    return out


@pytest.mark.parametrize("name", ["three_rows_most_experts_unpicked",
                                  "gigachat_decode64_clamped"])
def test_grouped_kernel_rounds_as_the_bf16_scan(name):
    """bfloat16 rows and weights, where every rounding in the kernel's
    body counts. Against the rounding written out: at most 0.5% of the
    outputs differ by more than 1e-3 of the largest (none did; a float32
    sum in another order can flip one rounding of a row), where leaving
    out any ONE rounding (gate, up, SiLU, product) moved 1.5-29% of them.
    Against the scan itself within 1e-2 of the largest: the CPU's scan
    rounds each step of the sigmoid too."""
    rows, hidden, ffn, n, experts, k, limit, scale, picks = CASES[name]
    h, gate, w, valid = _case(rows, hidden, ffn, n, experts, k, scale,
                              picks, seed=len(name))
    h = h.astype(jnp.bfloat16)
    w = tuple(a.astype(jnp.bfloat16) for a in w)
    got = np.asarray(jax.jit(pge.grouped_experts, static_argnames="limit")(
        h, gate, *w, valid, limit=limit))
    want = _rounded_as_the_scan(h, gate, w, limit)
    size = np.abs(want).max()
    assert size > 0.1
    assert (np.abs(got - want) > 1e-3 * size).mean() <= 5e-3
    np.testing.assert_allclose(
        got, np.asarray(moe.local_experts(h, gate, *w, limit)), rtol=0,
        atol=1e-2 * size)


@pytest.mark.parametrize("path", ["scan", "kernel"])
def test_moe_forward_counts_the_experts_its_path_reads(path, monkeypatch):
    """`read`: the held experts whose weights the layer read. The kernel
    fetches those some valid row picked; the scan (off the chip, or where
    `grouped_gate_reason` names a reason) reads every one, so
    `moe_experts_read_share` reads 100% wherever the scan ran. Both paths
    give the same layer."""
    rng = np.random.default_rng(7)

    def mk(*shape, s=1.0):
        return jnp.asarray(rng.standard_normal(shape) * s, jnp.float32)

    rows, hidden, ffn, n, experts = 4, 128, 128, 8, 64
    lw = {"router": mk(hidden, experts),
          "experts_gate": mk(n, hidden, ffn, s=hidden ** -0.5),
          "experts_up": mk(n, hidden, ffn, s=hidden ** -0.5),
          "experts_down": mk(n, ffn, hidden, s=ffn ** -0.5),
          "shared_gate": mk(hidden, ffn, s=hidden ** -0.5),
          "shared_up": mk(hidden, ffn, s=hidden ** -0.5),
          "shared_down": mk(ffn, hidden, s=ffn ** -0.5)}
    h = mk(rows, hidden)
    valid = jnp.asarray([True, True, True, False])
    kw = dict(top_k=8, first_expert=8, num_local=n, num_shared=1,
              valid=valid)
    scan_out = moe.moe_forward(h, lw, **kw)[0]
    if path == "kernel":
        monkeypatch.setattr(pge, "use_grouped_experts", lambda *a: True)
    out, picks, _, read = moe.moe_forward(h, lw, **kw)
    idx, _ = moe.route_sigmoid_topk(h, lw["router"], 8)
    mine = np.asarray(idx)[:3]
    mine = mine[(mine >= 8) & (mine < 8 + n)]
    assert int(picks) == mine.size > 0
    assert int(read) == (len(np.unique(mine)) if path == "kernel" else n)
    np.testing.assert_allclose(np.asarray(out)[:3],
                               np.asarray(scan_out)[:3], rtol=1e-5,
                               atol=1e-5)
