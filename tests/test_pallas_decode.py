"""Paged flash-decode kernel tests (round 10).

Interpret-mode parity of ops/pallas_decode.py's Pallas kernel against the
XLA gather+softmax composition (the numerics oracle) and a dense NumPy
reference: f32 ≤ 5e-5, bf16 tiered, GQA packing, int8-KV per-block
scales. Plus the routing gates shared with analysis D4.
"""
import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401  (flag registry + x64 init)
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas_decode import (decode_gate_reason,
                                          paged_decode_attention,
                                          paged_decode_attention_raw,
                                          paged_decode_attention_xla,
                                          use_pallas_decode)


def _setup(s=3, hq=8, hkv=2, d=128, bs=8, pages=4, blocks=16,
           dtype="float32", lens=None, seed=0):
    """Random paged cache + disjoint block tables (block 0 left as trash,
    like the engine allocates)."""
    rs = np.random.RandomState(seed)
    q = rs.randn(s, hq, d).astype("float32")
    kc = rs.randn(blocks, hkv, bs, d).astype("float32")
    vc = rs.randn(blocks, hkv, bs, d).astype("float32")
    ids = rs.choice(np.arange(1, blocks), (s * pages,), replace=False)
    tables = ids.reshape(s, pages).astype("int32")
    if lens is None:
        lens = rs.randint(1, pages * bs + 1, (s,))
    lens = np.asarray(lens, "int32")
    cast = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    return (jnp.asarray(q, cast), jnp.asarray(kc, cast),
            jnp.asarray(vc, cast), jnp.asarray(tables), jnp.asarray(lens))


def _dense_reference(q, kc, vc, tables, lens):
    """O(T) NumPy oracle: walk each sequence's block table token by
    token."""
    q, kc, vc = (np.asarray(x, "float32") for x in (q, kc, vc))
    tables, lens = np.asarray(tables), np.asarray(lens)
    s, hq, d = q.shape
    _, hkv, bs, _ = kc.shape
    rep = hq // hkv
    out = np.zeros((s, hq, d), "float32")
    for b in range(s):
        ks, vs = [], []
        for t in range(lens[b]):
            blk = tables[b, t // bs]
            ks.append(kc[blk, :, t % bs])
            vs.append(vc[blk, :, t % bs])
        ks = np.repeat(np.stack(ks), rep, axis=1)       # [T, Hq, D]
        vs = np.repeat(np.stack(vs), rep, axis=1)
        sc = np.einsum("hd,thd->ht", q[b], ks) / np.sqrt(d)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        out[b] = np.einsum("ht,thd->hd", p, vs)
    return out


def _quantize_per_block(c):
    """Per-block symmetric int8, the paged_cache scale convention."""
    c = np.asarray(c, "float32")
    scale = np.maximum(np.abs(c).max(axis=(1, 2, 3)) / 127.0, 1e-8)
    q8 = np.clip(np.round(c / scale[:, None, None, None]), -127,
                 127).astype("int8")
    return jnp.asarray(q8), jnp.asarray(scale.astype("float32"))


class TestInterpretParity:
    def test_f32_kernel_matches_xla_and_dense(self):
        q, kc, vc, tables, lens = _setup()
        got = np.asarray(paged_decode_attention_raw(q, kc, vc, tables,
                                                    lens), "float32")
        xla = np.asarray(paged_decode_attention_xla(q, kc, vc, tables,
                                                    lens), "float32")
        np.testing.assert_allclose(got, xla, atol=5e-5, rtol=5e-5)
        np.testing.assert_allclose(got, _dense_reference(q, kc, vc, tables,
                                                         lens),
                                   atol=5e-5, rtol=5e-5)

    def test_bf16_tiered(self):
        q, kc, vc, tables, lens = _setup(dtype="bfloat16")
        got = np.asarray(paged_decode_attention_raw(q, kc, vc, tables,
                                                    lens), "float32")
        ref = _dense_reference(q, kc, vc, tables, lens)
        # bf16 inputs, f32 accumulation: bounded by input rounding
        np.testing.assert_allclose(got, ref, atol=3e-2, rtol=3e-2)

    def test_gqa_packing(self):
        # 16 query heads over 4 kv heads: one [group, D] MXU tile each
        q, kc, vc, tables, lens = _setup(hq=16, hkv=4)
        got = np.asarray(paged_decode_attention_raw(q, kc, vc, tables,
                                                    lens), "float32")
        np.testing.assert_allclose(got, _dense_reference(q, kc, vc, tables,
                                                         lens),
                                   atol=5e-5, rtol=5e-5)

    def test_mha_group_of_one(self):
        q, kc, vc, tables, lens = _setup(hq=4, hkv=4)
        got = np.asarray(paged_decode_attention_raw(q, kc, vc, tables,
                                                    lens), "float32")
        np.testing.assert_allclose(got, _dense_reference(q, kc, vc, tables,
                                                         lens),
                                   atol=5e-5, rtol=5e-5)

    def test_single_token_and_full_cache_lens(self):
        # boundary lengths: 1 (one masked block) and pages*bs (no mask)
        q, kc, vc, tables, lens = _setup(lens=[1, 32, 17])
        got = np.asarray(paged_decode_attention_raw(q, kc, vc, tables,
                                                    lens), "float32")
        np.testing.assert_allclose(got, _dense_reference(q, kc, vc, tables,
                                                         lens),
                                   atol=5e-5, rtol=5e-5)

    def test_negative_table_padding_tolerated(self):
        q, kc, vc, tables, lens = _setup(lens=[5, 9, 3])
        tab = np.asarray(tables).copy()
        tab[:, 2:] = -1                   # pages past the data: padding
        got = np.asarray(paged_decode_attention_raw(
            q, kc, vc, jnp.asarray(tab), lens), "float32")
        want = np.asarray(paged_decode_attention_raw(q, kc, vc, tables,
                                                     lens), "float32")
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)

    def test_jit_wrapped(self):
        q, kc, vc, tables, lens = _setup()
        got = np.asarray(jax.jit(paged_decode_attention_raw)(
            q, kc, vc, tables, lens), "float32")
        np.testing.assert_allclose(got, _dense_reference(q, kc, vc, tables,
                                                         lens),
                                   atol=5e-5, rtol=5e-5)


class TestInt8KV:
    def test_int8_kernel_matches_int8_xla(self):
        q, kc, vc, tables, lens = _setup()
        k8, ks = _quantize_per_block(kc)
        v8, vs = _quantize_per_block(vc)
        got = np.asarray(paged_decode_attention_raw(q, k8, v8, tables,
                                                    lens, ks, vs),
                         "float32")
        xla = np.asarray(paged_decode_attention_xla(q, k8, v8, tables,
                                                    lens, ks, vs),
                         "float32")
        # same dequant math, f32 vs f32: kernel-vs-composition stays tight
        np.testing.assert_allclose(got, xla, atol=5e-5, rtol=5e-5)

    def test_int8_close_to_f32(self):
        q, kc, vc, tables, lens = _setup()
        k8, ks = _quantize_per_block(kc)
        v8, vs = _quantize_per_block(vc)
        got = np.asarray(paged_decode_attention_raw(q, k8, v8, tables,
                                                    lens, ks, vs),
                         "float32")
        ref = _dense_reference(q, kc, vc, tables, lens)
        np.testing.assert_allclose(got, ref, atol=8e-2, rtol=8e-2)

    def test_int8_gqa(self):
        q, kc, vc, tables, lens = _setup(hq=16, hkv=4)
        k8, ks = _quantize_per_block(kc)
        v8, vs = _quantize_per_block(vc)
        got = np.asarray(paged_decode_attention_raw(q, k8, v8, tables,
                                                    lens, ks, vs),
                         "float32")
        xla = np.asarray(paged_decode_attention_xla(q, k8, v8, tables,
                                                    lens, ks, vs),
                         "float32")
        np.testing.assert_allclose(got, xla, atol=5e-5, rtol=5e-5)


def _quantize_int4_per_block(c):
    """Per-block symmetric int4, packed split-half along tokens as the
    paged cache stores it (ops/quantized.int4_pack)."""
    from paddle_tpu.ops.quantized import int4_pack

    c = np.asarray(c, "float32")
    scale = np.maximum(np.abs(c).max(axis=(1, 2, 3)) / 7.0, 1e-8)
    q4 = np.clip(np.round(c / scale[:, None, None, None]), -7, 7)
    return (int4_pack(jnp.asarray(q4, jnp.int8), axis=2),
            jnp.asarray(scale.astype("float32")))


# compute blocks of PPS pages of BS tokens over PAGES pages: PPS does not
# divide PAGES, so the last compute block is short of a page
BS, PPS, PAGES = 8, 4, 10
BLOCK = BS * PPS


class TestComputeBlocks:
    """What streaming several pages a step can get wrong: the edges of a
    page and of a compute block, the hand-over of the double buffer from
    one slot to the next, tables that are not a run of fresh blocks."""

    @pytest.mark.parametrize("n", [
        1, BS - 1, BS, BS + 1, BLOCK - 1, BLOCK, BLOCK + 1,
        2 * BLOCK - 1, 2 * BLOCK, 2 * BLOCK + 1, PAGES * BS],
        ids=lambda n: f"len{n}")
    def test_length_edges(self, n):
        # the probed length sits between two others, so its first block is
        # prefetched by a neighbour's last and it prefetches a neighbour's
        q, kc, vc, tables, lens = _setup(s=3, pages=PAGES, blocks=40, bs=BS,
                                         lens=[PAGES * BS - 3, n, 13])
        got = np.asarray(paged_decode_attention_raw(
            q, kc, vc, tables, lens, pages_per_step_=PPS), "float32")
        xla = np.asarray(paged_decode_attention_xla(q, kc, vc, tables,
                                                    lens), "float32")
        np.testing.assert_allclose(got, xla, atol=5e-5, rtol=5e-5)
        np.testing.assert_allclose(got, _dense_reference(q, kc, vc, tables,
                                                         lens),
                                   atol=5e-5, rtol=5e-5)

    @pytest.mark.parametrize("pps", [1, 3, 4, 16], ids=lambda p: f"pps{p}")
    def test_any_block_size_same_answer(self, pps):
        # 1: the all-heads copy alone; 3, 4: do not divide 10 pages; 16:
        # more than the table holds
        q, kc, vc, tables, lens = _setup(s=4, pages=PAGES, blocks=48, bs=BS,
                                         seed=3)
        got = np.asarray(paged_decode_attention_raw(
            q, kc, vc, tables, lens, pages_per_step_=min(pps, PAGES)),
            "float32")
        np.testing.assert_allclose(got, _dense_reference(q, kc, vc, tables,
                                                         lens),
                                   atol=5e-5, rtol=5e-5)

    def test_derived_block_covers_small_table(self):
        from paddle_tpu.ops.pallas_decode import kv_steps, pages_per_step

        # the test geometry fits VMEM whole: one step a slot
        assert pages_per_step(PAGES, BS, 2, 128, 4) == PAGES
        assert kv_steps(3, PAGES, BS, 2, 128, 4) == 3
        # one page already over the share: one page a step, all heads
        assert pages_per_step(64, 512, 32, 128, 4) == 1

    def test_permuted_and_shared_pages(self):
        # prefix-cache sharing: slots 0 and 1 read the SAME first pages,
        # slot 2's table runs backwards through the pool
        q, kc, vc, tables, lens = _setup(s=3, pages=PAGES, blocks=40, bs=BS,
                                         lens=[70, 45, 80], seed=5)
        tab = np.asarray(tables).copy()
        tab[1, :5] = tab[0, :5]
        tab[2] = np.sort(tab[2])[::-1]
        tab = jnp.asarray(tab)
        got = np.asarray(paged_decode_attention_raw(
            q, kc, vc, tab, lens, pages_per_step_=PPS), "float32")
        np.testing.assert_allclose(got, _dense_reference(q, kc, vc, tab,
                                                         lens),
                                   atol=5e-5, rtol=5e-5)

    def test_padded_rows_beside_live_ones(self):
        # the engine pads a slot bucket with rows whose table is all
        # TRASH_BLOCK and whose pos is 0 (lens = 1), before, between and
        # after live rows
        from paddle_tpu.text.paged_cache import TRASH_BLOCK

        q, kc, vc, tables, lens = _setup(s=5, pages=PAGES, blocks=56, bs=BS,
                                         lens=[1, 77, 1, 33, 1], seed=7)
        tab = np.asarray(tables).copy()
        tab[[0, 2, 4]] = TRASH_BLOCK
        tab = jnp.asarray(tab)
        got = np.asarray(paged_decode_attention_raw(
            q, kc, vc, tab, lens, pages_per_step_=PPS), "float32")
        np.testing.assert_allclose(got, _dense_reference(q, kc, vc, tab,
                                                         lens),
                                   atol=5e-5, rtol=5e-5)

    def test_zero_length_row_reads_zero(self):
        q, kc, vc, tables, lens = _setup(s=3, pages=PAGES, blocks=40, bs=BS,
                                         lens=[40, 0, 9])
        got = np.asarray(paged_decode_attention_raw(
            q, kc, vc, tables, lens, pages_per_step_=PPS), "float32")
        ref = _dense_reference(q, kc, vc, tables, np.maximum(lens, 1))
        np.testing.assert_allclose(got[[0, 2]], ref[[0, 2]], atol=5e-5,
                                   rtol=5e-5)
        np.testing.assert_array_equal(got[1], 0.0)

    def test_stale_buffer_rows_never_leak(self):
        # slot 1's tail block owns one page of a buffer whose other pages
        # still hold what slot 0 streamed through them: NaN, here. (Slot
        # 0's own answer is NaN and not looked at.) A page of slot 1's
        # table past its length is NaN too: it must not be read at all.
        q, kc, vc, tables, lens = _setup(s=2, pages=PAGES, blocks=24, bs=BS,
                                         lens=[PAGES * BS, BLOCK + 3])
        tab = np.asarray(tables)
        bad = jnp.asarray([int(tab[0, i]) for i in (1, 2, 3, 5, 6, 7, 9)]
                          + [int(tab[1, 6])])
        kc = kc.at[bad].set(jnp.nan)
        vc = vc.at[bad].set(jnp.nan)
        got = np.asarray(paged_decode_attention_raw(
            q, kc, vc, tables, lens, pages_per_step_=PPS), "float32")
        assert np.isnan(got[0]).all()
        ref = _dense_reference(q[1:], kc, vc, tables[1:], lens[1:])
        np.testing.assert_allclose(got[1:], ref, atol=5e-5, rtol=5e-5)

    @pytest.mark.parametrize("hq,hkv", [(32, 8), (4, 4)],
                             ids=["gqa32_8", "group_of_one"])
    def test_head_geometries(self, hq, hkv):
        q, kc, vc, tables, lens = _setup(s=3, hq=hq, hkv=hkv, pages=PAGES,
                                         blocks=40, bs=BS,
                                         lens=[BLOCK + 1, 80, BS])
        got = np.asarray(paged_decode_attention_raw(
            q, kc, vc, tables, lens, pages_per_step_=PPS), "float32")
        np.testing.assert_allclose(got, _dense_reference(q, kc, vc, tables,
                                                         lens),
                                   atol=5e-5, rtol=5e-5)

    def test_bf16_across_blocks(self):
        q, kc, vc, tables, lens = _setup(s=3, pages=PAGES, blocks=40, bs=16,
                                         dtype="bfloat16",
                                         lens=[160, 65, 17])
        got = np.asarray(paged_decode_attention_raw(
            q, kc, vc, tables, lens, pages_per_step_=PPS), "float32")
        ref = _dense_reference(q, kc, vc, tables, lens)
        np.testing.assert_allclose(got, ref, atol=3e-2, rtol=3e-2)

    @pytest.mark.parametrize("mode", ["int8", "int4"])
    def test_quantized_across_blocks(self, mode):
        # scales change page by page inside one compute block and the
        # lengths end inside a page, at a block's edge and one past it
        q, kc, vc, tables, lens = _setup(s=3, pages=PAGES, blocks=40, bs=16,
                                         lens=[2 * 16 * PPS + 1, 16 * PPS,
                                               29], seed=11)
        quant = _quantize_per_block if mode == "int8" \
            else _quantize_int4_per_block
        kq, ks = quant(kc)
        vq, vs = quant(vc)
        kw = {"kv_int4": mode == "int4"}
        got = np.asarray(paged_decode_attention_raw(
            q, kq, vq, tables, lens, ks, vs, pages_per_step_=PPS, **kw),
            "float32")
        xla = np.asarray(paged_decode_attention_xla(q, kq, vq, tables, lens,
                                                    ks, vs, **kw),
                         "float32")
        np.testing.assert_allclose(got, xla, atol=5e-5, rtol=5e-5)


def _pallas_grid(jaxpr):
    """The grid of the one pallas_call in a jaxpr (sub-jaxprs searched)."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jaxpr.jaxpr)
    assert len(found) == 1, found
    return found[0]


@pytest.mark.parametrize("kv", ["bf16", "int8", "int4"])
def test_grid_at_the_serve_cells_geometry(kv):
    """mistral-7b.serve-chat: 16 slots, 32 query / 8 KV heads, 256 pages of
    16 tokens. The (seq, kv_head, page) grid took 32,768 steps a layer."""
    from paddle_tpu.ops.pallas_decode import kv_steps

    slots, hq, hkv, d, bs, pages, blocks = 16, 32, 8, 128, 16, 256, 4096
    rows = bs // 2 if kv == "int4" else bs
    cache = jax.ShapeDtypeStruct(
        (blocks, hkv, rows, d), jnp.bfloat16 if kv == "bf16" else jnp.int8)
    args = [jax.ShapeDtypeStruct((slots, hq, d), jnp.bfloat16), cache, cache,
            jax.ShapeDtypeStruct((slots, pages), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32)]
    if kv != "bf16":
        args += [jax.ShapeDtypeStruct((blocks,), jnp.float32)] * 2
    grid = _pallas_grid(jax.make_jaxpr(
        lambda *a: paged_decode_attention_raw(*a, kv_int4=kv == "int4"))(
            *args))
    steps = int(np.prod(grid))
    assert steps <= 512, grid
    assert steps == kv_steps(slots, pages, rows, hkv, d,
                             cache.dtype.itemsize)
    if kv == "bf16":
        assert grid == (16, 8)            # 32 pages, 512 tokens a step


class TestRouting:
    def test_off_tpu_routes_to_xla(self):
        q, kc, vc, tables, lens = _setup()
        assert not use_pallas_decode(q, kc, tables)  # CPU test host
        got = np.asarray(paged_decode_attention(q, kc, vc, tables, lens),
                         "float32")
        xla = np.asarray(paged_decode_attention_xla(q, kc, vc, tables,
                                                    lens), "float32")
        np.testing.assert_array_equal(got, xla)

    def test_gate_reasons_mirror_router(self):
        reason, sev = decode_gate_reason(1 << 20, "bfloat16", "cpu")
        assert sev == "note" and "not on TPU" in reason
        reason, sev = decode_gate_reason(100, "bfloat16", "tpu")
        assert sev == "note" and "size threshold" in reason
        reason, sev = decode_gate_reason(1 << 20, "float64", "tpu")
        assert sev == "note" and "unsupported" in reason
        reason, sev = decode_gate_reason(1 << 20, "bfloat16", "tpu",
                                         head_dim=64)
        assert sev == "note" and "lane-aligned" in reason
        reason, sev = decode_gate_reason(1 << 20, "bfloat16", "tpu",
                                         block_size=12)
        assert sev == "note" and "block_size" in reason
        reason, sev = decode_gate_reason(1 << 20, "bfloat16", "tpu",
                                         head_dim=128, block_size=16)
        assert sev == "warning"

    def test_flag_kills_kernel(self):
        paddle.set_flags({"FLAGS_pallas_decode": False})
        try:
            reason, sev = decode_gate_reason(1 << 20, "bfloat16", "tpu",
                                             head_dim=128, block_size=16)
            assert sev == "note" and "FLAGS_pallas_decode" in reason
        finally:
            paddle.set_flags({"FLAGS_pallas_decode": True})

    def test_shape_validation(self):
        q, kc, vc, tables, lens = _setup()
        with pytest.raises(ValueError):
            paged_decode_attention_raw(q[:, :, :64], kc, vc, tables, lens)
        with pytest.raises(ValueError):
            paged_decode_attention_raw(q[:, :3], kc, vc, tables, lens)


def test_registered_in_quick_tier():
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    src = open(os.path.join(here, "conftest.py")).read()
    assert '"test_pallas_decode.py"' in src.split("QUICK_MODULES")[1], \
        "tests/test_pallas_decode.py must be registered in QUICK_MODULES"
