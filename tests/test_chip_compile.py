"""Compile-for-v5e tests: every Pallas kernel of the train and serve main
paths, at LLaMA-7B widths, handed to the TPU's own compiler for a chip that
is described, not attached (on-chip-measurement guide, section 2 step 3).

Interpret-mode parity tests cannot see what Mosaic refuses — a lane slice
off the tiling, an i8 shift v5e does not legalize, a working set over the
16 MiB scoped-VMEM default. These compiles can, at no chip time. Nothing
runs: a pass here says the chip's compiler accepts the kernel, never that
its result is right or fast.

All of them live in THIS file and the topology is described inside a
module-scoped fixture: only one process may hold the TPU library, so the
call must not happen at import/collection time, and a second file could
land on another xdist worker where the fixture would skip in silence.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# LLaMA-2-7B published widths (text/models/llama.py:llama_7b_config)
HIDDEN, FFN, HEADS, HEAD_DIM = 4096, 11008, 32, 128
ROWS = 4096                      # tokens per step: b2 x s2048
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / lock held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_chip(monkeypatch, one_chip):
    """Steer the kernels off the interpreter (the described chip is not the
    default backend, so `_interpret()` would say True) and keep these
    compiles out of the persistent cache: an executable for an unattached
    chip cannot be read back and would warn on the next run. conftest.py
    pins matmul precision to "highest" for CPU parity; the chip runs the
    default, and Mosaic refuses an fp32-precision matmul on bf16 operands."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu.ops import (_pallas_common, pallas_attention,
                                pallas_decode, pallas_grouped_experts,
                                pallas_latent_chunk, pallas_norm, quantized)

    for mod in (pallas_attention, pallas_decode, pallas_grouped_experts,
                pallas_latent_chunk, pallas_norm, quantized):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    monkeypatch.setattr(_pallas_common, "interpret", lambda: False)
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_default_matmul_precision)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text, "kernel not in the compiled program"
        return compiled

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_default_matmul_precision", was[1])
    cc.reset_cache()


def _sum32(x):
    return jnp.sum(x.astype(jnp.float32))


# ------------------------------------------------------------ attention

@pytest.mark.parametrize("d,s", [(128, 2048), (64, 1024)],
                         ids=["d128_s2048", "d64_s1024"])
@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash(for_chip, d, s, bwd):
    from paddle_tpu.ops.pallas_attention import flash_attention_raw

    def fwd(q, k, v):
        return flash_attention_raw(q, k, v, causal=True)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: _sum32(fwd(*a)), argnums=(0, 1, 2))(
            q, k, v)

    shp = ((2, HEADS, s, d), BF16)
    for_chip(fwd_bwd if bwd else fwd, shp, shp, shp)


def test_flash_varlen(for_chip):
    from paddle_tpu.ops.pallas_attention import flash_attention_varlen_raw

    shp = ((2, HEADS, 2048, HEAD_DIM), BF16)
    for_chip(lambda q, k, v, lens: flash_attention_varlen_raw(
        q, k, v, lens, causal=True), shp, shp, shp, ((2,), jnp.int32))


# (query heads, KV heads, slots, pages, pool blocks, an int4 page's tokens):
# LLaMA-2's MHA at 8 slots x 128 pages (int4 on pages of 32, so that the
# packed tile holds 16 rows), and what mistral-7b.serve-chat runs: GQA 32/8,
# 16 slots x 256 pages of the default 16 (int4: 8 packed rows)
DECODE_GEOMETRIES = {"llama2_mha": (HEADS, HEADS, 8, 128, 512, 32),
                     "serve_cell": (32, 8, 16, 256, 4096, 16)}


@pytest.mark.parametrize("geometry", list(DECODE_GEOMETRIES))
@pytest.mark.parametrize("kv", ["bf16", "int8", "int4"])
def test_paged_decode(for_chip, kv, geometry):
    from paddle_tpu.ops.pallas_decode import paged_decode_attention_raw

    hq, hkv, slots, pages, blocks, bs4 = DECODE_GEOMETRIES[geometry]
    bs = bs4 if kv == "int4" else 16
    cache_dt = BF16 if kv == "bf16" else jnp.int8
    cache = ((blocks, hkv, bs // 2 if kv == "int4" else bs, HEAD_DIM),
             cache_dt)
    shapes = [((slots, hq, HEAD_DIM), BF16), cache, cache,
              ((slots, pages), jnp.int32), ((slots,), jnp.int32)]
    if kv == "bf16":
        fn = paged_decode_attention_raw
    else:
        shapes += [((blocks,), jnp.float32)] * 2

        def fn(q, k, v, tab, lens, ks, vs):
            return paged_decode_attention_raw(q, k, v, tab, lens, ks, vs,
                                              kv_int4=(kv == "int4"))
    for_chip(fn, *shapes)


# what command-a-plus-05-2026.serve-longdoc runs: GQA 128/8, 16 slots; a
# full layer's table is 1024 pages of 16, a window layer's ring view 273
# (window 4096 + chunk 256, + 1) with each slot's first live position
LAYERED_DECODE = {"full": (1024, 1 + 16 * 1024, False),
                  "window": (273, 1 + 16 * 273, True)}


@pytest.mark.parametrize("kind", list(LAYERED_DECODE))
def test_paged_decode_two_kinds(for_chip, kind):
    from paddle_tpu.ops.pallas_decode import paged_decode_attention_raw

    pages, blocks, windowed = LAYERED_DECODE[kind]
    cache = ((blocks, 8, 16, HEAD_DIM), BF16)
    shapes = [((16, 128, HEAD_DIM), BF16), cache, cache,
              ((16, pages), jnp.int32), ((16,), jnp.int32)]
    if not windowed:
        fn = paged_decode_attention_raw
    else:
        shapes.append(((16,), jnp.int32))

        def fn(q, k, v, tab, lens, start):
            return paged_decode_attention_raw(
                q, k, v, tab, lens, kv_start=start,
                name="paged_window_decode")
    text = for_chip(fn, *shapes).as_text()
    assert ("paged_window_decode" in text) == windowed


def test_paged_latent_decode(for_chip):
    """What openpangu-ultra-moe-718b.serve-longdoc runs: 128 query heads
    on ONE cached row of 640 (512 latent + 64 rotary + padding), 16 slots
    x 1024 pages of 16; the values are the first 512 columns of the key's
    own tile (a lane-aligned slice, each page copied once)."""
    from paddle_tpu.ops.pallas_decode import paged_latent_decode_raw

    text = for_chip(
        lambda q, pool, tab, lens: paged_latent_decode_raw(
            q, pool, tab, lens, 512, 192 ** -0.5),
        ((16, 128, 640), BF16), ((1 + 16 * 1024, 1, 16, 640), BF16),
        ((16, 1024), jnp.int32), ((16,), jnp.int32)).as_text()
    assert "paged_latent_decode" in text


def test_latent_chunk_attention(for_chip):
    """What a chunk of openpangu-ultra-moe-718b.serve-longdoc runs a
    layer: 512 queries x 128 heads (128 + 64 deep, values 128) over the
    rows of a 16384-position table, 640 wide, the chunk's first position
    an operand; a step takes 8 heads over 1024 positions, the softmax
    state and the expanded block in 24 MB of VMEM (over the default scope:
    the call raises its limit)."""
    from paddle_tpu.ops import pallas_latent_chunk as plc

    assert plc.context_block(512, 16384) == 1024
    text = for_chip(
        lambda qn, qr, rows, start, w: plc.latent_chunk_attention_raw(
            qn, qr, rows, start, w, 512, 192 ** -0.5),
        ((512, 128, 128), BF16), ((512, 128, 64), BF16),
        ((16384, 640), BF16), ((), jnp.int32),
        ((512, 128 * 256), BF16)).as_text()
    assert plc.NAME in text


def test_gated_delta_decode(for_chip, monkeypatch):
    """What a decode tick of gigachat3.5-432b-a28b.serve-longgen runs in
    each linear layer: 64 slots' states `[64 + 1, 64, 128, 128]` float32
    advanced one token (the pool aliased in and out: the decode program
    donates it, so there it is written in place), 32 value heads a grid
    step; the slot ids, beta and alpha scalar-prefetched."""
    from paddle_tpu.ops import pallas_gated_delta as pgd

    monkeypatch.setattr(pgd, "_interpret", lambda: False)
    f32 = jnp.float32
    compiled = for_chip(
        pgd.gated_delta_decode_raw, ((65, 64, 128, 128), f32),
        ((64,), jnp.int32), ((64, 64, 128), f32), ((64, 64, 128), f32),
        ((64, 64, 128), f32), ((64, 64), f32), ((64, 64), f32))
    assert "gated_delta_decode" in compiled.as_text()
    assert pgd.head_group(64, 128, 128) == 32


# (hidden, FFN width, held experts) of the three sparse cells' models:
# command-a-plus-05-2026 (rank 0 of 8), openpangu-ultra-moe-718b and
# gigachat3.5-432b-a28b (rank 0 of 32)
HELD_EXPERTS = {"cohere": (4096, 4096, 16), "pangu": (7680, 2048, 8),
                "gigachat": (7168, 2048, 8)}


@pytest.mark.parametrize("rows", [16, 64, 512],
                         ids=["decode16", "decode64", "chunk512"])
@pytest.mark.parametrize("model", list(HELD_EXPERTS))
def test_grouped_experts(for_chip, model, rows):
    """A layer's held experts as the grouped kernel: a decode bucket's
    rows in one tile an expert, a chunk's in tiles of 128; the three
    weight blocks of a step within 32 MiB (512 FFN columns at 4096 wide,
    256 at 7168 and 7680). Every route-time reason is absent at these
    shapes."""
    from paddle_tpu.ops import pallas_grouped_experts as pge

    hidden, ffn, n = HELD_EXPERTS[model]
    shapes = [((rows, hidden), BF16), ((rows, n), jnp.float32),
              ((n, hidden, ffn), BF16), ((n, hidden, ffn), BF16),
              ((n, ffn, hidden), BF16), ((rows,), jnp.bool_)]
    h, w = (jax.ShapeDtypeStruct(*s) for s in shapes[::2][:2])
    assert pge.grouped_gate_reason(h, w, "tpu")[1] == "warning"
    text = for_chip(lambda *a: pge.grouped_experts(*a, limit=10.0),
                    *shapes).as_text()
    assert pge.NAME in text


def test_a_kernel_program_keys_the_persistent_cache_by_itself(
        for_chip, one_chip, forget_programs):
    """jax's persistent-cache key strips the program's locations but not a
    Pallas kernel's serialized body, whose operations carry the Python
    traceback of whoever traced them (or a shared jitted helper) first: the
    same kernel program lowered afresh from two call sites keys the cache
    twice, which is how every program holding a kernel missed the cache in
    each fresh process on the chip. Lowered inside
    `frameless_locations`, the locations name no frames: one key, and the
    process's setting is as it was afterwards."""
    import hashlib

    from jax._src import cache_key

    from paddle_tpu.core.compile_cache import frameless_locations
    from paddle_tpu.ops import pallas_grouped_experts as pge

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((16, 256), BF16), ((16, 4), jnp.float32), ((4, 256, 256), BF16),
        ((4, 256, 256), BF16), ((4, 256, 256), BF16))]

    def site_a():
        return jax.jit(lambda *a: pge.grouped_experts(*a)).lower(*args)

    def site_b():
        return jax.jit(lambda *a: pge.grouped_experts(*a)).lower(*args)

    def key(site):
        forget_programs()           # each site traces the kernel afresh
        ir = site().compiler_ir("stablehlo")
        return hashlib.sha1(cache_key._canonicalize_ir(
            ir, cache_key.IgnoreCallbacks.NO)).hexdigest()

    was = jax.config.jax_traceback_in_locations_limit
    assert key(site_a) != key(site_b)
    with frameless_locations():
        assert jax.config.jax_traceback_in_locations_limit == 0
        assert key(site_a) == key(site_b)
    assert jax.config.jax_traceback_in_locations_limit == was


def test_only_the_layered_programs_lower_without_frames(monkeypatch,
                                                        tmp_path):
    """The layered step programs (the sparse models', which hold the
    grouped kernel) lower inside `frameless_locations`; turning the
    persistent cache on leaves every other program's locations, and so
    its key, as they were."""
    from paddle_tpu.core import compile_cache
    from paddle_tpu.inference import layered

    seen = []

    class Jitted:
        def lower(self, *args):
            seen.append(jax.config.jax_traceback_in_locations_limit)

    was = jax.config.jax_traceback_in_locations_limit
    for step in (layered.decode_step, layered.chunk_step):
        monkeypatch.setattr(step, "jitted", Jitted())
        step.lower()
    assert seen == [0, 0] and was != 0
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    limit = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        compile_cache.enable_compile_cache()
        assert jax.config.jax_traceback_in_locations_limit == was
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          limit)


def test_layered_decode_program_holds_the_grouped_kernel_once(
        for_chip, one_chip, monkeypatch):
    """A layered decode program at bucket 16 of a small Cohere-shaped
    model (4 expert layers, 8 held experts of 256 x 256, bf16), lowered
    for the described v5e: the grouped kernel's `tpu_custom_call` appears
    ONCE, in one private function that each expert layer calls. Lowered
    at every call site instead, it cost 11-15 s of set-up a process in
    the sparse cells."""
    import re

    import numpy as np

    from benchmark import weights
    from benchmark.families import cohere2_moe as fam
    from paddle_tpu.inference import layered
    from paddle_tpu.inference.engine import ServingEngine
    from paddle_tpu.ops import pallas_grouped_experts as pge

    cfg = {"name": "tiny", "family": "cohere2_moe", "hidden_size": 256,
           "intermediate_size": 256, "vocab_size": 256, "head_dim": 128,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "num_experts": 8, "num_experts_per_tok": 8,
           "num_shared_experts": 1,
           "expert_parallel": {"chips": 4, "rank": 1, "experts_total": 32},
           "sliding_window": 16, "layer_norm_eps": 1e-5,
           "rope_theta": 50000, "logit_scale": 1,
           "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
           "num_hidden_layers": {"serve": 4},
           "max_position_embeddings": 256, "tie_word_embeddings": True,
           "dtype": "bfloat16"}
    model = fam.build_model(cfg, 4, "serve")
    weights.assign(model, weights.make(fam.weight_spec(cfg, 4), 0,
                                       "bfloat16"))
    eng = ServingEngine(model, max_slots=16, kv_block_size=16,
                        max_model_len=256, chunked_prefill_tokens=64)
    lp = eng.programs
    # the router asks `jax.default_backend()`, which is the CPU here
    # (`for_chip` steers the kernels off the interpreter)
    monkeypatch.setattr(pge, "use_grouped_experts", lambda *a: True)
    ints = np.zeros((16, lp._ints_width()), np.int32)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip),
        (eng.params, ints, lp.cache.k, lp.cache.v, eng._last,
         eng._samp([], 16, False), eng._key))
    text = layered.decode_step.lower(lp.spec, False, *args).as_text()
    assert text.count("tpu_custom_call") == 1
    funcs = re.split(r"(?=func\.func )", text)
    home = [re.match(r"func\.func \w+ @(\S+)\(", f).group(1)
            for f in funcs if "tpu_custom_call" in f]
    assert len(home) == 1 and home[0] != "main"
    assert text.count(f"call @{home[0]}(") == lp.expert_layers == 4


# ------------------------------------- the dense serving step programs
# `inference/engine.py`'s decode program and one chunk program as
# mistral-7b.serve-chat runs them (32/8 heads x 128, FFN 14336, 16 slots x
# 4096 positions in pages of 16: 4097 blocks a layer), 2 of the 8 layers,
# abstract operands. What is held: neither program copies a pool. Their
# temporaries stay under ONE layer's slice of one pool (134 MB in bf16;
# with the pools as the layer scan's xs/ys and a 4-D scatter the decode
# program held 1.6 GB of them at 2 layers, 2.95 GB at 8), and the compiled
# text has no copy and no dynamic-update-slice the size of a pool or of a
# layer's slice of it.

SERVE_CELL = dict(hidden=4096, ffn=14336, heads=32, kv_heads=8, vocab=32000,
                  layers=2, slots=16, max_len=4096, block=16, chunk=256,
                  ctx_pages=128)


def _dense_programs(kv, sharding):
    """(name, lowered) of the decode and the chunk program at SERVE_CELL."""
    from paddle_tpu.inference import engine as E
    from paddle_tpu.text.generation import _GenSpec

    g = SERVE_CELL
    h, f, nh, nkv, v, n_l = (g[k] for k in ("hidden", "ffn", "heads",
                                            "kv_heads", "vocab", "layers"))
    bs, slots = g["block"], g["slots"]
    pages = g["max_len"] // bs
    blocks = slots * pages + 1

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    spec = _GenSpec(num_layers=n_l, num_heads=nh, num_kv_heads=nkv,
                    head_dim=HEAD_DIM, rope_theta=1e4, rms_eps=1e-5,
                    max_new_tokens=0, do_sample=False, top_k=0, top_p=1.0,
                    temperature=1.0, eos_token_id=-1, tie_embeddings=False)
    params = {
        "embed": sds((v, h)), "final_ln": sds((h,)), "lm_head": sds((h, v)),
        "rope_cos": sds((g["max_len"], HEAD_DIM)),
        "rope_sin": sds((g["max_len"], HEAD_DIM)),
        "layers": {"q": sds((n_l, h, nh * HEAD_DIM)),
                   "k": sds((n_l, h, nkv * HEAD_DIM)),
                   "v": sds((n_l, h, nkv * HEAD_DIM)),
                   "o": sds((n_l, nh * HEAD_DIM, h)),
                   "gate": sds((n_l, h, f)), "up": sds((n_l, h, f)),
                   "down": sds((n_l, f, h)), "input_ln": sds((n_l, h)),
                   "post_ln": sds((n_l, h))}}
    pool = sds((n_l, blocks, nkv, bs, HEAD_DIM),
               BF16 if kv == "bf16" else jnp.int8)
    scale = None if kv == "bf16" else sds((n_l, blocks), jnp.float32)
    mode = "model" if kv == "bf16" else kv
    i32 = jnp.int32

    def samp(b):
        return {"do_sample": sds((b,), jnp.bool_),
                "temperature": sds((b,), jnp.float32),
                "top_k": sds((b,), i32), "top_p": sds((b,), jnp.float32)}

    key = sds((2,), jnp.uint32)
    last = sds((slots + 1,), i32)           # each slot's last token
    # one packed int32 operand a call: [token, position, slot, table row]
    # a slot; [6 scalars, table row, the chunk's ids]
    yield "decode", pool, E._decode_step.lower(
        spec, bs, mode, False, params, sds((slots, 3 + pages), i32), pool,
        pool, scale, scale, last, samp(slots), key)
    yield "chunk", pool, E._chunk_prefill_step.lower(
        spec, bs, mode, False, False, g["ctx_pages"], pages, params,
        sds((6 + pages + g["chunk"],), i32), pool, pool, scale, scale,
        last, samp(1), key)


def _pool_sized_copies(text, pool):
    """Lines of a compiled program whose instruction (or fusion, by its
    name) is a copy or a dynamic-update-slice and whose result has as many
    elements of the pool's type as one layer's slice of it, or more."""
    import math
    import re

    slice_elems = math.prod(pool.shape[1:])
    dt = {"bfloat16": "bf16", "int8": "s8"}[jnp.dtype(pool.dtype).name]
    op = re.compile(r"^\s*(?:ROOT )?%(\S+) = " + dt
                    + r"\[([0-9,]+)\]\S* ([a-z\-]+)\(")
    found = []
    for line in text.splitlines():
        m = op.match(line)
        if not m:
            continue
        name, dims, opcode = m.groups()
        moves = opcode in ("copy", "dynamic-update-slice") or (
            opcode == "fusion" and ("copy" in name or "update" in name))
        if moves and math.prod(map(int, dims.split(","))) >= slice_elems:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_dense_step_programs_copy_no_pool(for_chip, one_chip, monkeypatch,
                                          kv):
    from paddle_tpu.ops import pallas_decode

    # the router asks `jax.default_backend()`, which is the CPU here
    monkeypatch.setattr(pallas_decode, "use_pallas_decode",
                        lambda *a, **k: True)
    for name, pool, lowered in _dense_programs(kv, one_chip):
        compiled = lowered.compile()
        text = compiled.as_text()
        if name == "decode":
            assert "paged_decode" in text
        one_slice = pool.size // pool.shape[0] * pool.dtype.itemsize
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < one_slice, (
            f"{name}/{kv}: {temp / 1e6:.0f} MB of temporaries, a layer's "
            f"pool slice is {one_slice / 1e6:.0f} MB")
        assert not _pool_sized_copies(text, pool), name


# ------------------------------------------------- fused norm/rope/swiglu

@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd_bwd"])
def test_add_rms_norm(for_chip, bwd):
    from paddle_tpu.ops.pallas_norm import add_rms_norm_raw

    def fwd(x, r, w):
        return add_rms_norm_raw(x, r, w, 1e-6)

    def fwd_bwd(x, r, w):
        return jax.grad(lambda *a: sum(map(_sum32, fwd(*a))),
                        argnums=(0, 1, 2))(x, r, w)

    row = ((ROWS, HIDDEN), BF16)
    for_chip(fwd_bwd if bwd else fwd, row, row, ((HIDDEN,), BF16))


@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd_bwd"])
def test_rms_norm(for_chip, bwd):
    from paddle_tpu.ops.pallas_norm import rms_norm_raw

    def fwd(x, w):
        return rms_norm_raw(x, w, 1e-6)

    def fwd_bwd(x, w):
        return jax.grad(lambda *a: _sum32(fwd(*a)), argnums=(0, 1))(x, w)

    for_chip(fwd_bwd if bwd else fwd, ((ROWS, HIDDEN), BF16),
             ((HIDDEN,), BF16))


@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd_bwd"])
def test_swiglu(for_chip, bwd):
    from paddle_tpu.ops.pallas_norm import swiglu_raw

    def fwd_bwd(g, u):
        return jax.grad(lambda *a: _sum32(swiglu_raw(*a)),
                        argnums=(0, 1))(g, u)

    shp = ((ROWS, FFN), BF16)
    for_chip(fwd_bwd if bwd else swiglu_raw, shp, shp)


@pytest.mark.parametrize("d", [128, 64], ids=["d128", "d64"])
@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd_bwd"])
def test_rope_qk(for_chip, bwd, d):
    """d128 rotates a full lane tile by half; d64 sits in half a tile and
    takes the two-rotation select."""
    from paddle_tpu.ops.pallas_norm import rope_qk_raw

    def fwd_bwd(q, k, c, s):
        return jax.grad(lambda a, b: sum(map(_sum32, rope_qk_raw(a, b, c, s))),
                        argnums=(0, 1))(q, k)

    qk = ((2, 2048, HEADS, d), BF16)
    tab = ((1, 2048, 1, d), BF16)
    for_chip(fwd_bwd if bwd else rope_qk_raw, qk, qk, tab, tab)


# ------------------------------------------------------- int4 dequant GEMM

@pytest.mark.parametrize("k,n", [(HIDDEN, FFN), (FFN, HIDDEN)],
                         ids=["up_proj", "down_proj"])
def test_quant_matmul_int4(for_chip, k, n):
    from paddle_tpu.ops.quantized import quant_matmul_raw

    for_chip(lambda x, w, s: quant_matmul_raw(x, w, s, k),
             ((8, k), BF16), ((k // 2, n), jnp.int8), ((n,), jnp.float32))
