"""`ops/pallas_latent_chunk.py` in the Pallas interpreter on the CPU
backend against the composition it replaces on the chip
(`latent_block.expanded_attention`), at small widths that keep the
published ratios (dn = dv = 128, dr = 64, heads a multiple of the head
group's 8); the sizing arithmetic and the gate's reasons by name. A pass
here says the kernel computes the composition's numbers, never that it is
fast: `tests/test_chip_compile.py` asks the chip's compiler, PERF.md the
chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_latent_chunk as plc
from paddle_tpu.text.models import latent_block as lb

Q, HEADS, DN, DR, DV, RKV, WIDTH, ROWS = 32, 8, 128, 64, 128, 128, 256, 512
BLOCK = 128


def _spec(heads=HEADS):
    return lb.BlockSpec(
        hidden_size=64, num_heads=heads, qk_nope_dim=DN, qk_rope_dim=DR,
        v_dim=DV, q_rank=32, kv_rank=RKV, eps=1e-6, rope_theta=1e4,
        layer_types=(lb.DENSE,), num_experts=0, top_k=0, first_expert=0,
        num_local_experts=0, num_shared_experts=0, routed_scale=1.0)


def _case(dtype, seed=0, heads=HEADS, q_rows=Q):
    """(q_nope, q_rope, the context's rows, Wkvb): the rows' padding
    columns hold numbers too, which nothing may read."""
    rng = np.random.default_rng(seed)
    mk = lambda *shape, s=1.0: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape) * s, dtype)
    return (mk(q_rows, heads, DN), mk(q_rows, heads, DR), mk(ROWS, WIDTH),
            mk(RKV, heads * (DN + DV), s=RKV ** -0.5))


def _composition(qn, qr, rows, w, start, spec, block=BLOCK):
    """`expanded_attention` as `layered._latent_chunk_attend` calls it."""
    n = min((start + qn.shape[0] + block - 1) // block, ROWS // block)
    return lb.expanded_attention(
        qn, qr, lambda j: jax.lax.dynamic_slice_in_dim(rows, j * block,
                                                       block),
        n, block, w, start + jnp.arange(qn.shape[0]), spec)


def _close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=0)


# a chunk that starts at 0, inside a block, on a block's edge, several
# blocks in, and whose last rows reach the table's end
STARTS = {"at_0": 0, "inside_a_block": 40, "on_an_edge": 128,
          "blocks_in": 300, "to_the_end": 480}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("where", list(STARTS))
def test_the_kernel_matches_the_composition(dtype, tol, where):
    """Tolerances as `test_paged_latent_decode_matches_the_composition`:
    float32 differs in summation order alone; in bfloat16 both sides round
    the expanded rows and the probabilities to bfloat16 and sum in another
    order (2e-2 on outputs of size ~1). At the kernel's block of 128 (the
    composition's), at 256 and at the derived one (512: the whole table a
    step)."""
    start = STARTS[where]
    qn, qr, rows, w = _case(dtype)
    spec = _spec()
    want = _composition(qn, qr, rows, w, start, spec)
    assert float(jnp.abs(want.astype(jnp.float32)).max()) > 0.3
    for block in (BLOCK, 256, None):
        got = plc.latent_chunk_attention_raw(
            qn, qr, rows, start, w, RKV, spec.scale, block_=block)
        assert got.shape == (Q, HEADS, DV) and got.dtype == dtype
        _close(got, want, tol)


@pytest.mark.parametrize("group", [8, 16])
def test_head_groups_walk_the_context_independently(group):
    """16 heads in one group and in two: a group's softmax state starts
    anew at its first step and its output is written by its last."""
    qn, qr, rows, w = _case(jnp.float32, seed=3, heads=16)
    spec = _spec(16)
    want = _composition(qn, qr, rows, w, 200, spec)
    got = plc.latent_chunk_attention_raw(
        qn, qr, rows, 200, w, RKV, spec.scale, block_=BLOCK,
        heads_per_step_=group)
    _close(got, want, 3e-6)


def test_padding_rows_of_a_short_last_chunk_stay_finite():
    """A last chunk of 20 real tokens in a program of 32 rows: the
    positions past its end were never written (the pool holds whatever
    was there: here large numbers), the rows past it attend them and are
    thrown away by the caller, and every row is finite; the real rows are
    the composition's. So is a chunk whose padded end passes the table's
    (the steps stop at the table's last block)."""
    qn, qr, rows, w = _case(jnp.float32, seed=5)
    spec = _spec()
    start, real = 300, 20
    rows = rows.at[start + real:].multiply(50.0)
    want = _composition(qn, qr, rows, w, start, spec)
    got = plc.latent_chunk_attention_raw(qn, qr, rows, start, w, RKV,
                                         spec.scale, block_=BLOCK)
    assert np.isfinite(np.asarray(got)).all()
    _close(got[:real], want[:real], 3e-6)
    _close(got, want, 2e-5)
    past = plc.latent_chunk_attention_raw(qn, qr, rows, ROWS - 8, w, RKV,
                                          spec.scale, block_=BLOCK)
    assert np.isfinite(np.asarray(past)).all()
    _close(past[:8], _composition(qn, qr, rows, w, ROWS - 8, spec)[:8],
           2e-5)


def test_one_program_serves_every_context_length():
    """The chunk's first position is an operand, not a shape: one trace
    and one compile for a chunk at 0 and a chunk three blocks in."""
    qn, qr, rows, w = _case(jnp.float32, seed=7)
    spec = _spec()
    traces = []

    @jax.jit
    def run(start):
        traces.append(1)
        return plc.latent_chunk_attention_raw(qn, qr, rows, start, w, RKV,
                                              spec.scale, block_=BLOCK)

    for start in (0, 40, 300):
        _close(run(jnp.int32(start)),
               _composition(qn, qr, rows, w, start, spec), 3e-6)
    assert len(traces) == 1 and run._cache_size() == 1


def test_the_live_blocks_and_the_tiles_follow_from_the_shapes():
    # the cell: 512 queries over a table of 16384 rows of 640, 128 heads
    assert plc.context_block(512, 16384) == 1024
    assert plc.context_block(1024, 16384) == 512    # the score tile's cap
    assert plc.context_block(512, 1536) == 512      # must divide the table
    assert plc.context_block(512, 100) == 0
    assert plc.heads_per_step(128, 512, 1024, 128, 64, 128, 512, 640,
                              2) == 8
    assert plc.heads_per_step(128, 512, 512, 128, 64, 128, 512, 640,
                              2) == 16
    assert plc.heads_per_step(4, 16, 32, 16, 8, 16, 32, 128, 4) == 4
    assert plc.heads_per_step(128, 512, 1024, 128, 64, 128, 512, 640,
                              2 << 10) == 0
    # a chunk of 512 at 0, 512, 1024 and at the table's end
    assert [plc.live_blocks(s, 512, 1024, 16384)
            for s in (0, 512, 1024, 15872, 16384)] == [1, 1, 2, 16, 16]
    assert int(plc.live_blocks(jnp.int32(1024), 512, 1024, 16384)) == 2


CELL = dict(platform="tpu", dtype="bfloat16", q_rows=512, ctx_rows=16384,
            heads=128, dn=128, dr=64, dv=128, rkv=512, width=640)


@pytest.mark.parametrize("change,names", [
    ({}, "no gating reason"),
    ({"platform": "cpu"}, "not on TPU"),
    ({"dtype": "float16"}, "dtype float16"),
    ({"dn": 192}, "not lane-aligned"),
    ({"dv": 64}, "not lane-aligned"),
    ({"rkv": 576}, "not lane-aligned"),
    ({"dr": 32}, "half-lane"),
    ({"width": 512}, "half-lane"),
    ({"q_rows": 16}, "whole lanes"),
    ({"ctx_rows": 16384 + 64}, "whole lanes"),
    ({"q_rows": 8192}, "whole lanes"),
    ({"heads": 12, "q_rows": 4096, "ctx_rows": 128, "dn": 1024},
     "VMEM share"),
])
def test_the_gate_names_why_a_shape_takes_the_composition(change, names):
    reason, severity = plc.chunk_gate_reason(**{**CELL, **change})
    assert names in reason
    assert severity == ("note" if change else "warning")
    args = {k: v for k, v in {**CELL, **change}.items() if k != "platform"}
    # off the chip the router never picks the kernel
    assert plc.use_latent_chunk_kernel(**args) is False


def test_the_kernels_name_is_no_other_kernels():
    """`trace_reduce.kernel_ns` matches event names by substring."""
    others = ("paged_decode", "paged_window_decode", "paged_latent_decode",
              "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
    assert all(plc.NAME not in o and o not in plc.NAME for o in others)
