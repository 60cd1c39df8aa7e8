"""The trace reducer on events built by hand (overlaps, nesting, gaps
under host spans) and on a trace recorded here through the same loader."""
import time

import pytest

from benchmark import trace_reduce as tr

MS = 1_000_000
#: three operations, two of them overlapping, inside a 100 ms slice
OPS = [("while.8", 10 * MS, 40 * MS),            # 10..50, a container
       ("paged_decode.11", 20 * MS, 10 * MS),    # nested in it
       ("fusion.3", 45 * MS, 15 * MS),           # 45..60 overlaps its end
       ("paged_decode.11", 70 * MS, 10 * MS)]    # 70..80
SPANS = [("bench.slice", 0, 100 * MS),
         ("bench.engine_step", 5 * MS, 60 * MS),     # 5..65
         ("bench.gen_wait", 65 * MS, 5 * MS),        # 65..70
         ("bench.engine_step", 70 * MS, 25 * MS)]    # 70..95


def test_union_and_busy_with_overlaps():
    assert tr.union(OPS) == [[10 * MS, 60 * MS], [70 * MS, 80 * MS]]
    assert tr.busy_ns(OPS) == 60 * MS


def test_kernel_time_and_count():
    assert tr.kernel_ns(OPS, "paged_decode") == (20 * MS, 2)
    assert tr.kernel_ns(OPS, "flash_fwd") == (0, 0)


def test_top_ops_leave_containers_out():
    top = tr.top_ops(OPS)
    assert top[0] == ["paged_decode.11", 0.02]
    assert [n for n, _ in top] == ["paged_decode.11", "fusion.3"]


def test_clip_cuts_at_the_window():
    assert tr.clip(OPS, 15 * MS, 47 * MS) == [
        ("while.8", 15 * MS, 32 * MS), ("paged_decode.11", 20 * MS, 10 * MS),
        ("fusion.3", 45 * MS, 2 * MS)]


def test_gaps_and_their_spans():
    assert tr.gaps(OPS, 0, 100 * MS) == [
        (0, 10 * MS), (60 * MS, 70 * MS), (80 * MS, 100 * MS)]
    by = dict(tr.gaps_by_span(OPS, SPANS[1:], 0, 100 * MS))
    # 5..10 and 60..65 and 80..95 under engine_step; 65..70 under gen_wait;
    # 0..5 and 95..100 under nothing
    assert by == {"bench.engine_step": pytest.approx(0.025),
                  "bench.gen_wait": pytest.approx(0.005),
                  "(no span)": pytest.approx(0.010)}


@pytest.mark.parametrize("n_dev", [1, 2])
def test_reduce_busy_idle_over_devices(n_dev):
    t = tr.Trace(device_ops={f"/device:TPU:{i}": list(OPS)
                             for i in range(n_dev)}, host_spans=list(SPANS))
    r = tr.reduce(t)
    assert r["devices"] == n_dev
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.06)
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(0.04)


def test_reduce_without_a_slice_span_uses_the_ops_extent():
    r = tr.reduce(tr.Trace(device_ops={"/device:TPU:0": list(OPS)}))
    assert r["window_s"] == pytest.approx(0.07)
    assert r["idle_gaps"] == [["(no span)", pytest.approx(0.01)]]


def test_reduce_refuses_a_trace_with_no_device_operation():
    with pytest.raises(LookupError):
        tr.reduce(tr.Trace(device_ops={"/device:TPU:0": []},
                           host_spans=list(SPANS)))


def test_short_names():
    assert tr.short("%paged_decode.11 = bf16[16,8]{1,0} custom-call(s32[] "
                    "%x), custom_call_target=\"tpu_custom_call\"") \
        == "paged_decode.11"
    assert tr.short("fusion.3") == "fusion.3"


def test_loader_reads_a_recorded_trace(tmp_path):
    """Record a trace here (the CPU's XLA worker threads stand for the
    device) and read it through the loader the chip runs use."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.slice"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                f(x).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)), device_prefix="/host:CPU",
                ops_lines=("tf_XLA",))
    names = [n for n, _, _ in t.host_spans]
    assert names.count("bench.train_step") == 3 and "bench.slice" in names
    r = tr.reduce(t)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] >= 0.03
    assert r["device_ops"] and r["idle_gaps"]
