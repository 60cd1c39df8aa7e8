"""`moe_experts_read_share`: the held experts a decode tick's picks named,
over the held experts, read from the program's span log."""
import pytest

from benchmark import harness
from benchmark.readers import span_attr_ratio

SPEC = harness.load_json("metrics", "moe_experts_read_share.json")
SPARSE = ["command-a-plus-05-2026.serve-longdoc",
          "openpangu-ultra-moe-718b.serve-longdoc",
          "gigachat3.5-432b-a28b.serve-longgen"]


def _span(name, start, **attrs):
    return (name, start, start + 0.01, None, attrs)


def test_the_entry_names_the_sparse_cells_and_the_span_attributes():
    entry = [m for m in harness.manifest()["per_layer"]
             if m["name"] == "moe_experts_read_share"][0]
    assert entry["workloads"] == SPARSE
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        "model step: experts", "ttft_p70_ms", "lower")
    assert SPEC["reader"] == "span_attr_ratio"
    assert SPEC["params"] == {
        "phase": "window", "spans": ["serving.decode.run"],
        "num": "moe_experts_read", "den": "moe_experts_held",
        "scale": 100.0}


@pytest.mark.parametrize("reads,want", [((3, 5), 25.0), ((16, 16), 100.0),
                                        ((0, 2), 6.25)])
def test_the_share_over_decode_ticks(reads, want):
    """Held 16 a tick (4 layers x 4): the window's sum of experts read
    over the sum held; chunk spans and spans outside the window count
    for nothing."""
    log = [_span("serving.decode.run", 1.0 + i, moe_experts_read=r,
                 moe_experts_held=16) for i, r in enumerate(reads)]
    log += [_span("serving.chunk.run", 1.5, moe_experts_read=16,
                  moe_experts_held=16),
            _span("serving.decode.run", 0.5, moe_experts_read=16,
                  moe_experts_held=16)]
    got = span_attr_ratio.compute(SPEC["params"], log, 0.0, 1.0, 10.0)
    assert got == pytest.approx(want)


def test_a_program_without_the_attributes_reports_nothing():
    """The parent's program carries no `moe_experts_held`: the metric is
    left out of the line, never read as 0."""
    log = [_span("serving.decode.run", 2.0, moe_local_picks=4)]
    assert span_attr_ratio.compute(SPEC["params"], log, 0.0, 1.0,
                                   10.0) is None
