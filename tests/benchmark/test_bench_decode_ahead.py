"""`decode_ahead_share`: the decode rows dispatched while an earlier
tick's tokens were still on the device, over all decode rows, read from
the program's span log."""
import pytest

from benchmark import harness
from benchmark.readers import span_attr_ratio

SPEC = harness.load_json("metrics", "decode_ahead_share.json")
SERVE = ["mistral-7b.serve-chat", "command-a-plus-05-2026.serve-longdoc",
         "openpangu-ultra-moe-718b.serve-longdoc",
         "gigachat3.5-432b-a28b.serve-longgen"]


def _span(name, start, **attrs):
    return (name, start, start + 0.01, None, attrs)


def test_the_entry_names_the_serve_cells_and_the_span_attributes():
    entry = [m for m in harness.manifest()["per_layer"]
             if m["name"] == "decode_ahead_share"][0]
    assert entry["workloads"] == SERVE
    assert (entry["layer"], entry["moves"], entry["better"], entry["unit"],
            entry["source"]) == ("entry: serving", "ttft_p70_ms", "higher",
                                 "%", "program_span")
    for cell in SERVE:
        assert entry["moves"] in {
            e["name"] for e in harness.resolve(
                harness.manifest(), cell)["end_to_end"]}
    assert SPEC["reader"] == "span_attr_ratio"
    assert SPEC["params"] == {
        "phase": "window", "spans": ["serving.decode.run"],
        "num": "ahead_slots", "den": "active", "scale": 100.0}


@pytest.mark.parametrize("ticks,want", [
    (((0, 3), (3, 3), (4, 4), (4, 4)), 11 / 14 * 100),
    (((5, 5), (5, 5)), 100.0), (((0, 2),), 0.0)])
def test_the_share_over_decode_ticks(ticks, want):
    """The window's sum of rows dispatched ahead over its sum of live
    rows; chunk spans and decode spans outside the window count for
    nothing."""
    log = [_span("serving.decode.run", 1.0 + i, ahead_slots=a, active=n)
           for i, (a, n) in enumerate(ticks)]
    log += [_span("serving.chunk.run", 1.5, ahead_slots=9, active=9),
            _span("serving.decode.run", 0.5, ahead_slots=0, active=16)]
    got = span_attr_ratio.compute(SPEC["params"], log, 0.0, 1.0, 10.0)
    assert got == pytest.approx(want)


def test_a_program_that_never_dispatches_ahead_reads_zero():
    """A program whose decode spans carry no `ahead_slots` (every tick's
    tokens fetched before the next was built) dispatched no row ahead:
    0%. A window with no decode tick reads nothing."""
    log = [_span("serving.decode.run", 2.0, active=4),
           _span("serving.decode.run", 3.0, active=2)]
    assert span_attr_ratio.compute(SPEC["params"], log, 0.0, 1.0,
                                   10.0) == 0.0
    assert span_attr_ratio.compute(SPEC["params"], log, 0.0, 5.0,
                                   10.0) is None
