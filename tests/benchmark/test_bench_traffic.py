"""The traffic generator: deterministic in --seed, the clips honoured,
every seed the same multiset of sizes and gaps."""
import re

import numpy as np
import pytest

from benchmark import harness, traffic_gen

MIX = harness.load_json("traffic", "chat-open-loop.json")
BIG_SEED = 2 ** 31 + 12345


def _reqs(seed, seconds=40.0):
    return traffic_gen.serve_requests(MIX, seed, seconds, 32000)


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_same_seed_same_inputs(seed):
    a, b = _reqs(seed), _reqs(seed)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x["due_s"] == y["due_s"]
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert np.array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("seed", [1, BIG_SEED])
def test_clips_and_window(seed):
    reqs = _reqs(seed)
    p, o = MIX["prompt_tokens"], MIX["output_tokens"]
    within = MIX["arrivals"]["due_within"] * 40.0
    assert len(reqs) == round(MIX["arrivals"]["rate_per_s"] * within)
    dues = [r["due_s"] for r in reqs]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < within
    for r in reqs:
        assert p["min"] <= len(r["prompt"]) <= p["max"]
        assert 1 <= r["max_new_tokens"] <= o["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] \
            <= MIX["max_total_tokens"]
        assert r["prompt"].min() >= 0 and r["prompt"].max() < 32000


def _schedule(reqs):
    return [(r["due_s"], len(r["prompt"]), r["max_new_tokens"])
            for r in reqs]


def test_seeds_share_the_schedule_and_differ_in_tokens():
    a, b = _reqs(3), _reqs(4)
    assert _schedule(a) == _schedule(b)
    assert not any(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(a, b))
    other = traffic_gen.serve_requests(dict(MIX, schedule_seed=1), 3, 40.0,
                                       32000)
    assert sorted(len(r["prompt"]) for r in other) \
        == sorted(len(r["prompt"]) for r in a)
    assert [len(r["prompt"]) for r in other] != [len(r["prompt"])
                                                 for r in a]
    assert traffic_gen.warmup_lengths(MIX, 40.0) \
        == sorted({len(r["prompt"]) for r in a})


@pytest.mark.parametrize("name", ["chat-open-loop", "longdoc-open-loop"])
def test_serve_mix_offers_a_share_of_a_knee_that_was_swept(name):
    """What a serve cell's rate rests on: `rate_from` names the two swept
    rates the knee lies between, the mix offers 0.6-0.8 of the lower, and
    a 51 s window of it is the same requests for every `--seed`."""
    mix = harness.load_json("traffic", name + ".json")
    arr = mix["arrivals"]
    low, high = (float(x) for x in
                 re.findall(r"of (\d+\.?\d*)", mix["rate_from"])[:2])
    assert low < high
    assert 0.6 * low <= arr["rate_per_s"] <= 0.8 * low + 1e-9
    a, b = (traffic_gen.serve_requests(mix, seed, 51.0, 32000)
            for seed in (5, BIG_SEED))
    assert len(a) == round(arr["rate_per_s"] * arr["due_within"] * 51)
    assert f"{len(a)} requests" in mix["rate_from"]
    for r in a:
        assert 1 <= r["max_new_tokens"]
        assert len(r["prompt"]) + r["max_new_tokens"] \
            <= mix["max_total_tokens"] <= mix["engine"]["max_model_len"]
    assert _schedule(a) == _schedule(b)
    assert 0 < a[0]["due_s"] and a[-1]["due_s"] < arr["due_within"] * 51


@pytest.mark.parametrize("spec", [
    MIX["prompt_tokens"], MIX["output_tokens"],
    {"dist": "lognormal", "median": 100, "sigma": 0.0, "min": 100,
     "max": 100}], ids=["prompts", "outputs", "no-spread"])
def test_sizes_quantiles(spec):
    x = traffic_gen.sizes(spec, 101)
    assert x.min() >= spec["min"] and x.max() <= spec["max"]
    assert list(x) == sorted(x) and x[50] == spec["median"]


def test_mix_names_its_modules():
    """A distribution, an arrival process and a prompt source are modules
    found by the name the mix gives: a new one is a new file."""
    with pytest.raises(ModuleNotFoundError):
        traffic_gen.sizes(dict(MIX["prompt_tokens"], dist="no-such"), 5)
    with pytest.raises(ModuleNotFoundError):
        traffic_gen.gaps(dict(MIX["arrivals"], process="no-such"), 5)
    with pytest.raises(ModuleNotFoundError):
        traffic_gen.serve_requests(dict(MIX, prompts="no-such"), 1, 40.0,
                                   32000)
    g = traffic_gen.gaps(MIX["arrivals"], 1000)
    assert g.mean() == pytest.approx(1 / MIX["arrivals"]["rate_per_s"],
                                     rel=0.01)


def test_train_batches_rows_differ():
    mix = {"tokens_per_step": 64, "seq": 16}
    g = traffic_gen.train_batches(mix, BIG_SEED, 1000)
    a, b = next(g), next(g)
    assert a.shape == (4, 16) and not np.array_equal(a, b)
    assert len({tuple(r) for r in a}) == 4
    a2 = next(traffic_gen.train_batches(mix, BIG_SEED, 1000))
    assert np.array_equal(a, a2)
