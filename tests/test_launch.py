"""Launcher / spawn / elastic / rpc / auto-tuner tests.

Reference parity model: launch/main.py:23 per-rank env contract +
CollectiveController watch/restart, fleet/elastic/manager.py membership,
rpc two-worker roundtrip, auto_tuner search/prune.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu.distributed.auto_tuner import AutoTuner, Candidate
from paddle_tpu.distributed.fleet.elastic import ElasticManager, ElasticStatus
from paddle_tpu.distributed.launch.main import _parse, launch_pod


SCRIPT_OK = """
import os, json, sys
print(json.dumps({
    "rank": os.environ["PADDLE_TRAINER_ID"],
    "world": os.environ["PADDLE_TRAINERS_NUM"],
    "master": os.environ["PADDLE_MASTER"],
}))
"""

SCRIPT_FLAKY = """
import os, sys
marker = os.environ["FLAKY_MARKER"]
if not os.path.exists(marker):
    open(marker, "w").write("x")
    sys.exit(1)   # first pod attempt fails
sys.exit(0)       # relaunch succeeds
"""


class TestLauncher:
    def test_env_contract_and_logs(self, tmp_path):
        script = tmp_path / "train.py"
        script.write_text(SCRIPT_OK)
        args = _parse(["--nproc_per_node", "2", "--log_dir",
                       str(tmp_path / "log"), str(script)])
        rc = launch_pod(args)
        assert rc == 0
        recs = {}
        for r in range(2):
            line = (tmp_path / "log" / f"workerlog.{r}").read_text().strip()
            recs[r] = json.loads(line.splitlines()[-1])
        assert recs[0]["rank"] == "0" and recs[1]["rank"] == "1"
        assert recs[0]["world"] == "2"
        assert recs[0]["master"] == recs[1]["master"]

    def test_restart_on_failure(self, tmp_path):
        script = tmp_path / "train.py"
        script.write_text(SCRIPT_FLAKY)
        os.environ["FLAKY_MARKER"] = str(tmp_path / "marker")
        try:
            args = _parse(["--max_restart", "2", "--log_dir",
                           str(tmp_path / "log"), str(script)])
            rc = launch_pod(args)
        finally:
            del os.environ["FLAKY_MARKER"]
        assert rc == 0  # failed once, relaunched, succeeded

    def test_gives_up_after_max_restart(self, tmp_path):
        script = tmp_path / "train.py"
        script.write_text("import sys; sys.exit(3)")
        args = _parse(["--max_restart", "1", "--log_dir",
                       str(tmp_path / "log"), str(script)])
        assert launch_pod(args) == 1

    def test_module_entrypoint(self, tmp_path):
        script = tmp_path / "train.py"
        script.write_text(SCRIPT_OK)
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "1", "--log_dir", str(tmp_path / "log"),
             str(script)],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr


class TestSpawn:
    def test_spawn_sets_rank_env(self, tmp_path):
        from paddle_tpu.distributed import spawn

        out = str(tmp_path / "rank{}.txt")

        spawn(_spawn_target, args=(out,), nprocs=2)
        ranks = sorted(open(out.format(i)).read() for i in range(2))
        assert ranks == ["0/2", "1/2"]

    def test_spawn_propagates_failure(self):
        from paddle_tpu.distributed import spawn

        with pytest.raises(RuntimeError, match="worker"):
            spawn(_spawn_fail, nprocs=2)


def _spawn_target(out_tpl):
    import os

    rank = os.environ["PADDLE_TRAINER_ID"]
    world = os.environ["PADDLE_TRAINERS_NUM"]
    open(out_tpl.format(rank), "w").write(f"{rank}/{world}")


def _spawn_fail():
    import os

    if os.environ["PADDLE_TRAINER_ID"] == "1":
        raise ValueError("rank 1 exploded")


class TestElastic:
    def test_membership_and_decisions(self, tmp_path):
        m0 = ElasticManager("job", "2:4", store_dir=str(tmp_path), timeout=5.0)
        m0.rank = 0
        m1 = ElasticManager("job", "2:4", store_dir=str(tmp_path), timeout=5.0)
        m1.rank = 1
        m0.heartbeat()
        m1.heartbeat()
        assert m0.alive_members() == [0, 1]
        assert m0.pod_status() == ElasticStatus.HOLD  # viable but below max
        assert m0.should_relaunch(expected_np=3)      # membership shrank
        assert not m0.should_relaunch(expected_np=2)
        m1.leave()
        assert m0.alive_members() == [0]
        assert m0.pod_status() == ElasticStatus.RESTART  # below min

    def test_stale_heartbeats_expire(self, tmp_path):
        m = ElasticManager("job2", "1:2", store_dir=str(tmp_path), timeout=0.2)
        m.heartbeat()
        assert m.alive_members() == [0]
        time.sleep(0.3)
        assert m.alive_members() == []

    def test_wait_for_ready(self, tmp_path):
        m = ElasticManager("job3", "1:1", store_dir=str(tmp_path))
        assert m.wait_for_ready(max_wait=5.0) == 1


def _rpc_add(a, b):
    return a + b


def _rpc_boom():
    raise ValueError("remote boom")


class TestRPC:
    def test_local_roundtrip(self):
        from paddle_tpu.distributed import rpc

        rpc.init_rpc("w0")
        try:
            assert rpc.rpc_sync("w0", _rpc_add, args=(2, 3)) == 5
            fut = rpc.rpc_async("w0", _rpc_add, args=(10, 20))
            assert fut.result(timeout=30) == 30
            info = rpc.get_current_worker_info()
            assert info.name == "w0" and info.rank == 0
            with pytest.raises(ValueError, match="remote boom"):
                rpc.rpc_sync("w0", _rpc_boom)
            with pytest.raises(ValueError, match="unknown rpc worker"):
                rpc.get_worker_info("nope")
        finally:
            rpc.shutdown()

    def test_reinit_after_shutdown(self):
        from paddle_tpu.distributed import rpc

        rpc.init_rpc("w0")
        rpc.shutdown()
        rpc.init_rpc("w0")
        try:
            assert rpc.rpc_sync("w0", _rpc_add, args=(1, 1)) == 2
        finally:
            rpc.shutdown()


class TestAutoTuner:
    def test_candidates_pruned(self):
        t = AutoTuner(8, num_heads=16, num_layers=8, global_batch=16)
        cands = t.candidates()
        assert cands, "no feasible candidates"
        for c in cands:
            assert c.degree == 8
            assert 16 % c.mp == 0 and 8 % c.pp == 0
            assert not (c.sharding_stage > 0 and c.dp == 1)
            assert 16 % (c.dp * c.micro_batch) == 0

    def test_heads_constraint_prunes_mp(self):
        t = AutoTuner(8, num_heads=6, global_batch=8)
        assert all(c.mp in (1, 2, 3, 6) for c in t.candidates())

    def test_tune_picks_best_and_skips_failures(self):
        t = AutoTuner(4, global_batch=8, micro_batches=(1, 2))

        def trial(c):
            if c.pp > 1:
                raise MemoryError("pipeline OOM (pretend)")
            return c.dp * 10 + c.micro_batch

        best = t.tune(trial)
        assert best is not None and best.pp == 1
        assert best.metric == max(c.metric for c in t.history
                                  if c.metric is not None)
        assert any(c.error for c in t.history)  # failures recorded

    def test_memory_model_prunes(self):
        from paddle_tpu.distributed.auto_tuner import default_memory_model

        mm = lambda c: default_memory_model(
            c, n_params=7e9, hidden=4096, layers=32, seq_len=2048,
            global_batch=64)
        t = AutoTuner(8, global_batch=64, memory_limit_bytes=16e9,
                      memory_model=mm)
        allowed = t.candidates()
        t2 = AutoTuner(8, global_batch=64)
        assert len(allowed) < len(t2.candidates())


class TestCostModel:
    """Analytic cost model (≙ auto_tuner/cost_model.py + prune.py): step-time
    prediction ranks candidates; memory predictor prunes OOM configs."""

    def _spec(self):
        from paddle_tpu.distributed.auto_tuner.cost_model import (
            ChipSpec, ModelSpec)

        # ~7B llama-ish
        return ModelSpec(n_params=7e9, hidden=4096, layers=32,
                         seq_len=2048), ChipSpec()

    def test_predict_terms_positive_and_scale(self):
        from paddle_tpu.distributed.auto_tuner.cost_model import (
            predict_step_time)

        model, chip = self._spec()
        c = Candidate(dp=8, mp=4, pp=2, sharding_stage=2, micro_batch=1)
        t = predict_step_time(c, model, chip, global_batch=64)
        assert t["total"] > 0 and t["compute"] > 0
        # doubling the batch ~doubles compute-bound total
        t2 = predict_step_time(c, model, chip, global_batch=128)
        assert 1.5 < t2["total"] / t["total"] < 2.5

    def test_ranking_prefers_sane_configs(self):
        from paddle_tpu.distributed.auto_tuner.cost_model import (
            ModelSpec, rank_candidates)

        # tiny model on 8 chips: dp-only should beat heavy mp/pp (mp
        # collectives + bubbles dominate when compute is negligible)
        model = ModelSpec(n_params=1e8, hidden=768, layers=12, seq_len=512)
        cands = [Candidate(8, 1, 1, 2, 1), Candidate(1, 8, 1, 0, 1),
                 Candidate(1, 1, 8, 0, 1)]
        ranked = rank_candidates(cands, model, None, global_batch=64)
        assert (ranked[0].dp, ranked[0].mp, ranked[0].pp) == (8, 1, 1)

    def test_memory_pruning_via_model_spec(self):
        from paddle_tpu.distributed.auto_tuner.cost_model import ModelSpec

        # 2B fp32 state cannot fit un-sharded on a 16GB chip (8+8+16 GB):
        # dp-only ZeRO-0 must be pruned while sharded configs survive
        model = ModelSpec(n_params=2e9, hidden=2048, layers=24, seq_len=1024)
        t = AutoTuner(8, num_heads=32, num_layers=24, global_batch=32,
                      model_spec=model, sharding_stages=(0, 2, 3))
        cands = t.candidates()
        assert cands, "everything pruned?"
        assert not any(c.mp == 1 and c.pp == 1 and c.sharding_stage == 0
                       for c in cands)

    def test_tuner_tries_predicted_best_first(self):
        from paddle_tpu.distributed.auto_tuner.cost_model import ModelSpec

        model = ModelSpec(n_params=1e8, hidden=768, layers=12, seq_len=512)
        t = AutoTuner(8, num_heads=12, num_layers=12, global_batch=64,
                      model_spec=model)
        tried = []
        t.tune(lambda c: (tried.append(c), 1.0)[1], max_trials=3)
        # for a tiny model the predictor avoids mp (activation allreduces
        # dominate); exact dp/pp split is the model's call
        assert tried and tried[0].mp == 1
        from paddle_tpu.distributed.auto_tuner.cost_model import (
            ChipSpec, predict_step_time)

        times = [predict_step_time(c, model, ChipSpec(), 64)["total"]
                 for c in tried]
        assert times == sorted(times)
