"""Elastic fault-tolerance END-TO-END (VERDICT r2 item 8): launch a 2-proc
run, kill one rank mid-training, the launcher detects the death, relaunches
at the surviving world size, and training RESUMES from the distributed
checkpoint (reshard-on-load) instead of restarting from scratch.

Reference analog: fleet/elastic/manager.py:125 membership + launch
controllers' watcher relaunch + distributed/checkpoint resume.
"""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAIN = textwrap.dedent("""
    import json, os, signal, sys, time
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    _stop = []
    signal.signal(signal.SIGTERM, lambda *a: _stop.append(1))
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed.fleet.elastic import ElasticManager

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    restart = int(os.environ.get("PADDLE_RESTART_COUNT", "0"))
    work = {work!r}
    ckpt = os.path.join(work, "ckpt")
    prog = os.path.join(work, f"progress.{{rank}}.jsonl")

    em = ElasticManager(job_id="e2e", np_range="1:2",
                        store_dir=os.path.join(work, "elastic"))
    em.heartbeat()

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    opt = paddle.optimizer.AdamW(learning_rate=0.05,
                                 parameters=model.parameters())
    start_step = 0
    if restart > 0 and os.path.isdir(ckpt):
        state = {{"model": model.state_dict(),
                  "step": paddle.to_tensor(np.zeros((), "int64"))}}
        paddle.distributed.load_state_dict(state, ckpt)
        model.set_state_dict(state["model"])
        start_step = int(np.asarray(state["step"]._data)) + 1

    rs = np.random.RandomState(42)
    X = rs.randn(64, 8).astype("float32")
    Y = (X.sum(1) > 0).astype("int64")

    for step in range(start_step, 10):
        if _stop:
            sys.exit(0)    # clean teardown at a step boundary
        em.heartbeat()
        # dp shard: each rank trains its slice of the batch
        sl = slice(rank * (64 // world), (rank + 1) * (64 // world))
        loss = F.cross_entropy(model(paddle.to_tensor(X[sl])),
                               paddle.to_tensor(Y[sl]))
        loss.backward(); opt.step(); opt.clear_grad()
        if rank == 0:
            paddle.distributed.save_state_dict(
                {{"model": model.state_dict(),
                  "step": paddle.to_tensor(np.asarray(step, "int64"))}},
                ckpt)
        with open(prog, "a") as f:
            f.write(json.dumps({{"step": step, "loss": float(loss),
                                 "world": world,
                                 "restart": restart}}) + "\\n")
        if rank == 1 and restart == 0 and step == 3:
            os._exit(17)   # simulated hardware failure
        time.sleep(0.3)    # keep independent ranks roughly lockstep
    em.leave()
""")


def test_kill_rank_relaunch_resume(tmp_path):
    work = str(tmp_path)
    script = os.path.join(work, "train.py")
    with open(script, "w") as f:
        f.write(TRAIN.format(repo=REPO, work=work))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--np", "1:2", "--elastic_level", "1",
         "--log_dir", os.path.join(work, "log"), script],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "elastic" in r.stderr and "world size 1" in r.stderr, r.stderr

    # rank 0 progress: incarnation 0 ran world=2 up to the kill, then the
    # relaunch ran world=1 RESUMING past the checkpointed step
    recs = [json.loads(ln) for ln in
            open(os.path.join(work, "progress.0.jsonl"))]
    first = [r_ for r_ in recs if r_["restart"] == 0]
    second = [r_ for r_ in recs if r_["restart"] == 1]
    assert first and second, recs
    assert all(r_["world"] == 2 for r_ in first)
    assert all(r_["world"] == 1 for r_ in second)
    kill_step = max(r_["step"] for r_ in first)
    assert second[0]["step"] == kill_step + 1, (kill_step, second[0])
    assert second[-1]["step"] == 9
    # resumed training continues to improve vs the pre-kill loss
    assert second[-1]["loss"] < first[0]["loss"]
