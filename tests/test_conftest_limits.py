"""The two run-wide mechanisms of `tests/conftest.py`, each seen from a
child pytest that loads the same conftest: a test over its time limit
fails alone, with its stack; and one compile cache serves every test and
worker of a run, and is gone when the run ends."""
import json
import os
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TESTS)


def _pytest(tmp_path, files, *args):
    """Run a child pytest over `files` (name -> source) written into
    `tmp_path`, with this suite's conftest as a plugin and two xdist
    workers; returns the finished process."""
    for name, src in files.items():
        (tmp_path / name).write_text(src)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")
           and k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([ROOT, TESTS]))
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "conftest", "-p", "xdist",
         "-n", "2", "-p", "no:cacheprovider", "-p", "no:randomly",
         "--rootdir", str(tmp_path), *args, *files],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=240)


OVERRUN = '''
import signal
import time

import pytest

import paddle_tpu  # noqa: F401  (conftest's seed fixture would import it
                   # under the first test's limit)

@pytest.mark.time_limit(2)
def test_sleeps_past_its_limit():
    time.sleep(30)


@pytest.mark.time_limit(2)
def test_hangs_where_the_alarm_cannot_reach():
    signal.pthread_sigmask(signal.SIG_BLOCK, [signal.SIGALRM])
    time.sleep(30)


def test_in_time():
    time.sleep(0.1)
'''


def test_a_test_over_its_limit_fails_alone_with_its_stack(tmp_path):
    """The alarm fails a test in Python code at its limit with the
    test's own traceback; one it cannot reach (SIGALRM blocked, as in a
    C call that never returns) has every thread's stack dumped and its
    worker ended after a grace as long again. The third test passes, and
    the run ends in seconds with rc 1, not at the run's own limit."""
    p = _pytest(tmp_path, {"test_overrun.py": OVERRUN}, "-rA")
    out = p.stdout + p.stderr
    assert p.returncode == 1, out
    assert "2 failed, 1 passed" in out, out
    assert "ran past its time limit of 2 s" in out
    assert "time.sleep(30)" in out            # where the first one was
    assert "Timeout (0:00:04)!" in out        # faulthandler's dump ...
    assert "line 18 in test_hangs_where_the_alarm_cannot_reach" in out
    assert "crashed while running" in out     # ... and the ended worker


CACHED = '''
import json
import os
import time

import jax
import jax.numpy as jnp

LOG = os.path.join(os.path.dirname(__file__), "compiles.jsonl")


def _compile_fresh():
    """The same program from a new closure: jax's in-memory caches miss,
    so only the persistent cache can spare the compile."""
    seen = []
    jax.monitoring.register_event_listener(
        lambda event, **_: seen.append(event))
    f = jax.jit(lambda x: jnp.sin(x) * 3.25 + jnp.cos(x) / 7.5)
    f.lower(jax.ShapeDtypeStruct((13, 17), jnp.float32)).compile()
    return {"hits": seen.count("/jax/compilation_cache/cache_hits"),
            "misses": seen.count("/jax/compilation_cache/cache_misses"),
            "dir": jax.config.jax_compilation_cache_dir,
            "env": os.environ.get("JAX_COMPILATION_CACHE_DIR")}


def _log(rec):
    with open(LOG, "a") as f:
        f.write(json.dumps(rec) + "\\n")


def test_first():
    _log(dict(_compile_fresh(), test="first"))


def test_second():
    for _ in range(600):                  # until the first has compiled
        if os.path.exists(LOG):
            break
        time.sleep(0.1)
    _log(dict(_compile_fresh(), test="second"))
'''


def test_one_compile_cache_serves_the_run_and_goes_with_it(tmp_path):
    """Two tests compile the same jitted function from fresh closures: the
    first misses the run's cache and writes it, the second loads the
    program from it (jax's `cache_hits` event), whichever worker it runs
    on. Both name one directory, outside the checkout and not in the
    environment, and the run removes it at its end."""
    p = _pytest(tmp_path, {"test_cached.py": CACHED})
    assert p.returncode == 0, p.stdout + p.stderr
    recs = {r["test"]: r for r in map(
        json.loads, (tmp_path / "compiles.jsonl").read_text().splitlines())}
    first, second = recs["first"], recs["second"]
    assert first["misses"] == 1 and first["hits"] == 0, first
    assert second["hits"] == 1 and second["misses"] == 0, second
    assert first["dir"] == second["dir"]
    assert first["env"] is None and second["env"] is None
    assert not first["dir"].startswith(ROOT + os.sep)
    assert not os.path.exists(first["dir"])

