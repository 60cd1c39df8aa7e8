"""Distributed checkpoint: save sharded, load resharded (SURVEY §5.4).

Reference parity: test model of
/root/reference/python/paddle/distributed/checkpoint/save_state_dict.py:135 /
load_state_dict.py:476 — save on one mesh/placement, load on another;
slice-intersection assembly must be exact.
"""
import re

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist


def _by_number(names):
    """`names` sorted with each run of digits read as a number."""
    return sorted(names, key=lambda n: [int(t) if t.isdigit() else t
                                        for t in re.split(r"(\d+)", n)])


def _mesh(shape, names):
    devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


def _place(arr, mesh, spec):
    return jax.device_put(arr, NamedSharding(mesh, spec))


def _gather(x):
    return np.asarray(jax.device_get(x))


class TestReshardOnLoad:
    def test_dp2mp4_to_dp4mp2(self, tmp_path):
        rs = np.random.RandomState(0)
        w = rs.randn(16, 32).astype("float32")
        b = rs.randn(32).astype("float32")

        m1 = _mesh((2, 4), ("dp", "mp"))
        sd = {
            "linear.weight": paddle.Tensor(_place(w, m1, P(None, "mp")), _internal=True),
            "linear.bias": paddle.Tensor(_place(b, m1, P("mp")), _internal=True),
            "step": 7,
        }
        dist.save_state_dict(sd, str(tmp_path / "ckpt"))

        m2 = _mesh((4, 2), ("dp", "mp"))
        target = {
            "linear.weight": paddle.Tensor(
                _place(np.zeros_like(w), m2, P("mp", None)), _internal=True),
            "linear.bias": paddle.Tensor(
                _place(np.zeros_like(b), m2, P(None)), _internal=True),
            "step": 0,
        }
        status = dist.load_state_dict(target, str(tmp_path / "ckpt"))
        assert sorted(status.loaded) == ["linear.bias", "linear.weight", "step"]
        np.testing.assert_array_equal(_gather(target["linear.weight"]._data), w)
        np.testing.assert_array_equal(_gather(target["linear.bias"]._data), b)
        assert target["step"] == 7
        # placement really is the target's, not the saved one
        assert target["linear.weight"]._data.sharding.spec == P("mp", None)

    def test_world_size_change(self, tmp_path):
        rs = np.random.RandomState(1)
        w = rs.randn(8, 8, 4).astype("float32")
        m8 = _mesh((8,), ("x",))
        sd = {"w": paddle.Tensor(_place(w, m8, P("x")), _internal=True)}
        dist.save_state_dict(sd, str(tmp_path / "c"))

        m2 = _mesh((2,), ("x",))  # "smaller pod"
        tgt = {"w": paddle.Tensor(_place(np.zeros_like(w), m2, P(None, "x")), _internal=True)}
        dist.load_state_dict(tgt, str(tmp_path / "c"))
        np.testing.assert_array_equal(_gather(tgt["w"]._data), w)

    def test_replicated_to_sharded(self, tmp_path):
        rs = np.random.RandomState(2)
        w = rs.randn(12, 6).astype("float32")
        sd = {"w": paddle.to_tensor(w)}  # single-device, fully replicated
        dist.save_state_dict(sd, str(tmp_path / "c"))

        m = _mesh((4,), ("mp",))
        tgt = {"w": paddle.Tensor(_place(np.zeros_like(w), m, P("mp")), _internal=True)}
        dist.load_state_dict(tgt, str(tmp_path / "c"))
        np.testing.assert_array_equal(_gather(tgt["w"]._data), w)

    def test_2d_sharding_to_2d_sharding(self, tmp_path):
        rs = np.random.RandomState(3)
        w = rs.randn(16, 16).astype("float32")
        m1 = _mesh((2, 4), ("a", "b"))
        sd = {"w": paddle.Tensor(_place(w, m1, P("a", "b")), _internal=True)}
        dist.save_state_dict(sd, str(tmp_path / "c"))

        m2 = _mesh((4, 2), ("a", "b"))
        tgt = {"w": paddle.Tensor(_place(np.zeros_like(w), m2, P("b", "a")), _internal=True)}
        dist.load_state_dict(tgt, str(tmp_path / "c"))
        np.testing.assert_array_equal(_gather(tgt["w"]._data), w)

    def test_nested_optimizer_state(self, tmp_path):
        paddle.seed(0)
        import paddle_tpu.nn as nn

        model = nn.Linear(8, 4)
        opt = paddle.optimizer.Adam(parameters=model.parameters())
        # one step so moments exist
        loss = model(paddle.rand([2, 8])).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        sd = {"model": model.state_dict(), "opt": opt.state_dict()}
        dist.save_state_dict(sd, str(tmp_path / "c"))

        paddle.seed(123)
        model2 = nn.Linear(8, 4)
        opt2 = paddle.optimizer.Adam(parameters=model2.parameters())
        loss = model2(paddle.rand([2, 8])).sum()
        loss.backward()
        opt2.step()
        opt2.clear_grad()
        # auto-generated tensor names differ because the in-process name
        # counter advanced; a fresh process regenerates identical names.
        # Remap the second model's opt-state keys onto the saved ones.
        opt_sd2 = opt2.state_dict()
        # in the counter's order: as text "tensor_11" sorts before
        # "tensor_9", and one model's names may cross a power of ten
        remap = dict(zip(_by_number(opt_sd2), _by_number(opt.state_dict())))
        opt_sd2 = {remap[k]: v for k, v in opt_sd2.items()}
        tgt = {"model": model2.state_dict(), "opt": opt_sd2}
        dist.load_state_dict(tgt, str(tmp_path / "c"))
        for k in model.state_dict():
            np.testing.assert_array_equal(
                model2.state_dict()[k].numpy(), model.state_dict()[k].numpy())

    def test_strict_missing_raises(self, tmp_path):
        sd = {"a": paddle.to_tensor(np.ones(3, "float32"))}
        dist.save_state_dict(sd, str(tmp_path / "c"))
        tgt = {"a": paddle.to_tensor(np.zeros(3, "float32")),
               "b": paddle.to_tensor(np.zeros(3, "float32"))}
        with pytest.raises(KeyError, match="missing"):
            dist.load_state_dict(tgt, str(tmp_path / "c"))
        status = dist.load_state_dict(tgt, str(tmp_path / "c"), strict=False)
        assert status.missing == ["b"]

    def test_shape_mismatch_raises(self, tmp_path):
        sd = {"a": paddle.to_tensor(np.ones((3, 3), "float32"))}
        dist.save_state_dict(sd, str(tmp_path / "c"))
        tgt = {"a": paddle.to_tensor(np.zeros((4, 4), "float32"))}
        with pytest.raises(ValueError, match="shape"):
            dist.load_state_dict(tgt, str(tmp_path / "c"))
