"""Distributed core: mesh, groups, collectives (traced + eager), auto-parallel
shard_tensor/reshard.

Runs on the conftest's 8-device virtual CPU platform — the analog of the
reference's multi-process-on-one-host collective tests
(/root/reference/test/legacy_test/test_dist_base.py:957) with the real XLA
partitioner instead of forked processes.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

import paddle_tpu as paddle
import paddle_tpu.distributed as dist


def test_env():
    dist.init_parallel_env()
    assert dist.get_world_size() == 1  # single process
    assert dist.get_rank() == 0
    assert dist.global_mesh().size == 8


def test_process_mesh_basic():
    mesh = dist.ProcessMesh([[0, 1, 2, 3], [4, 5, 6, 7]], dim_names=["dp", "mp"])
    assert mesh.shape == [2, 4]
    assert mesh.size == 8
    jm = mesh.to_jax_mesh()
    assert jm.axis_names == ("dp", "mp")
    assert jm.devices.shape == (2, 4)
    sub = mesh[0]
    assert sub.shape == [4]
    assert sub.dim_names == ["mp"]


def test_shard_tensor_and_placements():
    mesh = dist.ProcessMesh([[0, 1, 2, 3], [4, 5, 6, 7]], dim_names=["x", "y"])
    data = np.arange(64, dtype=np.float32).reshape(8, 8)
    t = dist.shard_tensor(paddle.to_tensor(data), mesh, [dist.Shard(0), dist.Shard(1)])
    assert t.is_dist()
    assert t.placements[0].is_shard(0) and t.placements[1].is_shard(1)
    np.testing.assert_array_equal(t.numpy(), data)
    # sharding really landed on the mesh
    sh = t._data.sharding
    assert isinstance(sh, NamedSharding)
    assert sh.spec == P("x", "y")


def test_reshard_s_to_r_and_s_to_s():
    mesh = dist.ProcessMesh(list(range(8)), dim_names=["x"])
    data = np.random.rand(16, 8).astype(np.float32)
    t = dist.shard_tensor(paddle.to_tensor(data), mesh, [dist.Shard(0)])
    r = dist.reshard(t, mesh, [dist.Replicate()])
    np.testing.assert_allclose(r.numpy(), data)
    assert r._data.sharding.is_fully_replicated
    s2 = dist.reshard(t, mesh, [dist.Shard(1)])
    np.testing.assert_allclose(s2.numpy(), data)
    assert s2._data.sharding.spec == P(None, "x")


def test_partial_invariant():
    mesh = dist.ProcessMesh([0, 1, 2, 3], dim_names=["x"])
    data = np.random.rand(4, 4).astype(np.float32)
    t = dist.shard_tensor(paddle.to_tensor(data), mesh, [dist.Partial()])
    assert t.placements[0].is_partial()
    r = dist.reshard(t, mesh, [dist.Replicate()])
    np.testing.assert_allclose(r.numpy(), data, rtol=1e-6)


def test_gspmd_propagation_matmul():
    # TP-style: x replicated, w col-sharded -> y col-sharded, no user comm code
    mesh = dist.ProcessMesh(list(range(8)), dim_names=["mp"])
    x = dist.shard_tensor(paddle.rand([4, 16]), mesh, [dist.Replicate()])
    w = dist.shard_tensor(paddle.rand([16, 32]), mesh, [dist.Shard(1)])
    y = paddle.matmul(x, w)
    np.testing.assert_allclose(
        y.numpy(), x.numpy() @ w.numpy(), rtol=2e-5, atol=2e-5)


def test_dtensor_from_fn():
    mesh = dist.ProcessMesh(list(range(8)), dim_names=["x"])
    t = dist.dtensor_from_fn(paddle.zeros, mesh, [dist.Shard(0)], [16, 8])
    assert t.shape == [16, 8]
    assert float(t.numpy().sum()) == 0.0
    assert t._data.sharding.spec[0] == "x"


def test_unshard():
    mesh = dist.ProcessMesh(list(range(8)), dim_names=["x"])
    data = np.random.rand(8, 8).astype(np.float32)
    t = dist.shard_tensor(paddle.to_tensor(data), mesh, [dist.Shard(0)])
    u = dist.unshard_dtensor(t)
    np.testing.assert_allclose(u.numpy(), data)


# --------------------------------------------------------------- collectives
def test_eager_all_reduce_replicated():
    g = dist.new_group(ranks=[0])  # world is 1 process
    t = paddle.to_tensor([1.0, 2.0])
    dist.all_reduce(t, group=g)
    np.testing.assert_allclose(t.numpy(), [1.0, 2.0])


def test_traced_collectives_shard_map():
    """Collective API used inside shard_map — the compiled SPMD path."""
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("g",))
    g = dist.Group(ranks=[0, 1, 2, 3], axis_name="g")

    def body(x):
        t = paddle.Tensor(x, _internal=True)
        dist.all_reduce(t, group=g)
        return t._data

    x = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
    out = jax.jit(shard_map(body, mesh=mesh, in_specs=P("g"), out_specs=P("g")))(x)
    expected = np.broadcast_to(x.sum(0, keepdims=True), (4, 2)).reshape(4, 2)
    np.testing.assert_allclose(np.asarray(out), expected)


def test_traced_all_gather_and_reduce_scatter():
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("g",))
    g = dist.Group(ranks=[0, 1, 2, 3], axis_name="g")

    def body(x):
        t = paddle.Tensor(x, _internal=True)
        parts = dist.all_gather(None, t, group=g)
        gathered = jnp.concatenate([p._data for p in parts], axis=0)
        rs_in = paddle.Tensor(gathered, _internal=True)
        out = paddle.Tensor(jnp.zeros((1, 2)), _internal=True)
        dist.reduce_scatter(out, rs_in, group=g)
        return out._data

    x = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
    out = jax.jit(shard_map(body, mesh=mesh, in_specs=P("g"), out_specs=P("g")))(x)
    # reduce_scatter(sum over ranks of gathered) -> each rank r gets sum of row r * ... :
    # gathered on every rank = full x; sum over ranks = 4x; rank r takes chunk r (one row)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 4)


def test_traced_ppermute_batch_isend_irecv():
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("g",))
    g = dist.Group(ranks=[0, 1, 2, 3], axis_name="g")

    def body(x):
        t = paddle.Tensor(x, _internal=True)
        r = paddle.Tensor(jnp.zeros_like(x), _internal=True)
        perm_ops = [dist.P2POp(dist.isend, t, (i + 1) % 4, g) for i in range(4)]
        recv_ops = [dist.P2POp(dist.irecv, r, 0, g)]
        dist.batch_isend_irecv(perm_ops[:1] + recv_ops)
        return r._data

    x = jnp.arange(4, dtype=jnp.float32).reshape(4, 1)
    out = jax.jit(shard_map(body, mesh=mesh, in_specs=P("g"), out_specs=P("g")))(x)
    np.testing.assert_allclose(np.asarray(out).ravel(), [3.0, 0.0, 1.0, 2.0])
