"""Gradient-sync primitive tests: psum/pmean correctness, bucket coalescing
equivalence, param replication (SURVEY.md §4 'multi-device without a cluster')."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributeddataparallel_tpu.parallel.data_parallel import (
    DataParallel,
    all_reduce_gradients,
    broadcast_params,
    bucket_gradients,
)
from distributeddataparallel_tpu.runtime.distributed import make_mesh


def _grad_tree(key, sizes=((8, 16), (128,), (4, 4, 4), (1000,))):
    keys = jax.random.split(key, len(sizes))
    return {
        f"p{i}": jax.random.normal(k, s)
        for i, (k, s) in enumerate(zip(keys, sizes))
    }


def test_all_reduce_mean_matches_manual(devices):
    mesh = make_mesh(("data",))
    n = mesh.shape["data"]
    # per-replica distinct grads: shard a leading axis
    trees = [_grad_tree(jax.random.PRNGKey(i)) for i in range(n)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    def f(shard):
        # shard has leading dim 1 per replica
        local = jax.tree.map(lambda x: x[0], shard)
        return all_reduce_gradients(local, "data", op="mean")

    out = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=(P("data"),), out_specs=P())
    )(stacked)
    expected = jax.tree.map(lambda *xs: jnp.mean(jnp.stack(xs), 0), *trees)
    for k in expected:
        # rtol/atol: XLA's psum may reduce in a different association
        # order than the host-side stack/mean — a few ulps of f32 slack
        # (atol covers near-zero elements where rtol alone is too sharp).
        np.testing.assert_allclose(out[k], expected[k], rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize(
    "sizes,small",
    [
        (((8, 16), (128,), (4, 4, 4), (1000,)), 2048),
        # leaves of 28 to 16384 bytes round a 1 KiB edge: some share a
        # bucket, some overflow one alone
        (((64, 64), (7,), (33, 5), (256,), (2, 3, 4)), 1024),
    ],
    ids=["2KiB", "straddle_1KiB"],
)
def test_bucketed_equals_unbucketed(devices, sizes, small):
    mesh = make_mesh(("data",))
    n = mesh.shape["data"]
    trees = [
        _grad_tree(jax.random.PRNGKey(100 + i), sizes) for i in range(n)
    ]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    def f(shard):
        local = jax.tree.map(lambda x: x[0], shard)
        plain = all_reduce_gradients(local, "data", op="mean")
        # tiny bucket size forces multiple buckets; large forces one
        multi = bucket_gradients(local, "data", op="mean", bucket_bytes=small)
        single = bucket_gradients(local, "data", op="mean", bucket_bytes=1 << 30)
        return plain, multi, single

    plain, multi, single = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=(P("data"),), out_specs=(P(), P(), P()))
    )(stacked)
    for k in plain:
        np.testing.assert_allclose(multi[k], plain[k], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(single[k], plain[k], rtol=1e-5, atol=1e-6)
        assert multi[k].dtype == plain[k].dtype


def test_bucket_sum_op(devices):
    mesh = make_mesh(("data",))

    def f(x):
        return bucket_gradients({"w": x}, "data", op="sum", bucket_bytes=64)["w"]

    xs = jnp.arange(8.0).reshape(8, 1)
    out = jax.jit(
        jax.shard_map(lambda x: f(x[0]), mesh=mesh, in_specs=(P("data"),), out_specs=P())
    )(xs)
    np.testing.assert_allclose(out, jnp.sum(xs))


def test_broadcast_params_replicates(devices):
    mesh = make_mesh(("data",))
    params = _grad_tree(jax.random.PRNGKey(0))
    rep = broadcast_params(params, mesh)
    for leaf in jax.tree.leaves(rep):
        assert leaf.sharding.is_fully_replicated
        assert len(leaf.sharding.device_set) == len(jax.devices())


def test_data_parallel_facade(devices):
    dp = DataParallel()
    assert dp.num_replicas == 8
    batch = {"image": np.ones((16, 4), np.float32), "label": np.zeros((16,), np.int32)}
    sharded = dp.shard_batch(batch)
    # leading dim split 8 ways -> 2 rows per device
    shard_shapes = {
        s.data.shape for s in sharded["image"].addressable_shards
    }
    assert shard_shapes == {(2, 4)}
    rep = dp.replicate({"w": np.ones((3, 3), np.float32)})
    assert jax.tree.leaves(rep)[0].sharding.is_fully_replicated
