"""The plain data-parallel gradient exchange inside the compiled step.

- *Numerics* (CPU mesh): coalesced buckets (``bucket_bytes=``) give the
  per-leaf exchange's gradients, through ``make_train_step`` too, alone
  and with accumulation and clipping; a bf16 leaf rides an f32 bucket and
  comes back bf16.
- *In-scan-body sync*: ``grad_sync_axis`` + ``presynced`` equal the stock
  step (no CLI entry since ``--overlap`` went: ROADMAP D18).
- *What the chip's readers see*: the step compiled for a described
  ``v5e:2x2`` holds its ``all-reduce``s under the scope ``grad_sync`` —
  what ``train_grad_sync_ms`` and ``train_exposed_collective_frac`` sum.
  Skipped where no topology can be described.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import distributeddataparallel_tpu as ddp
from distributeddataparallel_tpu.observability import scopes
from distributeddataparallel_tpu.parallel.data_parallel import (
    all_reduce_gradients,
)
from distributeddataparallel_tpu.runtime.distributed import (
    make_mesh,
    tpu_topology_mesh,
)


def test_bucket_mixed_dtypes(devices):
    """One f32 bucket holds an f32 and a bf16 leaf: both reduce in f32 and
    each comes back in its own dtype."""
    mesh = make_mesh(("data",))
    n = mesh.shape["data"]
    trees = [
        {
            "a": jax.random.normal(jax.random.PRNGKey(50 + i), (64, 8)),
            "b": jax.random.normal(
                jax.random.PRNGKey(80 + i), (16, 16)
            ).astype(jnp.bfloat16),
        }
        for i in range(n)
    ]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    def f(shard):
        local = jax.tree.map(lambda x: x[0], shard)
        return all_reduce_gradients(local, "data", bucket_bytes=1 << 20)

    out = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
                      check_vma=False)
    )(stacked)
    exp_a = jnp.mean(jnp.stack([t["a"] for t in trees]), 0)
    exp_b = (
        sum(t["b"].astype(jnp.float32) for t in trees) / n
    )
    np.testing.assert_allclose(out["a"], exp_a, rtol=1e-6, atol=1e-7)
    assert out["b"].dtype == jnp.bfloat16
    # the f32 mean, rounded once to bf16 (8 bits of mantissa)
    np.testing.assert_allclose(
        out["b"].astype(jnp.float32), exp_b, rtol=2 ** -8, atol=1e-6
    )


@pytest.mark.parametrize(
    "kw", [{}, {"accum_steps": 2}, {"grad_clip": 0.5}],
    ids=["plain", "accum2", "clip"],
)
def test_bucketed_train_step_matches_stock(devices, kw):
    """``bucket_bytes`` changes how the gradients travel, not what they
    are: same loss, same params as the per-leaf step."""
    mesh = make_mesh(("data",))

    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    params = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (16, 4)),
        "b": jnp.zeros((4,)),
    }
    batch = {
        "x": jax.random.normal(jax.random.PRNGKey(1), (32, 16)),
        "y": jax.random.normal(jax.random.PRNGKey(2), (32, 4)),
    }
    sharded = ddp.DataParallel(mesh).shard_batch(batch)

    outs = []
    for bucket_bytes in (None, 1 << 20):
        state = ddp.TrainState.create(
            apply_fn=None, params=jax.tree.map(jnp.copy, params),
            tx=optax.sgd(0.1),
        )
        state = ddp.broadcast_params(state, mesh)
        step = ddp.make_train_step(
            loss_fn, mesh=mesh, donate=False, bucket_bytes=bucket_bytes,
            **kw,
        )
        new_state, metrics = step(state, sharded, jax.random.PRNGKey(3))
        outs.append((new_state.params, float(metrics["loss"])))

    (stock_p, stock_loss), (bucket_p, bucket_loss) = outs
    np.testing.assert_allclose(stock_loss, bucket_loss, rtol=1e-6)
    assert not np.array_equal(stock_p["w"], params["w"])  # it stepped
    for k in params:
        np.testing.assert_allclose(
            stock_p[k], bucket_p[k], rtol=1e-6, atol=1e-7
        )


def test_scan_body_grad_sync_matches_stock(devices):
    """grad_sync_axis (in-scan-body pmean via sync_grad_in_backward) +
    presynced skip-list in the step == the stock DP step, bit-for-bit in
    params and loss — the reduction moves INTO the backward while loop,
    the math doesn't change."""
    import jax.numpy as jnp

    from distributeddataparallel_tpu.data.loader import shard_batch
    from distributeddataparallel_tpu.models import TransformerLM, tiny_lm
    from distributeddataparallel_tpu.ops import lm_cross_entropy

    mesh = make_mesh(("data",))
    n = mesh.shape["data"]
    seq = 16

    def build(grad_sync_axis, remat):
        cfg = tiny_lm(
            max_seq_len=seq, scan_layers=True, remat=remat,
            grad_sync_axis=grad_sync_axis,
        )
        model = TransformerLM(cfg)

        def loss_fn(params, batch, rng):
            toks = batch["tokens"]
            logits = model.apply({"params": params}, toks[:, :-1])
            return lm_cross_entropy(logits, toks[:, 1:]), {}

        return model, loss_fn

    toks = np.asarray(
        jax.random.randint(
            jax.random.PRNGKey(7), (4 * n, seq + 1), 0, 256
        ),
        np.int32,
    )
    for remat in (False, True):
        model0, loss0 = build(None, remat)
        model1, loss1 = build("data", remat)
        params = model0.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
        )["params"]
        # the map_variables wrap is identity at init: same param tree
        params1 = model1.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
        )["params"]
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(a, b),
            params, params1,
        )

        outs = []
        for loss_fn, kwargs in (
            (loss0, {}),
            (loss1, {"presynced": lambda p: p[0] == "layers"}),
        ):
            state = ddp.TrainState.create(
                apply_fn=None, params=jax.tree.map(jnp.copy, params),
                tx=optax.sgd(0.1),
            )
            state = ddp.broadcast_params(state, mesh)
            step = ddp.make_train_step(
                loss_fn, mesh=mesh, donate=False, **kwargs
            )
            new_state, metrics = step(
                state, shard_batch({"tokens": toks}, mesh),
                jax.random.PRNGKey(3),
            )
            outs.append((new_state.params, float(metrics["loss"])))

        np.testing.assert_allclose(outs[0][1], outs[1][1], rtol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
            ),
            outs[0][0], outs[1][0],
        )


def test_grad_sync_axis_requires_scan(devices):
    import jax.numpy as jnp

    from distributeddataparallel_tpu.models import TransformerLM, tiny_lm

    cfg = tiny_lm(scan_layers=False, grad_sync_axis="data")
    with pytest.raises(ValueError, match="scan_layers"):
        TransformerLM(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )


def test_presynced_rejects_zero_and_nosync(devices):
    mesh = make_mesh(("data",))

    def loss_fn(params, batch, rng):
        return jnp.sum(params["w"] * 0.0), {}

    with pytest.raises(ValueError, match="presynced"):
        ddp.make_train_step(
            loss_fn, mesh=mesh, zero=True, presynced=lambda p: False
        )
    with pytest.raises(ValueError, match="presynced"):
        ddp.make_train_step(
            loss_fn, mesh=mesh, grad_sync=False, presynced=lambda p: False
        )


def test_bucket_bytes_rejects_zero1_and_nosync(devices):
    mesh = make_mesh(("data",))

    def loss_fn(params, batch, rng):
        return jnp.sum(params["w"] * 0.0), {}

    with pytest.raises(ValueError, match="bucket_bytes"):
        ddp.make_train_step(
            loss_fn, mesh=mesh, zero=True, bucket_bytes=1 << 20
        )
    with pytest.raises(ValueError, match="bucket_bytes"):
        ddp.make_train_step(
            loss_fn, mesh=mesh, grad_sync=False, bucket_bytes=1 << 20
        )


@pytest.fixture(scope="module")
def v5e_mesh():
    try:
        return tpu_topology_mesh("v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e!r}")


@pytest.mark.parametrize(
    "bucket_bytes", [None, 25 * 1024 * 1024], ids=["per_leaf", "25MiB"]
)
def test_grad_sync_scope_on_compiled_all_reduces(v5e_mesh, bucket_bytes):
    """The data-parallel step of a small ``TransformerLM``, compiled for
    four described v5e chips: its gradient ``all-reduce``s carry the scope
    the chip's readers sum, whichever way the exchange is sized, and are
    at most one a leaf (the combiner merges, nothing reduces twice)."""
    from distributeddataparallel_tpu.models import TransformerLM, tiny_lm
    from distributeddataparallel_tpu.ops import lm_cross_entropy

    seq = 16
    model = TransformerLM(tiny_lm(max_seq_len=seq))

    def loss_fn(params, batch, rng):
        toks = batch["tokens"]
        logits = model.apply({"params": params}, toks[:, :-1])
        return lm_cross_entropy(logits, toks[:, 1:]), {}

    def make_state():
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
        )["params"]
        return ddp.TrainState.create(
            apply_fn=None, params=params, tx=optax.sgd(0.1)
        )

    state = jax.eval_shape(make_state)
    n_leaves = len(jax.tree.leaves(state.params))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (2 * v5e_mesh.devices.size, seq + 1), jnp.int32
    )}
    step = ddp.make_train_step(
        loss_fn, mesh=v5e_mesh, donate=False, bucket_bytes=bucket_bytes
    )
    text = step.lower(
        state, batch, jax.ShapeDtypeStruct((2,), jnp.uint32)
    ).compile().as_text()
    reduces = [
        line for line in text.splitlines()
        if re.search(r" all-reduce(-start)?\(", line)
    ]
    synced = [
        line for line in reduces
        if re.search(rf'op_name="[^"]*\b{scopes.GRAD_SYNC}\b', line)
    ]
    assert 1 <= len(synced) <= n_leaves, (len(synced), n_leaves)
    # the others are the step's scalar metrics, under their own scope
    for line in reduces:
        assert line in synced or scopes.METRICS in line, line
