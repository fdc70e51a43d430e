"""Runtime tests: process-group lifecycle, mesh construction, launcher."""

import functools

import jax
import numpy as np
import pytest

from distributeddataparallel_tpu.runtime import distributed as dist
from distributeddataparallel_tpu.runtime.launcher import (
    MULTIPROCESS_UNSUPPORTED_EXIT,
    guarded_worker,
    spawn,
)


def _skip_if_mp_unsupported(codes):
    if MULTIPROCESS_UNSUPPORTED_EXIT in codes:
        pytest.skip(
            "this jaxlib's CPU backend cannot run multiprocess computations"
        )


def test_init_destroy_lifecycle():
    assert not dist.is_initialized()
    dist.init_process_group("cpu")
    assert dist.is_initialized()
    with pytest.raises(RuntimeError):
        dist.init_process_group("cpu")
    assert dist.get_rank() == 0
    assert dist.get_world_size() == 1
    assert dist.local_device_count() == 8
    assert dist.global_device_count() == 8
    dist.destroy_process_group()
    assert not dist.is_initialized()
    # re-init after destroy works
    dist.init_process_group("cpu")
    dist.destroy_process_group()


def test_make_mesh_default(devices):
    mesh = dist.make_mesh(("data",))
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == 8


def test_make_mesh_2d(devices):
    mesh = dist.make_mesh(("data", "model"), shape=(4, 2))
    assert mesh.shape == {"data": 4, "model": 2}
    with pytest.raises(ValueError):
        dist.make_mesh(("data", "model"), shape=(3, 2))


def test_compiler_stamp():
    stamp = dist.compiler_stamp()
    assert stamp["jax"]  # at minimum the jax version is always present


def test_spawn_single_inprocess():
    out = []
    spawn(lambda i, x: out.append((i, x)), args=(42,), nprocs=1)
    assert out == [(0, 42)]


def test_spawn_validates():
    with pytest.raises(ValueError):
        spawn(lambda i: None, nprocs=0)


def test_barrier_single_process(devices):
    dist.barrier()  # must not deadlock or raise in single-process mode


def _mp_dp_worker(process_id, tmpdir):
    """Child of test_spawn_two_process_dp_step — fresh interpreter, so the
    JAX platform must be configured before any device query (the launcher's
    env contract supplies the rendezvous: JAX_COORDINATOR_ADDRESS etc.)."""
    import json
    import os

    import jax

    from distributeddataparallel_tpu.compat import configure_cpu_devices

    configure_cpu_devices(2)

    import jax.numpy as jnp
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data import DataLoader
    from distributeddataparallel_tpu.data.datasets import SyntheticClassification
    from distributeddataparallel_tpu.models import TinyMLP
    from distributeddataparallel_tpu.ops import cross_entropy_loss

    ddp.init_process_group("cpu")  # rendezvous via the spawned env vars
    assert jax.process_count() == 2, jax.process_count()
    assert jax.process_index() == process_id
    assert len(jax.devices()) == 4  # 2 hosts x 2 local devices

    mesh = ddp.make_mesh(("data",))  # global 4-way DP mesh
    ds = SyntheticClassification(num_examples=32, shape=(4, 4, 1), seed=0)
    # Multi-host loader: this process gathers rows for ITS 2 replicas only;
    # the global batch is assembled via make_array_from_process_local_data.
    loader = DataLoader(
        ds, per_replica_batch=4, mesh=mesh, shuffle=False, drop_last=True
    )

    model = TinyMLP(features=(16,))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 4, 1))
    )["params"]

    def loss_fn(p, batch, rng):
        logits = model.apply({"params": p}, batch["image"])
        return cross_entropy_loss(logits, batch["label"]), {}

    state = ddp.TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.sgd(0.1)
    )
    state = ddp.broadcast_params(state, mesh)
    step = ddp.make_train_step(loss_fn, mesh=mesh)
    batch = next(iter(loader))
    state, metrics = step(state, batch, jax.random.PRNGKey(1))

    checksum = sum(
        float(jnp.sum(l.astype(jnp.float32))) for l in jax.tree.leaves(state.params)
    )
    with open(os.path.join(tmpdir, f"rank{process_id}.json"), "w") as f:
        json.dump({"loss": float(metrics["loss"]), "checksum": checksum}, f)
    ddp.destroy_process_group()


def test_spawn_two_process_dp_step(tmp_path, devices):
    """The true L1 path (analog of ref dpp.py:20-24,62): two OS processes
    rendezvous over a localhost coordinator, build one global mesh, feed a
    batch through make_array_from_process_local_data, and take one DP step
    whose loss/params must equal the single-process computation on the same
    global batch (the DDP equivalence invariant, across real processes)."""
    import json

    import jax.numpy as jnp
    import optax

    from distributeddataparallel_tpu.data.datasets import SyntheticClassification
    from distributeddataparallel_tpu.models import TinyMLP
    from distributeddataparallel_tpu.ops import cross_entropy_loss
    from distributeddataparallel_tpu.parallel.sampler import DistributedSampler

    procs = spawn(
        functools.partial(guarded_worker, _mp_dp_worker),
        args=(str(tmp_path),), nprocs=2, join=False,
    )
    for p in procs:
        p.join(timeout=240)
    codes = [p.exitcode for p in procs]
    for p in procs:
        if p.is_alive():
            p.terminate()
    _skip_if_mp_unsupported(codes)
    assert codes == [0, 0], f"child exit codes {codes}"

    results = [
        json.load(open(tmp_path / f"rank{i}.json")) for i in range(2)
    ]
    # Both processes observe the same replicated loss and params.
    assert results[0]["loss"] == pytest.approx(results[1]["loss"], abs=1e-6)
    assert results[0]["checksum"] == pytest.approx(
        results[1]["checksum"], abs=1e-5
    )

    # Single-process reference on the same global batch (replica-major rows
    # from the same sampler striding the children's loader used).
    ds = SyntheticClassification(num_examples=32, shape=(4, 4, 1), seed=0)
    rows = np.concatenate([
        DistributedSampler(len(ds), num_replicas=4, rank=r, shuffle=False)
        .local_indices()[:4]
        for r in range(4)
    ])
    images = jnp.asarray(ds.images[rows])
    labels = jnp.asarray(ds.labels[rows])
    model = TinyMLP(features=(16,))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 4, 1)))["params"]

    def loss_fn(p):
        return cross_entropy_loss(model.apply({"params": p}, images), labels)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    tx = optax.sgd(0.1)
    updates, _ = tx.update(grads, tx.init(params), params)
    new_params = optax.apply_updates(params, updates)
    checksum = sum(
        float(jnp.sum(l.astype(jnp.float32))) for l in jax.tree.leaves(new_params)
    )
    assert results[0]["loss"] == pytest.approx(float(loss), abs=1e-5)
    assert results[0]["checksum"] == pytest.approx(checksum, rel=1e-5)


def _mp_tp_worker(process_id, tmpdir):
    """Child of test_spawn_two_process_dp_tp_step: DP(2) x TP(2) in the
    standard multi-host topology — the TP axis pairs each process's own
    devices (fastest interconnect) while the DP gradient sync crosses
    the process boundary over the collective backend."""
    import json
    import os

    import jax

    from distributeddataparallel_tpu.compat import configure_cpu_devices

    configure_cpu_devices(2)

    import dataclasses

    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data.loader import shard_batch
    from distributeddataparallel_tpu.models import TransformerLM, tiny_lm
    from distributeddataparallel_tpu.ops import lm_cross_entropy

    ddp.init_process_group("cpu")
    assert jax.process_count() == 2

    # 4 global devices as (data=2, model=2), row-major over
    # [p0d0, p0d1, p1d0, p1d1]: each process is one data row and its two
    # local devices form the model (TP) pair — TP stays intra-process,
    # DP crosses processes (the standard deployment layout).
    mesh = ddp.make_mesh(("data", "model"), shape=(2, 2))
    cfg = tiny_lm(num_heads=4, num_kv_heads=2, d_model=32, d_ff=64)
    model_tp = TransformerLM(dataclasses.replace(cfg, tp_axis="model"))
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
    )["params"]

    def loss_fn(p, batch, rng):
        toks = batch["tokens"]
        logits = model_tp.apply({"params": p}, toks[:, :-1])
        return lm_cross_entropy(logits, toks[:, 1:]), {}

    state = ddp.TrainState.create(
        apply_fn=model_tp.apply, params=params, tx=optax.sgd(0.1)
    )
    state = ddp.shard_state_tp(state, mesh)
    step = ddp.make_train_step(loss_fn, mesh=mesh, tp_axis="model")
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, 256, size=(4, 17)).astype(np.int32)
    batch = shard_batch({"tokens": tokens}, mesh)
    state, metrics = step(state, batch, jax.random.PRNGKey(0))

    with open(os.path.join(tmpdir, f"tp_rank{process_id}.json"), "w") as f:
        json.dump({"loss": float(metrics["loss"])}, f)
    ddp.destroy_process_group()


def test_spawn_two_process_dp_tp_step(tmp_path, devices):
    """Multi-process Megatron: two OS processes hold a (data=2, model=2)
    mesh (TP intra-process, DP across processes); the step's loss must
    match the single-process single-device computation."""
    import json

    import jax.numpy as jnp

    from distributeddataparallel_tpu.models import TransformerLM, tiny_lm
    from distributeddataparallel_tpu.ops import lm_cross_entropy

    procs = spawn(
        functools.partial(guarded_worker, _mp_tp_worker),
        args=(str(tmp_path),), nprocs=2, join=False,
    )
    for p in procs:
        p.join(timeout=240)
    codes = [p.exitcode for p in procs]
    for p in procs:
        if p.is_alive():
            p.terminate()
    _skip_if_mp_unsupported(codes)
    assert codes == [0, 0], f"child exit codes {codes}"

    results = [
        json.load(open(tmp_path / f"tp_rank{i}.json")) for i in range(2)
    ]
    assert results[0]["loss"] == pytest.approx(results[1]["loss"], abs=1e-6)

    # Single-device reference on the same global batch.
    cfg = tiny_lm(num_heads=4, num_kv_heads=2, d_model=32, d_ff=64)
    model = TransformerLM(cfg)
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, 256, size=(4, 17)).astype(np.int32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
    )["params"]
    logits = model.apply({"params": params}, jnp.asarray(tokens[:, :-1]))
    ref = float(lm_cross_entropy(logits, jnp.asarray(tokens[:, 1:])))
    assert results[0]["loss"] == pytest.approx(ref, rel=1e-5)


def _mp_fsdp_worker(process_id, tmpdir):
    """Child of test_spawn_two_process_fsdp_step: FSDP state built over a
    GLOBAL 2-host mesh (device_put with a cross-process NamedSharding),
    one step, gathered-param checksum written per rank."""
    import json
    import os

    import jax

    from distributeddataparallel_tpu.compat import configure_cpu_devices

    configure_cpu_devices(2)

    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data.loader import shard_batch
    from distributeddataparallel_tpu.models import TransformerLM, tiny_lm

    ddp.init_process_group("cpu")
    mesh = ddp.make_mesh(("data",))  # global 4-way
    cfg = tiny_lm(
        num_layers=2, num_heads=2, d_model=32, d_ff=64, max_seq_len=32,
        scan_layers=True,
    )
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
    )["params"]
    tokens = np.random.default_rng(0).integers(
        0, 256, size=(8, 17)
    ).astype(np.int32)

    state = ddp.fsdp_state(cfg, params, optax.sgd(0.1), mesh)
    step = ddp.make_fsdp_train_step(cfg, mesh=mesh, donate=False)
    state, metrics = step(
        state, shard_batch({"tokens": tokens}, mesh), jax.random.PRNGKey(1)
    )
    got = ddp.fsdp_gather_params(cfg, state, mesh)
    checksum = sum(
        float(jnp.sum(l.astype(jnp.float32))) for l in jax.tree.leaves(got)
    )
    with open(os.path.join(tmpdir, f"fsdp{process_id}.json"), "w") as f:
        json.dump({"loss": float(metrics["loss"]), "checksum": checksum}, f)
    ddp.destroy_process_group()


def test_spawn_two_process_fsdp_step(tmp_path, devices):
    """FSDP across real OS processes: the 1/N flats span BOTH hosts'
    devices; one step must equal the single-device reference on the same
    global batch (loss and gathered-params checksum, both ranks agreeing)."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributeddataparallel_tpu.models import TransformerLM, tiny_lm
    from distributeddataparallel_tpu.ops import lm_cross_entropy

    procs = spawn(
        functools.partial(guarded_worker, _mp_fsdp_worker),
        args=(str(tmp_path),), nprocs=2, join=False,
    )
    for p in procs:
        p.join(timeout=240)
    codes = [p.exitcode for p in procs]
    for p in procs:
        if p.is_alive():
            p.terminate()  # don't let a hung rank wedge the pytest exit
    _skip_if_mp_unsupported(codes)
    assert codes == [0, 0], f"child exit codes {codes}"

    results = [
        json.load(open(tmp_path / f"fsdp{r}.json")) for r in range(2)
    ]
    assert results[0] == results[1], results

    cfg = tiny_lm(
        num_layers=2, num_heads=2, d_model=32, d_ff=64, max_seq_len=32,
        scan_layers=True,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
    )["params"]
    tokens = np.random.default_rng(0).integers(
        0, 256, size=(8, 17)
    ).astype(np.int32)

    def ref_loss(p):
        logits = model.apply({"params": p}, jnp.asarray(tokens[:, :-1]))
        return lm_cross_entropy(logits, jnp.asarray(tokens[:, 1:]))

    loss_ref, grads = jax.value_and_grad(ref_loss)(params)
    tx = optax.sgd(0.1)
    updates, _ = tx.update(grads, tx.init(params), params)
    ref_params = optax.apply_updates(params, updates)
    ref_checksum = sum(
        float(jnp.sum(l.astype(jnp.float32)))
        for l in jax.tree.leaves(ref_params)
    )
    assert results[0]["loss"] == pytest.approx(float(loss_ref), rel=1e-5)
    assert results[0]["checksum"] == pytest.approx(ref_checksum, rel=1e-5)
