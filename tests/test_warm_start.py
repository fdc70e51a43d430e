"""Warm-start + dispatch subsystem tests (training.warm_start):

- AOT executable store round trip on CPU: serialize a compiled train
  step, load it back through a FRESH wrapper, same first-step numerics.
- Key-mismatch / corruption paths fall back LOUDLY to JIT (warning
  logged, strict mode raises) — a stale binary must never run silently.
- Persistent compile cache shared across two real spawned processes:
  the second process's compile is a cache HIT (counted via the
  monitoring events, not timing — deterministic in CI).
- Bounded async dispatch: the --dispatch-depth loop is numerically
  inert (bitwise-identical final params vs the blocking loop) and the
  nan-guard breaker still trips within max_bad_steps + depth steps.
"""

import logging
import multiprocessing as mp
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, "/root/repo")

import dpp  # noqa: E402
import distributeddataparallel_tpu as ddp  # noqa: E402
from distributeddataparallel_tpu.data.loader import shard_batch  # noqa: E402
from distributeddataparallel_tpu.models import TinyMLP  # noqa: E402
from distributeddataparallel_tpu.ops import cross_entropy_loss  # noqa: E402
from distributeddataparallel_tpu.training.warm_start import (  # noqa: E402
    BoundedDispatch,
    ExecutableStore,
    WarmStartMismatch,
    executable_key,
    warm_train_step,
)
from distributeddataparallel_tpu.utils.logging import get_logger  # noqa: E402


class _Capture(logging.Handler):
    """The repo logger has propagate=False, so caplog can't see it —
    capture by attaching directly."""

    def __init__(self):
        super().__init__()
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


class _capture_warnings:
    def __enter__(self):
        self._h = _Capture()
        get_logger().addHandler(self._h)
        return self._h.messages

    def __exit__(self, *exc):
        get_logger().removeHandler(self._h)


def _setup(mesh):
    model = TinyMLP(features=(16,))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 4, 1))
    )["params"]

    def loss_fn(p, b, r):
        logits = model.apply({"params": p}, b["image"])
        return cross_entropy_loss(logits, b["label"]), {}

    state = ddp.TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.sgd(0.1)
    )
    state = ddp.broadcast_params(state, mesh)
    # donate=False: the test reuses `state` across acquisition modes.
    step = ddp.make_train_step(loss_fn, mesh=mesh, donate=False)

    rng = np.random.default_rng(0)
    batch = shard_batch(
        {
            "image": rng.normal(size=(16, 4, 4, 1)).astype(np.float32),
            "label": rng.integers(0, 10, size=(16,)).astype(np.int32),
        },
        mesh,
    )
    return state, step, batch


def test_store_round_trip_smoke(devices, tmp_path):
    """Tier-1 smoke: compile -> save -> load through a fresh wrapper;
    the loaded executable must produce the cold path's step bitwise."""
    mesh = ddp.make_mesh(("data",))
    state, step, batch = _setup(mesh)
    store = ExecutableStore(str(tmp_path / "aot"))
    key = executable_key(
        mesh=mesh, step_signature=getattr(step, "aot_signature", None)
    )

    cold = warm_train_step(step, store=store, key=key)
    s1, m1 = cold(state, batch, jax.random.PRNGKey(1))
    assert cold.report["mode"] in ("cold", "cache-hit")
    meta = store.meta("train_step")
    assert meta is not None and meta["key"] == key
    assert "loss" in meta["metric_keys"]

    warm = warm_train_step(step, store=store, key=key)
    s2, m2 = warm(state, batch, jax.random.PRNGKey(1))
    assert warm.report["mode"] == "aot"
    assert float(m2["loss"]) == float(m1["loss"])
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_key_mismatch_falls_back_loudly(devices, tmp_path):
    """A stored executable whose key differs from the live run must not
    load: warning naming the differing fields + None (strict: raise)."""
    mesh = ddp.make_mesh(("data",))
    state, step, batch = _setup(mesh)
    store = ExecutableStore(str(tmp_path / "aot"))
    key = executable_key(
        mesh=mesh, step_signature=getattr(step, "aot_signature", None),
        extra={"lr": 0.1},
    )
    first = warm_train_step(step, store=store, key=key)
    first(state, batch, jax.random.PRNGKey(1))
    assert store.meta("train_step") is not None

    stale = executable_key(
        mesh=mesh, step_signature=getattr(step, "aot_signature", None),
        extra={"lr": 0.2},  # optax bakes hyperparams into the binary
    )
    args = (state, batch, jax.random.PRNGKey(1))
    with _capture_warnings() as messages:
        loaded = store.load(
            "train_step", stale, example_args=args, state=state
        )
    assert loaded is None
    assert any("key mismatch" in m and "extra" in m for m in messages)

    with pytest.raises(WarmStartMismatch, match="key mismatch"):
        store.load(
            "train_step", stale, example_args=args, state=state, strict=True
        )

    # The wrapper path: mismatch degrades to a working compile, loudly.
    with _capture_warnings() as messages:
        wrapped = warm_train_step(step, store=store, key=stale)
        _, m = wrapped(state, batch, jax.random.PRNGKey(1))
    assert wrapped.report["mode"] in ("cold", "cache-hit")
    assert float(m["loss"]) == float(m["loss"])  # finite step ran
    assert any("key mismatch" in m for m in messages)


def test_corrupt_artifact_falls_back_loudly(devices, tmp_path):
    """Truncated payload (killed writer, disk fault): load warns and
    returns None instead of raising into the train loop."""
    mesh = ddp.make_mesh(("data",))
    state, step, batch = _setup(mesh)
    store = ExecutableStore(str(tmp_path / "aot"))
    key = executable_key(mesh=mesh)
    warm_train_step(step, store=store, key=key)(
        state, batch, jax.random.PRNGKey(1)
    )
    aot_path, _ = store._paths("train_step")
    with open(aot_path, "wb") as fh:
        fh.write(b"not a pickled executable")
    with _capture_warnings() as messages:
        loaded = store.load(
            "train_step", key,
            example_args=(state, batch, jax.random.PRNGKey(1)), state=state,
        )
    assert loaded is None
    assert any("failed to load" in m for m in messages)


def _cache_probe_worker(cache_dir, out_path):
    """Spawn child: compile one jit function with the persistent cache
    the ENVIRONMENT placed at ``cache_dir`` (inherited at birth, read by
    jax at import) and record the hit/miss event counts."""
    import json

    from distributeddataparallel_tpu.compat import configure_cpu_devices

    configure_cpu_devices(2)

    import jax
    import jax.numpy as jnp

    from distributeddataparallel_tpu.training.warm_start import (
        CompileCacheStats,
        resolve_compile_cache,
    )

    # The resolver leaves an environment-placed cache alone (that jax
    # itself read it is what the second process's hit proves).
    assert resolve_compile_cache() == cache_dir
    stats = CompileCacheStats()

    @jax.jit
    def f(x):
        return jnp.tanh(x) @ x + jnp.sum(x, axis=0)

    jax.block_until_ready(f(jnp.arange(64.0).reshape(8, 8)))
    stats.close()
    with open(out_path, "w") as fh:
        json.dump({"hits": stats.hits, "misses": stats.misses}, fh)


def test_compile_cache_hit_across_processes(tmp_path, monkeypatch):
    """Two REAL processes, same JAX_COMPILATION_CACHE_DIR: the first
    compiles (miss), the second must hit — the event counters make this
    deterministic instead of a timing assertion."""
    import json

    cache = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    # The probe function compiles in well under JAX's 1 s default floor.
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    ctx = mp.get_context("spawn")
    results = []
    for run in range(2):
        out = tmp_path / f"probe{run}.json"
        p = ctx.Process(
            target=_cache_probe_worker, args=(cache, str(out))
        )
        p.start()
        p.join(timeout=240)
        if p.is_alive():
            p.terminate()
            p.join()
            pytest.fail(f"cache probe child {run} timed out")
        assert p.exitcode == 0, f"child {run} exit {p.exitcode}"
        results.append(json.load(open(out)))
    assert results[0]["misses"] >= 1 and results[0]["hits"] == 0, results
    assert results[1]["hits"] >= 1, results


def test_bounded_dispatch_window_semantics():
    d = BoundedDispatch(2)
    assert d.push("a", 0) == []
    assert d.push("b", 1) == []
    assert d.push("c", 2) == [("a", 0)]  # oldest falls out of the window
    assert len(d) == 2
    assert d.drain() == [("b", 1), ("c", 2)]
    assert len(d) == 0
    # depth 0 degenerates to the synchronous per-step pattern.
    sync = BoundedDispatch(0)
    assert sync.push("a", 0) == [("a", 0)]
    with pytest.raises(ValueError, match="depth"):
        BoundedDispatch(-1)


def _final_checkpoint(ckpt_dir):
    import orbax.checkpoint as ocp

    mgr = ocp.CheckpointManager(ckpt_dir)
    step = mgr.latest_step()
    assert step is not None, "no checkpoint written"
    # Template-free raw read: both runs' trees get the same treatment,
    # so a bitwise compare needs no TrainState reconstruction.
    tree = mgr.restore(step, args=ocp.args.StandardRestore())
    mgr.close()
    return step, tree


def test_async_dispatch_bitwise_matches_blocking_loop(devices, tmp_path):
    """--dispatch-depth 4 vs 0 on a fixed seed: same final loss AND
    bitwise-identical final checkpointed state — the dispatch window
    reorders host syncs, never the computation."""

    def run(depth):
        d = str(tmp_path / f"ckpt_depth{depth}")
        args = dpp.parse_args(
            ["--device", "cpu", "--dataset", "synthetic", "--model", "mlp",
             "--num-examples", "64", "--batch-size", "8", "--epochs", "2",
             "--log-every", "3", "--seed", "3",
             "--dispatch-depth", str(depth), "--checkpoint-dir", d]
        )
        loss = dpp.train(args)
        return loss, _final_checkpoint(d)

    loss0, (step0, tree0) = run(0)
    loss4, (step4, tree4) = run(4)
    assert loss0 == loss4  # bitwise: both are float(np.float32)
    assert step0 == step4
    l0, l4 = jax.tree.leaves(tree0), jax.tree.leaves(tree4)
    assert len(l0) == len(l4) and len(l0) > 0
    for a, b in zip(l0, l4):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_nan_guard_trips_under_deep_dispatch(devices):
    """With a K-deep dispatch window the breaker observes each step's
    flag at most K steps late — a sustained NaN burst must still abort
    within max_bad_steps + K steps instead of training through it."""
    from distributeddataparallel_tpu.training.fault_tolerance import (
        TrainingDiverged,
    )

    # 512 examples / (4 x 8-device) global batch = 16 steps: the burst
    # at steps 2-6 settles mid-loop (step S leaves the 4-deep window at
    # step S+4), tripping the breaker before the epoch-edge drain.
    args = dpp.parse_args(
        ["--device", "cpu", "--dataset", "synthetic", "--model", "mlp",
         "--num-examples", "512", "--batch-size", "4", "--epochs", "1",
         "--log-every", "1000", "--nan-guard", "--max-bad-steps", "3",
         "--dispatch-depth", "4",
         "--chaos",
         "nan-grad@2,nan-grad@3,nan-grad@4,nan-grad@5,nan-grad@6"]
    )
    with pytest.raises(TrainingDiverged, match="3 consecutive"):
        dpp.train(args)


def test_nan_guard_survives_isolated_nan_under_dispatch(devices):
    """One poisoned step inside the dispatch window is skipped in-graph;
    the run finishes finite exactly like the blocking loop's guard."""
    args = dpp.parse_args(
        ["--device", "cpu", "--dataset", "synthetic", "--model", "mlp",
         "--num-examples", "128", "--batch-size", "4", "--epochs", "1",
         "--log-every", "1000", "--nan-guard", "--dispatch-depth", "4",
         "--chaos", "nan-grad@1"]
    )
    loss = dpp.train(args)
    assert loss == loss and loss < 2.4
