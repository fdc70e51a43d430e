"""Observability subsystem: span nesting + the no-sync hot-path rule,
JSONL schema round-trip and validation, metrics registry + exporters,
gang-timeline merge ordering, capture-on-anomaly, and the acceptance
path — a supervised chaos run whose merged timeline shows injection,
skip-step, and restart attempt in causal order."""

import json
import logging as pylogging
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, "/root/repo")

import dpp  # noqa: E402
from distributeddataparallel_tpu.observability import (  # noqa: E402
    SCHEMA_VERSION,
    EventLog,
    JsonlExporter,
    MetricsRegistry,
    ProfilerOrchestrator,
    TextExporter,
    Tracer,
    events_path,
    json_safe,
    merge_timeline,
    parse_profile_steps,
    read_events,
    validate_file,
    validate_record,
)
from distributeddataparallel_tpu.runtime.launcher import spawn  # noqa: E402
from distributeddataparallel_tpu.utils import logging as ddp_logging  # noqa: E402
from distributeddataparallel_tpu.utils.metrics import FaultCounters  # noqa: E402

sys.path.insert(0, os.path.join("/root/repo", "scripts"))
import check_events  # noqa: E402


# ------------------------------------------------------- schema basics


def test_json_safe_coercion():
    out = json_safe({
        "nan": float("nan"),
        "inf": float("inf"),
        "ninf": float("-inf"),
        "np_f": np.float32(1.5),
        "np_i": np.int64(7),
        "np_0d": np.array(2.25),
        "np_bool": np.bool_(True),
        "bool": True,
        "tup": (1, 2.0, "x"),
        "nested": {"a": [np.float64("nan")]},
    })
    text = json.dumps(out)  # must not raise
    back = json.loads(text)
    assert back["nan"] == "nan" and back["inf"] == "inf"
    assert back["ninf"] == "-inf"
    assert back["np_f"] == 1.5 and back["np_i"] == 7
    assert back["np_0d"] == 2.25
    assert back["np_bool"] is True and back["bool"] is True
    assert back["tup"] == [1, 2.0, "x"]
    assert back["nested"]["a"] == ["nan"]


def test_fault_counters_summary_json_safe():
    """Satellite regression: warm-start timing can land as a numpy
    scalar or nan; summary() must stay serializable for the event log."""
    c = FaultCounters()
    c.warm_start_mode = "aot"
    c.compile_s = np.float32("nan")
    s = c.summary()
    text = json.dumps(s)  # the event log does exactly this
    assert json.loads(text)["first_step_s"] == "nan"
    c.compile_s = np.float64(1.23456)
    assert json.loads(json.dumps(c.summary()))["first_step_s"] == 1.235


def test_event_log_roundtrip_schema_version(tmp_path):
    path = str(tmp_path / "events-p0.jsonl")
    with EventLog(path, 0) as ev:
        ev.emit("run_start", argv=["--x"])
        ev.emit("nan_skip", step=3, extra=np.float32(0.5))
        ev.emit("run_end", status="ok")
    recs = read_events(path)
    assert [r["kind"] for r in recs] == ["run_start", "nan_skip", "run_end"]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    assert [r["seq"] for r in recs] == [0, 1, 2]  # per-writer monotonic
    assert recs[1]["extra"] == 0.5  # json_safe applied at emit
    assert validate_file(path) == []


def test_event_log_append_survives_restart(tmp_path):
    """A respawned incarnation reuses the same path: records append
    rather than erase the previous incarnation's history."""
    path = str(tmp_path / "events-p0.jsonl")
    with EventLog(path, 0) as ev:
        ev.emit("run_start", argv=[])
    with EventLog(path, 0) as ev:
        ev.emit("run_start", argv=[])
    assert len(read_events(path)) == 2


def test_validator_rejects_bad_records(tmp_path):
    assert validate_record({"v": 1}) != []  # missing envelope fields
    assert any(
        "version" in p
        for p in validate_record(
            {"v": 99, "ts": 0.0, "seq": 0, "proc": 0, "kind": "run_end",
             "status": "ok"}
        )
    )
    assert any(
        "unknown kind" in p
        for p in validate_record(
            {"v": 1, "ts": 0.0, "seq": 0, "proc": 0, "kind": "nope"}
        )
    )
    assert any(
        "missing required" in p
        for p in validate_record(
            {"v": 1, "ts": 0.0, "seq": 0, "proc": 0, "kind": "span"}
        )
    )
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"v": 1}\nnot json\n')
    assert check_events.main([str(bad)]) == 1
    good = tmp_path / "good.jsonl"
    with EventLog(str(good), 0) as ev:
        ev.emit("run_end", status="ok")
    assert check_events.main([str(good)]) == 0
    # --expect-order: present vs violated
    with EventLog(str(good), 0) as ev:
        ev.emit("run_start", argv=[])
    assert check_events.main(
        [str(good), "--expect-order", "run_end,run_start"]
    ) == 0
    assert check_events.main(
        [str(good), "--expect-order", "run_start,run_end"]
    ) == 1


# ----------------------------------------------------- tracer / spans


def test_span_nesting_depth_and_parent(tmp_path):
    path = str(tmp_path / "events-p0.jsonl")
    with EventLog(path, 0) as ev:
        tr = Tracer(ev)
        with tr.span("epoch", epoch=0):
            with tr.span("step", step=0):
                pass
            with tr.span("ckpt_save", epoch=0):
                pass
    spans = {r["name"]: r for r in read_events(path)}
    assert spans["step"]["depth"] == 1 and spans["step"]["parent"] == "epoch"
    assert spans["ckpt_save"]["parent"] == "epoch"
    assert spans["epoch"]["depth"] == 0 and spans["epoch"]["parent"] is None
    # children closed before the parent -> parent duration covers them
    assert spans["epoch"]["dur_s"] >= spans["step"]["dur_s"]
    assert validate_file(path) == []


def test_hot_path_never_syncs(tmp_path, monkeypatch, devices):
    """The no-sync rule, enforced: emitting spans, events, and metrics
    snapshots with an ASYNC jax computation in flight must not call
    block_until_ready (nor read a device value any other way)."""
    calls = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    f = jax.jit(lambda x: (x * 2.0).sum())
    path = str(tmp_path / "events-p0.jsonl")
    with EventLog(path, 0) as ev:
        reg = MetricsRegistry()
        reg.add_exporter(JsonlExporter(ev))
        reg.bind("gauge", lambda: 1.25)
        tr = Tracer(ev, reg)
        out = None
        for i in range(5):
            with tr.span("step", step=i):
                out = f(jnp.ones((256,)) * i)  # dispatched, NOT read
            ev.emit("nan_skip", step=i)
            reg.export(step=i)
        # a tracer with nowhere to write, whose spans are still the
        # profiler's annotations (jax is loaded here), with a trace being
        # taken
        bare = Tracer()
        jax.profiler.start_trace(str(tmp_path / "xprof"))
        try:
            for i in range(5):
                with bare.span("step", step=i), bare.span("settle"):
                    out = f(jnp.ones((256,)) * i)
        finally:
            jax.profiler.stop_trace()
    assert calls["n"] == 0, "observability hot path forced a device sync"
    real(out)  # drain before leaving the test
    assert validate_file(path) == []


# -------------------------------------------------- metrics registry


def test_registry_instruments_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("faults").inc()
    reg.counter("faults").inc(2)
    reg.gauge("depth").set(3)
    reg.bind("lazy", lambda: 7)
    h = reg.histogram("step_s")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["faults"] == 3
    assert snap["depth"] == 3 and snap["lazy"] == 7
    assert snap["step_s"]["count"] == 3
    assert abs(snap["step_s"]["mean"] - 0.2) < 1e-9
    assert snap["step_s"]["min"] == 0.1 and snap["step_s"]["max"] == 0.3
    with pytest.raises(TypeError):
        reg.gauge("faults")  # name already taken by a Counter


def test_registry_exporters(tmp_path):
    path = str(tmp_path / "events-p0.jsonl")
    txt = str(tmp_path / "metrics.txt")
    with EventLog(path, 0) as ev:
        reg = MetricsRegistry()
        reg.add_exporter(JsonlExporter(ev))
        reg.add_exporter(TextExporter(txt))
        reg.counter("nan_skips").inc(4)
        reg.histogram("span_step_s").observe(0.5)
        snap = reg.export(step=10)
    assert snap["nan_skips"] == 4
    recs = read_events(path)
    assert recs[0]["kind"] == "metrics" and recs[0]["step"] == 10
    assert recs[0]["snapshot"]["nan_skips"] == 4
    content = open(txt).read()
    assert "nan_skips 4" in content
    assert "span_step_s_count 1" in content  # dict metrics flattened
    assert validate_file(path) == []


# -------------------------------------------------- timeline merging


def test_merge_timeline_ordering(tmp_path):
    """Records from 3 writers interleave strictly by (ts, seq) in the
    merged gang timeline, whatever order the files listed in."""
    t0 = time.time()
    for proc, offsets in ((0, (0.0, 0.2)), (1, (0.1, 0.3)), (2, (0.05,))):
        with EventLog(events_path(str(tmp_path), proc), proc) as ev:
            for off in offsets:
                ev.emit("nan_skip", step=int(off * 100))
        # Rewrite with controlled timestamps (emit stamps real time).
        recs = read_events(events_path(str(tmp_path), proc))
        for r, off in zip(recs, offsets):
            r["ts"] = t0 + off
        with open(events_path(str(tmp_path), proc), "w") as fh:
            for r in recs:
                fh.write(json.dumps(r) + "\n")
    out = merge_timeline(str(tmp_path))
    assert out and out.endswith("timeline.jsonl")
    merged = read_events(out)
    assert [r["proc"] for r in merged] == [0, 2, 1, 0, 1]
    assert [r["ts"] for r in merged] == sorted(r["ts"] for r in merged)
    assert validate_file(out) == []
    # Torn trailing line (SIGKILLed writer) is dropped, not fatal.
    with open(events_path(str(tmp_path), 0), "a") as fh:
        fh.write('{"v": 1, "ts":')
    assert len(read_events(merge_timeline(str(tmp_path)))) == 5


def test_merge_timeline_empty_dir(tmp_path):
    assert merge_timeline(str(tmp_path)) is None


# ------------------------------------------------ profiler orchestration


def test_parse_profile_steps():
    assert parse_profile_steps(None) is None
    assert parse_profile_steps("") is None
    assert parse_profile_steps("10:20") == (10, 20)
    for bad in ("10", "20:10", "5:5", "-1:3", "a:b"):
        with pytest.raises(ValueError):
            parse_profile_steps(bad)


def test_profiler_window_capture(tmp_path, devices):
    path = str(tmp_path / "events-p0.jsonl")
    with EventLog(path, 0) as ev:
        prof = ProfilerOrchestrator(
            str(tmp_path / "xprof"), window=(1, 3), events=ev
        )
        x = jnp.ones((64,))
        for i in range(5):
            prof.on_step_start(i)
            x = x * 1.5
            prof.on_step_end(i, sync=x)
        assert not prof.active
        prof.close()
    kinds = [(r["kind"], r.get("step")) for r in read_events(path)]
    assert ("profile_start", 1) in kinds and ("profile_stop", 2) in kinds
    assert os.path.isdir(str(tmp_path / "xprof"))


def test_profiler_anomaly_is_first_only(tmp_path, devices):
    path = str(tmp_path / "events-p0.jsonl")
    with EventLog(path, 0) as ev:
        prof = ProfilerOrchestrator(str(tmp_path / "xprof"), events=ev)
        prof.trigger_anomaly("nan_grad", 7, immediate=True)
        prof.trigger_anomaly("nan_grad", 9, immediate=True)  # ignored
        prof.close()
    starts = [r for r in read_events(path) if r["kind"] == "profile_start"]
    assert len(starts) == 1
    assert starts[0]["reason"] == "anomaly:nan_grad"
    assert starts[0]["step"] == 7


def test_disabled_profiler_is_inert():
    prof = ProfilerOrchestrator(None, window=(0, 2))
    for i in range(3):
        prof.on_step_start(i)
        prof.on_step_end(i)
    prof.trigger_anomaly("nan_grad", 0)
    prof.close()
    assert not prof.active


# ------------------------------------------------------- loader gauge


def test_loader_prefetch_depth_and_starvation(devices, monkeypatch):
    from distributeddataparallel_tpu.data import DataLoader
    from distributeddataparallel_tpu.runtime.distributed import make_mesh

    class SlowDataset:
        def __init__(self, n):
            self.images = np.zeros((n, 4), np.float32)
            self.labels = np.zeros((n,), np.int64)

        def __len__(self):
            return len(self.images)

        def arrays(self):
            time.sleep(0.02)  # slow producer: consumer always outruns it
            return {"image": self.images, "label": self.labels}

    warned = []
    monkeypatch.setattr(
        ddp_logging, "warn_all", lambda msg, *a: warned.append(msg % a)
    )
    mesh = make_mesh(("data",))
    loader = DataLoader(
        SlowDataset(64), per_replica_batch=1, mesh=mesh, shuffle=False,
        workers=1, starvation_window=2,
    )
    assert loader.prefetch_depth == 0  # no iteration active
    depths = []
    for _ in loader:
        depths.append(loader.prefetch_depth)
    assert all(isinstance(d, int) and d >= 0 for d in depths)
    assert loader.prefetch_depth == 0  # reset after the epoch
    assert len(warned) == 1, warned  # one-time, not per-step
    assert "starving" in warned[0]


# ------------------------------------------------- logging satellites


def test_log_level_env_and_debug0(monkeypatch):
    monkeypatch.setenv("DDP_LOG_LEVEL", "DEBUG")
    monkeypatch.setattr(ddp_logging, "_LOGGER", None)
    logger = ddp_logging.get_logger()
    assert logger.level == pylogging.DEBUG
    ddp_logging.debug0("debug message %d", 1)  # must not raise
    monkeypatch.setenv("DDP_LOG_LEVEL", "nonsense")
    monkeypatch.setattr(ddp_logging, "_LOGGER", None)
    assert ddp_logging.get_logger().level == pylogging.INFO  # safe fallback
    monkeypatch.setenv("DDP_LOG_LEVEL", "15")
    monkeypatch.setattr(ddp_logging, "_LOGGER", None)
    assert ddp_logging.get_logger().level == 15
    monkeypatch.delenv("DDP_LOG_LEVEL")
    monkeypatch.setattr(ddp_logging, "_LOGGER", None)
    assert ddp_logging.get_logger().level == pylogging.INFO


def test_profile_trace_compat_reexport():
    from distributeddataparallel_tpu.observability.profiler import (
        profile_trace as canonical,
    )
    from distributeddataparallel_tpu.utils import profile_trace as via_pkg
    from distributeddataparallel_tpu.utils.metrics import (
        profile_trace as via_metrics,
    )

    assert via_metrics is canonical and via_pkg is canonical


# ------------------------------------------- end-to-end: train wiring


def test_train_events_and_capture_on_anomaly(devices, tmp_path):
    """In-process train with --events-dir: the event log carries the
    run envelope, spans, metrics snapshots, the chaos injection and the
    nan-guard skip, and the anomaly grabs an XLA trace."""
    ev_dir = str(tmp_path / "events")
    args = dpp.parse_args([
        "--device", "cpu", "--fake-devices", "8",
        "--model", "mlp", "--dataset", "synthetic",
        "--num-examples", "64", "--batch-size", "4",
        "--epochs", "1", "--steps-per-epoch", "3", "--log-every", "10",
        "--nan-guard", "--chaos", "nan-grad@1",
        "--events-dir", ev_dir, "--metrics-every", "1",
    ])
    dpp.train(args)
    recs = read_events(events_path(ev_dir, 0))
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    for want in ("span", "metrics", "chaos_inject", "nan_skip",
                 "warm_start", "profile_start"):
        assert want in kinds, (want, kinds)
    names = {r["name"] for r in recs if r["kind"] == "span"}
    assert {"epoch", "step"} <= names
    snaps = [r for r in recs if r["kind"] == "metrics"]
    assert any("faults" in s["snapshot"] for s in snaps)
    assert any(
        s["snapshot"].get("faults", {}).get("nonfinite_steps", 0) == 1
        for s in snaps
    ) or recs[-1]["faults"]["nonfinite_steps"] == 1
    assert validate_file(events_path(ev_dir, 0)) == []
    # Unsupervised single-process run merges its own timeline on exit.
    assert os.path.exists(os.path.join(ev_dir, "timeline.jsonl"))
    assert os.path.exists(os.path.join(ev_dir, "metrics.txt"))


def test_acceptance_chaos_timeline_causal_order(devices, tmp_path):
    """ISSUE acceptance: a supervised chaos run (nan injection + a
    preemption, --max-restarts 1) produces a merged gang timeline with
    injection -> skip-step -> restart attempt in causal order, and
    scripts/check_events.py validates it."""
    ev_dir = str(tmp_path / "events")
    ck = str(tmp_path / "ck")
    base = [
        "--device", "cpu", "--fake-devices", "8",
        "--model", "mlp", "--dataset", "synthetic",
        "--num-examples", "128", "--batch-size", "4",
        "--epochs", "3", "--steps-per-epoch", "4", "--log-every", "1",
        "--nan-guard",
        "--checkpoint-dir", ck, "--resume",
    ]
    spawn(
        dpp._worker,
        args=(base,),
        nprocs=1,
        max_restarts=1,
        env={
            "_DDP_SUPERVISED": "1",
            # nan-grad@2: epoch 0 -> chaos_inject + nan_skip.
            # preempt@6 (epoch 1, batch 2): dies AFTER epoch 0's
            # checkpoint -> supervisor logs restart_attempt.
            "DDP_CHAOS": "nan-grad@2,preempt@6",
            "DDP_CHAOS_STATE": os.path.join(ck, ".chaos"),
        },
        events_dir=ev_dir,
    )
    timeline = os.path.join(ev_dir, "timeline.jsonl")
    assert os.path.exists(timeline)
    # Schema-valid AND the causal chain is in order.
    assert check_events.main([
        timeline,
        "--expect-order", "chaos_inject,nan_skip,restart_attempt,run_end",
    ]) == 0
    recs = read_events(timeline)
    by_kind = {}
    for r in recs:
        by_kind.setdefault(r["kind"], []).append(r)
    # Both incarnations wrote run_start into the SAME per-proc file.
    assert len(by_kind["run_start"]) == 2
    assert by_kind["run_start"][1]["attempt"] == 1
    assert by_kind["restart_attempt"][0]["proc"] == "supervisor"
    # The injected preemption is on the timeline before the restart.
    inj = [r for r in by_kind["chaos_inject"] if "preempt" in r["entry"]]
    assert inj and inj[0]["ts"] <= by_kind["restart_attempt"][0]["ts"]


# ------------------------------------- satellite: dead-gang exit merge


def test_supervisor_merge_tolerates_gang_dead_before_events(
    devices, tmp_path,
):
    """A gang that dies before ANY worker writes events (here: argv that
    fails validation in parse_args) must still surface the restart-
    exhausted RuntimeError, and the exit-time merge must produce a
    supervisor-only timeline instead of crashing."""
    ev_dir = str(tmp_path / "events")
    # --mfu has no resnet cost model: SystemExit in parse_args, before
    # the worker ever opens its events file.
    bad = ["--device", "cpu", "--fake-devices", "8",
           "--model", "resnet18", "--mfu"]
    with pytest.raises(RuntimeError, match="restart budget"):
        spawn(
            dpp._worker, args=(bad,), nprocs=1, max_restarts=1,
            restart_backoff_s=0.05,
            env={"_DDP_SUPERVISED": "1"}, events_dir=ev_dir,
        )
    assert not os.path.exists(events_path(ev_dir, 0))
    timeline = os.path.join(ev_dir, "timeline.jsonl")
    assert os.path.exists(timeline)
    recs = read_events(timeline)
    assert recs and all(r["proc"] == "supervisor" for r in recs)
    assert {"restart_attempt", "restart_exhausted"} <= {
        r["kind"] for r in recs
    }


def test_supervisor_merge_failure_does_not_mask_run_error(
    devices, tmp_path, monkeypatch,
):
    """If the exit-time merge itself fails (unwritable dir, disk full),
    the run's real exception must still be the one that propagates."""
    from distributeddataparallel_tpu.runtime import launcher as launcher_mod
    from distributeddataparallel_tpu.observability import events as ev_mod

    def broken_merge(events_dir, out_name="timeline.jsonl"):
        raise OSError("disk full")

    monkeypatch.setattr(ev_mod, "merge_timeline", broken_merge)
    ev_dir = str(tmp_path / "events")
    bad = ["--device", "cpu", "--fake-devices", "8",
           "--model", "resnet18", "--mfu"]
    with pytest.raises(RuntimeError, match="restart budget"):
        launcher_mod.spawn(
            dpp._worker, args=(bad,), nprocs=1, max_restarts=1,
            restart_backoff_s=0.05,
            env={"_DDP_SUPERVISED": "1"}, events_dir=ev_dir,
        )
