"""scope_reduce.py on a synthetic trace, on a hand-encoded xplane file,
on a recorded v5e trace with its scopes kept, and through every reader
that PR 25 adds."""

import json
import os

import pytest

from benchmarks import flash_cost, flops, harness
from benchmarks import scope_reduce as sr
from benchmarks.tests.conftest import ROOT

US = 1000
STEP = "jit(step)/shard_map/"
NEW_READERS = [
    "train_scope_attributed_frac", "train_fwd_ms", "train_bwd_ms",
    "train_attn_ms", "train_mlp_ms", "train_head_loss_ms",
    "train_optimizer_ms", "train_grad_sync_ms", "flash_fwd_roofline",
    "flash_bwd_dq_roofline", "flash_bwd_dkv_roofline",
    "train_loader_batch_ms", "train_step_enqueue_ms",
]


def scoped(planes):
    """The scoped form from planes whose events are [name, scope, start,
    duration] (scope "" on a host plane)."""
    names, index, out = [], {}, []
    for plane_name, lines in planes:
        out_lines = []
        for line_name, events in lines:
            evs = []
            for name, scope, s, d in events:
                i = index.setdefault((name, scope), len(names))
                if i == len(names):
                    names.append([name, scope])
                evs.append([i, s, d])
            out_lines.append({"name": line_name, "events": evs})
        out.append({"name": plane_name, "lines": out_lines})
    return {"names": names, "planes": out}


def synthetic():
    """Window 0..200 us.  Chip 0: the forward kernel 10..30, a loop
    (40..100) that encloses an MLP fusion 40..60 (backward) and a copy
    under /attn/ 60..80, the all-reduce 100..120, the optimizer 120..150,
    an unscoped copy-done 150..160; idle 0..10, 30..40, 160..200.  Chip 1:
    one head fusion 0..200.  Host, the loop's thread: the dispatch with
    JAX's PjitFunction event nested twice inside (covers the gap 30..40),
    the program's loader span, a settle whose only cover of the last gap
    is a Python frame; another thread's event covers the first gap."""
    layer = STEP + "jvp(M)/layer_0/"
    back = STEP + "transpose(jvp(M))/layer_0/"
    chip0 = [
        ["flash_fwd.1 custom-call", layer + "attn/flash_fwd/pallas_call",
         10 * US, 20 * US],
        ["while.1", "", 40 * US, 60 * US],
        ["fusion.1", back + "mlp/up_proj/dot_general", 40 * US, 20 * US],
        ["copy.3", layer + "attn/transpose", 60 * US, 20 * US],
        ["all-reduce.1", STEP + "grad_sync/psum", 100 * US, 20 * US],
        ["fusion.2", STEP + "optimizer/add", 120 * US, 30 * US],
        ["copy-done.7", "", 150 * US, 10 * US],
    ]
    chip1 = [["fusion.9", STEP + "jvp(M)/head/dot_general", 0, 200 * US]]
    loop = [
        ["bench:window", "", 0, 200 * US],
        ["ddp:loader.batch", "", 1 * US, 6 * US],
        ["bench:dispatch", "", 8 * US, 40 * US],
        ["PjitFunction(step)", "", 20 * US, 25 * US],
        ["PjitFunction(step)", "", 21 * US, 23 * US],
        ["PjitFunction(fold_in)", "", 9 * US, 2 * US],
        ["ddp:loader.batch", "", 50 * US, 4 * US],
        ["$api.py:3097 block_until_ready", "", 155 * US, 45 * US],
    ]
    other_thread = [["Transpose::Execute", "", 2 * US, 6 * US]]
    return scoped([
        ("/device:TPU:0", [("XLA Ops", chip0)]),
        ("/device:TPU:1", [("XLA Ops", chip1)]),
        ("/host:CPU", [("python3", loop), ("pjrt-tpu-tasks/1", other_thread)]),
    ])


def test_buckets_phases_and_the_loops_own_share():
    r = sr.reduce(synthetic(), 1)
    assert r["window_s"] == pytest.approx(200e-6)
    assert r["busy_s"] == pytest.approx(140e-6)
    assert r["bucket_s"] == pytest.approx({
        "attn_kernel.fwd": 20e-6, "mlp": 20e-6, "attn": 20e-6,
        "grad_sync": 20e-6, "optimizer": 30e-6,
        # the loop keeps what its children do not cover; the copy-done
        # that the compiler made has no scope
        "other": 30e-6,
    })
    # the loop and the copy-done carry no scope: neither fwd nor bwd
    assert r["phase_s"] == pytest.approx(
        {"fwd": 40e-6, "bwd": 20e-6, "update": 50e-6, "unscoped": 30e-6}
    )
    assert sum(r["phase_s"].values()) == pytest.approx(r["busy_s"])
    # all of /attn/: the kernel and the copy
    assert sum(r["bucket_s"].get(b, 0) for b in sr.ATTN_BUCKETS) == (
        pytest.approx(40e-6))
    assert r["bucket_kind_s"]["attn"] == pytest.approx({"copy": 20e-6})
    assert r["bucket_kind_s"]["other"] == pytest.approx(
        {"while": 20e-6, "copy-done": 10e-6}
    )
    assert sum(r["bucket_s"].values()) == pytest.approx(r["busy_s"])


def test_times_are_means_over_the_chips():
    r = sr.reduce(synthetic(), 2)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(170e-6)
    assert r["bucket_s"]["head"] == pytest.approx(100e-6)
    assert r["bucket_s"]["mlp"] == pytest.approx(10e-6)
    assert r["bucket_s"]["attn_kernel.fwd"] == pytest.approx(10e-6)


def test_program_spans_and_the_steps_call_from_inside():
    r = sr.reduce(synthetic(), 1)
    assert r["spans"] == {
        "loader.batch": pytest.approx([6e-6, 4e-6]),
    }
    # the PjitFunction event with most time; its nested twin counted once
    assert r["step_call"] == {
        "name": "PjitFunction(step)", "count": 1,
        "mean_s": pytest.approx(25e-6),
    }


def test_idle_gaps_by_the_loop_threads_innermost_event_of_any_origin():
    r = sr.reduce(synthetic(), 1)
    assert r["idle_s"] == pytest.approx(60e-6)
    assert dict(r["idle_gaps"]) == pytest.approx({
        # 0..10: the program's span at the gap's middle, not the other
        # thread's event
        "ddp:loader.batch": 10e-6,
        # 30..40: JAX's own event, inside the harness's dispatch
        "PjitFunction(step)": 10e-6,
        # 160..200: nothing but a Python frame covers it
        "$api.py:3097 block_until_ready": 40e-6,
    })
    text = sr.tables(r)
    assert "attn_kernel.fwd" in text and "PjitFunction(step)" in text


def test_no_device_plane_is_nothing_to_read():
    host_only = scoped([("/host:CPU", [("python3", [
        ["bench:window", "", 0, 100 * US]])])])
    assert sr.reduce(host_only, 1) is None


# -- the file's wire format -------------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def ld(number, payload):
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def vi(number, value):
    return varint(number << 3) + varint(value)


def entry(key, message):
    return vi(1, key) + ld(2, message)


def test_op_scopes_reads_tf_op_from_the_metadata_and_inherits(tmp_path):
    stat_names = {1: "hlo_category", 2: "tf_op", 3: "flops"}
    def meta(i, name, tf_op=None):
        stats = ld(5, vi(1, 1) + ld(5, b"fusion")) + ld(5, vi(1, 3) + vi(4, 7))
        if tf_op is not None:
            stats += ld(5, vi(1, 2) + ld(5, tf_op.encode()))
        return ld(4, entry(i, vi(1, i) + ld(2, name.encode()) + stats))
    fusion = "%fusion.5 = bf16[8] fusion(bf16[8] %p.1), kind=kLoop"
    start = "%copy-start.2 = (bf16[8], bf16[8]) copy-start(bf16[8] %fusion.5)"
    done = "%copy-done.2 = bf16[8] copy-done((bf16[8], bf16[8]) %copy-start.2)"
    orphan = "%copy-done.9 = f32[4] copy-done((f32[4], f32[4]) %param.3)"
    plane = (
        vi(1, 7) + ld(2, b"/device:TPU:0")
        + ld(3, b"\x0a\x03abc")  # a line, skipped unread
        + meta(1, fusion, "jit(s)/jvp(M)/layer_0/mlp/dot_general:dot")
        + meta(2, start) + meta(3, done) + meta(4, orphan)
        + b"".join(
            ld(5, entry(i, vi(1, i) + ld(2, n.encode())))
            for i, n in stat_names.items()
        )
    )
    host = ld(2, b"/host:CPU") + meta(1, "PjitFunction(step)", "x:y")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ld(1, plane) + ld(1, host))
    scopes = sr.op_scopes(str(path))
    assert list(scopes) == ["/device:TPU:0"]
    mlp = "jit(s)/jvp(M)/layer_0/mlp/dot_general"
    assert scopes["/device:TPU:0"] == {fusion: mlp, start: mlp, done: mlp}


def test_load_xplane_of_a_cpu_profile_has_host_events_and_no_scopes(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("ddp:loader.batch"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    from benchmarks import trace_reduce

    trace = sr.load_xplane(trace_reduce.find_xplane(str(tmp_path)))
    host = {trace["names"][i][0] for plane in trace["planes"]
            for line in plane["lines"] for i, _, _ in line["events"]}
    assert {"bench:window", "ddp:loader.batch"} <= host
    assert any(n.startswith(sr.JIT_CALL_PREFIX) for n in host)
    assert sr.reduce(trace, 1) is None  # no /device:TPU: plane on the CPU


# -- the recorded trace, and the readers ------------------------------------

def recorded():
    path = os.path.join(ROOT, "benchmarks", "tests", "data",
                        "recorded_scoped_trace.json")
    with open(path) as fh:
        return json.load(fh)


def test_recorded_v5e_trace_with_its_scopes():
    """70 ms of a traced window of gpt2-medium.train-b8x1024 on a v5e chip
    (PR 25, cut by ``scope_reduce.py <trace> --fixture <out> 70 250``):
    the end of one step's backward pass, the update, and the start of the
    next step's forward pass."""
    data = recorded()
    r = sr.reduce(data["trace"], 1)
    for key, want in data["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
    assert set(sr.KERNEL_BUCKETS) <= set(r["bucket_s"])
    assert {"attn", "mlp", "optimizer", "embed"} <= set(r["bucket_s"])
    assert r["bucket_s"]["other"] < 0.08 * r["busy_s"]
    assert sum(r["bucket_s"].values()) == pytest.approx(r["busy_s"])
    assert r["phase_s"]["bwd"] > r["phase_s"]["fwd"] > r["phase_s"]["update"]
    assert 0 < r["phase_s"]["unscoped"] <= r["bucket_s"]["other"]
    assert sum(r["phase_s"].values()) == pytest.approx(r["busy_s"])
    assert r["step_call"]["name"] == "PjitFunction(_replica_step)"
    assert "loader.batch" in r["spans"]
    scopes = [scope for _, scope in data["trace"]["names"]]
    assert any("transpose(jvp(TransformerLM))" in s and "/attn/" in s
               for s in scopes)


def reader_ctx(reduced):
    cell = harness.load_cell("gpt2-medium.train-dp4-b8x1024")
    return {
        "cell": cell, "config": cell["config"], "traffic": cell["traffic"],
        "chips": 1, "peaks": harness.load_peaks("TPU v5 lite"),
        "trace": None, "spans": harness.Spans(),
        "measured": {"steps": 1}, "window_s": 0.07,
        "scope_reduced": reduced,
    }


@pytest.mark.parametrize("metric", NEW_READERS)
def test_every_new_reader_reads_the_recorded_trace(metric):
    reduced = sr.reduce(recorded()["trace"], 1)
    if metric == "train_grad_sync_ms":  # the recording is of one chip
        reduced["bucket_s"]["grad_sync"] = 0.021
    value = harness.load_module("layer_metrics", metric).read(
        reader_ctx(reduced)
    )
    assert isinstance(value, float) and value > 0
    if metric.endswith("_roofline") or metric.endswith("_frac"):
        assert value < 100


@pytest.mark.parametrize("metric", NEW_READERS)
def test_every_new_reader_is_silent_without_a_device_plane(metric, tmp_path):
    """As on the CPU, and as in a run whose trace was never written:
    None."""
    ctx = reader_ctx(None)
    assert harness.load_module("layer_metrics", metric).read(ctx) is None
    del ctx["scope_reduced"]
    ctx["cell"] = dict(ctx["cell"], root=str(tmp_path))  # no trace there
    assert harness.load_module("layer_metrics", metric).read(ctx) is None


def test_a_trace_that_cannot_be_parsed_fails_loudly(tmp_path):
    """A broken yardstick must not read as a metric that is silent."""
    where = tmp_path / "chiprun_out" / "benchmarks" / "trace" / "c"
    (where / "plugins" / "profile" / "x").mkdir(parents=True)
    # a plane whose first field has wire type 3, which xplane never uses
    (where / "plugins" / "profile" / "x" / "t.xplane.pb").write_bytes(
        ld(1, b"\x0b")
    )
    ctx = reader_ctx(None)
    del ctx["scope_reduced"]
    ctx["cell"] = dict(ctx["cell"], root=str(tmp_path), name="c")
    with pytest.raises(ValueError, match="wire type"):
        harness.load_module("layer_metrics", "train_fwd_ms").read(ctx)


def test_the_new_entries_name_readers_that_exist():
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = [m["name"] for m in bench["per_layer"]]
    assert listed[-len(NEW_READERS):] == NEW_READERS
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"][-len(NEW_READERS):]:
        assert set(m["workloads"]) <= cells and m["workloads"]
        assert m["moves"] == "train_tokens_s_chip"
        harness.load_module("layer_metrics", m["name"])


# -- the kernels' work ------------------------------------------------------

@pytest.mark.parametrize("name", ["gpt2-medium-355m", "gpt2-large-774m"])
@pytest.mark.parametrize("batch,seq_len", [(8, 1024), (32, 1024), (3, 384)])
def test_the_kernel_split_sums_to_the_whole(name, batch, seq_len):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as fh:
        config = json.load(fh)
    whole = flops.flash_attention_cost(config, batch, seq_len)
    parts = flash_cost.flash_kernel_costs(config, batch, seq_len)
    assert set(parts) == {"fwd", "dq", "dkv"}
    assert sum(p["flops"] for p in parts.values()) == whole["flops"]
    assert sum(p["bytes"] for p in parts.values()) == whole["bytes"]
    assert parts["fwd"]["flops"] * 2 == parts["dkv"]["flops"]
    assert parts["dq"]["flops"] * 2 == parts["fwd"]["flops"] * 3
