"""The benchmark's harness: one cell, one run, one process.

Everything that belongs to one configuration, one traffic mix, one kind
of run or one per-layer metric sits in a file of its own, found by the
name that ``BENCHMARK.json`` gives:

    configs/<config>.json         sizes as run, source, reduced, assumed
    traffic/<traffic>.json        the mix's parameters and its ``kind``
    kinds/<kind>.py               the driver: ``setup(env) -> session``
    layer_metrics/<metric>.py     ``read(ctx) -> number | None``

so a later PR adds a cell, a configuration or a metric as new files plus
one entry, and edits nothing that is here.

A session (what ``kinds/<kind>.setup`` returns) has three methods:
``measure(seconds) -> dict`` (the window), ``release()`` (free the
program's device state) and ``check() -> [(name, value, limit), ...]``
(the comparison with the plain reference; runs after ``release``).
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")


class BenchmarkError(Exception):
    """The run cannot produce a result: exit non-zero, print no result."""


# ---------------------------------------------------------------------------
# Data files
# ---------------------------------------------------------------------------

def read_json(path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise BenchmarkError(f"missing file: {path}") from None


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell's entry with its configuration and traffic files."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(cells)}"
        )
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = read_json(
        os.path.join(root, "benchmarks", "traffic", cell["traffic"] + ".json")
    )

    def reported_here(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "name": workload,
        "chips": int(cell["chips"]),
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if reported_here(m)],
        "per_layer": [m for m in bench["per_layer"] if reported_here(m)],
        "root": root,
    }


def load_module(kind_dir: str, name: str, root: str = ROOT):
    """Import ``benchmarks/<kind_dir>/<name>.py`` by file (metric names
    carry dots, so they are not importable by name)."""
    path = os.path.join(root, "benchmarks", kind_dir, name + ".py")
    if not os.path.exists(path):
        raise BenchmarkError(f"missing file: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind_dir}_{name.replace('.', '_').replace('-', '_')}",
        path,
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    table = read_json(os.path.join(root, "benchmarks", "peaks.json"))["peaks"]
    if device_kind not in table:
        raise BenchmarkError(
            f"no peaks on record for device kind {device_kind!r} "
            f"(known: {sorted(table)}); add it to benchmarks/peaks.json "
            "with its source"
        )
    return table[device_kind]


# ---------------------------------------------------------------------------
# Device, compile cache
# ---------------------------------------------------------------------------

def place_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR says, else
    the fixed ``<checkout>/.jax_cache`` (the program's entry points use
    the same rule).  Call before the first compile."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(root, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Small programs (weight init, norms) are worth a cache entry too: a
    # run's set-up should compile nothing after the cell's first run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def acquire_devices(chips: int, require_chip: bool = True) -> list:
    """The cell's devices, or BenchmarkError when JAX finds no TPU or
    fewer chips than the cell asks for.  Nothing falls back to the CPU;
    ``require_chip=False`` is for the benchmark's own CPU tests."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise BenchmarkError(f"JAX found no device: {exc}") from exc
    if require_chip and devices[0].platform != "tpu":
        raise BenchmarkError(
            f"JAX runs on platform {devices[0].platform!r}, not 'tpu': "
            "the benchmark measures only on the accelerator"
        )
    if len(devices) < chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chip(s), JAX found {len(devices)}"
        )
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where the backend
    reports none, as the CPU does)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


# ---------------------------------------------------------------------------
# Weights, made by the benchmark from the seed
# ---------------------------------------------------------------------------

def flatten(tree) -> dict:
    """``{"layer_0/attn/q_proj/kernel": leaf, ...}`` of a nested dict."""
    import jax

    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat["/".join(str(getattr(k, "key", k)) for k in path)] = leaf
    return flat


def make_weights(shapes, seed: int, num_layers: int, dtype, sharding=None):
    """Random weights in the tree structure ``shapes`` (ShapeDtypeStructs),
    made on the device in one jitted call: normal(0, 0.02) matrices and
    embeddings (the two output projections scaled by 1/sqrt(2 L), as GPT-2
    initialises them), biases normal(0, 0.02), norm scales 1 + normal(0,
    0.02) — nothing is left at a value (0, 1) that would hide a dropped
    term.  The same (shapes, seed) gives the same weights: the reference
    calls this again once the program's state is freed."""
    import jax
    import jax.numpy as jnp

    paths = list(flatten(shapes))
    leaves, treedef = jax.tree.flatten(shapes)

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(zip(paths, leaves)):
            x = 0.02 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, jnp.float32
            )
            if path.endswith("/scale"):
                x = 1.0 + x
            elif "o_proj/kernel" in path or "down_proj/kernel" in path:
                x = x / (2.0 * num_layers) ** 0.5
            out.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    # seeds run past 2**31: fold the high bits in instead of truncating
    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )
    return jax.jit(build, out_shardings=sharding)(key)


# ---------------------------------------------------------------------------
# Spans and counters (kept in memory; read by the per-layer metrics)
# ---------------------------------------------------------------------------

class Spans:
    """Host spans around the calls into each layer, on ``perf_counter``.
    With ``annotate`` they are also written into the profiler's trace
    (``jax.profiler.TraceAnnotation``), so idle gaps on the device can be
    attributed to what the host was doing."""

    def __init__(self, annotate: bool = False):
        self.records: list[tuple[str, float, float]] = []
        self.counters: dict[str, float] = {}
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        annotation = contextlib.nullcontext()
        if self.annotate:
            import jax

            annotation = jax.profiler.TraceAnnotation(f"bench:{name}")
        with annotation:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def count(self, name: str, by: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + by

    def durations(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1 in self.records if n == name]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

class Clock:
    """Where a run's time goes, on standard error: a line per phase, and
    at the end what compiled and what came out of the compile cache."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.compiles: list = []
        self.hits = self.misses = 0
        from jax import monitoring

        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **kw) -> None:
        if event.endswith("/cache_hits"):
            self.hits += 1
        elif event.endswith("/cache_misses"):
            self.misses += 1

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event.endswith("backend_compile_duration") and seconds >= 1.0:
            self.compiles.append(round(seconds, 1))

    def mark(self, what: str) -> None:
        print(f"[bench +{time.time() - self.t_start:7.1f}s] {what}",
              file=sys.stderr, flush=True)

    def summary(self) -> None:
        self.mark(
            f"compile cache: {self.hits} hit(s), {self.misses} miss(es); "
            f"compiles over 1 s: {self.compiles}"
        )


def percentile(values, q: float) -> float:
    """The q-th percentile (nearest rank, q in (0, 100]) of ``values``;
    ``inf`` entries (missing requests) sort last."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def trace_dir(cell: dict) -> str:
    return os.path.join(
        cell["root"], "chiprun_out", "benchmarks", "trace", cell["name"]
    )


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    root: str = ROOT,
    require_chip: bool = True,
    t_start: float | None = None,
) -> tuple[dict, list]:
    """Run one cell once.  Returns (the result object, the numbers
    compared as [(name, value, limit), ...])."""
    t_start = time.time() if t_start is None else t_start
    cell = load_cell(workload, root)
    place_compile_cache(root)
    devices = acquire_devices(cell["chips"], require_chip)

    import jax

    clock = Clock(t_start)
    clock.mark(f"devices acquired: {len(devices)} x {devices[0].device_kind}")
    peaks = load_peaks(devices[0].device_kind, root) if require_chip else None
    kind = load_module("kinds", cell["traffic"]["kind"], root)
    window_s = float(seconds)
    if trace:
        window_s = min(window_s, float(cell["traffic"]["trace_seconds"]))
    env = {
        "cell": cell, "config": cell["config"], "traffic": cell["traffic"],
        "devices": devices, "seed": int(seed), "root": root,
        "spans": Spans(annotate=trace), "window_s": window_s,
        "mark": clock.mark,
    }
    session = kind.setup(env)
    setup_s = time.time() - t_start
    clock.mark("set-up done, window opens")

    tdir = trace_dir(cell)
    if trace:
        import shutil

        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
    try:
        with env["spans"].span("window"):
            measured = session.measure(window_s)
    finally:
        if trace:
            jax.profiler.stop_trace()
    clock.mark("window closed")
    peak = memory_peak_bytes(devices)
    session.release()

    compared = session.check()
    clock.mark("comparison with the reference done")
    clock.summary()
    correct = all(value <= limit for _, value, limit in compared)

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": peak,
    }
    result = {
        "correct": bool(correct),
        "attempted": int(measured["attempted"]),
        "failed": int(measured["failed"]),
    }
    if not trace:
        values = dict(measured["end_to_end"], setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]
        }
        result["device"] = device
    else:
        from benchmarks import trace_reduce

        reduced = trace_reduce.reduce_dir(tdir, len(devices))
        ctx = {
            "cell": cell, "config": cell["config"],
            "traffic": cell["traffic"], "chips": len(devices),
            "peaks": peaks, "trace": reduced, "spans": env["spans"],
            "measured": measured, "window_s": measured["window_s"],
        }
        metrics = {}
        for m in cell["per_layer"]:
            value = load_module("layer_metrics", m["name"], root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["device"] = device
        result["breakdown"] = {
            "device_ops": reduced["top_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
    # the numbers compared, each beside its limit: last key of the line
    result["compared"] = {
        name: {"value": value, "limit": limit}
        for name, value, limit in compared
    }
    return result, compared


def main(argv=None, *, t_start: float | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, compared = run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=t_start,
        )
    except BenchmarkError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    for name, value, limit in compared:
        print(f"compared {name} = {value!r} limit {limit!r} "
              f"{'ok' if value <= limit else 'OVER'}", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    return 0
