#!/usr/bin/env python3
"""From the profiler's trace to who owns the device's time: every device
operation of the traced window under the program scope that its HLO
``op_name`` carries, bucketed and split forward / backward / update; the
program's own host spans (``ddp:<name>``) and the jitted step's call; and
chip 0's idle gaps by the innermost host event of any origin.

    python3 benchmarks/scope_reduce.py <trace dir or .xplane.pb> [chips]
    python3 benchmarks/scope_reduce.py <trace> --fixture <out.json> <ms> [<skip ms>]

``load_xplane`` reads an ``.xplane.pb`` with nothing but JAX into plain
data (the "scoped form"): ``{"names": [[name, scope], ...], "planes":
[{"name", "lines": [{"name", "events": [[name index, start_ns,
duration_ns], ...]}]}]}``; ``reduce`` works on that form, so the recorded
fixture under ``tests/data`` is JSON.  ``trace_reduce`` keeps each device
event's instruction name and drops the rest; this keeps the scope too.

Where the scope comes from on a TPU v5e (looked at by hand, PR 25): the
``tf_op`` stat of the operation's event METADATA (see ``SCOPE_STAT``).  A
fusion's event carries one ``op_name``, that of the instruction the trace
names (the fusion's root): the whole fusion counts under that scope,
though it may hold operations of a neighbouring one.

The bucket table is data kept here: the yardstick does not import what it
measures.  ``tests/test_scopes.py`` holds it against the program's
``observability/scopes.py``.
"""

from __future__ import annotations

import os
import re
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import trace_reduce as tr

#: the program's host spans in the profiler's trace (observability/trace.py)
PROGRAM_SPAN_PREFIX = "ddp:"
#: JAX's own host event round the call of a jitted function
JIT_CALL_PREFIX = "PjitFunction("
#: the Python tracer's frames (``$file.py:12 function``): kept out of the
#: attribution of idle gaps unless nothing else covers a gap
PYTHON_FRAME_PREFIX = "$"

#: the stat of a device operation's METADATA that holds its HLO
#: ``op_name`` on a TPU, as ``<op_name>:<op type>``.  ``ProfileData`` shows
#: an event's own stats (offset, duration) and not its metadata's, so the
#: metadata tables are read from the file's wire format (``op_scopes``).
SCOPE_STAT = "tf_op"


def _under(name: str) -> str:
    """``name`` as one whole component of a scope path: ``/name/``,
    ``jvp(name)``, at either end."""
    return rf"(?:^|[/(]){name}(?:[/)]|$)"


#: ordered: the first regex that finds something in the scope names the
#: bucket.  The update's scopes come before the model's (a gradient
#: exchange inside a scanned block is still the exchange).
BUCKETS = (
    ("attn_kernel.fwd", r"flash_fwd"),
    ("attn_kernel.dq", r"flash_bwd_dq"),
    ("attn_kernel.dkv", r"flash_bwd_dkv"),
    ("grad_sync", _under("grad_sync")),
    ("grad_clip", _under("grad_clip")),
    ("optimizer", _under("optimizer")),
    ("metrics", _under("metrics")),
    ("loss", _under("loss")),
    ("head", _under(r"(?:head|lm_head)")),
    ("embed", _under("embed")),
    ("attn", _under("attn")),
    ("mlp", _under("mlp")),
    ("norm", _under(r"\w*norm\w*")),
    # directly under a layer: residual adds; and the layer scan's own
    # slicing of stacked weights and residuals
    ("block", _under(r"(?:layer_\d+|layers|block)") + r"|jvp\(\w+\)\)?/while/"),
)
OTHER = "other"
UPDATE_BUCKETS = ("grad_sync", "grad_clip", "optimizer")
KERNEL_BUCKETS = ("attn_kernel.fwd", "attn_kernel.dq", "attn_kernel.dkv")
#: all of ``/attn/``: the kernels sit under it, ``attn`` is the rest of it
#: (projections, the copies round the kernels)
ATTN_BUCKETS = ("attn",) + KERNEL_BUCKETS
_COMPILED = tuple((b, re.compile(rx)) for b, rx in BUCKETS)


def bucket_of(scope: str) -> str:
    for bucket, rx in _COMPILED:
        if rx.search(scope):
            return bucket
    return OTHER


#: the phase of an operation that carries no scope at all (a copy the
#: compiler made of a tuple element): neither forward nor backward can be
#: told, so it is neither — fwd + bwd + update + unscoped = busy
UNSCOPED = "unscoped"


def phase_of(scope: str, bucket: str) -> str:
    """``update`` (exchange, clip, optimizer); else ``unscoped`` where
    there is no scope; else ``bwd`` where JAX wrote ``transpose(`` into
    the scope, else ``fwd``."""
    if bucket in UPDATE_BUCKETS:
        return "update"
    if not scope:
        return UNSCOPED
    return "bwd" if "transpose(" in scope else "fwd"


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------

def _varint(buf, pos: int):
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: a varint
    as an int, a length-delimited field as a memoryview, fixed ones as
    their bytes."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = buf[pos:pos + size]
            pos += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[pos:pos + size]
            pos += size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, value


def _map_value(entry):
    """The value (field 2) of a protobuf map entry."""
    for number, _, value in _fields(entry):
        if number == 2:
            return value
    return b""


def op_scopes(path: str) -> dict:
    """``{plane name: {event name: scope}}`` of the device planes of an
    ``.xplane.pb``: the ``tf_op`` stat of every event metadata, without
    its ``:<op type>`` tail.  Field numbers are xplane.proto's: XSpace.
    planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5;
    XEventMetadata.name 2, .stats 5; XStatMetadata.id 1, .name 2;
    XStat.metadata_id 1, .str_value 5, .ref_value 7.  Lines and events
    are skipped, a length at a time."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: dict = {}
    for number, _, plane in _fields(space):
        if number != 1:
            continue
        name = ""
        stat_names: dict = {}
        metadata = []
        for number, _, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                metadata.append(value)
            elif number == 5:
                fields = {n: v for n, _, v in _fields(_map_value(value))}
                stat_names[fields.get(1, 0)] = bytes(fields.get(2, b"")).decode()
        if not name.startswith(tr.DEVICE_PLANE):
            continue
        scope_ids = {i for i, n in stat_names.items() if n == SCOPE_STAT}
        scopes = out.setdefault(name, {})
        unscoped = []
        for entry in metadata:
            event_name = scope = None
            for number, _, value in _fields(_map_value(entry)):
                if number == 2:
                    event_name = bytes(value).decode()
                elif number == 5:
                    stat = {n: v for n, _, v in _fields(value)}
                    if stat.get(1) in scope_ids:
                        scope = (
                            bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat.get(7), "")
                        )
            if event_name is not None and scope:
                scopes[event_name] = scope.rsplit(":", 1)[0]
            elif event_name is not None:
                unscoped.append(event_name)
        _inherit(scopes, unscoped)
    return out


_INSTRUCTION = re.compile(r"%[\w.\-]+")


def _inherit(scopes: dict, unscoped: list) -> None:
    """An operation that the compiler made carries no ``op_name`` (the
    ``copy-start``/``copy-done`` and ``slice-start``/``slice-done`` pairs
    that move a tensor between memories, layout copies): count it under
    the scope of the instruction whose result it moves — its first
    operand, followed through other unscoped instructions.  What moves a
    parameter of the program stays without a scope."""
    by_instruction = {}
    for text, scope in scopes.items():
        found = _INSTRUCTION.match(text)
        if found:
            by_instruction[found.group()] = scope
    operand = {}
    for text in unscoped:
        found = _INSTRUCTION.findall(text)
        if len(found) > 1 and text.startswith(found[0]):
            operand[found[0]] = found[1]
    for text in unscoped:
        found = _INSTRUCTION.match(text)
        at = found and found.group()
        for _ in range(8):
            at = operand.get(at)
            if at is None or at in by_instruction:
                break
        if at in by_instruction:
            scopes[text] = by_instruction[at]


def load_xplane(path: str) -> dict:
    """The scoped form of an ``.xplane.pb``.  The scope is looked up once
    per distinct event name, not per event."""
    from jax.profiler import ProfileData

    scopes = op_scopes(path)
    data = ProfileData.from_file(path)
    names: list = []
    index: dict = {}
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(tr.DEVICE_PLANE)
        scope_of = scopes.get(plane.name, {})
        lines = []
        for line in plane.lines:
            if device and line.name not in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                key = (device, ev.name)
                i = index.get(key)
                if i is None:
                    i = index[key] = len(names)
                    names.append([tr.short_name(ev.name),
                                  scope_of.get(ev.name, "")])
                events.append([i, int(ev.start_ns), int(ev.duration_ns)])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"names": names, "planes": planes}


# ---------------------------------------------------------------------------
# Reducing
# ---------------------------------------------------------------------------

def _host_events(trace: dict) -> list:
    """[(name, start, end, thread)] of every event on a host plane;
    ``thread`` numbers the trace's host lines."""
    names = trace["names"]
    out = []
    thread = 0
    for plane in trace["planes"]:
        if plane["name"].startswith(tr.DEVICE_PLANE):
            continue
        for line in plane["lines"]:
            thread += 1
            for i, s, d in line["events"]:
                out.append((names[i][0], s, s + d, thread))
    return out


def _outermost(intervals: list) -> list:
    """Of possibly nested (start, end) intervals, those inside no other
    (JAX nests a second ``PjitFunction`` event inside the first)."""
    out: list = []
    for s, e in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        if not out or s >= out[-1][1]:
            out.append((s, e))
    return out


def _innermost_at(events: list, points: list) -> list:
    """For each of the ascending ``points``, the name of the shortest of
    ``events`` [(name, start, end)] that covers it, or None: one sweep,
    the events that have begun on a heap by length."""
    import heapq

    events = sorted(events, key=lambda ev: ev[1])
    begun: list = []
    out = []
    k = 0
    for at in points:
        while k < len(events) and events[k][1] <= at:
            name, s, e = events[k]
            heapq.heappush(begun, (e - s, e, name))
            k += 1
        while begun and begun[0][1] <= at:
            heapq.heappop(begun)
        # an ended event deeper in the heap is longer than the top, and is
        # dropped when it reaches it: the top is the shortest still open
        out.append(begun[0][2] if begun else None)
    return out


def reduce(trace: dict, chips: int) -> dict | None:
    """Numbers of one traced window, or None where the trace has no device
    plane with an ``XLA Ops`` line (a CPU run, the parent of a new cell).

    ``window_s``      length of the harness's ``bench:window`` span (else
                      the extent of the device events)
    ``busy_s``        union of the intervals in which an operation ran,
                      mean over the chips
    ``bucket_s``      {bucket: device self time, mean over the chips}
    ``phase_s``       {"fwd" | "bwd" | "update" | "unscoped": the same}
    ``bucket_kind_s`` {bucket: {kind of operation: the same}}
    ``spans``         {``ddp:`` span name without the prefix: [seconds of
                      each inside the window]}
    ``step_call``     {"name", "count", "mean_s"}: the ``PjitFunction(...)``
                      host event with most time in the window — the
                      jitted step's call, as JAX itself times it
    ``idle_gaps``     [[host event, seconds], ...]: idle time of the first
                      chip by the innermost event, of any origin, that
                      the window's own thread was in at each gap's middle
                      (a Python frame only where nothing else covers it)
    ``idle_s``        the first chip's idle time in the window
    """
    names = trace["names"]
    host = _host_events(trace)
    windows = [(s, e, t) for n, s, e, t in host if n == tr.WINDOW_SPAN]
    devices = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(tr.DEVICE_PLANE):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if tr.OPS_LINE in lines:
            devices.append((plane["name"], lines[tr.OPS_LINE]))
    devices.sort()
    devices = devices[:chips]
    if not devices:
        return None
    if windows:
        lo, hi, loop_thread = windows[0]
    else:
        loop_thread = None
        lo = min(s for _, evs in devices for _, s, _ in evs)
        hi = max(s + d for _, evs in devices for _, s, d in evs)

    # one look-up per distinct name, not per event
    bucket = [bucket_of(scope) for _, scope in names]
    phase = [phase_of(scope, b) for (_, scope), b in zip(names, bucket)]
    kind = [tr.base_name(short) for short, _ in names]

    n = len(devices)
    bucket_s: dict = {}
    phase_s: dict = {}
    bucket_kind_s: dict = {}
    busy_ns = 0
    gaps_first: list = []
    for d, (_, events) in enumerate(devices):
        ops = tr.clip(events, lo, hi)
        busy = tr.union([[s, s + dur] for _, s, dur in ops])
        busy_ns += tr.total(busy)
        if d == 0:
            gaps_first = tr.subtract([[lo, hi]], busy)
        for i, ns in tr.self_times(ops):
            b = bucket[i]
            bucket_s[b] = bucket_s.get(b, 0) + ns
            phase_s[phase[i]] = phase_s.get(phase[i], 0) + ns
            by_kind = bucket_kind_s.setdefault(b, {})
            by_kind[kind[i]] = by_kind.get(kind[i], 0) + ns

    spans: dict = {}
    calls: dict = {}
    for name, s, e, _ in host:
        if s < lo or e > hi:
            continue
        if name.startswith(PROGRAM_SPAN_PREFIX):
            spans.setdefault(name[len(PROGRAM_SPAN_PREFIX):], []).append(
                (e - s) / 1e9
            )
        elif name.startswith(JIT_CALL_PREFIX):
            calls.setdefault(name, []).append((s, e))
    step_call = None
    for name, ivs in calls.items():
        outer = _outermost(ivs)
        spent = sum(e - s for s, e in outer)
        if step_call is None or spent > step_call[3]:
            step_call = (name, len(outer), spent / len(outer) / 1e9, spent)

    # what the host was doing in a gap is what the loop's own thread was
    # doing: the one that opened the window (every thread, without one)
    loop = [ev[:3] for ev in host
            if loop_thread in (None, ev[3]) and ev[0] != tr.WINDOW_SPAN]
    named = [ev for ev in loop if not ev[0].startswith(PYTHON_FRAME_PREFIX)]
    frames = [ev for ev in loop if ev[0].startswith(PYTHON_FRAME_PREFIX)]
    by_event: dict = {}
    mids = [(s + e) // 2 for s, e in gaps_first]
    for (s, e), inner, frame in zip(
        gaps_first, _innermost_at(named, mids), _innermost_at(frames, mids)
    ):
        key = inner or frame or "outside-every-host-event"
        by_event[key] = by_event.get(key, 0) + (e - s)

    def mean_s(table: dict) -> dict:
        return {k: v / 1e9 / n for k, v in table.items()}

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "bucket_s": mean_s(bucket_s),
        "phase_s": mean_s(phase_s),
        "bucket_kind_s": {b: mean_s(t) for b, t in bucket_kind_s.items()},
        "spans": spans,
        "step_call": step_call and {
            "name": step_call[0], "count": step_call[1],
            "mean_s": step_call[2],
        },
        "idle_gaps": [
            [k, v / 1e9]
            for k, v in sorted(by_event.items(), key=lambda kv: -kv[1])
        ],
        "idle_s": tr.total(gaps_first) / 1e9,
        "devices": n,
    }


def tables(reduced: dict, top: int = 6) -> str:
    """The bucket x kind-of-operation table and the idle gaps, as text."""
    busy = reduced["busy_s"] or 1.0
    out = [f"device self time by program scope, mean of {reduced['devices']}"
           f" chip(s): busy {reduced['busy_s']:.4f} s of a "
           f"{reduced['window_s']:.4f} s window"]
    for b, s in sorted(reduced["bucket_s"].items(), key=lambda kv: -kv[1]):
        kinds = sorted(reduced["bucket_kind_s"][b].items(),
                       key=lambda kv: -kv[1])[:top]
        out.append(
            f"  {b:16s} {s:8.4f} s {100 * s / busy:5.1f} %  "
            + ", ".join(f"{k} {v:.4f}" for k, v in kinds)
        )
    out.append("  by phase: " + ", ".join(
        f"{k} {v:.4f} s" for k, v in sorted(reduced["phase_s"].items())))
    idle = reduced["idle_s"] or 1.0
    out.append(f"idle gaps of chip 0 by innermost host event: "
               f"{reduced['idle_s']:.4f} s idle")
    for name, s in reduced["idle_gaps"][:10]:
        out.append(f"  {s:8.4f} s {100 * s / idle:5.1f} %  {name[:90]}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# For the readers: one parse a trace
# ---------------------------------------------------------------------------

_CACHE: dict = {}


def for_ctx(ctx) -> dict | None:
    """The reduction of the run's own trace (``harness.trace_dir``), parsed
    once for all readers; None where there is no trace or no device plane
    in it (``reduce``).  The first parse prints the tables to standard
    error."""
    from benchmarks import harness

    if "scope_reduced" in ctx:  # a reduction handed in (the tests)
        return ctx["scope_reduced"]
    try:
        path = tr.find_xplane(harness.trace_dir(ctx["cell"]))
    except FileNotFoundError:  # no trace was taken
        return None
    key = (path, os.path.getmtime(path), ctx["chips"])
    if key not in _CACHE:
        t0 = time.time()
        # a trace that cannot be parsed or reduced raises: a broken
        # yardstick must not read as a metric that is silent in this cell
        _CACHE[key] = reduce(load_xplane(path), ctx["chips"])
        if _CACHE[key] is not None:
            print(tables(_CACHE[key]), file=sys.stderr)
        print(f"scope_reduce: {time.time() - t0:.1f} s", file=sys.stderr,
              flush=True)
    return _CACHE[key]


def per_step_ms(ctx, table: str, *keys: str) -> float | None:
    """The sum of ``reduced[table][key]`` over ``keys`` (device self time,
    mean over the chips) as milliseconds a step; None where there is
    nothing to read."""
    reduced = for_ctx(ctx)
    steps = ctx["measured"].get("steps")
    if reduced is None or not steps:
        return None
    seconds = sum(reduced[table].get(k, 0.0) for k in keys)
    return None if not seconds else 1e3 * seconds / steps


# ---------------------------------------------------------------------------
# Command line: look at a trace; cut a fixture
# ---------------------------------------------------------------------------

def write_fixture(trace: dict, out: str, ms: float, skip_ms: float) -> None:
    """Keep ``ms`` milliseconds of the harness's window from ``skip_ms``
    on, as a window of their own: device operations with their scopes,
    and every host event that is not a Python frame, renumbered to the
    names that are left."""
    import json

    host = _host_events(trace)
    lo = min(s for n, s, _, _ in host if n == tr.WINDOW_SPAN)
    lo += int(skip_ms * 1e6)
    hi = lo + int(ms * 1e6)
    names: list = []
    index: dict = {}

    def keep(events, host_plane):
        out_events = []
        for i, s, d in tr.clip(events, lo, hi):
            name = trace["names"][i]
            if host_plane and name[0].startswith(PYTHON_FRAME_PREFIX):
                continue
            if name[0] == tr.WINDOW_SPAN:
                s, d = lo, hi - lo
            j = index.setdefault(tuple(name), len(names))
            if j == len(names):
                names.append(name)
            out_events.append([j, s, d])
        return out_events

    planes = []
    for plane in trace["planes"]:
        device = plane["name"].startswith(tr.DEVICE_PLANE)
        lines = [{"name": ln["name"], "events": keep(ln["events"], not device)}
                 for ln in plane["lines"]]
        lines = [ln for ln in lines if ln["events"]]
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    kept = {"names": names, "planes": planes}
    reduced = reduce(kept, 1)
    expect = {k: reduced[k] for k in ("window_s", "busy_s", "bucket_s",
                                      "phase_s", "idle_s")}
    with open(out, "w") as fh:
        json.dump({"trace": kept, "expect": expect}, fh)
    print(out, os.path.getsize(out), "bytes")
    print(tables(reduced))


def main(argv) -> int:
    path = argv[0]
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    trace = load_xplane(path)
    if len(argv) > 1 and argv[1] == "--fixture":
        write_fixture(trace, argv[2], float(argv[3]),
                      float(argv[4]) if len(argv) > 4 else 0.0)
        return 0
    reduced = reduce(trace, int(argv[1]) if len(argv) > 1 else 1)
    if reduced is None:
        print("no device plane with an 'XLA Ops' line", file=sys.stderr)
        return 1
    print(tables(reduced, top=12))
    print("spans:", {k: (len(v), sum(v) / len(v))
                     for k, v in reduced["spans"].items()})
    print("step call:", reduced["step_call"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
