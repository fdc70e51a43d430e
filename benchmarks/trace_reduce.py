"""From the profiler's trace to numbers: device busy and idle time, time
per operation name, exposed collective time, and idle gaps by what the
host was doing.

``load_xplane`` reads an ``.xplane.pb`` with nothing but JAX into plain
data, ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``; ``reduce`` works on that form, so
the recorded fixture under ``tests/data`` is JSON.

What the trace holds on a TPU v5e (looked at by hand, PR 24): one plane
per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event
per executed HLO operation, named by its whole HLO text (control-flow
operations enclose their bodies' events; the Pallas kernels are the
``custom-call`` operations, named after their Flax scope: ``attn.72``) and whose line ``XLA Modules`` carries one event per
executed program; the plane ``/host:CPU`` has one line per host thread,
where the harness's spans appear as ``bench:<name>``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
COLLECTIVE_WORDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [
                [short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
            ]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_name(name: str) -> str:
    """A device operation's event carries its whole HLO text, ``%fusion.23
    = f32[...] fusion(...)``: keep the operation's name, and mark a custom
    call (the Pallas kernels reach the TPU as custom calls; the package
    gives them no ``name=``)."""
    if " = " not in name:
        return name
    head = name.split(" = ", 1)[0].lstrip("%")
    return head + " custom-call" if " custom-call(" in name else head


def base_name(name: str) -> str:
    """``fusion.23`` -> ``fusion``: operations grouped by kind."""
    return re.sub(r"\.\d+", "", name)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list) -> list:
    """Merged, sorted [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals: list) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(events: list, lo: int, hi: int) -> list:
    out = []
    for name, s, d in events:
        s2, e2 = max(s, lo), min(s + d, hi)
        if e2 > s2:
            out.append([name, s2, e2 - s2])
    return out


def self_times(events: list) -> list:
    """[(name, self_ns)] of possibly nested events on one line: an
    enclosing operation (a loop, a call) keeps only the time that its
    children do not cover."""
    out = []
    stack: list = []  # [name, end, self]
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2]))
    return out


def is_collective(name: str) -> bool:
    return any(word in name for word in COLLECTIVE_WORDS)


def _host_spans(trace: dict) -> list:
    spans = []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, s, s + d))
    return spans


def reduce(trace: dict, chips: int) -> dict:
    """Numbers of one traced window.

    ``window_s``     length of the harness's ``bench:window`` span (else
                     the extent of the device events)
    ``busy_s``       union of the intervals in which an operation ran,
                     averaged over the ``chips`` devices used
    ``busy_s_each``  the same per device
    ``op_self_s``    {operation name: self time, summed over devices}
    ``module_s``     {program name: [count, seconds], summed over devices}
    ``collective_s`` / ``exposed_collective_s``: time collectives ran on a
                     device / ran while no other operation did, averaged
    ``top_ops``      [[kind, seconds], ...]: self time by kind of operation
                     (``fusion.23`` counts under ``fusion``), per device mean
    ``idle_gaps``    [[host span, seconds], ...]: idle time of the first
                     device by the innermost host span at each gap's middle
    """
    spans = _host_spans(trace)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    devices = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PLANE):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if OPS_LINE in lines:
            devices.append((plane["name"], lines))
    devices.sort()
    devices = devices[:chips]
    if not devices:
        raise ValueError("the trace has no device plane with an "
                         f"{OPS_LINE!r} line")
    if windows:
        lo, hi = windows[0]
    else:
        every = [ev for _, ln in devices for ev in ln[OPS_LINE]]
        lo = min(s for _, s, _ in every)
        hi = max(s + d for _, s, d in every)

    busy_each = []
    op_self: dict = {}
    modules: dict = {}
    coll = exposed = 0
    gaps_first: list = []
    for i, (_, lines) in enumerate(devices):
        ops = clip(lines[OPS_LINE], lo, hi)
        busy = union([[s, s + d] for _, s, d in ops])
        busy_each.append(total(busy) / 1e9)
        for name, ns in self_times(ops):
            op_self[name] = op_self.get(name, 0) + ns
        for name, s, d in clip(lines.get(MODULES_LINE, []), lo, hi):
            c = modules.setdefault(name, [0, 0])
            c[0] += 1
            c[1] += d
        # an enclosing loop or call is neither collective nor compute
        leaves = [ev for ev in ops if not _encloses(ev[0])]
        c_iv = union([[s, s + d] for n, s, d in leaves if is_collective(n)])
        x_iv = union([[s, s + d] for n, s, d in leaves
                      if not is_collective(n)])
        coll += total(c_iv)
        exposed += total(subtract(c_iv, x_iv))
        if i == 0:
            gaps_first = subtract([[lo, hi]], busy)

    by_span: dict = {}
    for s, e in gaps_first:
        mid = (s + e) // 2
        inner = None
        for name, s2, e2 in spans:
            if name != WINDOW_SPAN and s2 <= mid < e2:
                if inner is None or (e2 - s2) < (inner[2] - inner[1]):
                    inner = (name, s2, e2)
        key = inner[0][len(SPAN_PREFIX):] if inner else "outside-spans"
        by_span[key] = by_span.get(key, 0) + (e - s)

    n = len(devices)
    grouped: dict = {}
    for name, ns in op_self.items():
        grouped[base_name(name)] = grouped.get(base_name(name), 0) + ns
    top = sorted(grouped.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_each) / n,
        "busy_s_each": busy_each,
        "op_self_s": {k: v / 1e9 for k, v in op_self.items()},
        "module_s": {k: [c, ns / 1e9] for k, (c, ns) in modules.items()},
        "collective_s": coll / 1e9 / n,
        "exposed_collective_s": exposed / 1e9 / n,
        "top_ops": [[k, v / 1e9 / n] for k, v in top[:20]],
        "idle_gaps": [
            [k, v / 1e9]
            for k, v in sorted(by_span.items(), key=lambda kv: -kv[1])
        ],
        "devices": n,
    }


def _encloses(name: str) -> bool:
    """Control-flow operations enclose their bodies' events: they are not
    compute that a collective could hide behind."""
    head = name.lstrip("%").split(".")[0].split(" ")[0]
    return head in ("while", "conditional", "call")


def op_seconds(reduced: dict, *needles: str) -> float:
    """Self time, summed over devices, of the operations whose name holds
    any of ``needles``."""
    return sum(
        v for k, v in reduced["op_self_s"].items()
        if any(n in k for n in needles)
    )


def reduce_dir(trace_dir: str, chips: int) -> dict:
    return reduce(load_xplane(find_xplane(trace_dir)), chips)
