#!/usr/bin/env python3
"""Device self time of a traced run under the expert FFN's scopes (the
program's ``observability/scopes.MOE_SCOPES`` inside the Flax module
named ``mlp``): ``scope_reduce``'s reduction with a table of its own, as
``mixer_scopes.py`` has one, since ``scope_reduce.BUCKETS`` counts all of
it under ``mlp`` beside the dense layers' FFNs.

    python3 benchmarks/moe_scopes.py <trace dir or .xplane.pb> [chips]

The table is data kept here: the yardstick does not import what it
measures.  ``tests/test_scopes.py`` holds it against the program's names.
"""

from __future__ import annotations

import os
import re
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr

#: ordered like ``scope_reduce.BUCKETS``: the first regex that finds
#: something in the scope names the part.  An operation under ``mlp`` and
#: under none of these is a dense layer's FFN, and is not counted here
PARTS = (
    ("moe_router", sr._under("moe_router")),
    ("moe_dispatch", sr._under("moe_dispatch")),
    ("moe_experts", sr._under("moe_experts")),
    ("moe_combine", sr._under("moe_combine")),
    ("moe_shared", sr._under("moe_shared")),
)
_PARTS = tuple((name, re.compile(rx)) for name, rx in PARTS)


def part_of(scope: str) -> str | None:
    """The expert FFN's part that ``scope`` lies under; None outside."""
    for name, rx in _PARTS:
        if rx.search(scope):
            return name
    return None


def reduce(trace: dict, chips: int) -> dict | None:
    """``{"part_s": {part: {"fwd" | "bwd": seconds}}, "devices": n}`` of a
    scoped trace's window (device self time, mean over the chips), or
    None where it has no device plane or nothing ran under the parts."""
    windows = [(s, e) for n, s, e, _ in sr._host_events(trace)
               if n == tr.WINDOW_SPAN]
    devices = sorted(
        (plane["name"], line["events"]) for plane in trace["planes"]
        if plane["name"].startswith(tr.DEVICE_PLANE)
        for line in plane["lines"] if line["name"] == tr.OPS_LINE
    )[:chips]
    if not devices:
        return None
    part = [part_of(scope) for _, scope in trace["names"]]
    phase = [sr.phase_of(scope, "") for _, scope in trace["names"]]
    part_ns: dict = {}
    for _, events in devices:
        if windows:
            events = tr.clip(events, *windows[0])
        for i, ns in tr.self_times(events):
            if part[i] is not None:
                by_phase = part_ns.setdefault(part[i], {})
                by_phase[phase[i]] = by_phase.get(phase[i], 0) + ns
    if not part_ns:
        return None
    n = len(devices)
    return {
        "part_s": {p: {k: v / 1e9 / n for k, v in by.items()}
                   for p, by in part_ns.items()},
        "devices": n,
    }


def table(reduced: dict, steps: int | None = None) -> str:
    out = [f"device self time under the expert FFNs by part, mean of "
           f"{reduced['devices']} chip(s)"
           + (f", ms a step over {steps} steps" if steps else ", s")]
    k = 1e3 / steps if steps else 1.0
    whole = 0.0
    for part, by in sorted(reduced["part_s"].items(),
                           key=lambda kv: -sum(kv[1].values())):
        whole += sum(by.values())
        out.append(f"  {part:14s} {k * sum(by.values()):10.4f}   " + ", ".join(
            f"{ph} {k * s:.4f}" for ph, s in sorted(by.items())))
    out.append(f"  {'all five':14s} {k * whole:10.4f}")
    return "\n".join(out)


_CACHE: dict = {}


def for_ctx(ctx) -> dict | None:
    """The reduction of the run's own trace, parsed once for all readers;
    None where there is no trace, no device plane, or — as on the parent
    of the PR that brought the expert layer's scopes — nothing under
    them.  The first parse prints the table to standard error."""
    from benchmarks import harness

    if "moe_reduced" in ctx:  # a reduction handed in (the tests)
        return ctx["moe_reduced"]
    try:
        path = tr.find_xplane(harness.trace_dir(ctx["cell"]))
    except FileNotFoundError:  # no trace was taken
        return None
    key = (path, os.path.getmtime(path), ctx["chips"])
    if key not in _CACHE:
        _CACHE[key] = reduce(sr.load_xplane(path), ctx["chips"])
        if _CACHE[key] is not None:
            print(table(_CACHE[key], ctx["measured"].get("steps")),
                  file=sys.stderr, flush=True)
    return _CACHE[key]


def seconds(ctx, *parts: str) -> float | None:
    """Device seconds of the window under ``parts`` (all five without
    any), forward and backward, mean over the chips; None where there is
    nothing to read."""
    reduced = for_ctx(ctx)
    if reduced is None:
        return None
    total = sum(
        sum(by.values()) for part, by in reduced["part_s"].items()
        if not parts or part in parts
    )
    return total or None


def per_step_ms(ctx, *parts: str) -> float | None:
    total = seconds(ctx, *parts)
    steps = ctx["measured"].get("steps")
    return None if total is None or not steps else 1e3 * total / steps


def main(argv) -> int:
    path = argv[0]
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    reduced = reduce(sr.load_xplane(path), int(argv[1]) if len(argv) > 1 else 1)
    if reduced is None:
        print("nothing ran under an expert FFN's scope", file=sys.stderr)
        return 1
    print(table(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
