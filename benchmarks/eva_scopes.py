#!/usr/bin/env python3
"""Device self time of a traced run under chunk-summarised attention's
scopes (the program's ``observability/scopes.EVA_SCOPES`` inside the Flax
module named ``attn``): ``scope_reduce``'s reduction with a table of its
own, as ``moe_scopes.py`` has one, since ``scope_reduce.BUCKETS`` counts
the flash kernels by their own names wherever they run and the rest under
``attn`` beside the projections.

    python3 benchmarks/eva_scopes.py <trace dir or .xplane.pb> [chips]

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
#: something in the scope names the part.  The three flash kernels run
#: under ``eva_local`` and again under ``eva_remote``: the local part's
#: are a part of their own, for their roofline.  An operation under
#: ``attn`` and under none of these is a projection or the rotation, and
#: is not counted here
PARTS = (
    ("eva_local.kernels",
     sr._under("eva_local") + r".*/flash_(?:fwd|bwd_dq|bwd_dkv)/"),
    ("eva_local", sr._under("eva_local")),
    ("eva_summaries", sr._under("eva_summaries")),
    ("eva_remote", sr._under("eva_remote")),
    ("eva_merge", sr._under("eva_merge")),
)
_PARTS = tuple((name, re.compile(rx)) for name, rx in PARTS)


def part_of(scope: str) -> str | None:
    """The attention's part that ``scope`` lies under; None outside."""
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
    out = [f"device self time under chunk-summarised attention by part, mean of "
           f"{reduced['devices']} chip(s)"
           + (f", ms a step over {steps} steps" if steps else ", s")]
    k = 1e3 / steps if steps else 1.0
    whole = 0.0
    for part, by in sorted(reduced["part_s"].items(),
                           key=lambda kv: -sum(kv[1].values())):
        whole += sum(by.values())
        out.append(f"  {part:18s} {k * sum(by.values()):10.4f}   " + ", ".join(
            f"{ph} {k * s:.4f}" for ph, s in sorted(by.items())))
    out.append(f"  {'all':18s} {k * whole:10.4f}")
    return "\n".join(out)


_CACHE: dict = {}


def for_ctx(ctx) -> dict | None:
    """The reduction of the run's own trace, parsed once for all readers;
    None where there is no trace, no device plane, or — as on the parent
    of the PR that brought these scopes — nothing under them.  The first parse prints the table to standard error."""
    from benchmarks import harness

    if "eva_reduced" in ctx:  # a reduction handed in (the tests)
        return ctx["eva_reduced"]
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
    """Device seconds of the window under ``parts`` (all of them without
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


def roofline_share(ctx, work: dict, *parts: str) -> float | None:
    """The least time the chip could take for ``work`` (``{"flops",
    "bytes"}`` of one step of the global batch: the larger of FLOPs over
    the bf16 peak and bytes over the HBM peak) over the device time under
    ``parts``, in per cent."""
    steps = ctx["measured"].get("steps")
    if not steps or ctx["peaks"] is None:
        return None
    # mean over the chips x chips: set against the global batch's work
    under = seconds(ctx, *parts)
    if under is None:
        return None
    least = steps * max(
        work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / (ctx["chips"] * under)


def main(argv) -> int:
    path = argv[0]
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    reduced = reduce(sr.load_xplane(path), int(argv[1]) if len(argv) > 1 else 1)
    if reduced is None:
        print("nothing ran under an eva scope", file=sys.stderr)
        return 1
    print(table(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
