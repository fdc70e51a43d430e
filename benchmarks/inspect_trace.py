#!/usr/bin/env python3
"""Print what a recorded trace holds: planes, lines, event counts and the
names that take most time on each line.  Look at one trace by hand with
this before writing a reader against it.

    python3 benchmarks/inspect_trace.py <trace dir or .xplane.pb> [top]
    python3 benchmarks/inspect_trace.py <trace> --fixture <out.json> <ms>

The second form keeps the first ``<ms>`` milliseconds of the harness's
window (device operations, programs, and the harness's host spans) with
what ``trace_reduce.reduce`` reads from them, as the recorded trace that
``tests/test_trace_reduce.py`` checks the reader against.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def write_fixture(trace: dict, out: str, ms: float) -> None:
    import json

    from benchmarks import trace_reduce as tr

    spans = [
        ev for plane in trace["planes"]
        if not plane["name"].startswith(tr.DEVICE_PLANE)
        for line in plane["lines"] for ev in line["events"]
        if ev[0].startswith(tr.SPAN_PREFIX)
    ]
    lo = min(s for n, s, _ in spans if n == tr.WINDOW_SPAN)
    hi = lo + int(ms * 1e6)
    kept = {"planes": [{"name": "/host:CPU", "lines": [{
        "name": "python",
        "events": [[tr.WINDOW_SPAN, lo, hi - lo]] + [
            ev for ev in tr.clip(spans, lo, hi) if ev[0] != tr.WINDOW_SPAN
        ],
    }]}]}
    for plane in trace["planes"]:
        if plane["name"].startswith(tr.DEVICE_PLANE):
            kept["planes"].append({"name": plane["name"], "lines": [
                {"name": line["name"], "events": tr.clip(line["events"], lo, hi)}
                for line in plane["lines"]
                if line["name"] in (tr.OPS_LINE, tr.MODULES_LINE)
            ]})
    reduced = tr.reduce(kept, 1)
    expect = {k: reduced[k] for k in (
        "window_s", "busy_s", "collective_s", "exposed_collective_s")}
    with open(out, "w") as fh:
        json.dump({"trace": kept, "expect": expect}, fh)
    print(out, os.path.getsize(out), "bytes", expect)


def main(argv) -> int:
    from benchmarks import trace_reduce

    path = argv[0]
    if len(argv) > 1 and argv[1] == "--fixture":
        if os.path.isdir(path):
            path = trace_reduce.find_xplane(path)
        write_fixture(trace_reduce.load_xplane(path), argv[2], float(argv[3]))
        return 0
    top = int(argv[1]) if len(argv) > 1 else 12
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    trace = trace_reduce.load_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in trace["planes"]:
        print(f"plane {plane['name']!r}: {len(plane['lines'])} lines")
        for line in plane["lines"]:
            ev = line["events"]
            if not ev:
                continue
            lo = min(s for _, s, _ in ev)
            hi = max(s + d for _, s, d in ev)
            print(f"  line {line['name']!r}: {len(ev)} events, "
                  f"{lo} .. {hi} ns")
            by: dict = {}
            for name, _, d in ev:
                c = by.setdefault(name, [0, 0])
                c[0] += 1
                c[1] += d
            for name, (n, ns) in sorted(
                by.items(), key=lambda kv: -kv[1][1]
            )[:top]:
                print(f"    {ns / 1e6:10.3f} ms {n:6d} x {name[:110]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
