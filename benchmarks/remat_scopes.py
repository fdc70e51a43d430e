#!/usr/bin/env python3
"""Device self time of a traced run that remat spends running the forward
a second time: every operation whose scope holds JAX's own
``rematted_computation`` (the program's ``observability/scopes.RECOMPUTE``),
split by ``scope_reduce``'s accepted buckets and by kind of operation, and
the Pallas kernels' second forward launches beside their first.

    python3 benchmarks/remat_scopes.py <trace dir or .xplane.pb> [chips]

The word lies inside ``transpose(``, so ``scope_reduce.phase_of`` counts
all of it as ``bwd``: this reads the part of ``train_bwd_ms`` that is
recompute.  A fusion counts under its root's scope, so a recomputed
operation that XLA fuses into a backward one is not seen, and backward
work fused under a recomputed root is counted; custom calls, fused with
nothing, are exact.

The word is data kept here: the yardstick does not import what it
measures.  ``tests/test_scopes.py`` holds it against the program's.
"""

from __future__ import annotations

import os
import re
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr

RECOMPUTE = "rematted_computation"
#: the forward kernels (``name=`` of their ``pallas_call``s), which remat
#: launches twice; no backward kernel runs under the word
KERNELS = ("flash_fwd", "ssd_fwd", "conv_fwd", "moe_gmm")
_RECOMPUTE = re.compile(sr._under(RECOMPUTE))
_KERNEL = re.compile(sr._under("(" + "|".join(KERNELS) + ")"))
#: ``trace_reduce.short_name``'s mark on a custom call
CUSTOM_CALL = " custom-call"


def kernel_of(short: str, scope: str) -> str | None:
    """The forward kernel that a custom call launches, found in its scope;
    None for any other operation (a copy that inherited the scope too)."""
    if not short.endswith(CUSTOM_CALL):
        return None
    found = _KERNEL.search(scope)
    return found.group(1) if found else None


def reduce(trace: dict, chips: int) -> dict | None:
    """Of a scoped trace's window (device self time, mean over the chips):

    ``bucket_kind_s``   {bucket: {kind of operation: seconds}} of what
                        carries the word
    ``kernel_s``        {kernel: seconds} of the forward kernels' custom
                        calls that carry it (remat's second launches)
    ``first_kernel_s``  {kernel: seconds} of those that carry neither it
                        nor ``transpose(``: the first forward, the same
                        shapes launched once more

    None where the trace has no device plane or nothing carries the word
    (a step without remat)."""
    windows = [(s, e) for n, s, e, _ in sr._host_events(trace)
               if n == tr.WINDOW_SPAN]
    devices = sorted(
        (plane["name"], line["events"]) for plane in trace["planes"]
        if plane["name"].startswith(tr.DEVICE_PLANE)
        for line in plane["lines"] if line["name"] == tr.OPS_LINE
    )[:chips]
    if not devices:
        return None
    # one look-up per distinct name, not per event
    recomputed = [bool(_RECOMPUTE.search(scope)) for _, scope in trace["names"]]
    bucket = [sr.bucket_of(scope) for _, scope in trace["names"]]
    kind = [tr.base_name(short) for short, _ in trace["names"]]
    kernel = [kernel_of(short, scope) for short, scope in trace["names"]]
    first = [k is not None and not r and "transpose(" not in scope
             for k, r, (_, scope) in zip(kernel, recomputed, trace["names"])]
    bucket_kind_ns: dict = {}
    kernel_ns: dict = {}
    first_ns: dict = {}
    for _, events in devices:
        if windows:
            events = tr.clip(events, *windows[0])
        for i, ns in tr.self_times(events):
            if recomputed[i]:
                by_kind = bucket_kind_ns.setdefault(bucket[i], {})
                by_kind[kind[i]] = by_kind.get(kind[i], 0) + ns
                if kernel[i] is not None:
                    kernel_ns[kernel[i]] = kernel_ns.get(kernel[i], 0) + ns
            elif first[i]:
                first_ns[kernel[i]] = first_ns.get(kernel[i], 0) + ns
    if not bucket_kind_ns:
        return None
    n = len(devices)

    def mean_s(table: dict) -> dict:
        return {k: v / 1e9 / n for k, v in table.items()}

    return {
        "bucket_kind_s": {b: mean_s(t) for b, t in bucket_kind_ns.items()},
        "kernel_s": mean_s(kernel_ns),
        "first_kernel_s": mean_s(first_ns),
        "devices": n,
    }


def seconds(reduced: dict | None, kernels: bool = False) -> float | None:
    """Device seconds of the window that carry the word (only the forward
    kernels' custom calls with ``kernels``), mean over the chips; None
    where there is nothing to read."""
    if reduced is None:
        return None
    if kernels:
        total = sum(reduced["kernel_s"].values())
    else:
        total = sum(sum(by.values()) for by in reduced["bucket_kind_s"].values())
    return total or None


def table(reduced: dict, steps: int | None = None) -> str:
    k = 1e3 / steps if steps else 1.0
    unit = f"ms a step over {steps} steps" if steps else "s"
    out = [f"device self time of remat's second forward by bucket, mean of "
           f"{reduced['devices']} chip(s), {unit}"]
    for bucket, by in sorted(reduced["bucket_kind_s"].items(),
                             key=lambda kv: -sum(kv[1].values())):
        kinds = sorted(by.items(), key=lambda kv: -kv[1])[:4]
        out.append(f"  {bucket:16s} {k * sum(by.values()):10.4f}   " + ", ".join(
            f"{kind} {k * s:.4f}" for kind, s in kinds))
    out.append(f"  {'all':16s} {k * seconds(reduced):10.4f}")
    out.append("  forward kernels, second launch / first launch:")
    for name in KERNELS:
        again = reduced["kernel_s"].get(name, 0.0)
        once = reduced["first_kernel_s"].get(name, 0.0)
        if again or once:
            ratio = f"{again / once:.4f}" if once else "-"
            out.append(f"    {name:12s} {k * again:10.4f} / {k * once:10.4f}"
                       f"   ratio {ratio}")
    return "\n".join(out)


_CACHE: dict = {}


def for_ctx(ctx) -> dict | None:
    """The reduction of the run's own trace, parsed once for all readers;
    None where there is no trace, no device plane, or nothing that carries
    the word.  The first parse prints the table and its own seconds to
    standard error."""
    from benchmarks import harness

    if "remat_reduced" in ctx:  # a reduction handed in (the tests)
        return ctx["remat_reduced"]
    try:
        path = tr.find_xplane(harness.trace_dir(ctx["cell"]))
    except FileNotFoundError:  # no trace was taken
        return None
    key = (path, os.path.getmtime(path), ctx["chips"])
    if key not in _CACHE:
        t0 = time.time()
        _CACHE[key] = reduce(sr.load_xplane(path), ctx["chips"])
        if _CACHE[key] is not None:
            print(table(_CACHE[key], ctx["measured"].get("steps")),
                  file=sys.stderr)
        print(f"remat_scopes: {time.time() - t0:.1f} s", file=sys.stderr,
              flush=True)
    return _CACHE[key]


def per_step_ms(ctx, kernels: bool = False) -> float | None:
    total = seconds(for_ctx(ctx), kernels)
    steps = ctx["measured"].get("steps")
    return None if total is None or not steps else 1e3 * total / steps


def main(argv) -> int:
    path = argv[0]
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    reduced = reduce(sr.load_xplane(path), int(argv[1]) if len(argv) > 1 else 1)
    if reduced is None:
        print(f"nothing ran under {RECOMPUTE}", file=sys.stderr)
        return 1
    print(table(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
