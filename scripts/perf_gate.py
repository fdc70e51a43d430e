#!/usr/bin/env python
"""Cross-run perf regression gate: compare a run against a baseline.

Usage:
    # Gate an events dir (run_summary extracted from its timeline):
    python scripts/perf_gate.py EVENTS_DIR --store runs/ --baseline main

    # Gate a headline file (a JSON object with a flat `parsed.headline`;
    # no script in the repo writes one any more — ROADMAP D19):
    python scripts/perf_gate.py HEADLINE.json --store runs/ --baseline bench

    # Promote the current run to be the named baseline:
    python scripts/perf_gate.py EVENTS_DIR --store runs/ --baseline main \
        --update-baseline

Exit codes: 0 = pass (or baseline updated), 1 = usage/IO error,
3 = regression.  A metric missing on either side is reported and
skipped ("missing"), never failed — a run that didn't enable --mfu
must not fail the MFU gate silently; it must say so.

RUN may be: an events directory (summary rebuilt from its merged
timeline), a run_summary JSON file, or a JSON file whose
``parsed.headline`` flat metrics are gated pairwise (direction inferred
from the metric name: bubble/step_s/bytes/overhead/us/restart metrics
are lower-better, everything else higher-better).

Every gated run is also appended to the store's ``index.jsonl``, so the
store accretes history whether or not the gate passes.

Import-light on purpose: stdlib + the stdlib-only observability
modules, never jax.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributeddataparallel_tpu.observability import baseline as bl  # noqa: E402
from distributeddataparallel_tpu.observability.events import (  # noqa: E402
    load_timeline,
)

REGRESS_EXIT = 3

#: Direction inference for bench-headline metric names: ONE ordered
#: (pattern, direction) table, first match wins, default "higher".
#: The ORDER carries the semantics — every earlier row exists to
#: override a later, broader one:
#:
#: 1. "higher" WIN suffixes first.  Throughput rates (tok_s, img_s,
#:    ..._per_s) and reclaimed_s (restart seconds the elastic resize
#:    path gave BACK — it ends in _s and contains "restart", but more
#:    of it is better) would otherwise hit row 3's ``_s$``/``restart``
#:    and gate backwards; the win-shares gain_frac (autotune speedup),
#:    _hit_frac (prefix-cache hit rate), _avoided_frac (prefill FLOPs
#:    skipped) and _speedup would be shadowed by row 3's ``_frac$``.
#: 2. "lower" TTFT-decomposition shares, pinned EXPLICITLY.  The
#:    tracing rollup's ``ttft_*_share_frac`` (queue/handoff seconds as
#:    a share of total TTFT) and ``ttft_decomp_err_frac`` (span-tree
#:    self-consistency error) are lower-better; today row 4's broad
#:    ``_frac$`` would catch them, but these gate the fleet smoke, and
#:    their direction must not silently flip if someone later widens
#:    row 1 with another ``..._frac`` win suffix (the ``gain_frac``
#:    shape is one keystroke away from ``share_frac``).
#: 3. "hard-zero" loss counters — the serving fleet's
#:    ``dropped_req_total`` shape (requests lost through an engine kill
#:    instead of drained-and-requeued).  A nonzero value fails the gate
#:    even when the baseline was just as bad: "no worse than a lossy
#:    baseline" is not a pass.  ``--allow-drops`` downgrades these to
#:    ordinary lower-better.  Must precede row 4, whose ``dropped``
#:    would claim them as merely lower-better.
#: 4. "lower" cost/waste names: time (step_s, _s/_us/_ms, latency),
#:    space (bytes), idle/waste shares (bubble, overhead, skew,
#:    _frac/_fraction), and failure-adjacent counts (restart, dropped).
#:
#: Anything unmatched defaults to "higher" (plain throughput/score
#: names).  tests/test_protocol_lint.py gates this table against its
#: recorded list of headline names.
_DIRECTION_TABLE: tuple[tuple[re.Pattern, str], ...] = (
    (re.compile(r"(tok_s|img_s|_per_s|reclaimed_s|gain_frac|_hit_frac"
                r"|_avoided_frac|_speedup)$"), "higher"),
    (re.compile(r"(_share_frac|_decomp_err_frac)$"), "lower"),
    (re.compile(r"dropped(_[a-z0-9]+)*_total$"), "hard-zero"),
    (re.compile(r"(bubble|step_s|_s$|bytes|overhead|_us$|_ms$|restart"
                r"|latency|skew|dropped|_frac$|_fraction$)"), "lower"),
)
_DEFAULT_DIRECTION = "higher"


def _bench_direction(name: str) -> str:
    """'higher' | 'lower' | 'hard-zero' for a headline metric name."""
    for pattern, direction in _DIRECTION_TABLE:
        if pattern.search(name):
            return direction
    return _DEFAULT_DIRECTION


def load_run(path: str) -> tuple[dict, str]:
    """RUN argument -> (flat metric dict, source label)."""
    if os.path.isdir(path):
        records = load_timeline(path)
        if not records:
            raise ValueError(f"no event records under {path}")
        return bl.run_summary_from_timeline(records), "events"
    with open(path) as fh:
        data = json.load(fh)
    headline = data.get("parsed", {}).get("headline") or data.get("headline")
    if isinstance(headline, dict):
        flat = {k: v for k, v in headline.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
        if not flat:
            raise ValueError(f"{path}: headline has no numeric metrics")
        return flat, "bench"
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a JSON object")
    return data, "summary"


def gate_metrics_for(summary: dict, source: str,
                     default_tol: float) -> dict[str, tuple[str, float]]:
    """The metric set to gate: the fixed GATE_METRICS for trainer
    summaries, or every shared numeric headline for bench files (with
    name-inferred direction)."""
    if source != "bench":
        return bl.GATE_METRICS
    # hard-zero metrics still gate pairwise as lower-better here; the
    # absolute value>0 check is main()'s post-pass over the same table
    return {
        name: (
            {"hard-zero": "lower"}.get(
                _bench_direction(name), _bench_direction(name)
            ),
            default_tol,
        )
        for name in sorted(summary)
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run", help="events dir, run_summary JSON, or "
                                "BENCH_*.json")
    ap.add_argument("--store", required=True,
                    help="runs store directory (index.jsonl + baselines/)")
    ap.add_argument("--baseline", required=True,
                    help="baseline name to gate against / update")
    ap.add_argument("--threshold", action="append", default=[],
                    metavar="METRIC=FRAC",
                    help="per-metric relative tolerance override "
                         "(repeatable), e.g. --threshold mfu_mean=0.02")
    ap.add_argument("--default-threshold", type=float, default=0.05,
                    help="tolerance for bench-headline metrics "
                         "(default 0.05)")
    ap.add_argument("--allow-drops", action="store_true",
                    help="gate dropped_*_total metrics as ordinary "
                         "lower-better instead of hard-zero")
    ap.add_argument("--update-baseline", action="store_true",
                    help="record this run as the named baseline instead "
                         "of gating")
    ap.add_argument("--json", action="store_true",
                    help="emit the comparison as JSON")
    args = ap.parse_args(argv)

    thresholds = {}
    for spec in args.threshold:
        name, sep, frac = spec.partition("=")
        if not sep:
            print(f"perf_gate: bad --threshold {spec!r} (want METRIC=FRAC)",
                  file=sys.stderr)
            return 1
        try:
            thresholds[name] = float(frac)
        except ValueError:
            print(f"perf_gate: bad --threshold value {frac!r}",
                  file=sys.stderr)
            return 1

    try:
        summary, source = load_run(args.run)
    except (OSError, ValueError, json.JSONDecodeError) as exc:
        print(f"perf_gate: cannot load run {args.run!r}: {exc}",
              file=sys.stderr)
        return 1

    bl.append_run(args.store, summary, name=args.baseline, source=source)

    if args.update_baseline:
        path = bl.save_baseline(args.store, args.baseline, summary)
        print(f"perf_gate: baseline {args.baseline!r} updated -> {path}")
        return 0

    base = bl.load_baseline(args.store, args.baseline)
    if base is None:
        print(f"perf_gate: no baseline {args.baseline!r} in {args.store}; "
              f"record one with --update-baseline", file=sys.stderr)
        return 1

    result = bl.compare_to_baseline(
        summary, base, thresholds=thresholds,
        metrics=gate_metrics_for(summary, source, args.default_threshold),
    )
    if not args.allow_drops:
        for name in sorted(summary):
            value = summary[name]
            if not (_bench_direction(name) == "hard-zero"
                    and isinstance(value, (int, float))
                    and not isinstance(value, bool) and value > 0):
                continue
            if name not in result["regressed"]:
                result["regressed"].append(name)
            result["ok"] = False
            result["checks"] = [
                c for c in result["checks"] if c["metric"] != name
            ] + [{
                "metric": name, "status": "regress", "value": value,
                "baseline": base.get(name, 0.0), "bound": 0.0,
                "direction": "hard-zero",
            }]
    # GL002 attribution: the fingerprint is an identity, not a gated
    # metric (compare_metric treats non-numerics as missing), so it gets
    # explicit handling — same graph means a regression is environment
    # drift; a different graph means the program itself changed.
    run_fp = summary.get("collective_fp")
    base_fp = base.get("collective_fp")
    attribution = None
    if run_fp and base_fp:
        attribution = (
            "collective graph unchanged vs baseline "
            f"(fp {run_fp}) — any regression is environment drift"
            if run_fp == base_fp else
            f"collective graph CHANGED vs baseline (fp {run_fp} != "
            f"{base_fp}) — a regression is attributable to the step's "
            "collective structure"
        )
        result["collective_fp"] = {
            "run": run_fp, "baseline": base_fp,
            "changed": run_fp != base_fp,
        }
    if args.json:
        print(json.dumps(result, indent=2))
    else:
        for c in result["checks"]:
            mark = {"pass": "ok", "regress": "REGRESS",
                    "missing": "missing"}[c["status"]]
            if c["status"] == "missing":
                print(f"  {c['metric']:<18} {mark:>8}  "
                      f"(run={c['value']!r} baseline={c['baseline']!r})")
            else:
                print(f"  {c['metric']:<18} {mark:>8}  "
                      f"run={c['value']:.6g} baseline={c['baseline']:.6g} "
                      f"bound={c['bound']:.6g} ({c['direction']})")
    if attribution and not args.json:
        print(f"  {attribution}")
    if not result["ok"]:
        print(f"perf_gate: REGRESSION vs {args.baseline!r}: "
              + ", ".join(result["regressed"]), file=sys.stderr)
        return REGRESS_EXIT
    note = (f" ({len(result['missing'])} metric(s) missing, skipped)"
            if result["missing"] else "")
    print(f"perf_gate: pass vs {args.baseline!r}{note}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
