#!/usr/bin/env python
"""Offline run report: one events dir in, one markdown (or JSON) out.

Usage:
    python scripts/ddp_report.py EVENTS_DIR            # markdown to stdout
    python scripts/ddp_report.py EVENTS_DIR --json     # machine-readable
    python scripts/ddp_report.py EVENTS_DIR -o report.md

Consumes the merged ``timeline.jsonl`` a run leaves behind (merging the
per-worker files itself when the run died before the exit-time merge)
and renders the four performance-attribution views:

- **Goodput** — wall time split into productive / compile / checkpoint /
  eval / restart / stall, reconstructed across every incarnation of a
  supervised run (``observability.goodput``);
- **MFU trend** — the per-window ``mfu`` events as a table (cost model
  vs hardware peak);
- **Memory** — per-rank live-array / device high-water marks from the
  ``memory`` and ``exec_memory`` events;
- **Stragglers** — per-rank step stats and cross-rank skew attribution
  (``observability.straggler``).

Sections a run didn't record (no --mfu, single rank, gang dead before
any worker wrote) degrade to an explanatory line, never a crash — the
report is most needed for the runs that ended badly.

Import-light on purpose: stdlib + the stdlib-only observability modules,
never jax — it must run on a laptop holding only the events dir.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributeddataparallel_tpu.analysis.conformance import (  # noqa: E402
    check_timeline,
)
from distributeddataparallel_tpu.observability.critical_path import (  # noqa: E402
    check_lineage,
    request_decompositions,
    tier_rollups,
    ttft_rollup,
)
from distributeddataparallel_tpu.observability.events import (  # noqa: E402
    load_timeline,
)
from distributeddataparallel_tpu.observability.goodput import (  # noqa: E402
    goodput_from_timeline,
)
from distributeddataparallel_tpu.observability.pipeline import (  # noqa: E402
    PHASE_COLUMNS,
    measured_bubble_fraction,
)
from distributeddataparallel_tpu.observability.straggler import (  # noqa: E402
    straggler_report,
)


def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{n} B"
        n /= 1024
    return f"{n}"


def _pct(x) -> str:
    return "-" if x is None else f"{100 * x:.2f}%"


def analyze(records: list[dict]) -> dict:
    """Everything the renderers need, as plain data — the --json face."""
    worker_procs = sorted(
        {r["proc"] for r in records if isinstance(r.get("proc"), int)}
    )
    out = {
        "n_records": len(records),
        "worker_procs": worker_procs,
        "goodput": None,
        "mfu": [],
        "memory": {},
        "exec_memory": [],
        "straggler": None,
        "pipeline": measured_bubble_fraction(records),
        "restarts": [],
        "elasticity": None,
        "integrity": None,
        "alerts": [],
        "lint": [],
        "run_summary": None,
        "serving": None,
        "fleet": None,
        "ttft_decomposition": None,
        "tuning": None,
    }
    if worker_procs:
        out["goodput"] = goodput_from_timeline(records, proc=worker_procs[0])
        out["straggler"] = straggler_report(records)

    for r in records:
        kind = r.get("kind")
        if kind == "mfu":
            out["mfu"].append({
                "step": r.get("step"),
                "mfu": r.get("mfu"),
                "hfu": r.get("hfu"),
                "model_flops_per_s": r.get("model_flops_per_s"),
            })
        elif kind == "memory":
            proc = r.get("proc")
            mem = out["memory"].setdefault(proc, {
                "samples": 0,
                "live_hwm_bytes": 0,
                "device_peak_bytes": None,
            })
            mem["samples"] += 1
            mem["live_hwm_bytes"] = max(
                mem["live_hwm_bytes"], r.get("live_hwm_bytes") or 0
            )
            if r.get("device_peak_bytes") is not None:
                mem["device_peak_bytes"] = max(
                    mem["device_peak_bytes"] or 0, r["device_peak_bytes"]
                )
        elif kind == "exec_memory":
            out["exec_memory"].append(
                {k: v for k, v in r.items() if k not in ("v", "seq")}
            )
        elif kind in ("restart_attempt", "restart_exhausted"):
            out["restarts"].append({
                "kind": kind,
                "attempt": r.get("attempt"),
                "failed": r.get("failed"),
            })
        elif kind in ("membership_epoch", "gang_resize", "resize_downtime",
                      "gang_suspect", "rdzv_rehost", "gang_verdict"):
            el = out["elasticity"]
            if el is None:
                el = out["elasticity"] = {
                    "epochs": {}, "resizes": [], "downtimes": {},
                    "suspects": [], "rehosts": [], "verdict": None,
                }
            if kind == "gang_suspect":
                el["suspects"].append({
                    "member": r.get("member"),
                    "age_s": r.get("age_s"),
                    "epoch": r.get("epoch"),
                })
            elif kind == "rdzv_rehost":
                el["rehosts"].append({
                    "generation": r.get("generation"),
                    "owner": r.get("owner"),
                })
            elif kind == "gang_verdict":
                # At most one per run (the supervisor's terminal ladder
                # record); keep the last in case a merged timeline holds
                # several supervised sub-runs.
                el["verdict"] = {
                    "rung": r.get("rung"),
                    "fault": r.get("fault"),
                    "fault_kind": r.get("fault_kind"),
                }
            elif kind == "membership_epoch":
                # Worker and supervisor may both emit an epoch record;
                # keyed by epoch so duplicates collapse (last wins).
                el["epochs"][r.get("epoch")] = {
                    "epoch": r.get("epoch"),
                    "size": r.get("size"),
                    "roster": r.get("roster") or [],
                }
            elif kind == "gang_resize":
                # Every survivor (and the supervisor) reports the same
                # transition; collapse duplicates of one epoch.
                if not any(
                    z["epoch"] == r.get("epoch") for z in el["resizes"]
                ):
                    el["resizes"].append({
                        "epoch": r.get("epoch"),
                        "old_size": r.get("old_size"),
                        "new_size": r.get("new_size"),
                        "left": r.get("left") or [],
                        "joined": r.get("joined") or [],
                    })
            else:
                if isinstance(r.get("seconds"), (int, float)):
                    ep = r.get("epoch")
                    el["downtimes"][ep] = max(
                        el["downtimes"].get(ep, 0.0), r["seconds"]
                    )
        elif kind in ("sdc_check", "sdc_detect", "sdc_evict"):
            ig = out["integrity"]
            if ig is None:
                ig = out["integrity"] = {
                    "checks": 0, "detects": [], "evictions": [],
                }
            if kind == "sdc_check":
                ig["checks"] += 1
            elif kind == "sdc_detect":
                ig["detects"].append({
                    "step": r.get("step"),
                    "rank": r.get("rank"),
                    "ranks": r.get("ranks") or [],
                    "leaves": r.get("leaves") or [],
                    "method": r.get("method"),
                    "tie": r.get("tie"),
                })
            else:
                ig["evictions"].append({
                    "step": r.get("step"),
                    "rank": r.get("rank"),
                })
        elif kind == "lint_report":
            out["lint"].append({
                "layer": r.get("layer"),
                "n_findings": r.get("n_findings"),
                "rules": r.get("rules"),
                "findings": r.get("findings") or [],
            })
        elif kind == "alert":
            out["alerts"].append({
                "rule": r.get("rule"),
                "proc": r.get("proc"),
                "step": r.get("step"),
                "ts": r.get("ts"),
                "value": r.get("value"),
                "threshold": r.get("threshold"),
            })
        elif kind == "run_summary":
            # Last one wins: the final incarnation's summary is the one
            # that reflects the whole (resumed) run.
            out["run_summary"] = {
                k: v for k, v in r.items() if k not in ("v", "seq", "kind")
            }
        elif kind in ("request_admit", "prefill_chunk", "decode_step",
                      "request_done", "kv_evict", "prefix_hit",
                      "spec_verify"):
            s = out["serving"]
            if s is None:
                s = out["serving"] = {
                    "admitted": 0, "completed": 0, "tokens_out": 0,
                    "prefill_chunks": 0, "decode_steps": 0,
                    "active_sum": 0, "active_max": 0,
                    "evictions": {"lru": 0, "preempt": 0},
                    "evicted_blocks": 0, "ttft_s": [],
                    "first_ts": None, "last_ts": None,
                    "ctx_tokens": 0, "prefix_hits": 0,
                    "prefix_hit_tokens": 0, "spec_dispatches": 0,
                    "spec_drafted": 0, "spec_accepted": 0,
                    "spec_rows": 0, "accept_hist": {},
                }
            ts = r.get("ts")
            if isinstance(ts, (int, float)):
                s["first_ts"] = ts if s["first_ts"] is None \
                    else min(s["first_ts"], ts)
                s["last_ts"] = ts if s["last_ts"] is None \
                    else max(s["last_ts"], ts)
            if kind == "request_admit":
                s["admitted"] += 1
                s["ctx_tokens"] += r.get("ctx_tokens") or 0
            elif kind == "prefill_chunk":
                s["prefill_chunks"] += 1
            elif kind == "decode_step":
                s["decode_steps"] += 1
                n = r.get("n_active") or 0
                s["active_sum"] += n
                s["active_max"] = max(s["active_max"], n)
            elif kind == "request_done":
                s["completed"] += 1
                s["tokens_out"] += r.get("tokens") or 0
                if isinstance(r.get("ttft_s"), (int, float)):
                    s["ttft_s"].append(r["ttft_s"])
            elif kind == "kv_evict":
                reason = r.get("reason") or "lru"
                s["evictions"][reason] = (
                    s["evictions"].get(reason, 0) + 1
                )
                s["evicted_blocks"] += r.get("blocks") or 0
            elif kind == "prefix_hit":
                s["prefix_hits"] += 1
                s["prefix_hit_tokens"] += r.get("tokens") or 0
            elif kind == "spec_verify":
                s["spec_dispatches"] += 1
                s["spec_drafted"] += r.get("drafted") or 0
                s["spec_accepted"] += r.get("accepted") or 0
                rows = r.get("rows") or 0
                s["spec_rows"] += rows
                if rows:
                    # accept-length histogram, bucketed by the
                    # dispatch's mean accepted tokens per row
                    b = int((r.get("accepted") or 0) // rows)
                    s["accept_hist"][b] = s["accept_hist"].get(b, 0) + 1
        elif kind in ("route_admit", "kv_handoff", "engine_verdict",
                      "tier_summary"):
            f = out["fleet"]
            if f is None:
                f = out["fleet"] = {
                    "routed": 0, "affinity_hits": 0,
                    "queue_depth_max": 0,
                    "handoffs": 0, "handoff_bytes": 0,
                    "handoff_s": [], "redelivered": 0,
                    "verdicts": [], "tiers": {},
                }
            if kind == "route_admit":
                f["routed"] += 1
                if r.get("affinity"):
                    f["affinity_hits"] += 1
                f["queue_depth_max"] = max(
                    f["queue_depth_max"], r.get("queue_depth") or 0
                )
            elif kind == "kv_handoff":
                f["handoffs"] += 1
                f["handoff_bytes"] += r.get("bytes") or 0
                if isinstance(r.get("handoff_s"), (int, float)):
                    f["handoff_s"].append(r["handoff_s"])
                if (r.get("attempts") or 1) > 1:
                    f["redelivered"] += 1
            elif kind == "engine_verdict":
                f["verdicts"].append({
                    k: r.get(k) for k in (
                        "engine", "rung", "tier", "requeued", "reason",
                    )
                })
            else:  # tier_summary (one rollup per tier per run)
                f["tiers"][r.get("tier")] = {
                    k: r.get(k) for k in (
                        "completed", "p50_ttft_s", "p99_ttft_s",
                        "p50_tpot_s", "p99_tpot_s",
                    ) if r.get(k) is not None
                }
        elif kind in ("tune_trial", "tune_result"):
            t = out["tuning"]
            if t is None:
                t = out["tuning"] = {
                    "trials": [], "result": None, "drift_fracs": [],
                }
            if kind == "tune_trial":
                t["trials"].append({
                    k: r.get(k) for k in (
                        "trial", "status", "predicted_step_s",
                        "measured_step_s", "score", "mfu", "drift_frac",
                        "warm_mode",
                    )
                })
                if isinstance(r.get("drift_frac"), (int, float)):
                    t["drift_fracs"].append(r["drift_frac"])
            else:
                # last one wins — a search followed by apply runs in the
                # same events dir reports the final applied state
                t["result"] = {
                    k: r.get(k) for k in (
                        "mode", "winner", "applied", "score", "mfu",
                        "gain_frac", "n_trials", "n_measured",
                        "store_path",
                    )
                }
    if out["serving"]:
        s = out["serving"]
        span = (
            (s["last_ts"] - s["first_ts"])
            if s["first_ts"] is not None else 0.0
        )
        s["tok_s"] = s["tokens_out"] / span if span > 0 else None
        s["mean_active"] = (
            s["active_sum"] / s["decode_steps"]
            if s["decode_steps"] else 0.0
        )
        ttfts = sorted(s.pop("ttft_s"))
        s["ttft_p50_s"] = _quantile(ttfts, 0.50)
        s["ttft_p99_s"] = _quantile(ttfts, 0.99)
        s["prefix_hit_frac"] = (
            s["prefix_hits"] / s["admitted"] if s["admitted"] else None
        )
        s["prefill_flops_avoided_frac"] = (
            s["prefix_hit_tokens"] / s["ctx_tokens"]
            if s["ctx_tokens"] else None
        )
        s["spec_accept_mean"] = (
            s["spec_accepted"] / s["spec_rows"]
            if s["spec_rows"] else None
        )
        s["accept_hist"] = {
            str(k): s["accept_hist"][k] for k in sorted(s["accept_hist"])
        }
    if out["fleet"]:
        f = out["fleet"]
        hs = sorted(f.pop("handoff_s"))
        f["handoff_s_mean"] = (sum(hs) / len(hs)) if hs else None
        f["handoff_s_p99"] = _quantile(hs, 0.99) if hs else None
        f["affinity_frac"] = (
            f["affinity_hits"] / f["routed"] if f["routed"] else None
        )
    if out["elasticity"]:
        el = out["elasticity"]
        # dicts keyed by epoch -> sorted lists for the --json face
        el["epochs"] = [el["epochs"][k]
                        for k in sorted(el["epochs"], key=lambda e: (e is None, e))]
        el["downtimes"] = [
            {"epoch": k, "seconds": v}
            for k, v in sorted(el["downtimes"].items(),
                               key=lambda kv: (kv[0] is None, kv[0]))
        ]
        el["n_resizes"] = len(el["resizes"])
        el["resize_downtime_s"] = round(
            sum(d["seconds"] for d in el["downtimes"]), 3
        )
        # Restart-seconds reclaimed: each resize replaced one cold
        # restart.  With restarts in the SAME timeline the mean restart
        # gap (goodput restart bucket / count) is the in-run baseline;
        # without one the comparison lives in bench elastic_resize.
        el["restart_reclaimed_s"] = None
        g = out["goodput"]
        if g and g.get("restarts") and el["downtimes"]:
            mean_restart = g["buckets"].get("restart", 0.0) / g["restarts"]
            if mean_restart > 0:
                el["restart_reclaimed_s"] = round(sum(
                    max(0.0, mean_restart - d["seconds"])
                    for d in el["downtimes"]
                ), 3)

    # TTFT decomposition: rebuild the schema-v2 span trees and account
    # for every completed request's first-token latency (queue wait /
    # prefill / handoff / decode), with the gateable share headlines
    # and the lineage problems (orphan spans, multi-root traces).
    decomps = request_decompositions(records)
    if decomps:
        roll = ttft_rollup(decomps)
        roll["tiers"] = tier_rollups(decomps)
        roll["lineage_problems"] = check_lineage(records)
        out["ttft_decomposition"] = roll

    # Protocol conformance: replay the whole timeline against the
    # declared state machines (analysis.protocol) — PL405 per violation.
    out["conformance"] = [str(f) for f in check_timeline(records)]
    return out


def _quantile(sorted_vals: list, q: float):
    """Nearest-rank quantile over an already-sorted list (stdlib-only —
    this script must run without numpy)."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def render_markdown(a: dict, events_dir: str) -> str:
    lines = [f"# Run report — `{events_dir}`", ""]
    if not a["n_records"]:
        lines.append("No event records found — nothing ever wrote to this "
                     "directory.")
        return "\n".join(lines) + "\n"
    if not a["worker_procs"]:
        lines += [
            f"{a['n_records']} supervisor-only records — the gang died "
            "before any worker wrote events.",
            "",
        ]

    # -- Goodput ------------------------------------------------------
    lines += ["## Goodput", ""]
    g = a["goodput"]
    if g is None:
        lines.append("No worker run_start in the timeline — goodput "
                     "cannot be attributed.")
    else:
        lines += [
            f"**{_pct(g['goodput'])}** of {g['total_s']:.1f}s wall time "
            f"was productive ({g['restarts']} restart(s)).",
            "",
            "| bucket | seconds | share |",
            "|---|---:|---:|",
            f"| productive | {g['productive_s']:.2f} | "
            f"{_pct(g['goodput'])} |",
        ]
        for name, secs in g["buckets"].items():
            share = secs / g["total_s"] if g["total_s"] else None
            lines.append(f"| {name} | {secs:.2f} | {_pct(share)} |")
        if g["restarts"]:
            lines += ["", f"Incarnations ({len(g['incarnations'])}):", ""]
            for i, inc in enumerate(g["incarnations"]):
                lines.append(
                    f"- attempt {i}: {inc['total_s']:.1f}s, "
                    f"status `{inc['status']}`"
                )
    lines.append("")

    # -- MFU ----------------------------------------------------------
    lines += ["## MFU trend", ""]
    if not a["mfu"]:
        lines.append("No `mfu` events — run with `--mfu` to record the "
                     "cost-model utilization per throughput window.")
    else:
        lines += ["| step | MFU | HFU | model FLOP/s |", "|---:|---:|---:|---:|"]
        for m in a["mfu"]:
            lines.append(
                f"| {m['step']} | {_pct(m['mfu'])} | {_pct(m['hfu'])} | "
                f"{m['model_flops_per_s']:.3e} |"
            )
        vals = [m["mfu"] for m in a["mfu"] if m["mfu"] is not None]
        if vals:
            lines += [
                "",
                f"Mean MFU {_pct(sum(vals) / len(vals))} over "
                f"{len(vals)} window(s); last {_pct(vals[-1])}.",
            ]
    lines.append("")

    # -- Memory -------------------------------------------------------
    lines += ["## Memory high-water marks", ""]
    if not a["memory"]:
        lines.append("No `memory` events — run with `--memory-telemetry` "
                     "to sample live-array/device memory at window "
                     "boundaries.")
    else:
        lines += [
            "| rank | samples | live-array HWM | device peak |",
            "|---:|---:|---:|---:|",
        ]
        for proc, mem in sorted(a["memory"].items(), key=lambda kv: str(kv[0])):
            lines.append(
                f"| {proc} | {mem['samples']} | "
                f"{_fmt_bytes(mem['live_hwm_bytes'])} | "
                f"{_fmt_bytes(mem['device_peak_bytes'])} |"
            )
    for e in a["exec_memory"]:
        parts = [
            f"{k.replace('_bytes', '')} {_fmt_bytes(v)}"
            for k, v in e.items()
            if k.endswith("_bytes") and v is not None
        ]
        lines += [
            "",
            f"Compiler budget for `{e.get('label')}` (rank {e.get('proc')}): "
            + ", ".join(parts),
        ]
    lines.append("")

    # -- Stragglers ---------------------------------------------------
    lines += ["## Stragglers", ""]
    s = a["straggler"]
    if s is None:
        lines.append("No step spans in the timeline — nothing ran.")
    else:
        lines += [
            "| rank | steps | mean step | max step |",
            "|---:|---:|---:|---:|",
        ]
        for proc, st in sorted(s["ranks"].items(), key=lambda kv: str(kv[0])):
            lines.append(
                f"| {proc} | {st['steps']} | {st['mean_step_s'] * 1e3:.2f} ms"
                f" | {st['max_step_s'] * 1e3:.2f} ms |"
            )
        if s["n_ranks"] < 2:
            lines += ["", "Single-rank gang: cross-rank skew is undefined."]
        elif s["steps_compared"]:
            lines += [
                "",
                f"Across {s['steps_compared']} gang steps: mean skew "
                f"{s['skew_mean_s'] * 1e3:.2f} ms, max "
                f"{s['skew_max_s'] * 1e3:.2f} ms; slowest rank "
                f"**{s['slowest_rank']}** (last to finish "
                f"{s['slowest_counts'].get(s['slowest_rank'], 0)} times).",
                "",
                "| skew bucket | gang steps |",
                "|---|---:|",
            ]
            for label, count in s["skew_histogram"].items():
                lines.append(f"| {label} | {count} |")
    lines.append("")

    # -- Pipeline -----------------------------------------------------
    lines += ["## Pipeline", ""]
    pp = a["pipeline"]
    if pp is None:
        lines.append("No `pp_phase` events — not a pipeline-parallel run "
                     "(train with `--pp N --pp-schedule 1f1b|zb` to "
                     "record the schedule's phase counters).")
    else:
        meas = pp.get("measured_bubble_fraction")
        ana = pp.get("analytic_bubble_fraction")
        drift = (
            None if meas is None or ana is None else round(meas - ana, 4)
        )
        lines += [
            f"Schedule **{pp.get('schedule')}** on {pp.get('n_stages')} "
            f"stage(s), {pp.get('microbatches')} microbatch(es), "
            f"virtual {pp.get('virtual')}: measured bubble "
            f"{_pct(meas)} vs analytic {_pct(ana)}"
            + ("" if drift is None else f" (drift {drift:+.4f})")
            + ".",
            "",
            "| stage | " + " | ".join(PHASE_COLUMNS)
            + " | useful slots | bubble |",
            "|---:|" + "---:|" * (len(PHASE_COLUMNS) + 2),
        ]
        for st in pp.get("per_stage", []):
            cols = " | ".join(str(st.get(c, 0)) for c in PHASE_COLUMNS)
            lines.append(
                f"| {st.get('stage')} | {cols} | {st.get('useful_slots')}"
                f" | {_pct(st.get('bubble_fraction'))} |"
            )
        if meas is not None and ana is not None and abs(drift) > 1e-9:
            lines += ["", "Measured and analytic bubbles DISAGREE — the "
                          "compiled schedule did not execute the tick "
                          "table the factory accounted for."]
    lines.append("")

    # -- Restarts -----------------------------------------------------
    if a["restarts"]:
        lines += ["## Restarts", ""]
        for r in a["restarts"]:
            lines.append(
                f"- `{r['kind']}` attempt {r['attempt']} "
                f"(failed: {r['failed']})"
            )
        lines.append("")

    # -- Elasticity ---------------------------------------------------
    lines += ["## Elasticity", ""]
    el = a["elasticity"]
    if el is None:
        lines.append("No membership events — a fixed-size gang (run with "
                     "`--elastic` to resize the mesh around worker loss "
                     "instead of restarting).")
    else:
        lines += [
            f"**{el['n_resizes']} resize(s)** across "
            f"{len(el['epochs'])} membership epoch(s), "
            f"{el['resize_downtime_s']:.2f}s total resize downtime.",
            "",
            "| epoch | size | roster |",
            "|---:|---:|---|",
        ]
        for ep in el["epochs"]:
            roster = ", ".join(str(m) for m in ep["roster"]) or "—"
            lines.append(f"| {ep['epoch']} | {ep['size']} | {roster} |")
        if el["resizes"]:
            down = {d["epoch"]: d["seconds"] for d in el["downtimes"]}
            lines += [
                "",
                "| epoch | resize | left | joined | downtime |",
                "|---:|---|---|---|---:|",
            ]
            for rz in el["resizes"]:
                d = down.get(rz["epoch"])
                lines.append(
                    f"| {rz['epoch']} | {rz['old_size']} -> "
                    f"{rz['new_size']} | "
                    f"{', '.join(rz['left']) or '—'} | "
                    f"{', '.join(rz['joined']) or '—'} | "
                    f"{'-' if d is None else f'{d:.2f}s'} |"
                )
        if el.get("suspects"):
            # Several survivors may flag the same member; collapse to
            # one line per suspect with the worst observed age.
            worst: dict = {}
            for s in el["suspects"]:
                m = s.get("member")
                if m not in worst or (s.get("age_s") or 0) > (
                    worst[m].get("age_s") or 0
                ):
                    worst[m] = s
            lines += [""] + [
                f"- suspect `{m}` (heartbeat age "
                f"{worst[m].get('age_s'):.2f}s, epoch "
                f"{worst[m].get('epoch')}) — hysteresis window, not yet "
                "tombstoned"
                for m in sorted(worst)
            ]
        if el.get("rehosts"):
            lines += [""] + [
                f"- rendezvous store re-hosted at generation "
                f"{rh['generation']} on `{rh['owner']}`"
                for rh in el["rehosts"]
            ]
        if el.get("verdict"):
            v = el["verdict"]
            fault = v["fault"] or "no injected fault"
            lines += [
                "",
                f"**Verdict: `{v['rung']}` rung** "
                f"(degradation ladder: resize -> checkpoint restart -> "
                f"loud fail), attributed to {fault}.",
            ]
        if el["restart_reclaimed_s"] is not None:
            lines += [
                "",
                f"Restart-seconds reclaimed: **"
                f"{el['restart_reclaimed_s']:.2f}s** vs this run's own "
                "mean restart gap.",
            ]
        elif el["downtimes"]:
            lines += [
                "",
                "No cold restarts in this timeline to reclaim against.",
            ]
    lines.append("")

    # -- Integrity ----------------------------------------------------
    ig = a["integrity"]
    if ig is not None:
        lines += ["## Integrity", ""]
        lines.append(
            f"**{ig['checks']} digest check(s)**, "
            f"{len(ig['detects'])} mismatch(es), "
            f"{len(ig['evictions'])} eviction(s)."
        )
        if ig["detects"]:
            lines += [
                "",
                "| step | rank(s) | method | leaves |",
                "|---:|---|---|---|",
            ]
            for d in ig["detects"]:
                ranks = ", ".join(str(x) for x in d["ranks"]) or (
                    "transient" if d["rank"] == -1 else str(d["rank"])
                )
                lines.append(
                    f"| {d['step']} | {ranks} | {d['method']} | "
                    f"{', '.join(d['leaves']) or '—'} |"
                )
        if ig["evictions"]:
            ev = ", ".join(
                f"rank {e['rank']} @ step {e['step']}"
                for e in ig["evictions"]
            )
            lines += ["", f"Evicted via elastic resize: {ev}."]
        lines.append("")

    # -- Alerts -------------------------------------------------------
    lines += ["## Alerts", ""]
    if not a["alerts"]:
        if a["run_summary"] is not None:
            # run_summary proves the run is new enough to have alerting;
            # silence genuinely means nothing fired.
            lines.append("No alerts fired.")
        else:
            lines.append("No `alert` events — this run predates alerting "
                         "or ran without `--alerts`.")
    else:
        by_rule: dict[str, list[dict]] = {}
        for al in a["alerts"]:
            by_rule.setdefault(str(al["rule"]), []).append(al)
        lines += [
            f"**{len(a['alerts'])} alert(s)** across "
            f"{len(by_rule)} rule(s):",
            "",
            "| rule | count | first (step) | last (step) |",
            "|---|---:|---:|---:|",
        ]
        for rule, als in sorted(by_rule.items()):
            lines.append(
                f"| {rule} | {len(als)} | {als[0].get('step')} | "
                f"{als[-1].get('step')} |"
            )
    lines.append("")

    # -- Lint ---------------------------------------------------------
    lines += ["## Lint", ""]
    if not a["lint"]:
        lines.append("No `lint_report` events — run "
                     "`python scripts/ddplint.py --events-dir DIR` (or "
                     "`dpp.py --lint-step`) to record static-analysis "
                     "health next to the runtime telemetry.")
    else:
        total = sum(l["n_findings"] or 0 for l in a["lint"])
        verdict = "clean" if total == 0 else f"**{total} finding(s)**"
        lines += [
            f"Static analysis {verdict} across "
            f"{len(a['lint'])} layer(s):",
            "",
            "| layer | findings | rules |",
            "|---|---:|---|",
        ]
        for l in a["lint"]:
            rules = ", ".join(l["rules"] or []) or "—"
            lines.append(
                f"| {l['layer']} | {l['n_findings']} | {rules} |"
            )
        for l in a["lint"]:
            for f in l["findings"]:
                lines += ["", f"- `{f}`"]
    lines.append("")

    # -- Protocol -----------------------------------------------------
    lines += ["## Protocol", ""]
    conf = a.get("conformance") or []
    if not conf:
        lines.append(
            "Timeline conforms to the declared protocol specs "
            "(rendezvous membership, request lifecycle, handoff NAK "
            "budget — `analysis.protocol`): no PL405 violations."
        )
    else:
        lines += [
            f"**{len(conf)} PL405 violation(s)** — the recorded "
            "timeline contradicts the declared protocol state "
            "machines:",
            "",
        ]
        lines += [f"- `{f}`" for f in conf]
    lines.append("")

    # -- Serving ------------------------------------------------------
    lines += ["## Serving", ""]
    sv = a["serving"]
    if sv is None:
        lines.append("No serving events — a training-only run (serve "
                     "with `python scripts/ddp_serve.py --events-dir "
                     "DIR` to record the request lifecycle).")
    else:
        tok_s = "-" if sv["tok_s"] is None else f"{sv['tok_s']:.1f}"
        p50 = sv["ttft_p50_s"]
        p99 = sv["ttft_p99_s"]
        lines += [
            f"**{sv['completed']}/{sv['admitted']} requests completed**, "
            f"{sv['tokens_out']} tokens out at {tok_s} tok/s "
            f"(event-span clock).",
            "",
            "| metric | value |",
            "|---|---:|",
            f"| TTFT p50 | {'-' if p50 is None else f'{p50 * 1e3:.1f} ms'} |",
            f"| TTFT p99 | {'-' if p99 is None else f'{p99 * 1e3:.1f} ms'} |",
            f"| decode steps | {sv['decode_steps']} |",
            f"| mean active slots | {sv['mean_active']:.2f} |",
            f"| max active slots | {sv['active_max']} |",
            f"| prefill chunks | {sv['prefill_chunks']} |",
            f"| LRU evictions | {sv['evictions'].get('lru', 0)} |",
            f"| preempt evictions | {sv['evictions'].get('preempt', 0)} |",
            f"| blocks reclaimed | {sv['evicted_blocks']} |",
        ]
        if sv["prefix_hits"]:
            hit = sv["prefix_hit_frac"]
            avoided = sv["prefill_flops_avoided_frac"]
            lines += [
                f"| prefix-cache hits | {sv['prefix_hits']} "
                f"({'-' if hit is None else f'{hit:.0%}'} of admits) |",
                f"| prefill FLOPs avoided | "
                f"{'-' if avoided is None else f'{avoided:.0%}'} "
                f"({sv['prefix_hit_tokens']} cached ctx tokens) |",
            ]
        if sv["spec_dispatches"]:
            lines += [
                f"| spec-verify dispatches | {sv['spec_dispatches']} |",
                f"| spec tokens drafted / accepted | "
                f"{sv['spec_drafted']} / {sv['spec_accepted']} |",
                f"| mean accepted tokens per row | "
                f"{sv['spec_accept_mean']:.2f} |",
            ]
            hist = " ".join(
                f"{k}:{v}" for k, v in sv["accept_hist"].items()
            )
            lines += [
                "",
                f"Accept-length histogram (dispatch mean, tokens/row): "
                f"`{hist}`",
            ]
    lines.append("")

    # -- Serving fleet ------------------------------------------------
    fl = a["fleet"]
    if fl is not None:
        lines += ["## Serving fleet", ""]
        aff = fl["affinity_frac"]
        ho_mean = fl["handoff_s_mean"]
        ho_p99 = fl["handoff_s_p99"]
        lines += [
            f"**{fl['routed']} requests routed**, "
            f"{fl['affinity_hits']} session-affinity hits "
            f"({'-' if aff is None else f'{aff:.0%}'}), "
            f"{fl['handoffs']} prefill->decode KV handoffs "
            f"({fl['handoff_bytes']} bytes).",
            "",
            "| metric | value |",
            "|---|---:|",
            f"| handoff mean | "
            f"{'-' if ho_mean is None else f'{ho_mean * 1e3:.1f} ms'} |",
            f"| handoff p99 | "
            f"{'-' if ho_p99 is None else f'{ho_p99 * 1e3:.1f} ms'} |",
            f"| re-delivered handoffs | {fl['redelivered']} |",
            f"| router queue depth max | {fl['queue_depth_max']} |",
        ]
        for tier in sorted(fl["tiers"]):
            t = fl["tiers"][tier]
            p50 = t.get("p50_ttft_s")
            p99 = t.get("p99_ttft_s")
            lines.append(
                f"| {tier} tier | {t.get('completed', 0)} done, "
                f"TTFT p50 "
                f"{'-' if p50 is None else f'{p50 * 1e3:.1f} ms'} / p99 "
                f"{'-' if p99 is None else f'{p99 * 1e3:.1f} ms'} |"
            )
        for v in fl["verdicts"]:
            lines.append(
                f"| engine verdict | `{v.get('engine')}` -> "
                f"**{v.get('rung')}** ({v.get('tier')} tier, "
                f"{v.get('requeued', 0)} requeued, "
                f"{v.get('reason')}) |"
            )
        lines.append("")

    # -- TTFT decomposition -------------------------------------------
    td = a["ttft_decomposition"]
    if td is not None:
        lines += ["## TTFT decomposition", ""]
        err = td.get("ttft_decomp_err_frac")
        lines += [
            f"**{td['requests']} traced request(s)** — span-tree "
            "accounting of each first-token latency "
            f"(worst self-consistency error "
            f"{'-' if err is None else f'{err:.1%}'}; gate ≤ 5%).",
            "",
            "| segment | share of TTFT | p50 | p99 |",
            "|---|---:|---:|---:|",
        ]
        for seg in ("queue", "prefill", "handoff", "decode"):
            share = td.get(f"ttft_{seg}_share_frac")
            p50 = td.get(f"{seg}_p50_s")
            p99 = td.get(f"{seg}_p99_s")
            lines.append(
                f"| {seg} | {'-' if share is None else f'{share:.1%}'} | "
                f"{'-' if p50 is None else f'{p50 * 1e3:.1f} ms'} | "
                f"{'-' if p99 is None else f'{p99 * 1e3:.1f} ms'} |"
            )
        for tier, roll in sorted((td.get("tiers") or {}).items()):
            if not roll.get("requests"):
                continue
            q = roll.get("ttft_queue_share_frac")
            lines.append(
                f"| {tier}-tier rollup | {roll['requests']} request(s), "
                f"queue share {'-' if q is None else f'{q:.1%}'} | | |"
            )
        problems = td.get("lineage_problems") or []
        if problems:
            lines += [""] + [
                f"- **lineage problem**: {p}" for p in problems[:5]
            ]
        lines.append("")

    # -- Tuning -------------------------------------------------------
    lines += ["## Tuning", ""]
    tu = a["tuning"]
    if tu is None:
        lines.append("No tune_* events — search with `dpp.py --autotune "
                     "search` (or `python scripts/ddp_tune.py search "
                     "--events-dir DIR`) to record trials here.")
    else:
        res = tu["result"]
        if res:
            gain = res.get("gain_frac")
            lines += [
                f"**autotune {res.get('mode')}**: winner "
                f"`{res.get('winner')}`"
                + (f", gain {gain * 100:+.1f}% vs baseline"
                   if isinstance(gain, (int, float)) else "")
                + ("" if res.get("applied") in (None, True)
                   else " — **NOT applied** (key mismatch, ran with CLI "
                        "defaults)")
                + ".",
                "",
            ]
        if tu["trials"]:
            lines += [
                "| trial | status | predicted | measured | drift | "
                "warm |",
                "|---|---|---:|---:|---:|---|",
            ]
            fmt = lambda v: (  # noqa: E731
                "-" if not isinstance(v, (int, float))
                else f"{v * 1e3:.1f} ms"
            )
            for t in tu["trials"]:
                d = t.get("drift_frac")
                lines.append(
                    f"| `{t['trial']}` | {t['status']} "
                    f"| {fmt(t.get('predicted_step_s'))} "
                    f"| {fmt(t.get('measured_step_s'))} "
                    f"| {'-' if not isinstance(d, (int, float)) else f'{d * 100:+.0f}%'} "
                    f"| {t.get('warm_mode') or '-'} |"
                )
            drifts = tu["drift_fracs"]
            if drifts:
                # the search doubles as a cost-model calibration probe:
                # consistent positive drift = the efficiency constant is
                # too optimistic for this backend, not a tuner bug
                mean = sum(drifts) / len(drifts)
                worst = max(drifts, key=abs)
                lines += [
                    "",
                    f"Cost-model drift over {len(drifts)} measured "
                    f"trial(s): mean {mean * 100:+.0f}%, worst "
                    f"{worst * 100:+.0f}% "
                    "((measured - predicted) / predicted).",
                ]
    lines.append("")

    # -- Run summary + trace ------------------------------------------
    rs = a["run_summary"]
    if rs:
        lines += ["## Run summary", ""]
        shown = ("windows", "steps_total", "mfu_mean", "step_s_p50",
                 "step_s_p99", "live_hwm_bytes", "goodput", "restarts",
                 "alerts_total", "status")
        parts = [f"{k} `{rs[k]}`" for k in shown if rs.get(k) is not None]
        lines += [", ".join(parts) + ".", "",
                  "Gate this run against a baseline with "
                  f"`python scripts/perf_gate.py {events_dir} "
                  "--store RUNS_DIR --baseline NAME`.", ""]
    lines += [
        "## Trace",
        "",
        "Export this timeline for https://ui.perfetto.dev with "
        f"`python scripts/ddp_trace.py {events_dir}` "
        "(per-rank tracks, mfu/step_s/memory counters, "
        "restart/nan/alert marks).",
        "",
    ]
    return "\n".join(lines) + "\n"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("events_dir", help="directory holding events-*.jsonl / "
                                       "timeline.jsonl")
    ap.add_argument("--json", action="store_true",
                    help="emit the analysis as JSON instead of markdown")
    ap.add_argument("-o", "--out", default=None,
                    help="write the report here instead of stdout")
    args = ap.parse_args(argv)

    if not os.path.isdir(args.events_dir):
        print(f"ddp_report: no such directory: {args.events_dir}",
              file=sys.stderr)
        return 1
    records = load_timeline(args.events_dir)
    analysis = analyze(records)
    text = (
        json.dumps(analysis, indent=2) + "\n" if args.json
        else render_markdown(analysis, args.events_dir)
    )
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text)
    else:
        sys.stdout.write(text)
    return 0 if records else 1


if __name__ == "__main__":
    raise SystemExit(main())
