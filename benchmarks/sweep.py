#!/usr/bin/env python3
"""Find the highest arrival rate a serve cell sustains, once, on the chip:

    python3 benchmarks/sweep.py --workload <cell> --rates 3,4,5,6,7,8 \
        [--seconds 20] [--seed 7]

One engine, one process; for each rate the cell's mix is offered for
``--seconds`` at that rate and the run's numbers are printed, one JSON
line a rate.  Below the knee the tokens completed keep up with the tokens
offered and nothing waits at the close; above it the queue grows all
through the window.  The cell's rate (0.8 x knee) is then written into
its mix file by hand.  The benchmark's own runs never search for a rate.
"""

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    from benchmarks import harness, traffic

    cell = harness.load_cell(args.workload)
    harness.place_compile_cache()
    devices = harness.acquire_devices(cell["chips"])
    kind = harness.load_module("kinds", cell["traffic"]["kind"])
    env = {
        "cell": cell, "config": cell["config"], "traffic": cell["traffic"],
        "devices": devices, "seed": args.seed, "root": harness.ROOT,
        "spans": harness.Spans(), "window_s": args.seconds,
    }
    session = kind.setup(env)
    for rate in (float(r) for r in args.rates.split(",")):
        requests = dict(cell["traffic"]["requests"], rate_rps=rate)
        session.trace = traffic.make_trace(
            requests, args.seed, args.seconds, session.cfg.vocab_size
        )
        m = session.measure(args.seconds)
        session.engine.completed.clear()
        ttft = sorted(
            1e3 * (r.first_token_s - r.arrival_s)
            for r in session.requests if r.first_token_s is not None
        )
        offered = sum(r["max_new_tokens"] for r in session.trace)
        print(json.dumps({
            "rate_rps": rate, "due": m["attempted"],
            "submitted": m["submitted"],
            "missing_first_token_at_close": m["missing_first_token"],
            "offered_tokens_s": offered / args.seconds,
            "waiting_at_close": m["attempted"] - len(m["queue_wait_ms"]),
            **m["end_to_end"],
            "ttft_p50_ms_all": statistics.median(ttft) if ttft else None,
            "queue_wait_p50_ms": statistics.median(m["queue_wait_ms"])
            if m["queue_wait_ms"] else None,
            "queue_wait_max_ms": max(m["queue_wait_ms"], default=None),
            "occupancy_mean": statistics.fmean(m["occupancy"])
            if m["occupancy"] else None,
            "steps": len(m["occupancy"]), "window_s": m["window_s"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
