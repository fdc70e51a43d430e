#!/usr/bin/env python
"""Serving CLI: continuous-batching engine under Poisson open-loop load.

Usage:
    python scripts/ddp_serve.py --model tiny --rate 20 --duration 2 \
        --events-dir runs/serve
    python scripts/ddp_serve.py --smoke          # CI: tiny burst, asserts
    python scripts/ddp_serve.py --device tpu --model gpt2_124m \
        --seq-len 1024 --slots 8 --blocks 512 --chunk 128 --rate 4 \
        --duration 5 --prompt-len 64,512 --output-len 16,64

``--device tpu|cpu`` is required, not preferred (same helper as
``dpp.py``); ``auto`` takes what JAX finds.  Builds the model with
randomly-initialized params (the traffic is synthetic token ids —
serving-path performance and correctness do not depend on trained
weights), wires the engine to an events dir + metrics registry, replays
a seeded loadgen trace, and prints the serving summary as one JSON line
naming the device it ran on.  On the real clock the programs are
compiled before the first arrival and the time reported as
``compile_s``.  The events dir afterwards holds a mergeable
timeline that ``ddp_trace.py`` exports to Perfetto (request spans,
active-slot counter) and ``ddp_report.py`` renders with its Serving
section.

``--smoke`` is the CI gate: tiny model, ~2s virtual burst, asserting
at least one completed request and a structurally valid trace export.
``--virtual-dt`` makes any run deterministic (the clock advances per
engine step instead of reading the host clock).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--device", choices=("tpu", "cpu", "auto"),
                    default="auto",
                    help="backend selector, as in dpp.py: a named device "
                         "is required, not preferred")
    ap.add_argument("--model", default="tiny",
                    choices=("tiny", "gpt2_124m"))
    ap.add_argument("--seq-len", type=int, default=None,
                    help="override max_seq_len (default: model's)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk tokens")
    ap.add_argument("--max-prefill-chunks", type=int, default=1)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--duration", type=float, default=2.0)
    ap.add_argument("--prompt-len", default="4,24",
                    help="uniform prompt length range 'lo,hi'")
    ap.add_argument("--output-len", default="4,16",
                    help="uniform output length range 'lo,hi'")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quantize-kv", action="store_true")
    ap.add_argument("--quantize-weights", action="store_true")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache: share KV blocks across "
                         "requests with a common prompt prefix")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: n-gram-draft k tokens "
                         "per step through the verify program (0 = off)")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="longest n-gram the self-draft proposer matches")
    ap.add_argument("--prefix-pool", type=int, default=0,
                    help="shared-prefix trace mode: pool of N fixed "
                         "prefixes sampled with Zipf rank weights")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="tokens per pooled shared prefix")
    ap.add_argument("--zipf-alpha", type=float, default=1.1,
                    help="Zipf exponent over the prefix pool ranks")
    ap.add_argument("--turns", type=int, default=1,
                    help="multi-turn sessions: each base request seeds "
                         "a session with N turns (follow-up prompts "
                         "extend the prior turn's)")
    ap.add_argument("--turn-gap", type=float, default=0.25,
                    help="mean seconds between a session's turns")
    ap.add_argument("--fleet", default=None, metavar="P:D",
                    help="disaggregated fleet: P prefill + D decode "
                         "engine processes behind the session-affinity "
                         "router, KV handoff over TCP")
    ap.add_argument("--kill-engine", default=None, metavar="NAME|auto",
                    help="fleet fault injection: terminate this engine "
                         "worker mid-run ('auto' picks a decode engine)")
    ap.add_argument("--kill-after", type=float, default=None,
                    help="seconds into the drive to kill (default: 60%% "
                         "through the arrival trace)")
    ap.add_argument("--events-dir", default=None)
    ap.add_argument("--store", default=None,
                    help="ExecutableStore dir (warm-start AOT reuse)")
    ap.add_argument("--virtual-dt", type=float, default=None,
                    help="deterministic mode: seconds per engine step")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny burst + trace validity asserts")
    return ap


def _range(spec: str) -> tuple[int, int]:
    lo, hi = (int(x) for x in spec.split(","))
    return lo, hi


def _run_fleet(args) -> int:
    """``--fleet P:D``: spawn the disaggregated tiers as worker
    processes under the launcher, drive a (multi-turn) loadgen trace
    through the router, and — under ``--smoke`` — assert the fleet
    contract: every request completes (zero dropped, even through an
    injected engine kill), at least one KV handoff crossed tiers, at
    least one follow-up was affinity-routed, the merged timeline stays
    schema- and trace-valid, every span tree is lineage-clean across
    the three process boundaries (router, prefill, decode), every
    completed request's TTFT decomposition reproduces its measured
    TTFT within 5%, and the mid-run /metrics scrape of every live
    process parsed and carried the required series."""
    from distributeddataparallel_tpu.models.transformer import (
        gpt2_124m,
        tiny_lm,
    )
    from distributeddataparallel_tpu.serving import (
        EngineConfig,
        LoadConfig,
        make_trace,
    )
    from distributeddataparallel_tpu.serving.fleet import (
        FleetConfig,
        FleetService,
    )

    try:
        n_prefill, n_decode = (int(x) for x in args.fleet.split(":"))
    except ValueError:
        print(f"ddp_serve: bad --fleet {args.fleet!r} (want P:D)",
              file=sys.stderr)
        return 1

    if args.smoke:
        args.model = "tiny"
        args.duration = min(args.duration, 1.5)
        args.rate = min(args.rate, 6.0)
        args.turns = max(args.turns, 2)
        # Affinity keys hash the first KV block: keep prompts at least
        # one block long so a follow-up's key matches its base turn's.
        args.prompt_len = "20,40"
        args.output_len = "6,12"
        if args.kill_engine is None:
            args.kill_engine = "auto"

    vocab = (gpt2_124m() if args.model == "gpt2_124m"
             else tiny_lm()).vocab_size
    trace = make_trace(LoadConfig(
        rate_rps=args.rate,
        duration_s=args.duration,
        prompt_len=_range(args.prompt_len),
        output_len=_range(args.output_len),
        vocab_size=vocab,
        seed=args.seed,
        prefix_pool=args.prefix_pool,
        prefix_len=args.prefix_len,
        zipf_alpha=args.zipf_alpha,
        turns=args.turns,
        turn_gap_s=args.turn_gap,
    ))
    kill_after = None
    kill_name = None
    if args.kill_engine:
        last_arrival = trace[-1]["arrival_s"] if trace else 0.0
        kill_after = (args.kill_after if args.kill_after is not None
                      else 0.6 * last_arrival)
        kill_name = (None if args.kill_engine == "auto"
                     else args.kill_engine)
    svc = FleetService(
        model=args.model,
        seq_len=args.seq_len,
        seed=args.seed,
        engine_config=EngineConfig(
            num_slots=args.slots,
            num_blocks=args.blocks,
            block_size=args.block_size,
            prefill_chunk=args.chunk,
            max_prefill_chunks_per_step=args.max_prefill_chunks,
            quantized_kv=args.quantize_kv,
            quantize_weights=args.quantize_weights,
            store_dir=args.store,
            # Affinity hits only pay off if the home decode engine's
            # prefix cache actually holds the session's blocks.
            prefix_cache=True,
            spec_k=args.spec_k,
            spec_ngram=args.spec_ngram,
        ),
        fleet_config=FleetConfig(prefill=n_prefill, decode=n_decode),
        events_dir=args.events_dir,
        kill_after_s=kill_after,
        kill_engine=kill_name,
    )
    out = svc.run(trace)
    out["fleet"] = f"{n_prefill}:{n_decode}"
    print(json.dumps(out, sort_keys=True, default=str))

    if not args.smoke:
        return 0
    failures = []
    if out["completed"] < len(trace):
        failures.append(
            f"fleet smoke: only {out['completed']}/{len(trace)} "
            "requests completed"
        )
    if out["dropped_req_total"] != 0:
        failures.append(
            f"fleet smoke: {out['dropped_req_total']} dropped requests "
            "(engine-kill drain must requeue, not lose)"
        )
    if out["handoffs"] < 1:
        failures.append("fleet smoke: no prefill->decode KV handoff")
    if args.kill_engine and out["kills"] < 1:
        failures.append("fleet smoke: engine kill did not fire")
    if args.events_dir:
        from distributeddataparallel_tpu.observability.events import (
            load_timeline,
        )
        from distributeddataparallel_tpu.observability.schema import (
            validate_file,
        )
        from distributeddataparallel_tpu.observability.trace_export import (
            to_trace_events,
            validate_trace,
        )

        problems = validate_file(
            os.path.join(args.events_dir, "timeline.jsonl")
        )
        failures.extend(problems[:5])
        records = load_timeline(args.events_dir)
        failures.extend(validate_trace(to_trace_events(records))[:5])
        kinds = {r.get("kind") for r in records}
        needed = ["route_admit", "kv_handoff", "tier_summary"]
        if args.kill_engine:
            needed.append("engine_verdict")
        for kind in needed:
            if kind not in kinds:
                failures.append(f"fleet smoke: no {kind} event")
        if not any(r.get("kind") == "route_admit" and r.get("affinity")
                   for r in records):
            failures.append(
                "fleet smoke: no affinity-routed follow-up turn"
            )
        # Distributed tracing: span trees must survive three process
        # boundaries (router -> prefill -> decode) with zero orphans,
        # and each request's critical-path decomposition must account
        # for its measured TTFT.
        from distributeddataparallel_tpu.observability.critical_path import (
            check_lineage,
            request_decompositions,
            ttft_rollup,
        )

        failures.extend(
            f"fleet smoke: {p}" for p in check_lineage(records)[:5]
        )
        decomps = request_decompositions(records)
        if len(decomps) < out["completed"]:
            failures.append(
                f"fleet smoke: TTFT decomposition covers only "
                f"{len(decomps)}/{out['completed']} completed requests"
            )
        bad = [d for d in decomps if d["err_frac"] > 0.05]
        if bad:
            failures.append(
                f"fleet smoke: {len(bad)} request(s) decompose to "
                "more than 5% off their measured TTFT (worst "
                f"{max(d['err_frac'] for d in bad):.1%}, "
                f"req {max(bad, key=lambda d: d['err_frac'])['req']})"
            )
        out["ttft_decomp"] = ttft_rollup(decomps)
    # Live /metrics plane: the service scraped every live endpoint
    # mid-run (at the first completion, while requests were still
    # outstanding); each payload must have parsed and carried the
    # series the monitor renders.
    scraped = out.get("metrics_scrape") or {}
    router_series = scraped.get("router")
    if not isinstance(router_series, dict) or "_error" in router_series:
        failures.append(
            "fleet smoke: router /metrics scrape failed "
            f"({(router_series or {}).get('_error', 'never scraped')})"
        )
    else:
        for name in ("router_queue_depth",
                     "fleet_prefill_p50_ttft_s",
                     "fleet_prefill_p99_ttft_s",
                     "fleet_decode_p50_ttft_s",
                     "fleet_decode_p99_ttft_s"):
            if name not in router_series:
                failures.append(
                    f"fleet smoke: router /metrics missing {name}"
                )
    workers = {k: v for k, v in scraped.items() if k != "router"}
    if not workers:
        failures.append("fleet smoke: no engine /metrics endpoint scraped")
    for wname, series in sorted(workers.items()):
        if not isinstance(series, dict) or "_error" in series:
            failures.append(
                f"fleet smoke: engine {wname} /metrics scrape failed "
                f"({(series or {}).get('_error', 'bad payload')})"
            )
        elif "serve_tok_s" not in series:
            failures.append(
                f"fleet smoke: engine {wname} /metrics missing "
                "serve_tok_s"
            )
    if failures:
        print("SMOKE FAIL:\n  " + "\n  ".join(failures), file=sys.stderr)
        return 1
    roll = out.get("ttft_decomp") or {}
    decomp_note = (
        f", ttft queue_share={roll['ttft_queue_share_frac']:.2f} "
        f"decomp_err={roll['ttft_decomp_err_frac']:.3f} "
        f"over {roll['requests']} traced request(s)"
        if roll.get("requests") else ""
    )
    print("fleet smoke OK: "
          f"{out['completed']}/{len(trace)} requests, "
          f"{out['handoffs']} handoffs, {out['requeued']} requeued "
          f"through {out['kills']} kill(s), "
          f"p99_ttft={out.get('serve_p99_ttft_s', 0):.3f}s"
          f"{decomp_note}")
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.fleet and args.device == "tpu":
        # Fleet workers are separate OS processes pinned to the CPU
        # (serving.fleet): a chip belongs to one process, so P+D workers
        # cannot share it, and one-chip-per-replica fleets are not built.
        raise SystemExit(
            "--fleet with --device tpu: fleet workers are CPU processes "
            "(one chip cannot be shared by several processes); run one "
            "engine with --device tpu, or the fleet with --device cpu|auto"
        )
    from distributeddataparallel_tpu.training.warm_start import (
        resolve_compile_cache,
    )

    resolve_compile_cache()
    if args.fleet:
        return _run_fleet(args)

    from distributeddataparallel_tpu.runtime.distributed import (
        device_summary,
        select_device,
    )

    select_device(args.device)
    device = device_summary(args.device)

    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddataparallel_tpu.models import TransformerLM
    from distributeddataparallel_tpu.models.transformer import (
        gpt2_124m,
        tiny_lm,
    )
    from distributeddataparallel_tpu.observability.events import (
        EventLog,
        events_path,
        merge_timeline,
    )
    from distributeddataparallel_tpu.observability.memory import (
        device_memory_stats,
    )
    from distributeddataparallel_tpu.observability.registry import (
        MetricsRegistry,
    )
    from distributeddataparallel_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        LoadConfig,
        VirtualClock,
        kv_pool_bytes,
        make_trace,
        run_load,
    )

    if args.smoke:
        args.model = "tiny"
        args.virtual_dt = args.virtual_dt or 0.005
        args.duration = min(args.duration, 2.0)

    if args.model == "gpt2_124m":
        cfg = gpt2_124m(max_seq_len=args.seq_len or 256,
                        dtype=jnp.bfloat16)
    else:
        cfg = tiny_lm(max_seq_len=args.seq_len or 128)
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(args.seed),
        jnp.zeros((1, 4), jnp.int32),
    )["params"]

    events = None
    if args.events_dir:
        os.makedirs(args.events_dir, exist_ok=True)
        events = EventLog(events_path(args.events_dir, 0), 0)
        events.emit(
            "run_start", argv=sys.argv[1:], role="serve",
            devices=device["count"], platform=device["platform"],
            device_kind=device["kind"],
        )
    registry = MetricsRegistry()

    clock = VirtualClock(args.virtual_dt) if args.virtual_dt else None
    ecfg = EngineConfig(
        num_slots=args.slots,
        num_blocks=args.blocks,
        block_size=args.block_size,
        prefill_chunk=args.chunk,
        max_prefill_chunks_per_step=args.max_prefill_chunks,
        quantized_kv=args.quantize_kv,
        quantize_weights=args.quantize_weights,
        store_dir=args.store,
        prefix_cache=args.prefix_cache,
        spec_k=args.spec_k,
        spec_ngram=args.spec_ngram,
    )
    engine = InferenceEngine(
        model, params, ecfg, events=events, registry=registry,
        **({"time_fn": clock} if clock else {}),
    )
    trace = make_trace(LoadConfig(
        rate_rps=args.rate,
        duration_s=args.duration,
        prompt_len=_range(args.prompt_len),
        output_len=_range(args.output_len),
        vocab_size=cfg.vocab_size,
        seed=args.seed,
        prefix_pool=args.prefix_pool,
        prefix_len=args.prefix_len,
        zipf_alpha=args.zipf_alpha,
        turns=args.turns,
        turn_gap_s=args.turn_gap,
    ))
    compile_s = None
    if clock is None:
        # Real clock: compile the programs before the first arrival, so
        # request latencies are the server's and not the compiler's
        # (under --virtual-dt compile time never reaches the clock).
        t0 = time.perf_counter()
        engine.submit(np.arange(4, dtype=np.int32) % cfg.vocab_size, 4)
        engine.run()
        engine.completed.clear()
        compile_s = round(time.perf_counter() - t0, 3)
    out = run_load(engine, trace, clock=clock)
    out["requests"] = len(trace)
    out["device"] = device
    out["model_dtype"] = jnp.dtype(cfg.dtype).name
    out["compile_s"] = compile_s
    out["compile_cache"] = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    mem = device_memory_stats()
    if mem:
        out["device_peak_bytes_each"] = [
            m["peak_bytes_in_use"] for m in mem
        ]
    out["kv_pool_bytes"] = kv_pool_bytes(
        cfg, args.blocks, args.block_size, quantized_kv=args.quantize_kv
    )
    if getattr(engine, "warm_report", None):
        out["warm_start"] = engine.warm_report

    if events is not None:
        events.emit("metrics", snapshot=registry.snapshot())
        events.emit("run_end", status="ok")
        events.close()
        merge_timeline(args.events_dir)

    print(json.dumps(out, sort_keys=True, default=str))

    if args.smoke:
        from distributeddataparallel_tpu.observability.trace_export import (
            to_trace_events,
            validate_trace,
        )

        failures = []
        if out["completed"] < 1:
            failures.append("smoke: no request completed")
        if args.events_dir:
            from distributeddataparallel_tpu.observability.events import (
                load_timeline,
            )
            from distributeddataparallel_tpu.observability.schema import (
                validate_file,
            )

            problems = validate_file(
                os.path.join(args.events_dir, "timeline.jsonl")
            )
            failures.extend(problems[:5])
            records = load_timeline(args.events_dir)
            trace_problems = validate_trace(to_trace_events(records))
            failures.extend(trace_problems[:5])
            kinds = {r.get("kind") for r in records}
            for needed in ("request_admit", "decode_step",
                           "request_done"):
                if needed not in kinds:
                    failures.append(f"smoke: no {needed} event")
            # Standalone engine derives its own root span per request;
            # the resulting trees must still be lineage-clean.
            from distributeddataparallel_tpu.observability.critical_path import (  # noqa: E501
                check_lineage,
            )

            failures.extend(
                f"smoke: {p}" for p in check_lineage(records)[:5]
            )

        # Phase 2: the serving fast path — prefix cache + speculative
        # decoding on a shared-prefix Zipf trace.  Gates that the radix
        # cache actually hits, the verifier actually accepts drafts,
        # and that the new prefix_hit / spec_verify kinds keep the
        # timeline and the Perfetto export schema-valid.
        fp_dir = None
        fp_events = None
        if args.events_dir:
            fp_dir = os.path.join(args.events_dir, "fastpath")
            os.makedirs(fp_dir, exist_ok=True)
            fp_events = EventLog(events_path(fp_dir, 0), 0)
            fp_events.emit("run_start", argv=["--smoke", "fastpath"],
                           role="serve")
        fp_clock = VirtualClock(args.virtual_dt)
        fp_engine = InferenceEngine(
            model, params,
            EngineConfig(
                num_slots=args.slots,
                num_blocks=args.blocks,
                block_size=args.block_size,
                prefill_chunk=args.chunk,
                max_prefill_chunks_per_step=args.max_prefill_chunks,
                quantized_kv=args.quantize_kv,
                quantize_weights=args.quantize_weights,
                store_dir=args.store,
                prefix_cache=True,
                spec_k=max(args.spec_k, 3),
                spec_ngram=args.spec_ngram,
            ),
            events=fp_events, time_fn=fp_clock,
        )
        fp_trace = make_trace(LoadConfig(
            rate_rps=24.0,
            duration_s=args.duration,
            prompt_len=(56, 72),
            output_len=(8, 16),
            vocab_size=cfg.vocab_size,
            seed=args.seed,
            prefix_pool=4,
            prefix_len=48,
            zipf_alpha=args.zipf_alpha,
        ))
        fp_out = run_load(fp_engine, fp_trace, clock=fp_clock)
        if fp_events is not None:
            fp_events.emit("run_end", status="ok")
            fp_events.close()
            merge_timeline(fp_dir)
        if fp_out["completed"] < len(fp_trace):
            failures.append(
                "smoke fastpath: only "
                f"{fp_out['completed']}/{len(fp_trace)} completed"
            )
        if fp_engine.prefix_hits < 1:
            failures.append("smoke fastpath: no prefix-cache hit")
        accept_mean = fp_out.get("spec_accept_mean", 0.0)
        if accept_mean <= 1.0:
            failures.append(
                "smoke fastpath: spec_accept_mean "
                f"{accept_mean:.2f} <= 1 (speculation not landing)"
            )
        if fp_dir is not None:
            problems = validate_file(
                os.path.join(fp_dir, "timeline.jsonl")
            )
            failures.extend(problems[:5])
            records = load_timeline(fp_dir)
            trace_problems = validate_trace(to_trace_events(records))
            failures.extend(trace_problems[:5])
            kinds = {r.get("kind") for r in records}
            for needed in ("prefix_hit", "spec_verify"):
                if needed not in kinds:
                    failures.append(f"smoke fastpath: no {needed} event")

        if failures:
            print("SMOKE FAIL:\n  " + "\n  ".join(failures),
                  file=sys.stderr)
            return 1
        print("serving smoke OK: "
              f"{out['completed']}/{out['requests']} requests, "
              f"{out.get('serve_tok_s', 0):.1f} tok/s; fastpath "
              f"hit_frac={fp_out.get('prefix_hit_frac', 0):.2f} "
              f"accept_mean={accept_mean:.2f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
