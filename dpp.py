#!/usr/bin/env python
"""Single-entrypoint data-parallel trainer — the reference `dpp.py`, TPU-native.

Usage (mirrors `python dpp.py` of the reference, ref dpp.py:60-65, plus the
flags SURVEY.md §5 notes the reference hard-codes):

    python dpp.py                              # toy CNN on synthetic data
    python dpp.py --model resnet18 --dataset cifar10 --device tpu
    python dpp.py --device cpu --fake-devices 8   # 8-way DP on one CPU

Structure intentionally parallels the reference script:
  setup()  -> runtime.init_process_group + mesh        (ref dpp.py:20-21)
  train()  -> build data/model/loss/optimizer, loop    (ref dpp.py:27-57)
  main()   -> device selection + launch                (ref dpp.py:60-62)

Differences by design (SURVEY.md §2d): self-contained init (no
MASTER_ADDR/PORT), no download race, multi-host capable, checkpoint/resume
and eval available, logging off the hot path.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def _dataset_arg(v: str) -> str:
    """Parse-time --dataset validation (argparse choices can't express the
    shards:DIR / tokens:FILE forms): typos fail at parse for CLI and
    programmatic train(parse_args([...])) callers alike, instead of
    falling through to the CIFAR-10 default in build_dataset."""
    if v in ("synthetic", "cifar10", "synthetic-lm") or v.startswith(
        ("shards:", "tokens:")
    ):
        return v
    raise argparse.ArgumentTypeError(
        f"{v!r} is not one of synthetic | cifar10 | synthetic-lm | "
        "shards:DIR | tokens:FILE"
    )


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--device", choices=["tpu", "cpu", "cuda", "auto"], default="auto",
                   help="backend selector (north-star --device flag)")
    p.add_argument("--fake-devices", type=int, default=0,
                   help="force N host-platform devices (CPU DP simulation)")
    p.add_argument("--model", default="cnn",
                   choices=["mlp", "cnn", "resnet18", "resnet50", "gpt2", "llama"],
                   help="model family (resnet18 matches the reference)")
    p.add_argument("--dataset", default=None, type=_dataset_arg,
                   help="one of synthetic | cifar10 | synthetic-lm | "
                        "shards:DIR (streaming memmapped image shards, "
                        "ImageNet-scale path; DIR or DIR/{train,val}) | "
                        "tokens:FILE (memmapped real-token LM corpus, "
                        ".npy stream or rows; eval reads the sibling val "
                        "split); default: synthetic-lm for gpt2/llama, "
                        "synthetic otherwise")
    p.add_argument("--seq-len", type=int, default=128,
                   help="LM sequence length")
    p.add_argument("--token-stride", type=int, default=None,
                   help="window-start spacing for tokens:FILE flat streams "
                        "(< seq-len overlaps windows; default seq-len). "
                        "Train split only — eval keeps non-overlapping "
                        "windows so its mean is over distinct text")
    p.add_argument("--dropout", type=float, default=0.0,
                   help="LM residual/embedding dropout rate (GPT-2 style). "
                        "Trains under DP/ZeRO/TP/EP/CP incl. scanned+remat "
                        "stacks (per-layer rngs split through the scan); "
                        "--fsdp/--pp reject it")
    p.add_argument("--vocab-size", type=int, default=256,
                   help="LM vocab size (synthetic data; real data overrides)")
    p.add_argument("--layers", type=int, default=None,
                   help="override the model family's layer count")
    p.add_argument("--d-model", type=int, default=None,
                   help="override the model family's width")
    p.add_argument("--data-root", default="data")
    p.add_argument("--pretrained", default=None, metavar="FILE",
                   help="initialize from a pretrained checkpoint before "
                        "training (ref dpp.py:14's pretrained=True analog): "
                        "torchvision ResNet state_dict, HF GPT-2 or Llama "
                        "tensors (.safetensors or torch .pth), or this "
                        "framework's save_params safetensors — the format "
                        "is sniffed from the key names")
    p.add_argument("--epochs", type=int, default=5)          # ref dpp.py:27
    p.add_argument("--batch-size", type=int, default=32,     # ref dpp.py:35
                   help="per-replica batch (global = batch × replicas)")
    p.add_argument("--lr", type=float, default=0.01)         # ref dpp.py:41
    p.add_argument("--momentum", type=float, default=0.0)
    p.add_argument("--optimizer", choices=["sgd", "adam", "adamw"],
                   default="sgd",
                   help="sgd mirrors the reference (ref dpp.py:41); "
                        "adam/adamw for the LM configs")
    p.add_argument("--weight-decay", type=float, default=0.0,
                   help="decoupled weight decay (adamw; ignored otherwise)")
    p.add_argument("--lr-schedule", choices=["constant", "cosine", "linear"],
                   default="constant",
                   help="learning-rate schedule over the whole run "
                        "(optional --warmup-steps linear warmup first)")
    p.add_argument("--warmup-steps", type=int, default=0,
                   help="linear LR warmup steps before the schedule")
    p.add_argument("--min-lr", type=float, default=0.0,
                   help="floor the cosine/linear decay at this LR")
    p.add_argument("--grad-clip", type=float, default=None,
                   help="clip the synced gradient to this global L2 norm "
                        "(torch clip_grad_norm_ analog; axis-aware exact "
                        "norm under every composition: --zero/--fsdp flat "
                        "chunks, --tp/--ep/--pp model-axis shards)")
    p.add_argument("--seed", type=int, default=0)            # ref dpp.py:29
    p.add_argument("--accum-steps", type=int, default=1,
                   help="gradient accumulation (DDP no_sync analog)")
    p.add_argument("--workers", type=int, default=0,
                   help="background input-pipeline threads (0 = inline)")
    p.add_argument("--augment", action="store_true",
                   help="standard CIFAR training augmentation (random "
                        "crop pad 4 + horizontal flip), deterministic per "
                        "(seed, epoch, step); image datasets only")
    p.add_argument("--cp", type=int, default=1,
                   help="context-parallel degree: shard the sequence over "
                        "a 'seq' mesh axis with collective attention (LM only)")
    p.add_argument("--cp-impl", choices=["ring", "ulysses"], default="ring",
                   help="sequence-parallel attention collective: 'ring' "
                        "(blockwise ppermute ring, O(S/N) memory) or "
                        "'ulysses' (all_to_all to head-sharded layout; "
                        "local flash attention over the full sequence, "
                        "needs num_heads %% cp == 0)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree: Megatron column/row "
                        "sharding of attention heads + MLP hidden over a "
                        "'model' mesh axis (LM only)")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel degree: GPipe stages over a "
                        "'pipe' mesh axis, layer stack sharded per stage "
                        "(scanned LM models only)")
    p.add_argument("--pp-microbatches", type=int, default=None,
                   help="pipeline microbatches per step (default: --pp)")
    p.add_argument("--pp-schedule", default="gpipe",
                   choices=["gpipe", "1f1b", "zb"],
                   help="pipeline schedule: gpipe (AD through the tick "
                        "loop, O(microbatches) activation memory), 1f1b "
                        "(interleaved manual backward, O(stages) activation "
                        "memory — the Megatron-LM 1F1B schedule), or zb "
                        "(ZB-H1-style zero-bubble: backward split into "
                        "activation-grad B and weight-grad W units so W "
                        "fills the warm-up/drain bubble; same memory as "
                        "1f1b)")
    p.add_argument("--pp-virtual", type=int, default=1,
                   help="interleaved 1F1B: virtual layer chunks per stage "
                        "(Megatron interleaved schedule; requires "
                        "--pp-schedule 1f1b, layers divisible by "
                        "pp x virtual; shrinks the warm-up/drain bubble)")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="replace every block's MLP with N routed experts "
                        "(LM only)")
    p.add_argument("--moe-top-k", type=int, default=1,
                   help="experts per token: 1 = switch routing, "
                        "2 = Mixtral-style renormalized top-2")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel degree: shard MoE experts over "
                        "an 'expert' mesh axis (requires --moe-experts)")
    p.add_argument("--moe-aux-weight", type=float, default=0.01,
                   help="weight of the switch load-balance auxiliary loss")
    p.add_argument("--moe-capacity-factor", type=float, default=0.0,
                   help="> 0 switches MoE to token-choice dispatch with "
                        "capacity ceil(K*T/E * factor) per expert (GShard "
                        "convention, overflow drops through the residual; "
                        "under --ep tokens travel via all_to_all); 0 = "
                        "dense einsum dispatch (every token through every "
                        "local expert — exact, right for tiny E)")
    p.add_argument("--fsdp-gather", choices=["f32", "bf16"], default="f32",
                   help="dtype for FSDP weight gathers: bf16 halves "
                        "collective bytes and gathered-weight residency "
                        "(f32 master storage either way)")
    p.add_argument("--zero", type=int, nargs="?", const=1, default=0,
                   choices=[0, 1, 2, 3], metavar="LEVEL",
                   help="ZeRO weight-update sharding across the data axis. "
                        "--zero (or --zero 1): optimizer state 1/N "
                        "(reduce_scatter + sharded update + all_gather). "
                        "--zero 2: bucketed reduce-scatter straight into "
                        "the 1/N flat grad shard (the full flat f32 grad "
                        "copy never materializes). --zero 3: params stay "
                        "sharded between steps too (1/N stored), gathered "
                        "per bucket inside the step. Levels 2/3 are "
                        "data-axis only and compose with --bucket-mb")
    p.add_argument("--moment-dtype", choices=["f32", "bf16", "int8"],
                   default=None,
                   help="optimizer-moment storage under --zero: bf16 or "
                        "blockwise int8 with stochastic rounding "
                        "(error-compensated, ops/quant.py) halve/quarter "
                        "the moment bytes; f32 = unchanged")
    p.add_argument("--fsdp", action="store_true",
                   help="fully-sharded data parallelism (ZeRO-3): params, "
                        "grads, and optimizer state all 1/N per device; "
                        "weights gathered one layer at a time inside the "
                        "step (scanned LM models, pure DP mesh)")
    p.add_argument("--bucket-mb", type=float, default=None,
                   help="explicit DDP-style gradient bucket size in MiB "
                        "(default: let XLA schedule the all-reduce)")
    p.add_argument("--grad-compress", choices=["bf16", "powersgd"],
                   default=None,
                   help="comm-hook gradient compression (torch DDP "
                        "ddp_comm_hooks analog). bf16: gradients cross "
                        "the wire in bfloat16, half the f32 bytes; "
                        "composes with --bucket-mb/"
                        "--accum-steps/--grad-clip (clip sees "
                        "decompressed grads). powersgd: rank-r low-rank "
                        "factors with per-replica error feedback "
                        "(orders of magnitude fewer wire bytes, lossy; "
                        "DP/CP only)")
    p.add_argument("--powersgd-rank", type=int, default=4,
                   help="PowerSGD approximation rank (with "
                        "--grad-compress powersgd)")
    p.add_argument("--buffer-sync", choices=["mean", "broadcast"],
                   default="mean",
                   help="BatchNorm-style buffer consistency across replicas: "
                        "'mean' averages running stats (SyncBN-flavored), "
                        "'broadcast' adopts replica 0's (exact DDP "
                        "broadcast_buffers semantics)")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="AOT executable store + tuned-config store rooted "
                        "at DIR (env: DDP_COMPILE_CACHE): a supervised "
                        "restart reloads the serialized train step instead "
                        "of recompiling.  JAX's own persistent compilation "
                        "cache is always on and is placed by "
                        "JAX_COMPILATION_CACHE_DIR, else <checkout>/"
                        ".jax_cache — this flag does not move it")
    p.add_argument("--dispatch-depth", type=int, default=2,
                   help="bounded async dispatch: keep up to K steps in "
                        "flight; the host syncs only at metrics-window and "
                        "checkpoint/eval boundaries (the nan guard then "
                        "observes each step's flag with a lag of at most "
                        "K).  0 = fully synchronous per-step loop")
    p.add_argument("--remat", choices=["auto", "on", "off"], default="auto",
                   help="activation rematerialization for LM models: "
                        "'auto' keeps the model family's default "
                        "(gpt2/llama: on), on/off force it — the knob the "
                        "autotuner searches")
    p.add_argument("--autotune", choices=["search", "apply", "off"],
                   default="off",
                   help="attribution-driven autotuning (tuning/): 'search' "
                        "runs a cost-model-pruned, measured search before "
                        "training and applies + persists the winner; "
                        "'apply' loads a previously-persisted TunedConfig "
                        "for this topology/model fingerprint (falling back "
                        "LOUDLY to the CLI values on any mismatch) and "
                        "starts training with zero search trials")
    p.add_argument("--tune-dir", default=None, metavar="DIR",
                   help="TunedConfig store directory (default: "
                        "<--compile-cache>/tuned when a compile cache is "
                        "set, else .ddp_tune)")
    p.add_argument("--tune-trials", type=int, default=3,
                   help="measured candidates per search (top-K after "
                        "analytic pruning)")
    p.add_argument("--tune-steps", type=int, default=4,
                   help="measured steps per candidate window")
    p.add_argument("--log-every", type=int, default=100)     # ref dpp.py:54
    p.add_argument("--steps-per-epoch", type=int, default=None,
                   help="cap steps per epoch (smoke runs)")
    p.add_argument("--num-examples", type=int, default=2048,
                   help="synthetic dataset size")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--max-restarts", type=int, default=0,
                   help="supervise the worker and restart it up to N "
                        "times on any crash — preemption, watchdog "
                        "exit, injected chaos (torchrun --max-restarts "
                        "analog).  Requires --checkpoint-dir; each "
                        "restart resumes from the newest intact "
                        "checkpoint")
    p.add_argument("--elastic", action="store_true",
                   help="elastic gang runtime (runtime.elastic_gang): on "
                        "a member death (chaos worker-kill, peer failure "
                        "detector) the survivors agree on the next "
                        "membership epoch, rebuild the mesh one device "
                        "smaller, and reshard the LIVE train state in "
                        "memory — no checkpoint restore, no process "
                        "restart.  Data reshards deterministically "
                        "(every sample still seen exactly once per "
                        "pass); with --compile-cache the N±1 step "
                        "executables are pre-compiled in the background "
                        "so the resize lands on an AOT hit.  DP and "
                        "--zero 1 layouts over the data axis only")
    p.add_argument("--min-procs", type=int, default=1,
                   help="with --elastic: smallest gang worth resizing "
                        "down to — fewer survivors than this is a "
                        "failure (supervised restart territory), not a "
                        "smaller gang")
    p.add_argument("--elastic-dir", default=None, metavar="DIR",
                   help="rendezvous store root for --elastic (env: "
                        "DDP_ELASTIC_DIR); defaults to EVENTS_DIR/gang "
                        "or CHECKPOINT_DIR/.gang")
    p.add_argument("--step-timeout", type=float, default=None,
                   help="wall-clock deadline in seconds per train step "
                        "(armed after the first, compile-bearing step): "
                        "a wedged step logs a diagnostic, best-effort "
                        "checkpoints the last completed state, and "
                        "exits 75 instead of hanging — with "
                        "--max-restarts the supervisor then restarts")
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="deterministic fault injection for testing the "
                        "recovery paths (utils.chaos; also via the "
                        "DDP_CHAOS env var): comma-separated "
                        "ckpt-io@N[:K] | nan-grad@S | slow-step@S[:SEC] "
                        "| preempt@S | worker-kill@S[:R] | "
                        "bitflip@S[:R][:leaf]")
    p.add_argument("--nan-guard", action="store_true",
                   help="skip-step numerical guard: a step whose "
                        "gradients contain NaN/Inf applies NO update "
                        "(params/opt state/hook state keep their "
                        "values) and is counted; --max-bad-steps "
                        "consecutive bad steps abort the run.  Adds "
                        "one host sync per step")
    p.add_argument("--max-bad-steps", type=int, default=5,
                   help="with --nan-guard: consecutive non-finite-grad "
                        "steps tolerated before the run aborts as "
                        "diverged")
    p.add_argument("--integrity-every", type=int, default=0, metavar="N",
                   help="silent-data-corruption defense "
                        "(training.integrity): every N steps the train "
                        "step digests its input state's bit patterns "
                        "per data rank and all_gathers the digests — "
                        "one extra sub-KB collective on cadence, zero "
                        "extra host syncs off cadence.  A mismatch "
                        "skips that step's update, names the corrupt "
                        "rank by majority vote (2-rank gangs fall back "
                        "to a shadow-replay tiebreak), and with "
                        "--elastic evicts it through the gang resize "
                        "path: no restart, no checkpoint read.  0 "
                        "disables.  Plain DP and --zero 1 only")
    p.add_argument("--integrity-shadow", action="store_true",
                   help="with --integrity-every: on cadence, re-run the "
                        "step on a copy of the same inputs and compare "
                        "result digests — catches TRANSIENT compute SDC "
                        "even at DP=1 (two runs of one deterministic "
                        "program must agree bitwise).  Roughly doubles "
                        "the cost of cadence steps; detections are "
                        "reported (sdc_detect, rank=-1) but nothing is "
                        "evicted")
    p.add_argument("--eval", action="store_true", help="run eval after each epoch")
    p.add_argument("--decode-quant", choices=["int8"], default=None,
                   help="serve --generate with int8-quantized matrices "
                        "(ops.quant): ~half the per-step HBM weight "
                        "bytes of bf16, <1%% per-channel quantization "
                        "error")
    p.add_argument("--generate", type=int, default=0,
                   help="after training, greedily generate N tokens from a "
                        "training prompt via the KV-cache decode path "
                        "(LM models with replicated params: plain DP/ZeRO)")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace for epoch 0 here "
                        "(legacy whole-epoch capture; --profile-steps "
                        "supersedes it when both are given)")
    p.add_argument("--events-dir", default=None, metavar="DIR",
                   help="observability: write schema-versioned JSONL "
                        "events (spans, metrics snapshots, fault events) "
                        "to DIR, one file per worker (env: "
                        "DDP_EVENTS_DIR).  With --max-restarts the "
                        "supervisor also logs restart attempts and "
                        "merges everything into DIR/timeline.jsonl on "
                        "exit")
    p.add_argument("--metrics-every", type=int, default=100,
                   help="export a metrics-registry snapshot every N "
                        "steps into the event log (host-only work: no "
                        "device sync).  0 disables periodic export; "
                        "end-of-run export always happens")
    p.add_argument("--mfu", action="store_true",
                   help="report MFU/HFU per throughput window from the "
                        "analytic cost model (observability.cost_model): "
                        "model FLOPs/s over the chips' peak.  Computed at "
                        "window boundaries only — zero per-step cost.  "
                        "Supported for cnn/mlp and the LM models")
    p.add_argument("--memory-telemetry", action="store_true",
                   help="sample device/live-array memory at throughput-"
                        "window boundaries (observability.memory) and "
                        "record the train step's compiler memory budget "
                        "once after the first step (costs one extra AOT "
                        "compile of the step program)")
    p.add_argument("--alerts", nargs="?", const="", default=None,
                   metavar="SPEC",
                   help="observability: evaluate SLO alert rules at "
                        "throughput-window boundaries (zero extra host "
                        "syncs) and emit `alert` events + registry "
                        "counters.  Bare --alerts enables every rule at "
                        "defaults; SPEC overrides thresholds, e.g. "
                        "--alerts mfu_floor=0.3,step_spike=2.5 "
                        "(rules: step_spike, mfu_floor, goodput_floor, "
                        "restart_storm, sdc_storm, loader_starved, "
                        "mem_growth).  "
                        "Watch live with scripts/ddp_monitor.py")
    p.add_argument("--runs-dir", default=None, metavar="DIR",
                   help="longitudinal run store: append this run's "
                        "run_summary (MFU, step-time percentiles, memory "
                        "HWM, goodput, restarts, alerts) to "
                        "DIR/index.jsonl at run end (env: DDP_RUNS_DIR); "
                        "gate later runs with scripts/perf_gate.py")
    p.add_argument("--profile-steps", default=None, metavar="A:B",
                   help="capture a jax.profiler trace covering global "
                        "steps [A, B) — a windowed alternative to "
                        "--profile-dir's whole-epoch trace.  Traces go "
                        "to --profile-dir if set, else "
                        "EVENTS_DIR/xprof.  Also arms capture-on-"
                        "anomaly: the first nan-guard trip or watchdog "
                        "fire grabs a short trace")
    p.add_argument("--bw-probe", action="store_true",
                   help="measure grad all-reduce bandwidth utilization "
                        "over the data axis before training")
    p.add_argument("--lint-step", action="store_true",
                   help="graph-lint the selected train step "
                        "(analysis.graph_lint) on the first batch and "
                        "abort on violations — trace-only, so it fails "
                        "fast BEFORE the first XLA compile")
    p.add_argument("--coordinator", default=None,
                   help="host:port for multi-process rendezvous")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    args = p.parse_args(argv)
    # Resolve the dataset default here so direct train(parse_args([...]))
    # callers (tests, notebooks) get the same behavior as main().
    if args.dataset is None:
        args.dataset = "synthetic-lm" if is_lm(args) else "synthetic"
    # Env fallbacks: library callers and spawned workers pick these up
    # without threading the flags everywhere.
    if args.compile_cache is None:
        args.compile_cache = os.environ.get("DDP_COMPILE_CACHE") or None
    if args.events_dir is None:
        args.events_dir = os.environ.get("DDP_EVENTS_DIR") or None
    if args.runs_dir is None:
        args.runs_dir = os.environ.get("DDP_RUNS_DIR") or None
    if args.alerts is None and os.environ.get("DDP_ALERTS") is not None:
        args.alerts = os.environ.get("DDP_ALERTS")
    if args.elastic_dir is None:
        args.elastic_dir = os.environ.get("DDP_ELASTIC_DIR") or None
    if args.elastic and os.environ.get("DDP_ELASTIC_WORLD"):
        # A resize-respawn from the elastic supervisor: the gang comes
        # back at the surviving size, not the argv's original one.
        args.fake_devices = int(os.environ["DDP_ELASTIC_WORLD"])
    if args.alerts is not None:
        from distributeddataparallel_tpu.observability.alerts import (
            parse_alert_spec,
        )

        try:
            parse_alert_spec(args.alerts)
        except ValueError as e:
            raise SystemExit(f"--alerts: {e}") from None
    if args.dispatch_depth < 0:
        raise SystemExit(
            f"--dispatch-depth must be >= 0, got {args.dispatch_depth}"
        )
    if args.mfu and args.model in ("resnet18", "resnet50"):
        raise SystemExit(
            "--mfu: no analytic cost model for resnet yet (supported: "
            "cnn, mlp, gpt2, llama) — a wrong FLOP count would report a "
            "confidently wrong MFU"
        )
    if args.profile_steps is not None:
        from distributeddataparallel_tpu.observability import (
            parse_profile_steps,
        )

        try:
            parse_profile_steps(args.profile_steps)
        except ValueError as e:
            raise SystemExit(str(e)) from None
    return args


def select_device(args) -> None:
    """Select the backend (the north-star --device flag) before first use:
    ``runtime.select_device`` with this CLI's flags."""
    from distributeddataparallel_tpu.runtime.distributed import (
        select_device as _select,
    )

    _select(args.device, args.fake_devices)


def setup(args):
    """init_process_group + mesh (analog of ref dpp.py:20-21)."""
    import distributeddataparallel_tpu as ddp

    ddp.init_process_group(
        None if args.device == "auto" else args.device,
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    # The backend comes up here (after any rendezvous, which must precede
    # it): a named --device that JAX did not deliver stops the run.
    from distributeddataparallel_tpu.runtime.distributed import device_summary

    n = device_summary(args.device)["count"]
    # One general mesh builder: whatever parallelism axes are requested,
    # in canonical order (data outermost, then seq/pipe/expert/model) —
    # unsupported combinations were already rejected by validate_args.
    axes, sizes = ["data"], []
    for degree, name in (
        (args.cp, "seq"),
        (args.pp, "pipe"),
        (args.ep, "expert"),
        (args.tp, "model"),
    ):
        if degree > 1:
            axes.append(name)
            sizes.append(degree)
    denom = 1
    for d in sizes:
        denom *= d
    if n % denom:
        raise SystemExit(
            f"requested parallelism ({' x '.join(f'{a}={d}' for a, d in zip(axes[1:], sizes))}) "
            f"does not divide {n} devices"
        )
    return ddp.make_mesh(tuple(axes), shape=(n // denom, *sizes))


def is_lm(args) -> bool:
    return args.model in ("gpt2", "llama")


def validate_args(args) -> None:
    lm_ds = args.dataset == "synthetic-lm" or str(args.dataset).startswith(
        "tokens:"
    )
    if is_lm(args) and not lm_ds:
        raise SystemExit(
            f"--model {args.model} is a language model; it trains on "
            f"--dataset synthetic-lm or tokens:FILE (got {args.dataset!r})"
        )
    if not is_lm(args) and lm_ds:
        raise SystemExit(
            f"--dataset {args.dataset} requires an LM model "
            f"(--model gpt2|llama), got --model {args.model}"
        )
    if args.cp > 1:
        if not is_lm(args):
            raise SystemExit("--cp requires an LM model (--model gpt2|llama)")
        if args.seq_len % args.cp:
            raise SystemExit("--seq-len must be divisible by --cp")
    if args.tp > 1:
        if not is_lm(args):
            raise SystemExit("--tp requires an LM model (--model gpt2|llama)")
    if args.pp > 1:
        if not is_lm(args):
            raise SystemExit("--pp requires an LM model (--model gpt2|llama)")
        if args.eval and args.cp > 1:
            raise SystemExit("--pp --eval does not support --cp")
        if args.accum_steps > 1:
            raise SystemExit(
                "--pp's microbatch loop IS the accumulation; use "
                "--pp-microbatches instead of --accum-steps"
            )
        if args.bucket_mb:
            raise SystemExit("--pp does not support --bucket-mb")
        if args.layers and args.layers % (args.pp * args.pp_virtual):
            raise SystemExit(
                f"--layers {args.layers} must be divisible by --pp "
                f"{args.pp}"
                + (f" x --pp-virtual {args.pp_virtual}"
                   if args.pp_virtual > 1 else "")
            )
        if args.pp_schedule == "zb":
            M = args.pp_microbatches or args.pp
            if M < args.pp:
                raise SystemExit(
                    f"--pp-schedule zb needs --pp-microbatches >= --pp "
                    f"(got {M} < {args.pp}): with fewer microbatches than "
                    f"stages the steady state never forms and there is no "
                    f"W work to fill the bubble — use 1f1b"
                )
            if args.cp > 1:
                raise SystemExit(
                    "--pp-schedule zb does not compose with --cp yet; "
                    "use --pp-schedule 1f1b for context-parallel pipelines"
                )
            if args.moe_experts and args.moe_aux_weight:
                raise SystemExit(
                    "--pp-schedule zb does not support the MoE aux loss "
                    "(the B/W split has no aux cotangent path); set "
                    "--moe-aux-weight 0 or use --pp-schedule 1f1b"
                )
        if args.pp_virtual > 1:
            if args.pp_schedule not in ("1f1b", "zb"):
                raise SystemExit(
                    "--pp-virtual requires --pp-schedule 1f1b or zb"
                )
            if args.zero:
                # ZeRO's flat layouts flatten the PERMUTED local shards;
                # the elastic reshard's logical-geometry assumption would
                # silently break — reject until the flats are
                # interleave-aware.
                raise SystemExit("--pp-virtual does not compose with "
                                 "--zero yet")
            if args.eval or args.generate:
                # The GPipe eval path and the decode path assume the
                # contiguous logical layer layout.
                raise SystemExit("--pp-virtual does not support "
                                 "--eval/--generate")
    elif args.pp_virtual > 1:
        raise SystemExit("--pp-virtual requires --pp > 1")
    if args.fsdp:
        if not is_lm(args):
            raise SystemExit("--fsdp requires an LM model (--model gpt2|llama)")
        bad = [
            f for f, on in (
                ("--zero", args.zero),
                ("--pp", args.pp > 1), ("--cp", args.cp > 1),
                ("--ep", args.ep > 1), ("--moe-experts", bool(args.moe_experts)),
                ("--bucket-mb", bool(args.bucket_mb)),
            ) if on
        ]
        if bad:
            raise SystemExit(
                f"--fsdp composes with --tp only; drop {', '.join(bad)}"
            )
    if args.augment and is_lm(args):
        raise SystemExit("--augment is for image datasets only")
    if args.dropout:
        # ONE consistent gate (VERDICT r4 item 7) instead of per-module
        # ValueErrors: the layouts that re-drive the forward themselves
        # (FSDP's per-layer gathers, the pipeline tick loops) have no
        # dropout-rng plumbing; everything else trains with it.
        if not is_lm(args):
            raise SystemExit("--dropout applies to LM models "
                             "(--model gpt2|llama)")
        if not 0.0 < args.dropout < 1.0:
            raise SystemExit("--dropout must be in (0, 1)")
        if args.fsdp or args.pp > 1:
            raise SystemExit(
                "--dropout trains under DP/ZeRO/TP/EP/CP (scan + remat "
                "included); --fsdp and --pp do not support it"
            )
    if args.grad_clip is not None and args.grad_clip <= 0:
        raise SystemExit("--grad-clip must be > 0")
    if args.max_restarts:
        if args.max_restarts < 0:
            raise SystemExit("--max-restarts must be >= 0")
        if not args.checkpoint_dir:
            # A restart without a checkpoint replays the run from zero —
            # that is a retry loop, not fault tolerance.
            raise SystemExit("--max-restarts requires --checkpoint-dir "
                             "(restarts resume from the last checkpoint)")
    if args.step_timeout is not None and args.step_timeout <= 0:
        raise SystemExit("--step-timeout must be > 0 seconds")
    if args.min_procs < 1:
        raise SystemExit("--min-procs must be >= 1")
    if args.elastic:
        bad = [
            f for f, on in (
                ("--fsdp", args.fsdp), ("--pp", args.pp > 1),
                ("--tp", args.tp > 1), ("--ep", args.ep > 1),
                ("--cp", args.cp > 1),
            ) if on
        ]
        if bad:
            raise SystemExit(
                f"--elastic resizes over the data axis only; drop "
                f"{', '.join(bad)}"
            )
        if args.zero >= 2:
            raise SystemExit(
                "--elastic supports plain DP and --zero 1; the ZeRO-2/3 "
                "resident weight shards resize through supervised "
                "restart + elastic_restore instead"
            )
        if args.moment_dtype:
            raise SystemExit(
                "--elastic does not compose with --moment-dtype: the "
                "in-memory reshard has no dequant/requant path for "
                "low-bit moments"
            )
        if args.grad_compress:
            raise SystemExit(
                "--elastic does not compose with --grad-compress: the "
                "hook state layout is replica-count-dependent"
            )
        if not (args.elastic_dir or args.events_dir or args.checkpoint_dir):
            raise SystemExit(
                "--elastic needs a rendezvous root: --elastic-dir, or "
                "--events-dir/--checkpoint-dir to derive one"
            )
    if args.chaos:
        from distributeddataparallel_tpu.utils.chaos import parse_chaos_spec

        try:
            parse_chaos_spec(args.chaos)
        except ValueError as e:
            raise SystemExit(f"--chaos: {e}")
    if args.nan_guard:
        if args.fsdp or args.pp > 1:
            # Those step factories own their update loops; the guard is
            # wired through make_train_step only.
            raise SystemExit("--nan-guard supports the DP/ZeRO/TP/EP/CP "
                             "step; drop --fsdp/--pp")
        if args.max_bad_steps < 1:
            raise SystemExit("--max-bad-steps must be >= 1")
    if args.integrity_every:
        if args.integrity_every < 0:
            raise SystemExit("--integrity-every must be >= 0")
        # The digest compares state that must be bitwise-replicated over
        # the data axis — sharded/model-parallel layouts have no such
        # replicated domain (mirrors the make_train_step gate).
        bad = [
            f for f, on in (
                ("--fsdp", args.fsdp), ("--pp", args.pp > 1),
                ("--tp", args.tp > 1), ("--ep", args.ep > 1),
                ("--cp", args.cp > 1),
            ) if on
        ]
        if bad:
            raise SystemExit(
                f"--integrity-every compares replicated data-axis state; "
                f"drop {', '.join(bad)}"
            )
        if args.zero >= 2:
            raise SystemExit(
                "--integrity-every supports plain DP and --zero 1; "
                "ZeRO-2/3 shard the comparable state away"
            )
    elif args.integrity_shadow:
        raise SystemExit(
            "--integrity-shadow needs a cadence: set --integrity-every N"
        )
    if args.zero >= 2:
        # Levels 2/3 shard the update over the data axis only; the
        # model-axis compositions ride ZeRO-1's flat layouts.
        bad = [
            f for f, on in (
                ("--tp", args.tp > 1), ("--ep", args.ep > 1),
                ("--pp", args.pp > 1),
            ) if on
        ]
        if bad:
            raise SystemExit(
                f"--zero {args.zero} shards over the data axis only; "
                f"drop {', '.join(bad)} or use --zero 1"
            )
    if args.moment_dtype and not args.zero:
        raise SystemExit("--moment-dtype rides the ZeRO sharded update; "
                         "add --zero")
    if args.autotune != "off":
        # The tuner owns the generic DP/ZeRO knobs; layouts with their
        # own step factories (and llama/resnet scale) are out of its
        # search space.
        bad = [
            f for f, on in (
                ("--fsdp", args.fsdp), ("--pp", args.pp > 1),
                ("--tp", args.tp > 1), ("--ep", args.ep > 1),
                ("--cp", args.cp > 1), ("--elastic", args.elastic),
            ) if on
        ]
        if bad:
            raise SystemExit(
                f"--autotune searches the DP/ZeRO space only; drop "
                f"{', '.join(bad)}"
            )
        if args.model not in ("mlp", "cnn", "gpt2"):
            raise SystemExit(
                "--autotune supports --model mlp|cnn|gpt2 (the tuning "
                f"harness registry); got {args.model!r}"
            )
        if args.tune_trials < 1:
            raise SystemExit("--tune-trials must be >= 1")
        if args.tune_steps < 1:
            raise SystemExit("--tune-steps must be >= 1")
    if args.remat != "auto" and not is_lm(args):
        raise SystemExit("--remat applies to LM models (--model gpt2|llama)")
    if args.grad_compress and (args.zero or args.fsdp or args.pp > 1):
        # Those layouts own their reductions (reduce_scatter / per-layer
        # gathers / stage collectives); the comm hook is the plain-DP
        # all-reduce's.
        raise SystemExit(
            "--grad-compress applies to the DP all-reduce; drop "
            "--zero/--fsdp/--pp"
        )
    if args.decode_quant and not args.generate:
        raise SystemExit("--decode-quant only affects --generate; add "
                         "--generate N")
    if args.grad_compress == "powersgd":
        if args.tp > 1 or args.ep > 1:
            # The model-axis placement helpers shard (params, opt); the
            # hook-state layout under TP/EP is untested — reject rather
            # than misplace it.
            raise SystemExit(
                "--grad-compress powersgd supports DP/CP layouts; drop "
                "--tp/--ep"
            )
        if args.powersgd_rank < 1:
            raise SystemExit("--powersgd-rank must be >= 1")
    if args.generate:
        if not is_lm(args):
            raise SystemExit("--generate requires an LM model")
        if (args.tp > 1 and not args.fsdp) or args.pp > 1 or args.ep > 1:
            # Decode runs on replicated params.  FSDP (incl. FSDP x TP)
            # is exempt: its eval/generate path host-gathers the sharded
            # flats back to the full model layout first (fsdp_gather_params
            # -- the tested --fsdp --tp 2 --generate CLI path).
            raise SystemExit(
                "--generate needs replicated params (no --tp/--pp/--ep; "
                "--fsdp [--tp N] generates via the host gather)"
            )
    if args.moe_experts and not is_lm(args):
        raise SystemExit("--moe-experts requires an LM model")
    if args.moe_experts and not 1 <= args.moe_top_k <= args.moe_experts:
        raise SystemExit(
            f"--moe-top-k {args.moe_top_k} must be in [1, {args.moe_experts}]"
        )
    if args.moe_top_k != 1 and not args.moe_experts:
        raise SystemExit("--moe-top-k requires --moe-experts")
    if args.moe_capacity_factor and not args.moe_experts:
        raise SystemExit("--moe-capacity-factor requires --moe-experts")
    if args.moe_capacity_factor < 0:
        raise SystemExit("--moe-capacity-factor must be >= 0")
    if args.ep > 1:
        if not args.moe_experts:
            raise SystemExit("--ep requires --moe-experts")
        if args.moe_experts % args.ep:
            raise SystemExit(
                f"--moe-experts {args.moe_experts} must be divisible by "
                f"--ep {args.ep}"
            )
        if args.pp > 1 and args.tp > 1:
            raise SystemExit("--ep with BOTH --pp and --tp is untested")
        if args.cp > 1 and (args.pp > 1 or args.tp > 1):
            raise SystemExit(
                "--ep with --cp composes pairwise only (no extra --pp/--tp)"
            )


def elastic_store_dir(args) -> str:
    """The rendezvous root shared by trainer and supervisor (both derive
    it from the same argv, so a respawn finds the same store)."""
    if args.elastic_dir:
        return args.elastic_dir
    if args.events_dir:
        return os.path.join(args.events_dir, "gang")
    return os.path.join(args.checkpoint_dir, ".gang")


class _SwappableStream:
    """Iterator of ``(batch_idx, batch)`` whose underlying loader can be
    swapped mid-epoch: the elastic resize replaces the remainder of the
    epoch with a tail loader resharded for the new world, and the batch
    index keeps counting — the global step stays monotone across the
    swap."""

    def __init__(self, loader):
        self._it = iter(loader)
        self._idx = -1

    def __iter__(self):
        return self

    def __next__(self):
        self._idx += 1
        return self._idx, next(self._it)

    def swap(self, loader) -> None:
        close = getattr(self._it, "close", None)
        if close is not None:
            close()
        self._it = iter(loader)


def build_model(args, num_classes: int = 10, vocab_size: int | None = None):
    from distributeddataparallel_tpu import models

    if args.model == "mlp":
        return models.TinyMLP(num_classes=num_classes)
    if args.model == "cnn":
        return models.SimpleCNN(num_classes=num_classes)
    if args.model == "resnet18":
        from distributeddataparallel_tpu.models.resnet import ResNet18
        return ResNet18(num_classes=num_classes, stem="cifar")
    if args.model == "resnet50":
        from distributeddataparallel_tpu.models.resnet import ResNet50
        return ResNet50(num_classes=num_classes)
    if is_lm(args):
        from distributeddataparallel_tpu.models import transformer as tfm

        family = tfm.gpt2_124m if args.model == "gpt2" else tfm.llama3_8b
        overrides = dict(
            vocab_size=vocab_size or args.vocab_size,
            max_seq_len=args.seq_len,
        )
        if args.cp > 1:
            overrides["cp_axis"] = "seq"
            overrides["cp_impl"] = args.cp_impl
        if args.tp > 1:
            overrides["tp_axis"] = "model"
        if args.pp > 1 or args.fsdp:
            # GPipe/FSDP operate on the scanned layer stack's leading dim.
            overrides["scan_layers"] = True
        if args.dropout:
            overrides["dropout_rate"] = args.dropout
        if args.moe_experts:
            overrides["moe_experts"] = args.moe_experts
            overrides["moe_top_k"] = args.moe_top_k
            overrides["moe_capacity_factor"] = args.moe_capacity_factor
        if args.ep > 1:
            overrides["ep_axis"] = "expert"
        if args.layers:
            overrides["num_layers"] = args.layers
        if args.remat != "auto":
            overrides["remat"] = args.remat == "on"
        if args.d_model:
            # Scale heads with width (head_dim 16, even for RoPE) instead of
            # keeping the family's head count, which would give tiny or odd
            # head dims at small widths.
            if args.d_model % 16:
                raise SystemExit("--d-model must be a multiple of 16")
            heads = max(1, args.d_model // 16)
            overrides.update(
                d_model=args.d_model, d_ff=4 * args.d_model, num_heads=heads
            )
            if args.model == "llama":
                # Largest kv count <= heads/4 that divides heads (GQA
                # requires num_heads % num_kv_heads == 0) — and that the
                # TP degree divides (kv heads shard over the model axis).
                kv = max(
                    (
                        d for d in range(1, max(heads // 4, args.tp) + 1)
                        if heads % d == 0 and d % args.tp == 0
                    ),
                    default=None,
                )
                if kv is None:
                    raise SystemExit(
                        f"no GQA kv-head count divides heads={heads} and "
                        f"is divisible by --tp {args.tp}; pick a larger "
                        f"--d-model"
                    )
                overrides["num_kv_heads"] = kv
        cfg = family(**overrides)
        return tfm.TransformerLM(cfg)
    raise NotImplementedError(f"--model {args.model}")


def build_dataset(args, train=True):
    from distributeddataparallel_tpu import data

    if str(args.dataset).startswith("tokens:"):
        # Memmapped real-token corpus (data.tokens).  FILE trains; eval
        # reads FILE's sibling val split: DIR/val.npy when FILE is
        # DIR/train.npy, else STEM.val.npy next to STEM.npy.
        path = args.dataset.split(":", 1)[1]
        if not train:
            base = os.path.basename(path)
            if base in ("train.npy", "train"):
                path = os.path.join(os.path.dirname(path), "val.npy")
            else:
                path = (path[:-4] if path.endswith(".npy") else path) \
                    + ".val.npy"
            if not os.path.exists(path):
                raise SystemExit(
                    f"--eval with --dataset tokens: needs a val split at "
                    f"{path}"
                )
        return data.TokenFileDataset(
            path, seq_len=args.seq_len,
            stride=(args.token_stride if train else None),
        )
    if is_lm(args) or args.dataset == "synthetic-lm":
        return data.SyntheticLM(
            num_examples=args.num_examples, seq_len=args.seq_len,
            vocab_size=args.vocab_size,
            seed=args.seed if train else args.seed + 1,
        )
    if args.dataset == "synthetic":
        return data.SyntheticClassification(
            num_examples=args.num_examples, seed=args.seed if train else args.seed + 1
        )
    if str(args.dataset).startswith("shards:"):
        # Streaming memmapped shard directory (data.sharded): the
        # ImageNet-scale path — per-batch disk reads, never full-RAM.
        root = args.dataset.split(":", 1)[1]
        split = os.path.join(root, "train" if train else "val")
        if os.path.isdir(split):
            root = split
        elif not train:
            raise SystemExit(
                f"--eval with --dataset shards: needs {split} "
                "(no val split in the shard directory)"
            )
        # device_normalize: ship raw u8 to the chip (4x fewer host->device
        # bytes, no host float conversion); normalize fuses into the
        # compiled step (ops.normalize_u8_images).
        return data.ShardedImageDataset(root, device_normalize=True)
    from distributeddataparallel_tpu import native

    # u8 storage + fused native normalize-on-gather when the native lib
    # is available (identical numerics, less RAM, faster input path).
    return data.load_cifar10(
        args.data_root, train=train, keep_u8=native.available()
    )


def build_optimizer(args, total_steps: int):
    """Optimizer + LR schedule from flags.

    The reference hardcodes ``optim.SGD(lr=0.01)`` (ref dpp.py:41,
    SURVEY §2b optimizer row); ``--optimizer sgd`` with the default
    constant schedule reproduces that.  adam/adamw + warmup-cosine are
    the standard LM-config surface.  Schedule state is one scalar step
    count, so every composition (ZeRO flat chunks included) carries it
    unchanged.
    """
    import optax

    if args.lr_schedule == "constant" and not args.warmup_steps:
        lr = args.lr
    else:
        decay = max(total_steps - args.warmup_steps, 1)
        if args.lr_schedule == "cosine":
            sched = optax.cosine_decay_schedule(
                args.lr, decay,
                alpha=(args.min_lr / args.lr) if args.lr else 0.0,
            )
        elif args.lr_schedule == "linear":
            sched = optax.linear_schedule(args.lr, args.min_lr, decay)
        else:
            sched = optax.constant_schedule(args.lr)
        if args.warmup_steps:
            warm = optax.linear_schedule(0.0, args.lr, args.warmup_steps)
            sched = optax.join_schedules([warm, sched], [args.warmup_steps])
        lr = sched
    if args.optimizer == "sgd":
        return optax.sgd(lr, momentum=args.momentum or None)
    if args.optimizer == "adam":
        return optax.adam(lr)
    return optax.adamw(lr, weight_decay=args.weight_decay)


def _apply_trial_to_args(args, config: dict, *, n_chips: int = 0) -> None:
    """Overwrite the tunable knobs on ``args`` with a TunedConfig.

    Only the knobs the tuner owns are touched — everything else
    (model, dataset, steps, parallelism axes) keeps its CLI value, so
    an applied record can never change WHAT trains, only how fast.
    A persisted batch that would starve the dataset (global batch >
    examples, possible when a record tuned against one --num-examples
    is replayed against a smaller one) keeps the CLI batch/accum
    instead of training zero steps.
    """
    from distributeddataparallel_tpu.tuning import TrialConfig
    from distributeddataparallel_tpu.utils.logging import get_logger

    trial = TrialConfig.from_dict(config)
    cap = (args.num_examples // n_chips
           if n_chips and getattr(args, "num_examples", None) else None)
    if cap is not None and trial.batch_per_chip > cap:
        get_logger().warning(
            "tuned batch %d/chip needs %d examples but --num-examples "
            "is %d — keeping --batch-size %d (re-run --autotune search "
            "against this dataset)",
            trial.batch_per_chip, trial.batch_per_chip * n_chips,
            args.num_examples, args.batch_size,
        )
    else:
        args.batch_size = trial.batch_per_chip
        args.accum_steps = trial.accum_steps
    args.zero = trial.zero
    # dpp stores "no override" as None; the tuner's explicit "f32" is
    # the same thing (and would trip the --moment-dtype-needs---zero
    # gate at zero=0 if kept literal).
    args.moment_dtype = (
        None if trial.moment_dtype == "f32" else trial.moment_dtype
    )
    args.bucket_mb = trial.bucket_mb
    args.dispatch_depth = trial.dispatch_depth
    if is_lm(args):
        args.remat = "on" if trial.remat else "off"


def _tune_dir_for(args) -> str:
    if args.tune_dir:
        return args.tune_dir
    if args.compile_cache:
        return os.path.join(args.compile_cache, "tuned")
    return ".ddp_tune"


def _run_autotune(args, mesh, events=None) -> None:
    """``--autotune`` entry: mutate ``args`` in place before anything
    model-shaped is built.

    ``apply`` loads the persisted TunedConfig for this (topology, model,
    toolchain) fingerprint and replays it — zero search trials, loud
    fallback to the CLI defaults on any key mismatch.  ``search`` runs
    the full prune→measure pipeline on the live mesh first, persists
    the winner, then applies it; the next run can use ``apply``.
    """
    from distributeddataparallel_tpu.tuning import (
        TrialConfig,
        TuningStore,
        default_tuned_key,
        search_model,
    )
    from distributeddataparallel_tpu.utils.logging import get_logger

    log = get_logger()
    model = "gpt2-small" if args.model == "gpt2" else args.model
    n_chips = int(mesh.shape["data"])
    name = f"{model}@d{n_chips}"
    seq = args.seq_len if is_lm(args) else 128
    store = TuningStore(_tune_dir_for(args))
    key = default_tuned_key(model, mesh, seq=seq)

    if args.autotune == "apply":
        record = store.load(name, key)
        applied = record is not None
        if applied:
            _apply_trial_to_args(args, record["config"], n_chips=n_chips)
            log.info(
                "autotune apply: %r -> %s (score %s, tuned %s)",
                name, record["config"], record.get("score"),
                os.path.join(store.root, name),
            )
        else:
            log.warning(
                "autotune apply: no matching TunedConfig %r under %s — "
                "running with the CLI defaults (use --autotune search "
                "to create one)", name, store.root,
            )
        if events is not None:
            events.emit(
                "tune_result",
                mode="apply",
                winner=record["config"] if applied else None,
                applied=applied,
                score=record.get("score") if applied else None,
                store_path=store.root,
            )
        return

    exec_store = None
    if args.compile_cache:
        from distributeddataparallel_tpu.training.warm_start import (
            ExecutableStore,
        )

        exec_store = ExecutableStore(args.compile_cache)
    # Cap the space by what the dataset can feed: a winner whose global
    # batch exceeds --num-examples would train zero steps when applied.
    from distributeddataparallel_tpu.tuning import default_space_for

    space = default_space_for(model)
    if getattr(args, "num_examples", None):
        import dataclasses

        cap = max(1, args.num_examples // n_chips)
        fit = tuple(b for b in space.batch_per_chip if b <= cap)
        space = dataclasses.replace(
            space, batch_per_chip=fit or (min(cap, args.batch_size),)
        )
    # The CLI flags as given ARE the hand-picked baseline: it is always
    # measured and always eligible to win, so the reported gain_frac is
    # an honest "what did tuning buy over what I typed".
    baseline = TrialConfig(
        batch_per_chip=args.batch_size,
        accum_steps=args.accum_steps,
        remat=(args.remat == "on" if args.remat != "auto"
               else is_lm(args) and args.model == "gpt2"),
        zero=args.zero,
        moment_dtype=args.moment_dtype or "f32",
        bucket_mb=args.bucket_mb,
        dispatch_depth=args.dispatch_depth,
    )
    summary = search_model(
        model,
        mesh=mesh,
        seq=seq,
        space=space,
        top_k=args.tune_trials,
        measure_steps=args.tune_steps,
        seed=args.seed,
        baseline=baseline,
        tune_store=store,
        store_name=name,
        key=key,
        exec_store=exec_store,
        events=events,
    )
    winner = summary.get("winner")
    if winner is None:
        log.warning(
            "autotune search measured no viable trial — keeping the "
            "CLI defaults"
        )
        return
    _apply_trial_to_args(args, winner["config"], n_chips=n_chips)
    log.info(
        "autotune search: winner %s (gain %+.1f%% vs baseline), "
        "persisted to %s",
        winner["trial"],
        100.0 * (summary.get("gain_frac") or 0.0),
        summary.get("store_path"),
    )


def train(args) -> float:
    """Per-job trainer (analog of ref dpp.py:27-57). Returns final loss."""
    # Library/test callers reach train() without going through main();
    # run the flag-combination gate here too (idempotent) so unsupported
    # compositions fail with the SAME SystemExit messages either way —
    # not a per-module ValueError deep inside a step factory.
    validate_args(args)
    import jax
    import jax.numpy as jnp
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data import DataLoader
    from distributeddataparallel_tpu.ops import accuracy, cross_entropy_loss
    from distributeddataparallel_tpu.training.train_step import make_eval_step
    from distributeddataparallel_tpu.utils import (
        StepTimer,
        allreduce_bandwidth,
        log0,
        profile_trace,
        warn0,
        warn_all,
    )

    mesh = setup(args)
    from distributeddataparallel_tpu import native
    from distributeddataparallel_tpu.runtime.distributed import device_summary

    device = device_summary(args.device)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    n_replicas = mesh.shape["data"]
    log0(
        "world: %d process(es), %d device(s), %d-way DP, global batch %d, "
        "platform %s [%s], native: %s, compile cache: %s",
        ddp.get_world_size(), device["count"], n_replicas,
        args.batch_size * n_replicas, device["platform"], device["kind"],
        "built" if native.available() else "numpy",
        cache_dir or "off",
    )

    # Observability (distributeddataparallel_tpu.observability): one
    # schema-versioned JSONL event log + metrics registry per process,
    # and an XLA-profiler orchestrator for windowed / on-anomaly capture.
    # Everything stays host-side — emitting an event or exporting a
    # snapshot never reads a device value, so none of it adds a sync.
    events = registry = prof = None
    if args.events_dir or args.profile_steps:
        from distributeddataparallel_tpu.observability import (
            EventLog,
            JsonlExporter,
            MetricsRegistry,
            ProfilerOrchestrator,
            TextExporter,
            events_path,
            parse_profile_steps,
        )

        proc = jax.process_index()
        if args.events_dir:
            events = EventLog(events_path(args.events_dir, proc), proc)
            events.emit(
                "run_start",
                argv=sys.argv[1:],
                attempt=int(os.environ.get("DDP_RESTART_ATTEMPT", "0") or 0),
                devices=device["count"],
                platform=device["platform"],
                device_kind=device["kind"],
                compile_cache=cache_dir,
            )
            registry = MetricsRegistry()
            registry.add_exporter(JsonlExporter(events))
            if proc == 0:
                # Rank-0 plaintext /metrics-style snapshot, refreshed at
                # every export — the file a human or node scraper reads.
                registry.add_exporter(
                    TextExporter(os.path.join(args.events_dir, "metrics.txt"))
                )
        # Trace destination: --profile-dir when given, else a subdir of
        # the events dir.  The orchestrator is armed whenever it has
        # somewhere to write — --profile-steps drives the window, and
        # the first nan-guard trip or watchdog fire grabs a short
        # anomaly capture either way.
        prof_dir = args.profile_dir or (
            os.path.join(args.events_dir, "xprof") if args.events_dir
            else None
        )
        if prof_dir:
            prof = ProfilerOrchestrator(
                prof_dir,
                window=parse_profile_steps(args.profile_steps),
                events=events,
            )

    # One tracer per run.  It always exists: a profiler capture
    # (--profile-steps, --profile-dir) reads its spans as ``ddp:<name>``
    # with or without an event log.  It is installed as the process's,
    # for the loader's ``loader.batch``, only inside the ``try`` whose
    # ``finally`` takes it out again.
    from distributeddataparallel_tpu.observability import Tracer, set_tracer

    tracer = Tracer(events, registry)
    _span = tracer.span

    # Autotune BEFORE anything batch-shaped exists: apply replays a
    # persisted winner (zero trials), search measures on the live mesh
    # and persists one.  Either way the tuned knobs land on ``args`` and
    # the loader/model/step below are built from them.
    if args.autotune != "off":
        _run_autotune(args, mesh, events)

    cp = args.cp > 1
    if cp:
        from distributeddataparallel_tpu.data import shard_lm_batch

        # CP: host-side input/target shift + DP×CP placement, inside the
        # loader's prefetch pipeline.
        place_fn = lambda b: shard_lm_batch(b["tokens"], mesh)
    else:
        place_fn = None
    dataset = build_dataset(args, train=True)
    augment = None
    if args.augment:  # validated LM-free in validate_args
        from distributeddataparallel_tpu.data import CifarAugment
        augment = CifarAugment()  # fused native u8 path when available
    loader = DataLoader(
        dataset, per_replica_batch=args.batch_size, mesh=mesh,
        shuffle=True, seed=args.seed, place_fn=place_fn,
        workers=args.workers, augment=augment,
    )
    # Structured starvation events land in the same per-worker log as
    # everything else (events is None without --events-dir — the loader
    # then only warns).
    loader.events = events

    lm = is_lm(args)
    num_classes = getattr(dataset, "num_classes", None)
    if not lm and hasattr(dataset, "num_classes") and num_classes is None:
        raise SystemExit(
            "shard manifest lacks num_classes — rewrite the shards with "
            "write_image_shards(..., num_classes=...) so the classifier "
            "head can be sized"
        )
    model = build_model(
        args,
        num_classes=num_classes or 10,
        vocab_size=getattr(dataset, "vocab_size", None),
    )
    rng = jax.random.PRNGKey(args.seed)            # ref dpp.py:29 analog
    if lm:
        c = model.cfg
        log0(
            "model: %s %d layers, d_model %d, %d heads, vocab %d, seq %d, "
            "dtype %s, attention %s",
            args.model, c.num_layers, c.d_model, c.num_heads, c.vocab_size,
            c.max_seq_len, jnp.dtype(c.dtype).name, c.attn_impl,
        )
        sample = jnp.zeros((1, args.seq_len), jnp.int32)
        init_model = model
        if cp:
            # Init outside shard_map with a non-CP twin config: ring
            # attention and cp_positions need the seq axis bound, but the
            # param structure is identical either way.
            import dataclasses

            from distributeddataparallel_tpu.models import TransformerLM

            init_model = TransformerLM(
                dataclasses.replace(model.cfg, cp_axis=None)
            )
        variables = init_model.init(rng, sample)
    else:
        shape = getattr(dataset, "image_shape", None) or dataset.images.shape[1:]
        sample = jnp.zeros((1,) + tuple(shape), jnp.float32)
        variables = model.init(rng, sample)
    if args.pretrained:
        # Fine-tune flow (ref dpp.py:14-15): replace the random init with
        # converted pretrained weights; every sharded placement below
        # (DP broadcast / ZeRO / TP / EP / PP / FSDP) then distributes
        # the pretrained tree exactly like a fresh one.
        from distributeddataparallel_tpu.models.io import load_pretrained

        variables = load_pretrained(args.pretrained, model, variables)
        log0("loaded pretrained weights from %s", args.pretrained)
    params = variables["params"]
    # Non-param collections (BatchNorm running stats for ResNets) become
    # framework-managed model state — the torch "buffers" DDP broadcasts.
    model_state = {k: v for k, v in variables.items() if k != "params"}
    has_ms = bool(model_state)

    spe = loader.steps_per_epoch                         # ref dpp.py:41
    if args.steps_per_epoch:
        spe = min(spe, args.steps_per_epoch)
    tx = build_optimizer(args, total_steps=max(spe * args.epochs, 1))
    if args.fsdp:
        # Fully-sharded: params/grads/opt state 1/N per device; the step
        # gathers one layer at a time (parallel/fsdp.py).
        state = ddp.fsdp_state(
            model.cfg, params, tx, mesh, apply_fn=model.apply,
            tp_axis="model" if args.tp > 1 else None,
        )
    elif args.zero:
        # With --tp/--ep/--pp, zero_state places params in the sharded
        # layout itself and shards the flat opt state over ALL the axes.
        if args.tp == 1 and args.ep == 1 and args.pp == 1:
            params = ddp.broadcast_params(params, mesh)
        model_state = ddp.broadcast_params(model_state, mesh)
        state = ddp.zero_state(
            apply_fn=model.apply, params=params, tx=tx, mesh=mesh,
            tp_axis="model" if args.tp > 1 else None,
            ep_axis="expert" if args.ep > 1 else None,
            pp_axis="pipe" if args.pp > 1 else None,
            model_state=model_state,
            level=args.zero,
            moment_dtype=args.moment_dtype,
            bucket_bytes=(
                int(args.bucket_mb * 1024 * 1024)
                if args.bucket_mb and args.zero >= 2 else None
            ),
        )
    elif args.pp > 1:
        state = ddp.TrainState.create(
            apply_fn=model.apply, params=params, tx=tx, model_state=model_state
        )
        # PP layout: the stacked layer dim sharded over the 'pipe' axis
        # (plus Megatron / expert trailing-dim sharding under --tp/--ep).
        state = ddp.shard_state_pp(
            state, mesh,
            tp_axis="model" if args.tp > 1 else None,
            ep_axis="expert" if args.ep > 1 else None,
            virtual=args.pp_virtual,
        )
    elif args.ep > 1:
        state = ddp.TrainState.create(
            apply_fn=model.apply, params=params, tx=tx, model_state=model_state
        )
        if args.tp > 1:
            # Combined EP x TP placement (disjoint leaf sets) — ONE spec
            # source shared with the train step's in_specs.
            from distributeddataparallel_tpu.parallel.expert_parallel import (
                shard_state_model_axes,
            )

            state = shard_state_model_axes(
                state, mesh, tp_axis="model", ep_axis="expert"
            )
        else:
            state = ddp.shard_state_ep(state, mesh)
    elif args.tp > 1:
        state = ddp.TrainState.create(
            apply_fn=model.apply, params=params, tx=tx, model_state=model_state
        )
        # TP layout: Megatron param sharding over the 'model' axis,
        # replicated over 'data' (the broadcast analog for a 2-D mesh).
        state = ddp.shard_state_tp(state, mesh)
    else:
        state = ddp.TrainState.create(
            apply_fn=model.apply, params=params, tx=tx, model_state=model_state
        )
        state = ddp.broadcast_params(state, mesh)   # DDP ctor broadcast analog
        if args.grad_compress == "powersgd":
            # Low-rank comm-hook state: warm Q replicated, per-replica
            # error residuals allocated DIRECTLY in their sharded layout
            # (leading data-axis dim) — no full-tree transient on one
            # device.
            from distributeddataparallel_tpu.parallel.powersgd import (
                powersgd_state,
            )

            state = state.replace(
                comm_state=powersgd_state(
                    state.params, int(mesh.shape["data"]),
                    args.powersgd_rank, seed=args.seed, mesh=mesh,
                )
            )

    # Streaming shard datasets ship raw u8 images; normalize in-graph
    # (ops.normalize_u8_images — XLA fuses it under the first conv).
    if getattr(dataset, "device_normalize", False):
        from distributeddataparallel_tpu.ops import normalize_u8_images

        _img = lambda batch: normalize_u8_images(batch["image"])
    else:
        _img = lambda batch: batch["image"]

    if lm:
        from distributeddataparallel_tpu.ops import lm_cross_entropy

        # CP batches arrive pre-split (the next-token shift crosses shard
        # boundaries, so the host does it — see shard_lm_batch); plain LM
        # batches carry raw tokens and shift here.
        if cp:
            extract = lambda batch: (batch["inputs"], batch["targets"])
        else:
            extract = lambda batch: (
                batch["tokens"][:, :-1], batch["tokens"][:, 1:]
            )

        def _train_apply_kwargs(rng):
            # Dropout: the step's rng is already folded per data (and
            # cp) position, so masks decorrelate across replicas while
            # tp/ep peers — which re-run identical replicated compute —
            # share one mask by construction.  The scan splits it again
            # per layer (scanned_layer_cls split_rngs) and remat replays
            # the same mask deterministically.
            if args.dropout:
                return {"deterministic": False, "rngs": {"dropout": rng}}
            return {}

        if args.moe_experts and args.moe_aux_weight > 0:
            from distributeddataparallel_tpu.models.transformer import (
                moe_aux_from_intermediates,
            )

            def loss_fn(params, batch, rng):
                inputs, targets = extract(batch)
                logits, col = model.apply(
                    {"params": params}, inputs, mutable=["intermediates"],
                    **_train_apply_kwargs(rng),
                )
                aux = moe_aux_from_intermediates(col)
                loss = (
                    lm_cross_entropy(logits, targets)
                    + args.moe_aux_weight * aux
                )
                return loss, {
                    "accuracy": accuracy(logits, targets),
                    "moe_aux": aux,
                }
        else:
            def loss_fn(params, batch, rng):
                inputs, targets = extract(batch)
                logits = model.apply(
                    {"params": params}, inputs, **_train_apply_kwargs(rng)
                )
                loss = lm_cross_entropy(logits, targets)
                return loss, {"accuracy": accuracy(logits, targets)}
    elif has_ms:
        def loss_fn(params, ms, batch, rng):
            logits, new_vars = model.apply(
                {"params": params, **ms}, _img(batch), train=True,
                mutable=list(ms.keys()),
            )
            loss = cross_entropy_loss(logits, batch["label"])  # ref dpp.py:40
            aux = {"accuracy": accuracy(logits, batch["label"])}
            return loss, (aux, new_vars)
    else:
        def loss_fn(params, batch, rng):
            logits = model.apply({"params": params}, _img(batch))
            loss = cross_entropy_loss(logits, batch["label"])  # ref dpp.py:40
            return loss, {"accuracy": accuracy(logits, batch["label"])}

    # Off-cadence twin for --integrity-every (built in the generic
    # branch below; the layouts the other branches build are rejected
    # by the integrity CLI gate above).
    step_fn_off = None
    if args.fsdp:
        # FSDP: the step factory takes the model CONFIG (it decomposes
        # the transformer into embed / layer scan / head around the
        # per-layer weight gathers).
        step_fn = ddp.make_fsdp_train_step(
            model.cfg, mesh=mesh, grad_clip=args.grad_clip,
            accum_steps=args.accum_steps,
            tp_axis="model" if args.tp > 1 else None,
            gather_dtype=jnp.bfloat16 if args.fsdp_gather == "bf16" else None,
        )
    elif args.pp > 1:
        # GPipe: the step factory takes the model CONFIG (it decomposes
        # the transformer into embed / stage stack / head itself); the
        # microbatch loop is the accumulation.
        M = args.pp_microbatches or args.pp
        if args.batch_size % M:
            raise SystemExit(
                f"--batch-size {args.batch_size} must be divisible by "
                f"--pp-microbatches {M}"
            )
        if model.cfg.num_layers % (args.pp * args.pp_virtual):
            raise SystemExit(
                f"model layer count {model.cfg.num_layers} must be "
                f"divisible by --pp {args.pp}"
                + (f" x --pp-virtual {args.pp_virtual}"
                   if args.pp_virtual > 1 else "")
            )
        step_fn = ddp.make_pp_train_step(
            model.cfg, mesh=mesh, microbatches=M, zero=args.zero,
            moe_aux_weight=args.moe_aux_weight if args.moe_experts else 0.0,
            schedule=args.pp_schedule, grad_clip=args.grad_clip,
            virtual=args.pp_virtual,
        )
    else:
        # One factory for the other compositions: DP × {accum, buckets,
        # ZeRO} × CP/TP.  Factored over the mesh so the elastic resize
        # can rebuild the identical step for the shrunken world.
        def build_step_fn(for_mesh, integrity=True):
            return ddp.make_train_step(
                loss_fn, mesh=for_mesh, accum_steps=args.accum_steps,
                bucket_bytes=int(args.bucket_mb * 1024 * 1024) if args.bucket_mb else None,
                with_model_state=has_ms, zero=args.zero,
                buffer_sync=args.buffer_sync,
                cp_axis="seq" if cp else None,
                tp_axis="model" if args.tp > 1 else None,
                ep_axis="expert" if args.ep > 1 else None,
                grad_clip=args.grad_clip,
                grad_compress=args.grad_compress,
                nonfinite_guard=args.nan_guard,
                integrity_every=(
                    (args.integrity_every or None) if integrity else None
                ),
            )

        step_fn = build_step_fn(mesh)
        if args.integrity_every:
            # Off-cadence twin: the digest-armed program carries an
            # in-graph cadence cond, and routing the state past that
            # conditional has a measurable per-step cost even on the
            # cond's zero branch.  The host loop already mirrors the
            # cadence gate (IntegrityChecker.due on a host counter — no
            # sync), so off-cadence steps dispatch this bit-identical
            # plain program instead and pay exactly nothing; the digest
            # program runs only on the 1-in-N cadence steps.
            step_fn_off = build_step_fn(mesh, integrity=False)

    # Graph lint wants the RAW factory step: the warm-start wrapper below
    # may swap in a deserialized AOT executable, which cannot be traced.
    lint_target = step_fn if args.lint_step else None
    # Same constraint for the GL002 fingerprint the run_summary carries
    # (perf_gate uses it to tell graph changes from environment drift).
    fp_target = step_fn

    warm_report = {}
    if args.compile_cache:
        # AOT executable store under the cache dir: load the serialized
        # train step on restart, compile-and-save otherwise.  The key
        # must cover everything the CLI can change about the compiled
        # program — including optimizer hyperparameters, which optax
        # bakes into the executable as constants (a stale-lr binary
        # would train silently wrong, which is exactly what the key
        # check turns into a loud JIT fallback).
        from distributeddataparallel_tpu.training.warm_start import (
            ExecutableStore,
            executable_key,
            warm_train_step,
        )

        warm_store = ExecutableStore(os.path.join(args.compile_cache, "aot"))

        def _exec_key(fn, for_mesh):
            return executable_key(
                mesh=for_mesh,
                model_config=getattr(model, "cfg", None),
                step_signature=getattr(fn, "aot_signature", None),
                extra={
                    "model": args.model,
                    "batch_size": args.batch_size,
                    "seq_len": args.seq_len if lm else None,
                    "optimizer": args.optimizer,
                    "lr": args.lr,
                    "momentum": args.momentum,
                    "weight_decay": args.weight_decay,
                    "lr_schedule": args.lr_schedule,
                    "warmup_steps": args.warmup_steps,
                    "min_lr": args.min_lr,
                    "fsdp": args.fsdp,
                    "pp": args.pp,
                    "pp_schedule": args.pp_schedule,
                    "pp_virtual": args.pp_virtual,
                },
            )

        def _wrap_warm(fn, for_mesh, name="train_step"):
            # Per-topology store names ("train_step@d7", ...): the
            # elastic resize re-wraps against the entry the background
            # pre-compiler saved for exactly that device count.
            return warm_train_step(
                fn,
                store=warm_store,
                key=_exec_key(fn, for_mesh),
                name=name,
                on_ready=lambda rep: warm_report.update(rep),
            )

        step_fn = _wrap_warm(step_fn, mesh)
        if step_fn_off is not None:
            # Distinct store entry: the twin's aot_signature differs
            # only in integrity_every=None.
            step_fn_off = _wrap_warm(
                step_fn_off, mesh, name="train_step_off"
            )

    def full_params():
        """The replicated param tree for eval/generate: under FSDP the
        sharded flats are gathered back to the model layout (reads the
        CURRENT state)."""
        if args.fsdp:
            # Host-side assembly: no device-memory spike from the gather
            # itself (a device-side replicated gather would OOM at the 8B
            # scale FSDP exists for).  Before committing back to device,
            # cast to the model's compute dtype on HOST — the bf16 copy
            # is what decode runs on and is half the f32 tree.  (f32
            # configs commit f32: those are the small/test models.)
            host = ddp.fsdp_gather_params(
                model.cfg, state, mesh,
                tp_axis="model" if args.tp > 1 else None, host=True,
            )
            if model.cfg.dtype == jnp.bfloat16:
                import ml_dtypes

                host = jax.tree.map(
                    lambda x: x.astype(ml_dtypes.bfloat16), host
                )
            return jax.tree.map(jnp.asarray, host)
        if args.zero >= 3:
            # ZeRO-3 stores params as a flat 1/N shard; reassemble the
            # model-layout tree (device-side: the zero3 scale ceiling is
            # the opt+param residency, and eval needs the full tree
            # resident anyway).
            from distributeddataparallel_tpu.parallel.zero import (
                zero3_gather_params,
            )

            return zero3_gather_params(state, mesh)
        return state.params

    # Fault-tolerance wiring (training.fault_tolerance / utils.chaos):
    # the injector is a no-op unless --chaos / DDP_CHAOS asks for faults;
    # the counters make any recovery visible in the end-of-run log.
    from distributeddataparallel_tpu.training.fault_tolerance import (
        NonFiniteBreaker,
        ResilientCheckpointer,
        StepWatchdog,
    )
    from distributeddataparallel_tpu.utils.chaos import (
        FaultInjector,
        SimulatedPreemption,
    )
    from distributeddataparallel_tpu.utils.metrics import FaultCounters

    counters = FaultCounters()
    # Set by the launcher's supervision loop: which incarnation this is.
    counters.restarts = int(os.environ.get("DDP_RESTART_ATTEMPT", "0") or 0)
    if registry is not None:
        # Every subsystem's telemetry registers here instead of owning a
        # private dict; values are pulled lazily at export time (pure
        # host reads — the loader gauge is a qsize() call).
        registry.bind("faults", counters.summary)
        registry.bind("loader_prefetch_depth", lambda: loader.prefetch_depth)
    if args.chaos:
        # Marker state under the checkpoint dir: each chaos entry fires
        # at most once ACROSS supervised restarts.
        injector = FaultInjector(
            args.chaos,
            state_dir=(
                os.path.join(args.checkpoint_dir, ".chaos")
                if args.checkpoint_dir else None
            ),
        )
    else:
        injector = FaultInjector.from_env()
    # Injections land in the event stream next to their effects
    # (nan_skip / ckpt_retry / restart_attempt) — the gang timeline's
    # cause-and-effect pairs.
    injector.events = events
    breaker = NonFiniteBreaker(args.max_bad_steps) if args.nan_guard else None

    # Elastic gang runtime: on this CPU-simulation topology one process
    # hosts every fake-device rank as a gang member (the per-"proc"
    # analog used repo-wide), so the coordinator registers them all and
    # the resize is an in-process mesh rebuild.  On real multi-host TPU
    # the same coordinator runs one-member-per-process.
    gang = None

    def _data_mesh(m):
        return ddp.make_mesh(("data",), devices=jax.devices()[:m])

    if args.elastic:
        from distributeddataparallel_tpu.runtime.elastic_gang import (
            ElasticGangCoordinator,
        )

        _hb_env = os.environ.get("DDP_HEARTBEAT_TIMEOUT")
        _sus_env = os.environ.get("DDP_SUSPECT_AFTER")
        gang = ElasticGangCoordinator(
            elastic_store_dir(args),
            world=[f"proc{i}" for i in range(n_replicas)],
            min_size=args.min_procs,
            events=events,
            heartbeat_timeout_s=float(_hb_env) if _hb_env else None,
            suspect_after_s=float(_sus_env) if _sus_env else None,
        )
        gang.start()
        # The chaos worker-kill/host-kill/proposer-kill entries tombstone
        # members through the coordinator (and worker-join resurrects
        # them); the next poll() on the survivors runs the resize.  The
        # coordinator consults the injector back for slow-heartbeat
        # suppression, and fault breadcrumbs land in the store root so
        # the supervisor's gang_verdict can name the triggering fault.
        injector.gang = gang
        gang.chaos = injector
        injector.hosts = {
            str(i): f"proc{i}" for i in range(n_replicas)
        }
        injector.store_root = elastic_store_dir(args)
        if injector.fault_log is None:
            injector.fault_log = os.path.join(
                elastic_store_dir(args), "faults.jsonl"
            )

    precompiler = None

    def _launch_precompiler(live_state, live_batch, live_rng):
        """Background AOT compiles of the N±1 train steps (the
        topology-portable key family): a later resize re-wraps the step
        under the per-topology store name and lands on the executable
        compiled here instead of paying a cold compile mid-resize."""
        from distributeddataparallel_tpu.runtime.elastic_gang import (
            batch_template_for,
            state_template_for,
        )
        from distributeddataparallel_tpu.training.warm_start import (
            BackgroundPrecompiler,
        )

        rng_t = jax.ShapeDtypeStruct(live_rng.shape, live_rng.dtype)
        n_now = mesh.shape["data"]
        jobs = []
        for m in (n_now - 1, n_now + 1):
            if m < max(args.min_procs, 1) or m > len(jax.devices()):
                continue
            tgt = _data_mesh(m)
            fn = build_step_fn(tgt)
            st = state_template_for(live_state, mesh, tgt, zero=args.zero)
            bt = batch_template_for(live_batch, mesh, tgt)
            jobs.append((
                f"train_step@d{m}",
                _exec_key(fn, tgt),
                lambda fn=fn, st=st, bt=bt: (fn, (st, bt, rng_t)),
            ))
        return BackgroundPrecompiler(warm_store, jobs).start()

    ckpt = None
    start_epoch = 0
    preempted = {"signal": None}
    if args.checkpoint_dir:
        from distributeddataparallel_tpu.training.elastic import (
            elastic_restore,
            topology_meta,
        )

        ckpt = ResilientCheckpointer(
            args.checkpoint_dir, injector=injector, counters=counters,
            events=events,
        )
        flat_tp = (
            "model"
            if ((args.fsdp or args.zero) and args.tp > 1)
            else None
        )
        flat_ep = "expert" if (args.zero and args.ep > 1) else None
        # The pipe degree is recorded for EVERY pp run (not just ZeRO
        # flats): interleaved-1F1B storage (--pp-virtual) bakes the
        # (pp, virtual) geometry into the layer ROW ORDER, and the
        # restore guard needs both recorded to reject a mismatch.
        flat_pp = "pipe" if args.pp > 1 else None
        ckpt_meta = topology_meta(
            mesh,
            "fsdp" if args.fsdp
            else f"zero{args.zero}" if args.zero
            else "replicated",
            tp_axis=flat_tp,
            ep_axis=flat_ep,
            pp_axis=flat_pp,
            pp_virtual=args.pp_virtual,
        )
        if args.resume:
            # Elastic resume: the flat ZeRO/FSDP layouts reshard when the
            # checkpoint was written at a different topology.  FSDP
            # reshards across the data AND Megatron TP degrees; ZeRO-1
            # reshards across data AND any of its model axes (tp/ep/pp —
            # incl. PP stage-count changes).  Replicated layouts (plain
            # DP, and TP/EP/PP param layouts without flat opt state)
            # carry N-independent global shapes, so orbax re-slices them
            # to the new mesh on its own.
            state, start_epoch = elastic_restore(
                ckpt, state, mesh,
                layout=ckpt_meta["layout"],
                cfg=model.cfg if args.fsdp else None,
                tp_axis=flat_tp,
                ep_axis=flat_ep,
                pp_axis=flat_pp,
                pp_virtual=args.pp_virtual,
            )
        # Preemption handling (TPU-VM maintenance events deliver SIGTERM):
        # finish the in-flight step, checkpoint, exit cleanly.  Epoch
        # granularity: --resume continues from the NEXT epoch — the
        # interrupted epoch's remaining batches are skipped (the loader's
        # position isn't part of the state; params stay monotone, no
        # batch is ever applied twice).  The reference has no failure
        # handling at all beyond fail-fast join (ref dpp.py:62; SURVEY §5).
        import signal

        def _on_term(signum, frame):
            preempted["signal"] = signum
            log0("signal %d: will checkpoint at the current epoch and exit",
                 signum)

        try:
            signal.signal(signal.SIGTERM, _on_term)
        except ValueError:
            pass  # non-main thread (library use): no handler, no harm

    # Multi-host agreement cadence: the host-level allgather below forces
    # a cross-process sync, so it runs every k batches, not every batch
    # (bounded k-step response to the signal, 1/k the sync cost).
    PREEMPT_CHECK_EVERY = 8

    def preempt_agreed(batch_idx: int) -> bool:
        """Do ALL processes agree to stop?  SIGTERM delivery can straddle
        a batch boundary across hosts; acting on the local flag alone
        would send processes into mismatched collectives (a hang, and no
        checkpoint).  Multi-host: agree via a host-level allgather on a
        fixed batch cadence — every process calls it at the same batch
        indices, so the collective order stays uniform; any one signaled
        process stops everyone."""
        if ddp.get_world_size() == 1:
            return preempted["signal"] is not None
        if batch_idx % PREEMPT_CHECK_EVERY:
            return False
        import numpy as np
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.array([preempted["signal"] is not None], np.int32)
        )
        return bool(flags.sum() > 0)

    # Evaluation is exact over the padded tail: the loader emits a per-row
    # "valid" mask (0 on sampler-padded duplicate rows) and the masked eval
    # steps take per-row metrics, so padded rows contribute nothing.
    # Under --tp/--ep, eval runs directly on the sharded params (same
    # model, same per-layer psums) — no gathered replica is ever
    # materialized, and the specs come from the SAME source the train
    # step compiled with.
    eval_param_specs = None
    if args.tp > 1 or args.ep > 1:
        from distributeddataparallel_tpu.parallel.expert_parallel import (
            model_axes_param_specs,
        )

        eval_param_specs = model_axes_param_specs(
            state.params,
            tp_axis="model" if args.tp > 1 else None,
            ep_axis="expert" if args.ep > 1 else None,
        )
    eval_step = None
    if args.eval and args.pp > 1:
        # Pipelined forward-only eval: same microbatch ticks as training,
        # masked exactly over the sampler-padded tail.
        from distributeddataparallel_tpu.parallel import make_pp_eval_step

        eval_step = make_pp_eval_step(
            model.cfg, mesh=mesh,
            microbatches=args.pp_microbatches or args.pp,
        )
        eval_loader = DataLoader(
            build_dataset(args, train=False), per_replica_batch=args.batch_size,
            mesh=mesh, shuffle=False, seed=args.seed, drop_last=False,
            with_mask=True,
        )
    elif args.eval and args.fsdp:
        # Streaming masked eval over the sharded flats: per-layer gathers,
        # no full replicated tree, no 2x-params transient (ADVICE r2).
        eval_step = ddp.make_fsdp_eval_step(
            model.cfg, mesh=mesh,
            tp_axis="model" if args.tp > 1 else None,
            gather_dtype=jnp.bfloat16 if args.fsdp_gather == "bf16" else None,
        )
        eval_loader = DataLoader(
            build_dataset(args, train=False), per_replica_batch=args.batch_size,
            mesh=mesh, shuffle=False, seed=args.seed, drop_last=False,
            with_mask=True,
        )
    elif args.eval and cp:
        from distributeddataparallel_tpu.data import shard_lm_batch
        from distributeddataparallel_tpu.ops import (
            per_example_accuracy,
            per_example_cross_entropy,
        )
        from distributeddataparallel_tpu.parallel import make_cp_eval_step

        def metric_fn(params, batch):
            logits = model.apply({"params": params}, batch["inputs"])
            return {
                "loss": per_example_cross_entropy(logits, batch["targets"]),
                "accuracy": per_example_accuracy(logits, batch["targets"]),
            }
        eval_step = make_cp_eval_step(
            metric_fn, mesh=mesh, masked=True,
            param_specs=eval_param_specs,
        )
        eval_loader = DataLoader(
            build_dataset(args, train=False), per_replica_batch=args.batch_size,
            mesh=mesh, shuffle=False, seed=args.seed, drop_last=False,
            with_mask=True,
            place_fn=lambda b: shard_lm_batch(
                b["tokens"], mesh, valid=b["valid"]
            ),
        )
    elif args.eval:
        from distributeddataparallel_tpu.ops import (
            per_example_accuracy,
            per_example_cross_entropy,
        )

        if lm:
            def metric_fn(params, batch):
                toks = batch["tokens"]
                logits = model.apply({"params": params}, toks[:, :-1])
                return {
                    "loss": per_example_cross_entropy(logits, toks[:, 1:]),
                    "accuracy": per_example_accuracy(logits, toks[:, 1:]),
                }
        elif has_ms:
            def metric_fn(params, ms, batch):
                logits = model.apply(
                    {"params": params, **ms}, _img(batch), train=False
                )
                return {
                    "loss": per_example_cross_entropy(logits, batch["label"]),
                    "accuracy": per_example_accuracy(logits, batch["label"]),
                }
        else:
            def metric_fn(params, batch):
                logits = model.apply({"params": params}, _img(batch))
                return {
                    "loss": per_example_cross_entropy(logits, batch["label"]),
                    "accuracy": per_example_accuracy(logits, batch["label"]),
                }
        eval_step = make_eval_step(
            metric_fn, mesh=mesh, with_model_state=has_ms, masked=True,
            param_specs=eval_param_specs,
        )
        # drop_last=False: evaluation must cover the tail of the eval set
        # (sampler padding keeps per-replica counts equal, so the one
        # ragged final batch still shards evenly — worth the extra compile).
        eval_loader = DataLoader(
            build_dataset(args, train=False), per_replica_batch=args.batch_size,
            mesh=mesh, shuffle=False, seed=args.seed, drop_last=False,
            with_mask=True,
        )

    if len(loader) == 0:
        raise SystemExit(
            f"no training steps: dataset gives {loader.steps_per_epoch} "
            f"batches per replica (dataset too small for "
            f"--batch-size {args.batch_size} × {n_replicas} replicas)"
        )
    if args.bw_probe:
        probe = allreduce_bandwidth(mesh)
        log0(
            "all-reduce probe: %d dev, %.0f MB -> %.1f GB/s bus BW, "
            "%.1f%% of %s GB/s ICI peak",
            probe["devices"], probe["payload_mb"], probe["bus_bw_gb_s"],
            100 * probe["utilization"],
            f"{probe['peak_gb_s']:.0f}" if probe["peak_gb_s"] else "unknown",
        )

    # Throughput accounting: tokens/step for LMs, images/step otherwise
    # (the BASELINE tokens/s/chip and img/s/chip metrics).
    if lm:
        items_per_step, unit = args.batch_size * n_replicas * args.seq_len, "tok"
    else:
        items_per_step, unit = args.batch_size * n_replicas, "img"
    timer = StepTimer(window=max(20, args.log_every))

    # Performance attribution (observability.{cost_model,memory,goodput}):
    # MFU/HFU from the analytic FLOP model, memory sampling, and a
    # wall-clock goodput ledger.  Everything below is constructed once
    # here and consulted only at window boundaries / run edges — the hot
    # path never sees it.
    mfu_meter = mem_tel = goodput = None
    if events is not None:
        from distributeddataparallel_tpu.observability import GoodputLedger

        goodput = GoodputLedger()
    if args.mfu:
        from distributeddataparallel_tpu.observability import (
            MFUMeter,
            mlp_fwd_flops,
            peak_flops_for,
            simple_cnn_fwd_flops,
            train_step_flops,
            transformer_fwd_flops,
        )

        gbatch = args.batch_size * n_replicas
        remat = False
        if lm:
            # The LM step trains on the shifted sequence: seq_len-1
            # positions do forward/backward work.
            fwd = transformer_fwd_flops(
                model.cfg, batch=gbatch, seq_len=args.seq_len - 1
            )
            remat = bool(getattr(model.cfg, "remat", False))
        else:
            shape = tuple(
                getattr(dataset, "image_shape", None)
                or dataset.images.shape[1:]
            )
            if args.model == "cnn":
                fwd = simple_cnn_fwd_flops(
                    batch=gbatch, image_shape=shape,
                    num_classes=num_classes or 10,
                )
            else:  # mlp (resnet rejected in parse_args)
                in_features = 1
                for d in shape:
                    in_features *= int(d)
                fwd = mlp_fwd_flops(
                    batch=gbatch, in_features=in_features,
                    num_classes=num_classes or 10,
                )
        step_flops = train_step_flops(
            fwd, remat=remat,
            flop_signature=getattr(step_fn, "flop_signature", None),
        )
        peak = peak_flops_for(jax.devices()[0])
        mfu_meter = MFUMeter(
            step_flops,
            n_chips=ddp.global_device_count(),
            peak_flops_per_chip=peak,
            registry=registry,
            events=events,
        )
        log0(
            "mfu: %.3e model FLOPs/step (%.3e hw) over %d chip(s), "
            "peak %s FLOP/s/chip",
            step_flops["model_flops"], step_flops["hardware_flops"],
            ddp.global_device_count(),
            f"{peak:.2e}" if peak else "unknown",
        )
    if args.memory_telemetry:
        from distributeddataparallel_tpu.observability import MemoryTelemetry

        mem_tel = MemoryTelemetry(
            registry=registry, events=events, devices=jax.local_devices()
        )
    steps_total = (
        registry.counter("steps_total") if registry is not None else None
    )
    # Alerting + run summary: both consume ONLY numbers the window
    # boundary below already computed (same zero-extra-syncs discipline
    # as the meters above).
    alert_engine = None
    if args.alerts is not None:
        from distributeddataparallel_tpu.observability import (
            AlertEngine,
            parse_alert_spec,
        )

        alert_engine = AlertEngine(
            parse_alert_spec(args.alerts),
            events=events,
            registry=registry,
            on_fire=lambda a: warn0(
                "alert [%s] at step %s: value %s vs threshold %s",
                a["rule"], a["step"], a.get("value"), a.get("threshold"),
            ),
        )
    summary_builder = None
    if events is not None or args.runs_dir:
        from distributeddataparallel_tpu.observability import (
            RunSummaryBuilder,
        )

        summary_builder = RunSummaryBuilder()

    # Bounded async dispatch (training.warm_start.BoundedDispatch): the
    # loop no longer blocks the host every step — up to --dispatch-depth
    # steps stay in flight, and each step's guard handle (the nan flag
    # when --nan-guard is armed, else the loss) is settled when it falls
    # out of the window or at a boundary drain.  Numerically inert: the
    # devices execute the identical step sequence either way; only WHEN
    # the host reads the results changes.
    from distributeddataparallel_tpu.training.fault_tolerance import (
        note_warm_start,
    )
    from distributeddataparallel_tpu.training.warm_start import (
        BoundedDispatch,
        CompileCacheStats,
    )

    dispatch = BoundedDispatch(args.dispatch_depth)

    def settle(handle, where) -> None:
        """Host-sync one in-flight step: read the nan flag into the
        breaker (which may raise TrainingDiverged — within depth steps
        of the threshold crossing), or just block on the handle."""
        if breaker is None:
            jax.block_until_ready(handle)
            return
        bad = float(handle)
        if bad:
            counters.nonfinite_steps += 1
            e, b = where
            if events is not None:
                events.emit("nan_skip", step=e * spe + b, epoch=e, batch=b)
            if prof is not None:
                # First anomaly grabs a short trace of the steps right
                # after the blow-up — while it is still happening.
                prof.trigger_anomaly("nan_grad", e * spe + b)
            warn0(
                "non-finite gradients at epoch %d batch %d:"
                " update skipped", e, b,
            )
        breaker.observe(bad)

    def drain() -> None:
        """Boundary sync: settle everything in flight.  Runs at metrics
        windows, log lines, checkpoint/eval edges, and epoch ends, so
        those points always observe fully-synced state and the nan
        guard's decision point is never crossed unobserved."""
        for h, w in dispatch.drain():
            with _span("settle"):
                settle(h, w)

    # Step watchdog: a wedged collective or infeed stall should produce a
    # diagnostic and a best-effort checkpoint, not a silent hang.  Armed
    # only after the first step completes so compile time never counts
    # against the deadline.
    watchdog = None
    if args.step_timeout:
        def _on_wedge(diag):
            counters.watchdog_fires += 1
            last = diag.get("last_known_state") or {}
            if events is not None:
                events.emit(
                    "watchdog_fire",
                    seconds_since_heartbeat=diag.get(
                        "seconds_since_heartbeat"
                    ),
                    last_known_state=last,
                )
                events.flush()  # the process is about to exit 75
            if prof is not None:
                # immediate=True: the loop is wedged — there may never
                # be another step to close a windowed capture on.
                prof.trigger_anomaly(
                    "watchdog",
                    int(last.get("epoch", 0)) * spe
                    + int(last.get("batch", 0)),
                    immediate=True,
                )
            if ckpt is None:
                return
            # Best-effort: saving may itself block on the wedged
            # computation, in which case the watchdog's grace timer
            # still terminates the process.
            try:
                last = diag.get("last_known_state") or {}
                ckpt.save(state, int(last.get("epoch", start_epoch)),
                          meta=ckpt_meta)
            # ddplint: allow[broad-except] — the process is exiting
            except Exception:  # noqa: BLE001 — the process is exiting
                warn_all("watchdog: emergency checkpoint failed")
        watchdog = StepWatchdog(args.step_timeout, on_timeout=_on_wedge)

    # Global step index for the chaos schedule: stable across restarts
    # because it is (epoch, batch)-derived, not a live counter.
    spe = len(loader)
    if args.steps_per_epoch:
        spe = min(spe, args.steps_per_epoch)

    last_loss = float("nan")
    warm_logged = False

    # Silent-data-corruption defense (training.integrity): the compiled
    # step already carries the cadence-gated digest (integrity_every was
    # passed to the factory); this host side mirrors the cadence gate —
    # ONE device sync pre-loop, then pure host arithmetic — votes on the
    # gathered digest matrix when a check lands, and evicts the corrupt
    # rank through the elastic gang.
    integrity = None
    integrity_shadow_fn = None
    integrity_step = 0
    sdc_source = None  # voted-healthy rank to re-replicate from on evict
    if args.integrity_every:
        from distributeddataparallel_tpu.training import (
            integrity as integrity_mod,
        )

        integrity = integrity_mod.IntegrityChecker(
            every=args.integrity_every,
            leaf_names=integrity_mod.digest_leaf_names(
                integrity_mod.digest_parts(state, args.zero)
            ),
            events=events, counters=counters,
        )

        def _integrity_rearm(for_step_fn, for_mesh, world):
            # The replay tiebreak only exists where the vote cannot
            # decide (exactly 2 ranks); shadow mode replaces it (the
            # double-execution check needs the pre-step copy for
            # itself).  Rebuilt on every topology change.
            nonlocal integrity_shadow_fn
            integrity.arbiter = (
                integrity_mod.ShadowArbiter(
                    for_step_fn,
                    integrity_mod.make_digest_fn(
                        for_mesh, zero_level=args.zero
                    ),
                )
                if world == 2 and not args.integrity_shadow else None
            )
            integrity_shadow_fn = (
                integrity_mod.make_digest_fn(for_mesh, zero_level=args.zero)
                if args.integrity_shadow else None
            )

        _integrity_rearm(step_fn, mesh, n_replicas)
        integrity_step = int(jax.device_get(state.step))

    # Per-step RNG is a pure function of (seed, epoch, batch): a --resume'd
    # run continues the exact stochastic stream (dropout etc.) the
    # uninterrupted run would have used, instead of replaying epoch-0 keys.
    base_rng = jax.random.PRNGKey(args.seed + 1)
    try:
        set_tracer(tracer)
        for epoch in range(start_epoch, args.epochs):    # ref dpp.py:44
            epoch_rng = jax.random.fold_in(base_rng, epoch)
            # Legacy whole-epoch trace only when the windowed capture
            # isn't driving the (global, single-slot) profiler.
            with _span("epoch", epoch=epoch), profile_trace(
                args.profile_dir
                if epoch == start_epoch and not args.profile_steps
                else None,
                sync=lambda: state.params,  # resolves to latest state at exit
            ):
                loader.set_epoch(epoch)                  # ref dpp.py:46
                stream = _SwappableStream(loader)
                for batch_idx, batch in stream:          # ref dpp.py:47
                    if args.steps_per_epoch \
                            and batch_idx >= args.steps_per_epoch:
                        break
                    gstep = epoch * spe + batch_idx
                    if prof is not None:
                        prof.on_step_start(gstep)
                    injector.before_step(gstep)   # slow-step / preempt
                    batch = injector.corrupt_batch(batch, gstep)
                    # Silent HBM corruption: XOR one bit of one param
                    # leaf on one rank (chaos bitflip; a no-op without a
                    # matching entry).
                    state = injector.corrupt_state(state, gstep, mesh=mesh)
                    sub = jax.random.fold_in(epoch_rng, batch_idx)
                    sdc_pend = None
                    if (
                        integrity is not None
                        and integrity.due(integrity_step)
                        and (integrity.arbiter is not None
                             or integrity_shadow_fn is not None)
                    ):
                        # The replay tiebreak / shadow re-execution needs
                        # this step's input state, and the step donates
                        # it — copy before dispatch, only on cadence.
                        sdc_pend = integrity_mod.copy_tree(state)
                    if lint_target is not None:
                        # First batch: everything the step consumes is
                        # now concrete, and nothing is compiled yet —
                        # trace-only lint fails fast before the compile.
                        from distributeddataparallel_tpu.analysis import (
                            graph_lint,
                            schedule_lint,
                            shard_flow,
                        )
                        from distributeddataparallel_tpu.observability.memory import (
                            hbm_budget_bytes,
                        )

                        rep = graph_lint.lint_train_step(
                            lint_target, state, batch, sub
                        )
                        if summary_builder is not None:
                            summary_builder.sample(
                                collective_fp=rep.fingerprint
                            )
                        fp_target = None
                        flow = shard_flow.analyze_step(
                            lint_target, state, batch, sub,
                            mode=rep.mode,
                            hbm_budget_bytes=hbm_budget_bytes(),
                        )
                        all_findings = rep.findings + flow.findings
                        ir = getattr(lint_target, "schedule_ir", None)
                        if ir is None and getattr(
                            lint_target, "comm_schedule", None
                        ) is not None:
                            ir = lint_target.comm_schedule(state.params)
                        if ir is not None:
                            hops = sum(
                                c.effective_count
                                for c in (rep.collectives or [])
                                if c.prim == ir.hop_prim
                                and ir.hop_axis in c.axes and c.nonscalar
                            )
                            all_findings += schedule_lint.lint_schedule(
                                ir,
                                manifest=getattr(
                                    lint_target, "collective_manifest",
                                    None,
                                ),
                                traced_hops=hops,
                                bubble=getattr(
                                    lint_target, "bubble_accounting",
                                    None,
                                ),
                                where=f"sched:{rep.mode}:{ir.kind}",
                            )
                        lint_target = None
                        if all_findings:
                            raise SystemExit(
                                "--lint-step: train step violates its "
                                "SPMD invariants:\n" + "\n".join(
                                    str(f) for f in all_findings
                                )
                            )
                        log0(
                            "lint-step [%s] clean: collective fp=%s %s "
                            "flow-collectives=%d%s",
                            rep.mode, rep.fingerprint,
                            rep.collective_counts,
                            len(flow.collectives),
                            f" schedule={ir.kind}" if ir is not None
                            else "",
                        )
                    if fp_target is not None:
                        # One trace on the first batch to stamp the
                        # run_summary with the GL002 collective
                        # fingerprint (skipped if --lint-step already
                        # computed it above).
                        if summary_builder is not None:
                            from distributeddataparallel_tpu.analysis import (
                                graph_lint,
                            )

                            try:
                                summary_builder.sample(
                                    collective_fp=graph_lint.collective_fingerprint(
                                        graph_lint.collect_collectives(
                                            jax.make_jaxpr(fp_target)(
                                                state, batch, sub
                                            )
                                        )
                                    )
                                )
                            # ddplint: allow[broad-except] — fingerprint is
                            # telemetry; an untraceable step must not kill
                            # the run
                            except Exception:  # noqa: BLE001
                                pass
                        fp_target = None
                    # The step span times host-side dispatch (plus any
                    # window-overflow settles) — the honest per-step
                    # number for an async loop; device wall time lands
                    # in the readings at drain boundaries.
                    with _span("step", step=gstep):
                        # Off cadence the plain twin runs — bit-identical
                        # update, no digest machinery in the program at
                        # all (the host counter mirrors the in-graph
                        # cadence gate, so the two never disagree).
                        use_fn = (
                            step_fn_off
                            if step_fn_off is not None
                            and integrity is not None
                            and not integrity.due(integrity_step)
                            else step_fn
                        )
                        if not warm_logged:
                            # The first dispatch traces and compiles:
                            # count its persistent-cache traffic alone
                            # (hit = entry loaded, miss = entry written;
                            # other jits make their own).
                            cache_stats = CompileCacheStats()
                        state, metrics = use_fn(state, batch, sub)
                        if not warm_logged:
                            cache_stats.close()
                        # Bounded async dispatch: enqueue this step's
                        # guard handle and settle only what falls out of
                        # the K-deep window (the old pattern blocked
                        # here every step when the nan guard was armed).
                        guard = (
                            metrics["nonfinite_grad"]
                            if breaker is not None
                            else metrics["loss"]
                        )
                        for h, w in dispatch.push(guard, (epoch, batch_idx)):
                            with _span("settle"):
                                settle(h, w)
                    if integrity is not None:
                        on_cadence = integrity.due(integrity_step)
                        integrity_step += 1
                        if on_cadence:
                            import numpy as np

                            # The ONLY integrity host sync, and only on
                            # cadence: fetch the (n_ranks, n_leaves)
                            # digest matrix the step just gathered.
                            mat = np.asarray(
                                jax.device_get(metrics["sdc_digest"])
                            )
                            verdict = integrity.check(mat, step=gstep)
                            if verdict.ok:
                                if integrity.arbiter is not None:
                                    integrity.arbiter.commit(sdc_pend)
                                if (
                                    integrity_shadow_fn is not None
                                    and sdc_pend is not None
                                ):
                                    # Transient-SDC probe: same program,
                                    # same inputs, second execution —
                                    # any digest disagreement is compute
                                    # corruption, catchable even at DP=1.
                                    shadow_state, _ = step_fn(
                                        sdc_pend, batch, sub
                                    )
                                    live_d = np.asarray(jax.device_get(
                                        integrity_shadow_fn(state)
                                    ))
                                    shad_d = np.asarray(jax.device_get(
                                        integrity_shadow_fn(shadow_state)
                                    ))
                                    if not (live_d == shad_d).all():
                                        integrity.note_shadow_mismatch(
                                            step=gstep
                                        )
                            elif verdict.corrupt and gang is not None:
                                # Closed loop: tombstone the corrupt
                                # rank(s); this iteration's gang.poll()
                                # below lands the resize, resharding the
                                # survivors' verified live state from a
                                # voted-healthy source rank.  The step
                                # that detected the mismatch already
                                # discarded its own update, so nothing
                                # the liar sent ever reached the
                                # surviving params.  No restart budget,
                                # no checkpoint read.
                                sdc_source = next(
                                    r for r in range(n_replicas)
                                    if r not in verdict.corrupt
                                )
                                for bad in verdict.corrupt:
                                    gang.kill(str(bad))
                                    integrity.note_eviction(bad, step=gstep)
                                log0(
                                    "integrity: digest mismatch at step "
                                    "%d — rank(s) %s corrupt (%s, leaves "
                                    "%s); evicting via elastic resize",
                                    gstep, list(verdict.corrupt),
                                    verdict.method, list(verdict.leaves),
                                )
                            else:
                                # Detection without an eviction path (no
                                # --elastic, or an unresolved tie): the
                                # update was discarded in-program, so
                                # state is still clean — stop loudly
                                # rather than train on with known-bad
                                # hardware.
                                raise SystemExit(
                                    f"integrity: replica digest mismatch "
                                    f"at step {gstep} "
                                    f"(corrupt={list(verdict.corrupt)}, "
                                    f"tie={verdict.tie}) and no eviction "
                                    f"path — rerun with --elastic, or "
                                    f"restore from a verified checkpoint"
                                )
                        if integrity.arbiter is not None:
                            integrity.arbiter.hold(batch, sub)
                    if steps_total is not None:
                        steps_total.inc()  # host int increment, no sync
                    if prof is not None:
                        prof.on_step_end(gstep)
                    if watchdog is not None:
                        if watchdog.running:
                            watchdog.beat(epoch=epoch, batch=batch_idx)
                        else:
                            jax.block_until_ready(state.step)
                            watchdog.start(epoch=epoch, batch=batch_idx)
                    reading = timer.tick(items_per_step, sync=state.step)
                    if timer.compile_s is not None and not warm_logged:
                        # First step done: record how it was acquired
                        # (aot / cache-hit / cold / jit) + time-to-ready,
                        # per incarnation — the restart path's warm-start
                        # regression signal.
                        warm_logged = True
                        note_warm_start(
                            counters,
                            mode=warm_report.get("mode", "jit"),
                            first_step_s=timer.compile_s,
                            events=events,
                            cache_hits=cache_stats.hits,
                            cache_misses=cache_stats.misses,
                        )
                        if goodput is not None:
                            goodput.add("compile", timer.compile_s)
                        if (
                            gang is not None
                            and args.compile_cache
                            and precompiler is None
                        ):
                            # First step done (live avals now known):
                            # queue the N±1 pre-compiles off-thread.
                            precompiler = _launch_precompiler(
                                state, batch, sub
                            )
                        if events is not None and "pp_phase_counts" in metrics:
                            # Measured-schedule counters: the compiled
                            # scan counted useful (valid) slots per
                            # stage per phase; emit them once with the
                            # factory's analytic accounting so the
                            # report can reconstruct the measured
                            # bubble post hoc.
                            from distributeddataparallel_tpu.observability.pipeline import (
                                phase_counts_payload,
                            )
                            events.emit("pp_phase", **phase_counts_payload(
                                jax.device_get(metrics["pp_phase_counts"]),
                                schedule=args.pp_schedule,
                                n_stages=args.pp,
                                virtual=args.pp_virtual,
                                microbatches=args.pp_microbatches or args.pp,
                                accounting=getattr(
                                    step_fn, "bubble_accounting", None
                                ),
                                step=gstep,
                            ))
                        if mem_tel is not None:
                            # One-time compiler memory budget for the
                            # step program.  lower().compile() is a
                            # SECOND compile (the jit cache does not
                            # serve AOT lowering), so it runs here —
                            # after the first step was timed — and only
                            # under --memory-telemetry.
                            lower = getattr(step_fn, "lower", None)
                            if lower is not None:
                                t_aot = time.perf_counter()
                                try:
                                    compiled = lower(
                                        state, batch, sub
                                    ).compile()
                                    # Pallas kernels reach the TPU as
                                    # this custom call: its count says
                                    # which attention the step runs.
                                    mem_tel.note_executable(
                                        compiled,
                                        label="train_step",
                                        tpu_custom_calls=compiled.as_text()
                                        .count(
                                            'custom_call_target='
                                            '"tpu_custom_call"'
                                        ),
                                    )
                                # ddplint: allow[broad-except] — optional
                                # telemetry; backends without AOT memory
                                # analysis must degrade, not abort train
                                except Exception:  # noqa: BLE001
                                    warn0(
                                        "memory-telemetry: step memory "
                                        "analysis unavailable"
                                    )
                                if goodput is not None:
                                    goodput.add(
                                        "compile",
                                        time.perf_counter() - t_aot,
                                    )
                                timer.reset()  # don't bill the window
                    if reading:
                        drain()  # window boundary: fully-synced state
                        if registry is not None:
                            # StepTimer readings feed the registry; the
                            # values are already host floats.
                            g = registry.gauge
                            g("items_per_s").set(reading["items_per_s"])
                            g("items_per_s_per_chip").set(
                                reading["items_per_s_per_chip"]
                            )
                            g("steps_per_s").set(reading["steps_per_s"])
                        if mfu_meter is not None:
                            att = mfu_meter.on_reading(reading, step=gstep)
                            if att["mfu"] is not None:
                                log0(
                                    "mfu: %.2f%% (hfu %.2f%%, "
                                    "%.3e model FLOP/s)",
                                    100 * att["mfu"], 100 * att["hfu"],
                                    att["model_flops_per_s"],
                                )
                        mem_sample = None
                        if mem_tel is not None:
                            # Window boundary: drain() already ran, so
                            # this never introduces a sync of its own.
                            mem_sample = mem_tel.sample(gstep)
                        window_step_s = (
                            1.0 / reading["steps_per_s"]
                            if reading["steps_per_s"] else None
                        )
                        window_mfu = (
                            att["mfu"] if mfu_meter is not None else None
                        )
                        window_hwm = (
                            mem_sample.get("live_hwm_bytes")
                            if mem_sample else None
                        )
                        if summary_builder is not None:
                            summary_builder.sample(
                                step_s=window_step_s,
                                mfu=window_mfu,
                                live_hwm_bytes=window_hwm,
                                steps_total=gstep + 1,
                            )
                        if alert_engine is not None:
                            # Same boundary discipline as the meters
                            # above: every signal is a host float this
                            # block already computed — evaluating the
                            # rules can never force a device sync.
                            gsum = (
                                goodput.summary()
                                if goodput is not None else {}
                            )
                            alert_engine.observe(
                                step=gstep,
                                step_s=window_step_s,
                                mfu=window_mfu,
                                live_hwm_bytes=window_hwm,
                                goodput=gsum.get("goodput"),
                                elapsed_s=gsum.get("total_s"),
                                prefetch_depth=(
                                    loader.prefetch_depth
                                    if args.workers > 0 else None
                                ),
                                restarts=counters.restarts,
                                sdc_detects=counters.sdc_detects,
                                gang_suspects=(
                                    len(gang.suspects_now)
                                    if gang is not None else 0
                                ),
                            )
                        log0(
                            "throughput: %.0f %s/s (%.1f %s/s/chip)",
                            reading["items_per_s"], unit,
                            reading["items_per_s_per_chip"], unit,
                        )
                    if (
                        registry is not None
                        and args.metrics_every
                        and gstep % args.metrics_every == 0
                    ):
                        # Periodic snapshot into the event log: pure
                        # host reads (counters, gauges, the loader's
                        # qsize), so this cadence adds no device sync.
                        registry.export(step=gstep)
                    if batch_idx % args.log_every == 0:  # ref dpp.py:54-55
                        drain()
                        last_loss = float(metrics["loss"])
                        log0("Epoch %d, Batch %d, Loss: %.4f",
                             epoch, batch_idx, last_loss)
                    if ckpt is not None and preempt_agreed(batch_idx):
                        drain()  # checkpoint edge: fully-synced state
                        t_ck = time.perf_counter()
                        with _span("ckpt_save", epoch=epoch):
                            ckpt.save(state, epoch, meta=ckpt_meta)
                            ckpt.wait()
                        if goodput is not None:
                            goodput.add(
                                "checkpoint", time.perf_counter() - t_ck
                            )
                        log0("preempted: checkpoint saved mid-epoch %d; "
                             "--resume continues from epoch %d",
                             epoch, epoch + 1)
                        ddp.destroy_process_group()
                        return float(metrics["loss"])
                    if gang is not None:
                        decision = gang.poll()
                        if decision is not None:
                            # RESIZE, not restart: survivors agreed on
                            # membership epoch k+1 — rebuild the mesh one
                            # (or more) members smaller and keep going
                            # with the LIVE state.  Nothing below reads a
                            # checkpoint.
                            t_rs = time.perf_counter()
                            drain()  # nothing in flight crosses the swap
                            from distributeddataparallel_tpu.data.sharded import (  # noqa: E501
                                resize_index_plan,
                            )
                            from distributeddataparallel_tpu.runtime.elastic_gang import (  # noqa: E501
                                measure_downtime,
                                reshard_live_state,
                            )

                            old_world = n_replicas
                            new_world = decision.new_size
                            old_mesh, mesh = mesh, _data_mesh(new_world)
                            # Checkpoint-free shrink: host round-trip of
                            # the live arrays through the positional
                            # flat-reshard math (training.elastic).
                            # After an SDC eviction the replicated
                            # leaves re-replicate from the voted-healthy
                            # rank — device_get's default (device 0's
                            # buffer) would resurrect the corruption
                            # when rank 0 was the liar.
                            state = reshard_live_state(
                                state, old_mesh, mesh, zero=args.zero,
                                source=sdc_source,
                            )
                            sdc_source = None
                            # Exactly-once data: the unconsumed tail of
                            # this epoch's permutation, reshuffled under
                            # an epoch-keyed reseed and dealt to the new
                            # world.
                            plan = resize_index_plan(
                                len(dataset),
                                per_replica_batch=args.batch_size,
                                old_world=old_world,
                                new_world=new_world,
                                consumed_steps=batch_idx + 1,
                                seed=args.seed, epoch=epoch,
                                membership_epoch=decision.epoch,
                            )
                            tail = DataLoader(
                                dataset,
                                per_replica_batch=args.batch_size,
                                mesh=mesh, shuffle=True, seed=args.seed,
                                place_fn=place_fn, workers=args.workers,
                                augment=augment, index_shards=plan,
                            )
                            tail.events = events
                            stream.swap(tail)
                            step_fn = build_step_fn(mesh)
                            if step_fn_off is not None:
                                step_fn_off = build_step_fn(
                                    mesh, integrity=False
                                )
                            if args.compile_cache:
                                # The per-topology store name the
                                # background pre-compiler saved — a
                                # resize lands on an AOT load.
                                step_fn = _wrap_warm(
                                    step_fn, mesh,
                                    name=f"train_step@d{new_world}",
                                )
                                if step_fn_off is not None:
                                    step_fn_off = _wrap_warm(
                                        step_fn_off, mesh,
                                        name=f"train_step_off@d{new_world}",
                                    )
                            n_replicas = new_world
                            if integrity is not None:
                                # New mesh, new step: rebuild the shadow
                                # digest fn and (de)arm the 2-rank
                                # replay tiebreak for the new world.
                                _integrity_rearm(step_fn, mesh, new_world)
                            items_per_step = (
                                args.batch_size * n_replicas * args.seq_len
                                if lm
                                else args.batch_size * n_replicas
                            )
                            if ckpt is not None:
                                ckpt_meta = topology_meta(
                                    mesh,
                                    f"zero{args.zero}" if args.zero
                                    else "replicated",
                                )
                            if eval_step is not None:
                                eval_step = make_eval_step(
                                    metric_fn, mesh=mesh,
                                    with_model_state=has_ms, masked=True,
                                )
                                eval_loader = DataLoader(
                                    build_dataset(args, train=False),
                                    per_replica_batch=args.batch_size,
                                    mesh=mesh, shuffle=False,
                                    seed=args.seed, drop_last=False,
                                    with_mask=True,
                                )
                            if mfu_meter is not None:
                                mfu_meter = None
                                warn0(
                                    "elastic resize: MFU meter disabled "
                                    "(chip count changed mid-run)"
                                )
                            downtime = measure_downtime(t_rs)
                            if events is not None:
                                events.emit(
                                    "resize_downtime",
                                    epoch=decision.epoch,
                                    seconds=round(downtime, 3),
                                )
                            if goodput is not None:
                                goodput.add("resize", downtime)
                            log0(
                                "elastic resize: %d -> %d replicas "
                                "(membership epoch %d, left: %s) in "
                                "%.2fs — no checkpoint read",
                                old_world, new_world, decision.epoch,
                                list(decision.left), downtime,
                            )
                            timer.reset()  # don't bill the window
            drain()  # epoch edge: eval/checkpoint see fully-synced state
            last_loss = float(metrics["loss"])
            if eval_step is not None:
                # Masked eval: each step returns (masked means, valid-row
                # count); weighting means by counts is exactly the mean over
                # unique samples — sampler pad duplicates contribute nothing.
                # FSDP streams over the sharded flats; everything else gets
                # the (possibly gathered) model-layout tree.
                t_ev = time.perf_counter()
                with _span("eval", epoch=epoch):
                    eval_params = state.params if args.fsdp else full_params()
                    evals = []
                    for b in eval_loader:
                        m, cnt = (
                            eval_step(eval_params, state.model_state, b)
                            if has_ms and not cp
                            else eval_step(eval_params, b)
                        )
                        evals.append((m, float(cnt)))
                    # Free the gathered copy NOW — keeping a full
                    # replicated param tree alive through the next
                    # training epoch would undo exactly the memory FSDP
                    # shards away.
                    del eval_params
                if goodput is not None:
                    goodput.add("eval", time.perf_counter() - t_ev)
                if evals:
                    total = sum(n for _, n in evals)
                    mean = {
                        k: float(sum(float(e[k]) * n for e, n in evals) / total)
                        for k in evals[0][0]
                    }
                    log0("Epoch %d eval: %s", epoch, mean)
            if ckpt is not None:
                t_ck = time.perf_counter()
                with _span("ckpt_save", epoch=epoch):
                    ckpt.save(state, epoch, meta=ckpt_meta)
                if goodput is not None:
                    goodput.add("checkpoint", time.perf_counter() - t_ck)
            if eval_step is not None or ckpt is not None:
                # Don't let eval/checkpoint wall time pollute throughput.
                timer.reset()
    except SimulatedPreemption as pe:
        # Chaos preemption dies the way a real one does — abruptly and
        # nonzero, WITHOUT a parting checkpoint — so the supervisor
        # (--max-restarts) resumes from the last durable epoch.
        warn_all("%s", pe)
        raise SystemExit(1) from pe
    # ddplint: allow[broad-except] — re-raises after releasing the group
    except BaseException:
        # Divergence (nan-guard breaker) or any other abort must not
        # strand the process group: the next train() in this process —
        # a supervised respawn runs in a fresh one — would hit the
        # init-twice guard.
        ddp.destroy_process_group()
        raise
    finally:
        # The run's tracer leaves with the run: its event log closes below,
        # and a later loader in this process must not span into it.
        set_tracer(None)
        if watchdog is not None:
            watchdog.stop()
        if prof is not None:
            prof.close()
        if registry is not None:
            # Final snapshot always lands, whatever the exit path.
            try:
                registry.export(final=True)
            # ddplint: allow[broad-except] — telemetry must not mask exit
            except Exception:  # noqa: BLE001 — telemetry must not mask
                pass
        run_summary = None
        if summary_builder is not None:
            exc = sys.exc_info()[1]
            try:
                run_summary = summary_builder.build(
                    goodput=goodput.summary() if goodput is not None else None,
                    restarts=counters.restarts,
                    alerts_total=(
                        len(alert_engine.fired)
                        if alert_engine is not None else 0
                    ),
                    status="ok" if exc is None else type(exc).__name__,
                )
            # ddplint: allow[broad-except] — telemetry must not mask exit
            except Exception:  # noqa: BLE001
                run_summary = None
        if events is not None:
            exc = sys.exc_info()[1]
            if goodput is not None:
                # The run's own wall-time attribution, just before
                # run_end; the offline reconstruction adds what this
                # incarnation cannot see (inter-incarnation restart gaps).
                events.emit("goodput", **goodput.summary())
            if run_summary is not None:
                # The ~10 numbers this incarnation boils down to — what
                # the runs store and perf gate consume.
                events.emit("run_summary", **run_summary)
            events.emit(
                "run_end",
                status="ok" if exc is None else type(exc).__name__,
                faults=counters.summary(),
            )
            events.close()
            if jax.process_index() == 0 and not os.environ.get(
                "_DDP_SUPERVISED"
            ):
                # Unsupervised runs merge their own gang timeline; under
                # supervision the launcher does it after the LAST
                # incarnation, so the merge sees every attempt's events.
                from distributeddataparallel_tpu.observability import (
                    merge_timeline,
                )

                merge_timeline(args.events_dir)
        if (
            run_summary is not None
            and args.runs_dir
            and jax.process_index() == 0
            and not os.environ.get("_DDP_SUPERVISED")
        ):
            # Longitudinal store: one line per run.  Supervised runs are
            # appended by the launcher instead, whose summary spans every
            # incarnation (this one would only cover the last).
            from distributeddataparallel_tpu.observability import append_run

            try:
                append_run(args.runs_dir, run_summary, source="trainer")
            # ddplint: allow[broad-except] — telemetry must not mask exit
            except Exception:  # noqa: BLE001
                warn0("runs-dir: could not append run summary")
    if counters.total:
        log0("fault summary: %s", counters.summary())

    if args.generate:
        # Demo of the KV-cache decode path: greedily continue a training
        # prompt with the trained params (models.generate).  Replicated
        # params only (plain DP / ZeRO) — sharded-layout serving is not
        # wired into the CLI.
        import numpy as np

        from distributeddataparallel_tpu.models import generate as _gen

        prompt = jnp.asarray(
            dataset.tokens[:2, : max(args.seq_len // 4, 1)], jnp.int32
        )
        n_new = min(args.generate, model.cfg.max_seq_len - prompt.shape[1])
        gen_model = model
        if args.fsdp and model.cfg.tp_axis is not None:
            # FSDP x TP: full_params() reassembled the FULL unsharded
            # tree, so decode runs on a TP-free twin config.
            import dataclasses

            from distributeddataparallel_tpu.models import TransformerLM

            gen_model = TransformerLM(
                dataclasses.replace(model.cfg, tp_axis=None)
            )
        out = _gen(
            gen_model, full_params(), prompt, n_new,
            quantize=args.decode_quant,
        )
        log0("generate: prompt %s -> %s (last 8 tokens: %s)%s",
             prompt.shape, out.shape, np.asarray(out[0, -8:]).tolist(),
             " [int8 weights]" if args.decode_quant else "")

    if ckpt is not None:
        ckpt.wait()
    if precompiler is not None:
        # XLA calls std::terminate if the interpreter tears down while
        # the background thread is mid-compile — wait the N±1 jobs out.
        precompiler.join(timeout=300)
    if gang is not None:
        # Clean exit: deregister the hosted members so a later run in
        # the same store starts from an empty gang, not ghost members.
        gang.stop()
    ddp.destroy_process_group()                          # ref dpp.py:57
    return last_loss


def _worker(process_id, argv, result_file=None):
    """Supervised-run payload: one full train() in a child process.

    Module-level (not a closure) so the spawn start method can pickle it;
    the ``if __name__`` guard below keeps the re-import from recursing.
    ``result_file``, when given, receives the final loss — the only
    channel a crashed-and-restarted child has back to its test harness.
    """
    del process_id  # single-process gangs; jax sees a local mesh
    args = parse_args(argv)
    validate_args(args)
    select_device(args)
    loss = train(args)
    if result_file:
        with open(result_file, "w") as fh:
            fh.write(repr(float(loss)))


def main(argv=None):
    args = parse_args(argv)
    validate_args(args)
    # Before anything compiles or spawns: JAX's persistent cache goes
    # where the environment says (else <checkout>/.jax_cache), exported
    # so supervised workers and respawns land on the same entries.
    from distributeddataparallel_tpu.training.warm_start import (
        resolve_compile_cache,
    )

    resolve_compile_cache()
    if args.max_restarts > 0 and not os.environ.get("_DDP_SUPERVISED"):
        # Supervised mode: run the trainer in a child gang under
        # runtime.launcher.spawn, which restarts it (up to the budget) on
        # any nonzero exit — chaos preemption, watchdog exit code 75, a
        # real crash.  The child argv gains --resume so every restart
        # continues from the newest intact checkpoint instead of epoch 0.
        from distributeddataparallel_tpu.runtime.launcher import spawn

        child_argv = list(argv) if argv is not None else sys.argv[1:]
        if "--resume" not in child_argv:
            child_argv.append("--resume")
        child_env = {"_DDP_SUPERVISED": "1"}
        if args.elastic:
            # Same rendezvous root for every incarnation: the supervisor
            # reads it to tell a shrunk-roster death (resize-respawn)
            # from a plain crash (restart).
            child_env["DDP_ELASTIC_DIR"] = elastic_store_dir(args)
        spawn(
            _worker, args=(child_argv,), nprocs=1,
            max_restarts=args.max_restarts,
            env=child_env,
            # Supervisor-side observability: restart attempts land in
            # events-supervisor.jsonl and the per-worker logs merge into
            # one gang timeline.jsonl when supervision ends.
            events_dir=args.events_dir,
            # The supervisor writes the runs-store summary for supervised
            # runs — its view spans every incarnation + restart gaps.
            runs_dir=args.runs_dir,
            elastic_store=elastic_store_dir(args) if args.elastic else None,
            min_procs=args.min_procs,
        )
        return
    select_device(args)
    train(args)


if __name__ == "__main__":
    main()
