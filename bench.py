#!/usr/bin/env python
"""Benchmark harness: prints ONE JSON line with the headline metric.

Headline (BASELINE.md config 3): ResNet-50 ImageNet-shape data-parallel
training throughput, img/s/chip, target >=70% of A100 NCCL-DDP per-chip
throughput.  A100 DDP ResNet-50 (mixed precision, per-chip) is ~2500
img/s; vs_baseline is measured against 0.7 * 2500 = 1750 img/s/chip.

The JSON line also carries an ``extras`` payload (BASELINE config 4 +
VERDICT r1 items 3/10): GPT-2 124M LM tokens/s/chip with the Pallas
flash kernel vs the XLA attention path (winner recorded), device kind,
batch geometry, and per-step time distribution.

Runs on however many chips are visible (the driver provides one real TPU
chip); DP sharding is exercised whenever device_count > 1.
"""

from __future__ import annotations

import json
import time

A100_DDP_RESNET50_IMG_S = 2500.0  # per-chip, AMP, the BASELINE §3 yardstick
TARGET_FRACTION = 0.70


#: Peak bf16 FLOPS / HBM bandwidth by device kind — the MFU and
#: HBM-utilization denominators.  A device kind that is not in the table
#: is an error: a utilization against a guessed peak is not a number.
_PEAKS = {
    "tpu v5 lite": (197e12, 819e9),
    "tpu v5e": (197e12, 819e9),
    "tpu v5p": (459e12, 2765e9),
    "tpu v4": (275e12, 1228e9),
}


def _device_peaks() -> dict:
    import jax

    kind = getattr(jax.devices()[0], "device_kind", "unknown").lower()
    for key, (flops, hbm) in _PEAKS.items():
        if key in kind:
            return {
                "device_kind": kind, "flops": flops, "hbm_bytes_s": hbm,
            }
    raise RuntimeError(
        f"no peak FLOP/s / HBM bandwidth on record for device kind "
        f"{kind!r} (known: {sorted(_PEAKS)}); add it to _PEAKS with its "
        "source before benchmarking on it"
    )


def _fence(state) -> float:
    """End a timed region: read a value computed from the updated
    params.  On the v5e chip this and ``jax.block_until_ready(state)``
    time the same 16 donated GPT-2 steps identically (81.98-82.00 vs
    81.98-82.03 ms/step, PR 21 chip run); without either, the loop
    measures the enqueue (3.3-3.9 ms/step)."""
    import jax
    import jax.numpy as jnp

    leaf = jax.tree.leaves(state.params)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def _time_steps(step, state, batch, key, *, warmup: int, iters: int):
    """Run timed steps after warmup; returns (state, mean_s, dist_ms).

    The headline mean times ``iters`` back-to-back dispatches behind ONE
    value fence — fencing inside the timed region would insert a host
    round-trip into every sample.  A second, shorter pass fences every 4 steps to get a
    per-step distribution; its samples carry ~RTT/4 overhead each and
    are reported separately from the headline.
    """
    for _ in range(warmup):
        state, _ = step(state, batch, key)
    f = _fence(state)
    assert f == f, "NaN params after warmup"

    t0 = time.perf_counter()
    for _ in range(iters):
        state, _ = step(state, batch, key)
    _fence(state)
    mean_s = (time.perf_counter() - t0) / iters

    chunk, chunks = 4, 3
    dist: list[float] = []
    for _ in range(chunks):
        t0 = time.perf_counter()
        for _ in range(chunk):
            state, _ = step(state, batch, key)
        _fence(state)
        dist.append((time.perf_counter() - t0) / chunk * 1e3)
    return state, mean_s, dist


def bench_resnet50() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data.loader import shard_batch
    from distributeddataparallel_tpu.models.resnet import ResNet50
    from distributeddataparallel_tpu.ops import cross_entropy_loss

    mesh = ddp.make_mesh(("data",))
    n_dev = len(jax.devices())

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    image_shape = (224, 224, 3)
    per_chip_batch = 128

    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((1,) + image_shape, jnp.float32)
    # jit the init: eager flax init dispatches one op at a time.
    variables = jax.jit(model.init)(rng, sample)
    params = variables["params"]
    model_state = {k: v for k, v in variables.items() if k != "params"}

    def loss_fn(params, ms, batch, rng):
        logits, new_vars = model.apply(
            {"params": params, **ms}, batch["image"], train=True,
            mutable=list(ms.keys()),
        )
        return cross_entropy_loss(logits, batch["label"]), ({}, new_vars)

    state = ddp.TrainState.create(
        apply_fn=model.apply,
        params=params,
        tx=optax.sgd(0.1, momentum=0.9),
        model_state=model_state,
    )
    state = ddp.broadcast_params(state, mesh)
    step = ddp.make_train_step(loss_fn, mesh=mesh, with_model_state=True)

    B = per_chip_batch * n_dev
    npr = np.random.default_rng(0)
    batch = shard_batch(
        {
            "image": npr.normal(size=(B,) + image_shape).astype(np.float32),
            "label": npr.integers(0, 1000, size=(B,)).astype(np.int32),
        },
        mesh,
    )
    state, mean_s, dist = _time_steps(
        step, state, batch, jax.random.PRNGKey(1), warmup=4, iters=20
    )

    # End-to-end variant: the DataLoader feeds the step from host RAM
    # every step (threaded worker + prefetch — the input pipeline under
    # load, not a resident batch).  Same compiled step, same shapes.
    # Two numbers: the host pipeline alone (gather + collate rate), and
    # the full loader->device->step path, which pays the host->device
    # copy of every batch (~77 MB) — flagged via h2d_note.
    from distributeddataparallel_tpu.data import DataLoader
    from distributeddataparallel_tpu.data.datasets import SyntheticClassification

    ds = SyntheticClassification(
        num_examples=B * 2, shape=image_shape, num_classes=1000, seed=1
    )
    def host_rate(dataset, augment=None) -> float:
        loader = DataLoader(
            dataset, per_replica_batch=per_chip_batch, mesh=mesh,
            shuffle=True, seed=0, device_feed=False, augment=augment,
        )
        rows = 0
        t0 = time.perf_counter()
        for epoch in range(2):
            loader.set_epoch(epoch)
            for b in loader:
                rows += b["image"].shape[0]
        return rows / (time.perf_counter() - t0)

    host_img_s = host_rate(ds)
    # u8 storage mode: same pipeline through the fused native C++
    # gather+normalize kernel (csrc) — the production input path for
    # image payloads (CIFAR stores u8).
    from distributeddataparallel_tpu import native

    ds_u8 = SyntheticClassification(
        num_examples=B * 2, shape=image_shape, num_classes=1000, seed=1,
        keep_u8=True,
    )
    host_u8_img_s = host_rate(ds_u8)
    # Full training-augmentation chain fused into the same native pass
    # (gather + RandomCrop + flip + normalize, csrc ddp_gather_augment_u8).
    from distributeddataparallel_tpu.data import CifarAugment

    host_u8_aug_img_s = host_rate(ds_u8, augment=CifarAugment())

    loader = DataLoader(
        ds, per_replica_batch=per_chip_batch, mesh=mesh, shuffle=True,
        seed=0, workers=1,
    )
    key = jax.random.PRNGKey(2)
    for b in loader:  # warm epoch (loader thread spin-up, no recompile)
        state, _ = step(state, b, key)
    _fence(state)
    steps = 0
    t0 = time.perf_counter()
    for epoch in range(1, 3):
        loader.set_epoch(epoch)
        for b in loader:
            state, _ = step(state, b, key)
            steps += 1
    _fence(state)
    e2e_s = (time.perf_counter() - t0) / max(steps, 1)

    return {
        "img_s_chip": round(per_chip_batch / mean_s, 2),
        # Roofline context (VERDICT r2 weak 4): ResNet-50 fwd at 224² is
        # ~4.1 GFLOPs/img, training ~3x that; utilization against the
        # device kind's bf16 peak (_device_peaks).
        "mfu_est": round(
            (per_chip_batch / mean_s) * 3 * 4.1e9 / _device_peaks()["flops"],
            4,
        ),
        "per_chip_batch": per_chip_batch,
        "step_ms_mean": round(mean_s * 1e3, 3),
        "step_ms_fenced_chunks": [round(t, 3) for t in dist],
        "host_pipeline_img_s": round(host_img_s, 1),
        # Label says what actually ran: without the built C++ library the
        # u8 path silently falls back to NumPy, which must not be
        # reported under a 'native' name.
        ("host_pipeline_u8_native_img_s" if native.available()
         else "host_pipeline_u8_numpy_img_s"): round(host_u8_img_s, 1),
        ("host_pipeline_u8_augment_native_img_s" if native.available()
         else "host_pipeline_u8_augment_numpy_img_s"):
            round(host_u8_aug_img_s, 1),
        "native_kernels": native.available(),
        "e2e_img_s_chip": round(per_chip_batch / e2e_s, 2),
        "e2e_step_ms": round(e2e_s * 1e3, 3),
        "e2e_steps": steps,
        "h2d_note": (
            "e2e pays host->device transfer of every batch (see "
            "host_pipeline_img_s for the input machinery's rate)"
        ),
    }


def _gpt2_setup(attn_impl: str, *, per_chip_batch: int = 8,
                seq_len: int = 1024, tx=None):
    """Shared GPT-2 124M DP fixture: (mesh, loss_fn, state, batch).

    Used by both the throughput and overlap sections so they measure the
    SAME workload (config, batch geometry, loss) and cannot diverge.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data.loader import shard_batch
    from distributeddataparallel_tpu.models import TransformerLM, gpt2_124m
    from distributeddataparallel_tpu.ops import lm_cross_entropy

    mesh = ddp.make_mesh(("data",))
    B = per_chip_batch * len(jax.devices())
    cfg = gpt2_124m(max_seq_len=seq_len, dtype=jnp.bfloat16,
                    attn_impl=attn_impl)
    model = TransformerLM(cfg)
    # init at full seq_len (the forced-pallas path rejects non-block-
    # aligned shapes); jit'd to avoid eager per-op dispatch.
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, seq_len), jnp.int32)
    )["params"]

    def loss_fn(params, batch, rng):
        toks = batch["tokens"]
        logits = model.apply({"params": params}, toks[:, :-1])
        return lm_cross_entropy(logits, toks[:, 1:]), {}

    state = ddp.TrainState.create(
        apply_fn=model.apply, params=params, tx=tx or optax.adamw(3e-4)
    )
    state = ddp.broadcast_params(state, mesh)
    npr = np.random.default_rng(0)
    batch = shard_batch(
        {"tokens": npr.integers(
            0, 50257, size=(B, seq_len + 1)
        ).astype(np.int32)},
        mesh,
    )
    return mesh, loss_fn, state, batch


def bench_gpt2() -> dict:
    """GPT-2 124M pure-DP LM step (BASELINE config 4): tokens/s/chip,
    measured once with the Pallas flash kernel and once with the XLA
    attention path; the winner is what users get from attn_impl='auto'."""
    import jax

    import distributeddataparallel_tpu as ddp

    N_PARAMS = 124.4e6  # GPT-2 124M
    seq_len = 1024
    results = {}
    # (impl, per-chip batch): the b16 pallas row is the MFU lever —
    # a bigger per-chip batch amortizes the non-matmul time (VERDICT r2
    # weak 4: b8 ran ~42% MFU with no roofline context reported).
    # (A per-chip-batch-16 pallas variant was measured in development
    # and did NOT raise MFU — 41.97% vs 42.88% at b8 — so the batch
    # lever is closed: the residual gap vs the llama section's ~53% is
    # the learned-positional/LayerNorm f32 VPU work and the
    # tied-embedding head.)
    pcb = 8
    for impl in ("pallas", "xla"):
        want_pallas = impl == "pallas" and jax.default_backend() == "tpu"
        mesh, loss_fn, state, batch = _gpt2_setup(
            "pallas" if want_pallas else "xla",
            per_chip_batch=pcb, seq_len=seq_len,
        )
        step = ddp.make_train_step(loss_fn, mesh=mesh)
        state, mean_s, dist = _time_steps(
            step, state, batch, jax.random.PRNGKey(1), warmup=3, iters=12
        )
        toks = pcb * seq_len / mean_s
        results[impl] = {
            "tokens_s_chip": round(toks, 1),
            "mfu_est": round(6 * N_PARAMS * toks / _device_peaks()["flops"], 4),
            "per_chip_batch": pcb,
            "step_ms_mean": round(mean_s * 1e3, 3),
            "step_ms_fenced_chunks": [round(t, 3) for t in dist],
            "ran_pallas": want_pallas,
        }
        del state, step

    winner = max(results, key=lambda k: results[k]["tokens_s_chip"])
    return {
        "tokens_s_chip": results[winner]["tokens_s_chip"],
        "mfu_est": results[winner]["mfu_est"],
        "attn_winner": winner,
        "per_impl": results,
        "seq_len": seq_len,
    }


def bench_llama() -> dict:
    """Llama-family DP step (BASELINE config 5's model class, scaled to
    one chip): GQA 16q/4kv, RoPE, SwiGLU, remat + scanned layers, bf16 —
    the flash kernel consumes the grouped kv natively.  ~0.6B params;
    the full 8B memory story lives in MEMFIT.md."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data.loader import shard_batch
    from distributeddataparallel_tpu.models import TransformerLM, llama3_8b
    from distributeddataparallel_tpu.ops import lm_cross_entropy

    mesh = ddp.make_mesh(("data",))
    n_dev = len(jax.devices())
    per_chip_batch, seq_len = 4, 2048

    cfg = llama3_8b(
        num_layers=8, d_model=2048, d_ff=7168, num_heads=16, num_kv_heads=4,
        vocab_size=32000, max_seq_len=seq_len,
    )
    model = TransformerLM(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, seq_len), jnp.int32)
    )["params"]
    n_params = sum(l.size for l in jax.tree.leaves(params))

    def loss_fn(params, batch, rng):
        toks = batch["tokens"]
        logits = model.apply({"params": params}, toks[:, :-1])
        return lm_cross_entropy(logits, toks[:, 1:]), {}

    state = ddp.TrainState.create(
        apply_fn=model.apply, params=params,
        tx=optax.sgd(1e-3, momentum=0.9),
    )
    state = ddp.broadcast_params(state, mesh)
    step = ddp.make_train_step(loss_fn, mesh=mesh)
    npr = np.random.default_rng(0)
    batch = shard_batch(
        {"tokens": npr.integers(
            0, 32000, size=(per_chip_batch * n_dev, seq_len + 1)
        ).astype(np.int32)},
        mesh,
    )
    state, mean_s, dist = _time_steps(
        step, state, batch, jax.random.PRNGKey(1), warmup=3, iters=8
    )
    toks_per_s = per_chip_batch * seq_len / mean_s
    return {
        "tokens_s_chip": round(toks_per_s, 1),
        "params_m": round(n_params / 1e6, 1),
        # Model FLOPs utilization from the 6*N*T estimate against the
        # device's bf16 peak (attention flops excluded -> conservative).
        "mfu_est": round(
            6 * n_params * toks_per_s / _device_peaks()["flops"], 4
        ),
        "per_chip_batch": per_chip_batch,
        "seq_len": seq_len,
        "step_ms_mean": round(mean_s * 1e3, 3),
        "step_ms_fenced_chunks": [round(t, 3) for t in dist],
    }


def bench_decode() -> dict:
    """KV-cache decode throughput (models.generate): batched greedy
    generation on GPT-2 124M, bf16.  tokens/s/chip counts GENERATED
    tokens across the batch; the timed region includes the prefill (one
    compiled full-prompt apply) and the lax.scan of single-token steps.
    Decode is memory-bandwidth-bound (the whole weight matrix streams
    from HBM per token), so this is the framework's HBM-bound surface
    next to the MXU-bound training numbers."""
    import jax
    import jax.numpy as jnp

    from distributeddataparallel_tpu.models import (
        TransformerLM,
        generate,
        gpt2_124m,
    )

    P, N = 128, 128
    cfg = gpt2_124m(max_seq_len=P + N, dtype=jnp.bfloat16)
    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(
        rng, jax.random.randint(rng, (1, P), 0, cfg.vocab_size)
    )["params"]
    n_params = sum(l.size for l in jax.tree.leaves(params))
    # generate() casts f32 masters to the compute dtype before the loop
    # (half the streamed bytes — the VERDICT r3 item 7 lever).
    weight_bytes = 2 * n_params
    # KV-cache bytes touched per decode step at position t: read the
    # whole cache so far + write one slot, per layer, per row.
    kv_per_tok = (
        2 * cfg.num_layers
        * (cfg.num_kv_heads or cfg.num_heads) * cfg.dims_per_head * 2
    )
    peak = _device_peaks()["hbm_bytes_s"]

    per_batch = {}
    # Batch sweep (VERDICT r2 weak 7): the weight stream is shared by
    # the batch, so tokens/s scales with B until the per-row KV-cache
    # stream takes over as the dominant byte budget.  B=256 shows the
    # utilization trend toward the byte roofline as per-op latency
    # amortizes.  (Two points, not three: each B costs two warm
    # executable loads and the driver's bench budget is 560 s total.)
    for B in (8, 256):
        prompt = jax.random.randint(rng, (B, P), 0, cfg.vocab_size)
        out = generate(model, params, prompt, N)  # compile
        assert int(jnp.sum(out)) >= 0  # fence
        out1 = generate(model, params, prompt, 1)  # compile the baseline
        assert int(jnp.sum(out1)) >= 0  # fence the compile tail too
        iters = 3
        t0 = time.perf_counter()
        for _ in range(iters):
            out = generate(model, params, prompt, N)
        assert int(jnp.sum(out)) >= 0  # fence
        dt = (time.perf_counter() - t0) / iters
        # Prefill baseline: generate(.., 1) is the prompt forward + one
        # sample and none of the scanned decode steps — subtracting it
        # isolates the per-step decode cost (the B x P prefill would
        # otherwise contaminate the roofline gap, badly at B=256).
        t0 = time.perf_counter()
        for _ in range(iters):
            out1 = generate(model, params, prompt, 1)
        assert int(jnp.sum(out1)) >= 0
        dt_prefill = (time.perf_counter() - t0) / iters
        # Byte budget per decode step: weights once + the KV cache.  The
        # cache is STATIC max_seq_len-long (masked slots still stream
        # from HBM), so every step reads the full P+N window.
        cache_bytes = B * cfg.max_seq_len * kv_per_tok
        step_bytes = weight_bytes + cache_bytes
        roofline_step_ms = step_bytes / peak * 1e3
        measured_step_ms = max(dt - dt_prefill, 1e-9) / (N - 1) * 1e3
        per_batch[B] = {
            "decode_tokens_s_chip": round(B * N / dt, 1),
            "steps_per_s": round(N / dt, 1),
            # Utilization vs the FULL byte budget (weights + KV cache)
            # of the device's HBM peak: roofline step time over
            # measured.  The r03 metric counted weights only, which
            # understated b64 (cache-dominated) and ran f32 weights.
            "hbm_util_est": round(roofline_step_ms / measured_step_ms, 4),
            "roofline": {
                "weight_mb_per_step": round(weight_bytes / 1e6, 1),
                "kv_cache_mb_per_step": round(cache_bytes / 1e6, 1),
                "roofline_step_ms": round(roofline_step_ms, 4),
                "measured_step_ms": round(measured_step_ms, 4),
                "prefill_ms": round(dt_prefill * 1e3, 1),
            },
            "gen_wall_ms": round(dt * 1e3, 1),
        }
    # int8 weight-only serving (ops.quant): matrices stream as int8 +
    # per-channel scales, ~half the bf16 weight bytes.  On GPT-2 124M
    # at b8 the step is SMALL-OP-FLOOR-bound (b8_bound_analysis), so
    # the byte saving cannot show — recorded here as the honest ~1.0x;
    # the byte-bound measurement lives in int8_llama_0p6b below, where
    # the weight stream is ~10x and the dequant-fusion speedup is real
    # (measured 1.7x per step).  A HOISTED dequant would re-materialize
    # bf16 weights and erase that llama speedup — the llama number is
    # the fusion proof.
    int8 = {}
    try:
        from distributeddataparallel_tpu.ops.quant import (
            quantize_for_decode,
            quantized_bytes,
        )

        B = 8
        prompt = jax.random.randint(rng, (B, P), 0, cfg.vocab_size)
        # Quantize ONCE outside the timed loop (generate() detects the
        # QuantLeaf tree and reuses it) — timing the per-call quantize
        # pass would deflate the steady-state serving number.
        qparams = quantize_for_decode(params)
        out = generate(model, qparams, prompt, N)
        assert int(jnp.sum(out)) >= 0
        out1 = generate(model, qparams, prompt, 1)
        assert int(jnp.sum(out1)) >= 0
        iters = 3
        t0 = time.perf_counter()
        for _ in range(iters):
            out = generate(model, qparams, prompt, N)
        assert int(jnp.sum(out)) >= 0
        dt = (time.perf_counter() - t0) / iters
        t0 = time.perf_counter()
        for _ in range(iters):
            out1 = generate(model, qparams, prompt, 1)
        assert int(jnp.sum(out1)) >= 0
        dt_prefill = (time.perf_counter() - t0) / iters
        qb = quantized_bytes(qparams)["bytes"]
        cache_bytes = B * cfg.max_seq_len * kv_per_tok
        roof_ms = (qb + cache_bytes) / peak * 1e3
        meas_ms = max(dt - dt_prefill, 1e-9) / (N - 1) * 1e3
        int8 = {
            "decode_tokens_s_chip": round(B * N / dt, 1),
            # like-for-like per-step ratio (the llama section's metric):
            # end-to-end tokens/s would fold prefill into the compare
            "step_speedup_int8": round(
                per_batch[8]["roofline"]["measured_step_ms"] / meas_ms,
                3,
            ),
            "weight_mb_per_step": round(qb / 1e6, 1),
            "hbm_util_est": round(roof_ms / meas_ms, 4),
            "measured_step_ms": round(meas_ms, 4),
        }
    except Exception as e:  # noqa: BLE001 - keep the bf16 numbers
        int8 = {"error": repr(e)}

    # Byte-bound int8 proof point: Llama-0.6B-class (567M params,
    # 1.13 GB bf16 weight stream — step roofline ~1.4 ms, well above
    # the op floor).  Two variants, two timed programs each.
    int8_llama = {}
    try:
        from distributeddataparallel_tpu.models import llama3_8b

        # scan_layers: ONE compiled layer body (the production llama
        # config) — the 8-layer unrolled decode compile blew the bench
        # budget (~4 min/variant); byte totals are identical.
        lcfg = llama3_8b(
            num_layers=8, d_model=2048, d_ff=7168, num_heads=16,
            num_kv_heads=4, vocab_size=32000, max_seq_len=P + N,
            scan_layers=True, remat=False,
        )
        lmodel = TransformerLM(lcfg)
        lparams = jax.jit(lmodel.init)(
            rng, jax.random.randint(rng, (1, P), 0, lcfg.vocab_size)
        )["params"]
        B = 8
        lprompt = jax.random.randint(rng, (B, P), 0, lcfg.vocab_size)
        from distributeddataparallel_tpu.ops.quant import (
            quantize_for_decode,
        )

        lq = quantize_for_decode(lparams, scan_layers=True)
        res = {}
        for q, ps in ((None, lparams), ("int8", lq)):
            out = generate(lmodel, ps, lprompt, N)
            assert int(jnp.sum(out)) >= 0
            out1 = generate(lmodel, ps, lprompt, 1)
            assert int(jnp.sum(out1)) >= 0
            iters = 2
            t0 = time.perf_counter()
            for _ in range(iters):
                out = generate(lmodel, ps, lprompt, N)
            assert int(jnp.sum(out)) >= 0
            dt = (time.perf_counter() - t0) / iters
            t0 = time.perf_counter()
            for _ in range(iters):
                out1 = generate(lmodel, ps, lprompt, 1)
            assert int(jnp.sum(out1)) >= 0
            dtp = (time.perf_counter() - t0) / iters
            res[q or "bf16"] = {
                "decode_tokens_s_chip": round(B * N / dt, 1),
                "step_ms": round(
                    max(dt - dtp, 1e-9) / (N - 1) * 1e3, 4
                ),
            }
        int8_llama = {
            **res,
            "step_speedup_int8": round(
                res["bf16"]["step_ms"] / res["int8"]["step_ms"], 3
            ),
            "params_m": round(
                sum(x.size for x in jax.tree.leaves(lparams)) / 1e6, 1
            ),
        }
    except Exception as e:  # noqa: BLE001
        int8_llama = {"error": repr(e)}

    best = max(per_batch, key=lambda b: per_batch[b]["decode_tokens_s_chip"])
    b8 = per_batch[8]["roofline"]
    return {
        "decode_tokens_s_chip": per_batch[best]["decode_tokens_s_chip"],
        "best_batch": best,
        "hbm_util_est": per_batch[best]["hbm_util_est"],
        "hbm_util_b8": per_batch[8]["hbm_util_est"],
        "per_batch": {str(k): v for k, v in per_batch.items()},
        "int8_b8": int8,
        "int8_llama_0p6b": int8_llama,
        "prompt_len": P,
        "new_tokens": N,
        "weights_dtype": "bf16 (cast once inside the decode jit)",
        # The VERDICT r3 item 7 written roofline: at B=8 a GPT-2-124M
        # decode step's matmuls are 8-row — orders below MXU tile
        # amortization — so the step is bounded by per-op issue latency
        # across the scan body's ~25 ops/layer x 12 layers + head, not
        # by HBM bytes.  The byte roofline becomes the bound as B
        # amortizes the op overheads (see per_batch).  gap_ms is the
        # measured excess over the byte roofline; divided over ~300
        # scan-body ops it lands on the TPU's ~1-2 us small-op floor
        # (measured 0.94 us at b8).
        "b8_bound_analysis": {
            "roofline_step_ms": b8["roofline_step_ms"],
            "measured_step_ms": b8["measured_step_ms"],
            "gap_ms": round(
                b8["measured_step_ms"] - b8["roofline_step_ms"], 4
            ),
            "implied_per_op_us_at_300_ops": round(
                (b8["measured_step_ms"] - b8["roofline_step_ms"])
                / 300 * 1e3, 2,
            ),
        },
    }


def bench_moe_scaling() -> dict:
    """Token-choice MoE compute scaling (VERDICT r2 next 1's bench half):
    tokens/s as the expert count doubles at fixed top-k=2.  With
    capacity-bounded token-choice dispatch (ops.moe) the expert FLOPs
    are ~K*T regardless of E, so throughput should stay roughly flat —
    the property the dense-einsum dispatch (FLOPs ~E*T) lacks.  Single
    chip: the dispatch/capacity machinery itself; the EP all_to_all
    variant is pinned by equivalence tests and the multichip dryrun."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data.loader import shard_batch
    from distributeddataparallel_tpu.models import TransformerLM, gpt2_124m
    from distributeddataparallel_tpu.ops import lm_cross_entropy

    mesh = ddp.make_mesh(("data",))
    per_chip_batch, seq_len = 8, 512
    npr = np.random.default_rng(0)
    batch = shard_batch(
        {"tokens": npr.integers(
            0, 8192,
            size=(per_chip_batch * len(jax.devices()), seq_len + 1),
        ).astype(np.int32)},
        mesh,
    )

    # Build all configs first, then time in INTERLEAVED rounds taking the
    # best rate per E: the r03 artifact recorded a spurious "E=16 cliff"
    # (0.71x) that re-measurement shows was cross-section drift, not
    # dispatch cost — sequential one-shot timing is not drift-robust.  (Re-measured: E16/E4 ~ 1.05-1.13;
    # ops-level components are flat in E by construction, E*C slots and
    # expert FLOPs are E-independent at fixed top-k.)
    runs = {}
    for E in (4, 8, 16):
        cfg = gpt2_124m(
            num_layers=6, d_model=512, d_ff=2048, num_heads=8,
            vocab_size=8192, max_seq_len=seq_len, dtype=jnp.bfloat16,
            moe_experts=E, moe_top_k=2, moe_capacity_factor=1.25,
        )
        model = TransformerLM(cfg)
        params = jax.jit(model.init)(
            jax.random.PRNGKey(0), jnp.zeros((1, seq_len), jnp.int32)
        )["params"]

        def loss_fn(params, b, rng, _m=model):
            toks = b["tokens"]
            logits = _m.apply({"params": params}, toks[:, :-1])
            return lm_cross_entropy(logits, toks[:, 1:]), {}

        state = ddp.TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.sgd(0.01)
        )
        state = ddp.broadcast_params(state, mesh)
        # donate=True (production config): the E-sweep is weight-traffic
        # sensitive and an undonated step adds a full param-tree copy
        # per step — linear in E, exactly the confound being measured.
        step = ddp.make_train_step(loss_fn, mesh=mesh)
        # warm (compile + first dispatches)
        for _ in range(2):
            state, _ = step(state, batch, jax.random.PRNGKey(1))
        _fence(state)
        n_params = sum(
            l.size for l in jax.tree.leaves(state.params)
        )
        runs[E] = [step, state, n_params]

    # MEDIAN of several interleaved rounds: single ~150 ms samples
    # carried +-30% hiccups in BOTH directions (a lucky
    # spike on one E is as misleading as a stall on another), so the
    # per-E median across interleaved rounds is the defensible
    # dispatch-cost estimate.
    samples = {E: [] for E in runs}
    for _ in range(5):
        for E, run in runs.items():
            step, state, _ = run
            t0 = time.perf_counter()
            for _ in range(8):
                state, _ = step(state, batch, jax.random.PRNGKey(1))
            run[1] = state  # donated chain: keep the live buffers
            _fence(state)
            samples[E].append(
                per_chip_batch * seq_len * 8 / (time.perf_counter() - t0)
            )
    per_e = {
        E: round(float(np.median(v)), 1) for E, v in samples.items()
    }

    # Weight-traffic roofline: at fixed tokens/chip, growing E grows the
    # f32 master weights resident per chip (dispatch slots E*C and
    # expert FLOPs stay constant at fixed top-k — the token-choice
    # property).  Each step touches ~24 B/param of experts (f32 read +
    # bf16 cast write + bf16 bwd read + f32 grad write + sgd
    # read/read/write), so the expected slowdown from E=4 to E=16 is
    # pure HBM traffic — the cost EP removes by sharding experts, not a
    # dispatch defect.  e16_over_e4_roofline is that model's prediction
    # for THIS device's bandwidth; compare with the measured ratio.
    bw = _device_peaks()["hbm_bytes_s"]
    t4 = per_chip_batch * seq_len / per_e[4]
    extra_s = (runs[16][2] - runs[4][2]) * 24 / bw
    roofline_ratio = round(t4 / (t4 + extra_s), 3)
    return {
        "tokens_s_chip_by_experts": {str(k): v for k, v in per_e.items()},
        "e16_over_e4": round(per_e[16] / per_e[4], 3),
        "e16_over_e4_weight_traffic_roofline": roofline_ratio,
        "params_m_by_experts": {
            str(E): round(r[2] / 1e6, 1) for E, r in runs.items()
        },
        "top_k": 2,
        "capacity_factor": 1.25,
        "per_chip_batch": per_chip_batch,
        "seq_len": seq_len,
        # Measured (not roofline-argued) EP weight sharding: AOT per-chip
        # memory analysis of the real EP train step, v5e 2x4 (VERDICT r4
        # weak 6).  Needs the TPU compiler; degrade loudly.
        "ep_memory": _ep_memory_evidence(),
    }


def _ep_memory_evidence() -> dict:
    from distributeddataparallel_tpu.parallel.expert_parallel import (
        ep_memory_evidence,
    )

    try:
        return ep_memory_evidence()
    except Exception as e:  # no TPU compiler reachable
        return {"error": repr(e)}


def bench_cp_ring() -> dict:
    """Ring-attention block math: Pallas-per-hop vs XLA-einsum blocks,
    fwd+bwd at training shapes (VERDICT r2 weak 6 / next 5).  One chip is
    visible, so the mesh axis has size 1 — this measures the per-hop
    BLOCK computation the ring spends its time in (the part the round-2
    README conceded was slow), not ICI transfer; multi-hop correctness
    incl. wrap masking is pinned by tests on 2/4-device rings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import distributeddataparallel_tpu as ddp
    from jax.sharding import PartitionSpec as P
    from distributeddataparallel_tpu.parallel.context_parallel import (
        ring_attention,
    )

    mesh = ddp.make_mesh(("seq",))
    B, S, H, D = 2, 4096, 12, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)

    def timed(impl):
        def loss(q, k, v):
            o = ring_attention(q, k, v, axis_name="seq", impl=impl)
            return jnp.sum(o.astype(jnp.float32))

        f = jax.jit(jax.shard_map(
            jax.grad(loss, argnums=(0, 1, 2)), mesh=mesh,
            in_specs=(P(None, "seq"),) * 3,
            out_specs=(P(None, "seq"),) * 3, check_vma=False,
        ))
        g = f(q, k, v)
        assert float(jnp.sum(g[0].astype(jnp.float32))) == float(
            jnp.sum(g[0].astype(jnp.float32))
        )
        iters = 8
        t0 = time.perf_counter()
        for _ in range(iters):
            g = f(q, k, v)
        float(jnp.sum(g[0].astype(jnp.float32)))  # fence
        return (time.perf_counter() - t0) / iters * 1e3

    ran_pallas = jax.default_backend() == "tpu"
    xla_ms = timed("xla")
    flash_ms = timed("pallas" if ran_pallas else "xla")
    return {
        "block_fwd_bwd_ms_xla": round(xla_ms, 2),
        "block_fwd_bwd_ms_flash": round(flash_ms, 2),
        "flash_speedup": round(xla_ms / flash_ms, 2),
        "ran_pallas": ran_pallas,
        "shape": [B, S, H, D],
        "note": (
            "single visible chip: per-hop block math only; ring comms "
            "need a multi-chip axis"
        ),
    }


def bench_input_pipeline() -> dict:
    """Streaming input pipeline vs device rate (config 3's host side):
    ImageNet-geometry batches (global batch 128) streamed from a
    memmapped shard set (data.sharded) in the TPU-native split — host
    does the u8 shard gather, the device does normalize in-graph.

    Rates reported: ``host_gather_img_s`` (the pipeline's sustainable
    feed rate) and ``host_to_device_img_s`` (including placement on
    the device).  The done-bar comparison host_gather >= device rate is
    computed in main() against bench_resnet50's img/s/chip.
    """
    import os
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data import (
        DataLoader,
        ShardedImageDataset,
        write_synthetic_image_shards,
    )

    n_examples, shape = 2048, (224, 224, 3)
    # Geometry-keyed cache dir: changing the constants regenerates, and
    # a partial/stale corpus (killed prior run) is detected and rebuilt.
    root = os.path.join(
        tempfile.gettempdir(),
        f"ddp_bench_shards_{n_examples}x{'x'.join(map(str, shape))}",
    )

    def _valid():
        try:
            import json as _json

            with open(os.path.join(root, "index.json")) as fh:
                m = _json.load(fh)
            return (
                m["num_examples"] == n_examples
                and tuple(m["shape"]) == shape
                and all(
                    os.path.exists(
                        os.path.join(root, f"shard_{s:05d}_images.npy")
                    )
                    for s in range(len(m["shard_counts"]))
                )
            )
        except Exception:  # noqa: BLE001
            return False

    if not _valid():
        import shutil

        shutil.rmtree(root, ignore_errors=True)
        write_synthetic_image_shards(
            root, n_examples, shape, 1000, shard_rows=512, seed=0
        )
    ds = ShardedImageDataset(root, device_normalize=True)
    mesh = ddp.make_mesh(("data",))
    n = mesh.shape["data"]
    per = max(128 // n, 1)
    out = {
        "corpus_mb": round(len(ds) * np.prod(ds.image_shape) / 1e6, 1),
        "global_batch": per * n,
        "image_shape": list(ds.image_shape),
    }

    # Host gather rate: one full epoch of u8 shard gathers (no device).
    loader = DataLoader(
        ds, per_replica_batch=per, mesh=mesh, seed=0, device_feed=False
    )
    next(iter(loader))  # touch pages once so timing sees steady state
    t0 = time.perf_counter()
    rows = 0
    for b in loader:
        rows += b["image"].shape[0]
    out["host_gather_img_s"] = round(rows / (time.perf_counter() - t0), 1)

    # Gather + device placement (capped steps).
    loader = DataLoader(
        ds, per_replica_batch=per, mesh=mesh, seed=0, device_feed=True
    )
    it = iter(loader)
    first = next(it)  # compile/placement warmup
    jax.block_until_ready(first["image"])
    t0 = time.perf_counter()
    rows = 0
    last = first
    for _ in range(6):
        try:
            last = next(it)
        except StopIteration:
            break
        rows += per * n
    # value fence (see _fence)
    float(jnp.sum(last["image"].astype(jnp.int32)))
    if rows:
        out["host_to_device_img_s"] = round(
            rows / (time.perf_counter() - t0), 1
        )

    # Token host-gather rate (data.tokens vectorized sliding-window
    # gather, VERDICT r4 item 8): same >=-device-rate done-bar as images,
    # computed in main() against bench_gpt2's tokens/s/chip.
    import tempfile as _tf

    from distributeddataparallel_tpu.data import TokenFileDataset

    from distributeddataparallel_tpu.data import write_token_file

    tok_path = os.path.join(_tf.gettempdir(), "ddp_bench_tokens.npy")
    n_tok, S = 8_000_000, 1024
    if not (
        os.path.exists(tok_path)
        and np.load(tok_path, mmap_mode="r").shape == (n_tok,)
    ):
        npr = np.random.default_rng(0)
        write_token_file(
            tok_path, npr.integers(0, 50257, size=(n_tok,))
        )
    tds = TokenFileDataset(tok_path, seq_len=S)
    bsz = 64
    order = np.random.default_rng(1).permutation(len(tds))
    tds.gather(order[:bsz])  # touch pages once
    t0 = time.perf_counter()
    toks = 0
    for lo in range(0, len(order) - bsz, bsz):
        b = tds.gather(order[lo : lo + bsz])
        toks += b["tokens"].size
    out["token_gather_tok_s"] = round(toks / (time.perf_counter() - t0), 1)
    return out


def bench_pipeline_bubble() -> dict:
    """Interleaved-1F1B bubble accounting (VERDICT r4 item 5): exact
    per-device idle from the schedule's own tick arithmetic
    (``pp_bubble_fraction`` — the compiled scan length IS this T), for
    the plain vs interleaved schedules at bench-relevant geometry.
    Schedule math, not wall clock, so it is fabric-independent; the
    numerics equivalence is pinned by tests/test_pipeline_parallel.py."""
    from distributeddataparallel_tpu.parallel.pipeline_parallel import (
        pp_bubble_fraction,
    )

    out = {}
    for n, m in ((4, 16), (8, 32)):
        row = {}
        for v in (1, 2, 4):
            b = pp_bubble_fraction(n, m, v)
            row[f"v{v}"] = {
                "bubble_fraction": b["bubble_fraction"],
                "bubble_stage_units": b["bubble_stage_units"],
            }
        row["v4_over_v1_bubble"] = round(
            row["v4"]["bubble_stage_units"] / row["v1"]["bubble_stage_units"],
            3,
        )
        out[f"stages{n}_mb{m}"] = row
    return out


def _pipeline_zb_child(out_path, events_dir, env):
    """Measured-bubble comparison in a fresh 8-device CPU-mesh
    interpreter: run the REAL compiled 1f1b and zb schedules at
    (4 stages, 16 mb) and (8 stages, 32 mb), timing steady-state steps
    and — the point of the exercise — recovering the bubble from the
    schedules' own phase counters through the events pipeline: emit a
    ``pp_phase`` record per (config, schedule), then reconstruct
    ``measured_bubble_fraction`` from the merged timeline exactly the
    way ddp_report does post hoc.  The measured number comes from what
    the compiled scans executed, not from tick arithmetic."""
    import os

    os.environ.update(env)
    import json
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data.loader import shard_batch
    from distributeddataparallel_tpu.models import TransformerLM, tiny_lm
    from distributeddataparallel_tpu.observability.events import (
        EventLog,
        events_path,
        load_timeline,
    )
    from distributeddataparallel_tpu.observability.pipeline import (
        measured_bubble_fraction,
        phase_counts_payload,
    )
    from distributeddataparallel_tpu.parallel.pipeline_parallel import (
        make_pp_train_step,
        shard_state_pp,
    )

    out = {}
    for stages, M in ((4, 16), (8, 32)):
        # 8 layers: divisible by both stage counts; local batch shard =
        # M rows (one row per microbatch) so the M-way reshape is exact.
        cfg = tiny_lm(
            num_layers=8, num_heads=2, d_model=32, d_ff=64,
            scan_layers=True, max_seq_len=32,
        )
        n_data = 8 // stages
        mesh = ddp.make_mesh(("data", "pipe"), shape=(n_data, stages))
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32)
        )["params"]
        tokens = np.random.default_rng(stages).integers(
            0, 256, size=(M * n_data, 33)
        ).astype(np.int32)
        batch = shard_batch({"tokens": tokens}, mesh)
        row = {}
        for schedule in ("1f1b", "zb"):
            step = make_pp_train_step(
                cfg, mesh=mesh, microbatches=M, donate=False,
                schedule=schedule,
            )
            state = shard_state_pp(
                ddp.TrainState.create(
                    apply_fn=None, params=params, tx=optax.sgd(0.1)
                ),
                mesh,
            )
            state, metrics = step(state, batch, jax.random.PRNGKey(0))
            jax.block_until_ready(metrics["loss"])  # compile + warm
            times = []
            for it in range(1, 4):
                t0 = time.perf_counter()
                state, metrics = step(state, batch, jax.random.PRNGKey(it))
                jax.block_until_ready(metrics["loss"])
                times.append(time.perf_counter() - t0)

            # One events dir per (config, schedule): the bench IS a
            # miniature run, reconstructed the same way a real run is.
            edir = os.path.join(
                events_dir, f"stages{stages}_{schedule}"
            )
            with EventLog(events_path(edir, 0), proc=0) as log:
                log.emit("pp_phase", **phase_counts_payload(
                    jax.device_get(metrics["pp_phase_counts"]),
                    schedule=schedule, n_stages=stages, virtual=1,
                    microbatches=M,
                    accounting=step.bubble_accounting,
                ))
            measured = measured_bubble_fraction(load_timeline(edir))
            row[schedule] = {
                "step_s": round(sorted(times)[len(times) // 2], 4),
                "measured_bubble_fraction": (
                    measured or {}
                ).get("measured_bubble_fraction"),
                "analytic_bubble_fraction": (
                    measured or {}
                ).get("analytic_bubble_fraction"),
                "per_stage_useful": [
                    s["useful_slots"] for s in (measured or {}).get(
                        "per_stage", []
                    )
                ],
            }
        zb, fb = row["zb"], row["1f1b"]
        if None not in (
            zb["measured_bubble_fraction"], fb["measured_bubble_fraction"]
        ):
            row["zb_vs_1f1b_measured"] = round(
                zb["measured_bubble_fraction"]
                / max(fb["measured_bubble_fraction"], 1e-9), 3,
            )
        out[f"stages{stages}_mb{M}"] = row
    with open(out_path, "w") as fh:
        json.dump(out, fh)


def bench_pipeline_zb() -> dict:
    """Zero-bubble pipeline done bar: measured zb bubble (from the
    compiled schedules' phase counters, reconstructed through the
    events timeline) below the ANALYTIC 1F1B fraction at the same
    (stages, microbatches) — both the v1 geometry it replaces and the
    interleave-v4 roofline the 1F1B study recorded.  The analytic
    table from ``bench_pipeline_bubble`` rides along as the roofline
    column; headline keys ``zb_bubble_frac`` / ``zb_step_s`` are gated
    lower-is-better by perf_gate."""
    import json as _json
    import multiprocessing as mp
    import os
    import tempfile

    out = {"analytic": bench_pipeline_bubble()}
    root = tempfile.mkdtemp(prefix="ddp_bench_zb_")
    out_path = os.path.join(root, "out.json")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    ctx = mp.get_context("spawn")
    p = ctx.Process(
        target=_pipeline_zb_child,
        args=(out_path, os.path.join(root, "events"), env),
    )
    p.start()
    p.join(timeout=600)
    if p.is_alive():
        p.terminate()
        p.join()
        out["error"] = "child timed out"
        return out
    if p.exitcode != 0 or not os.path.exists(out_path):
        out["error"] = f"child exit {p.exitcode}"
        return out
    with open(out_path) as fh:
        out["measured"] = _json.load(fh)

    beats = []
    for key in ("stages4_mb16", "stages8_mb32"):
        row = out["measured"].get(key, {})
        zb = row.get("zb", {}).get("measured_bubble_fraction")
        roof = out["analytic"].get(key, {})
        row["analytic_1f1b_v1_bubble"] = (
            roof.get("v1", {}).get("bubble_fraction")
        )
        row["analytic_1f1b_v4_bubble"] = (
            roof.get("v4", {}).get("bubble_fraction")
        )
        if zb is not None and row["analytic_1f1b_v1_bubble"] is not None:
            row["zb_beats_1f1b_analytic"] = bool(
                zb < row["analytic_1f1b_v1_bubble"]
                and zb < row["analytic_1f1b_v4_bubble"]
            )
            beats.append(row["zb_beats_1f1b_analytic"])
    zb_fracs = [
        out["measured"][k]["zb"]["measured_bubble_fraction"]
        for k in ("stages4_mb16", "stages8_mb32")
        if out["measured"].get(k, {}).get("zb", {}).get(
            "measured_bubble_fraction"
        ) is not None
    ]
    if zb_fracs:
        # worst (largest) measured bubble across configs — conservative
        out["zb_bubble_frac"] = max(zb_fracs)
    step_s = out["measured"].get("stages8_mb32", {}).get("zb", {}).get(
        "step_s"
    )
    if step_s is not None:
        out["zb_step_s"] = step_s
    out["zb_beats_1f1b_analytic"] = bool(beats) and all(beats)
    return out


def bench_overlap() -> dict:
    """Comm/compute overlap on the GPT-2 124M DP step (BASELINE config 5's
    "overlap demonstrated"): full step vs compute-only (grad_sync=False,
    the no_sync analog) vs bare grad-tree all-reduce.  With one visible
    chip the collective is a no-op (overlap_frac None); on a multi-chip
    axis the fraction quantifies how much of the psum XLA hides under the
    backward."""
    import jax
    import optax

    from distributeddataparallel_tpu.utils.metrics import overlap_probe

    mesh, loss_fn, state, batch = _gpt2_setup("auto", tx=optax.sgd(0.01))
    out = overlap_probe(
        loss_fn, state, batch, jax.random.PRNGKey(1), mesh=mesh, iters=4
    )

    # The scheduled-HLO demonstration (OVERLAP.md): AOT-compile the REAL
    # train steps — GPT-2 124M (unrolled, adamw) and the Llama-0.6B
    # scan+remat config with the in-scan-body reduction — for an 8-chip
    # v5e topology and report how much backward compute the TPU compiler
    # scheduled inside the async-collective windows (VERDICT r4 item 1:
    # rounds 1-4 recorded an 8-layer-MLP proxy here).  The MLP pair
    # (chain-vs-stock contrast) still lands in MULTICHIP_PROBES.json
    # every dryrun.
    keys = (
        "n_async_windows", "n_sync_collectives", "n_comm_fused",
        "overlapped_compute_cycles", "total_compute_cycles",
        "overlapped_frac_of_compute", "async_collective_bytes",
        "sync_collective_bytes", "async_bytes_frac", "topology",
        "n_chips", "compiler", "compile_s", "config", "while_bodies",
    )
    from distributeddataparallel_tpu.parallel.overlap import (
        train_step_schedule_evidence,
    )

    for m in ("gpt2", "llama"):
        try:
            rep = train_step_schedule_evidence(model=m)
            out[f"real_step_schedule_{m}"] = {k: rep[k] for k in keys}
        except Exception as e:  # noqa: BLE001 - keep the other sections
            out[f"real_step_schedule_{m}"] = {"error": repr(e)}

    # Comm-hook wire-byte ledgers for the GPT-2 124M gradient tree
    # (shape math, no compile): the bf16 hook halves the wire; the
    # PowerSGD hook's rank-4 factors cut it by orders of magnitude.
    # Schedule-level measurements for bf16 are in OVERLAP.md §6.
    from distributeddataparallel_tpu.parallel.powersgd import (
        powersgd_wire_bytes,
    )

    try:
        out["comm_hooks_wire_bytes"] = {
            "powersgd_rank4": powersgd_wire_bytes(state.params, rank=4),
            "bf16_wire_bytes": sum(
                2 * l.size for l in jax.tree.leaves(state.params)
            ),
        }
    except Exception as e:  # noqa: BLE001
        out["comm_hooks_wire_bytes"] = {"error": repr(e)}
    return out


def _warm_start_child(mode, store_dir, out_path, env):
    """One warm-start measurement, run in a FRESH interpreter (spawn):
    compile/cache/AOT state is per-process, so only a new process can
    observe a cold start or a genuine restart.  Always an 8-device
    virtual CPU mesh (env pins JAX_PLATFORMS, the host device count and
    this measurement's own JAX_COMPILATION_CACHE_DIR before jax
    imports) — the measurement is host-side executable acquisition,
    which needs no chip."""
    import os

    os.environ.update(env)
    import json
    import time

    t_start = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data.loader import shard_batch
    from distributeddataparallel_tpu.models import TransformerLM, gpt2_124m
    from distributeddataparallel_tpu.ops import lm_cross_entropy
    from distributeddataparallel_tpu.training.warm_start import (
        ExecutableStore,
        executable_key,
        warm_train_step,
    )

    mesh = ddp.make_mesh(("data",))
    # GPT-2 124M with scanned layers at short seq: full-width weight
    # tree (the compile cost that matters) at a CPU-affordable step.
    seq_len = 64
    cfg = gpt2_124m(max_seq_len=seq_len, scan_layers=True)
    model = TransformerLM(cfg)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, seq_len), jnp.int32)
    )
    # Zero params via eval_shape: real init costs more than the step on
    # CPU and the timing target is the executable path, not the values.
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes
    )["params"]

    def loss_fn(params, batch, rng):
        toks = batch["tokens"]
        logits = model.apply({"params": params}, toks[:, :-1])
        return lm_cross_entropy(logits, toks[:, 1:]), {}

    state = ddp.TrainState.create(
        apply_fn=model.apply, params=params,
        tx=optax.sgd(0.01, momentum=0.9),
    )
    state = ddp.broadcast_params(state, mesh)
    step_fn = ddp.make_train_step(loss_fn, mesh=mesh, donate=False)
    warm = warm_train_step(
        step_fn,
        store=ExecutableStore(store_dir),
        key=executable_key(
            mesh=mesh, model_config=cfg,
            step_signature=getattr(step_fn, "aot_signature", None),
            extra={"bench": "warm_start", "seq_len": seq_len},
        ),
    )
    npr = np.random.default_rng(0)
    B = 2 * len(jax.devices())
    batch = shard_batch(
        {"tokens": npr.integers(
            0, 50257, size=(B, seq_len + 1)
        ).astype(np.int32)},
        mesh,
    )
    # Time ACQUISITION only (resolve, not a step): on the 8-thread
    # virtual CPU mesh one GPT-2 step takes ~60 s of execution, which
    # would drown the compile-vs-load contrast being measured.  The
    # loaded binary's bitwise equivalence to the cold compile is pinned
    # by tests/test_warm_start.py on the same backend.
    rep = warm.resolve(state, batch, jax.random.PRNGKey(0))
    rep["acquire_s"] = rep.get("load_s", rep.get("compile_s"))
    rep.update(
        requested=mode,
        start_to_ready_s=round(time.perf_counter() - t_start, 3),
    )
    with open(out_path, "w") as fh:
        json.dump(rep, fh)


def _restart_latency_worker(process_id, store_dir, out_dir):
    """Supervised-gang worker for the restart-latency measurement: the
    first incarnation compiles, saves the executable, then dies like a
    preemption; the respawn (DDP_RESTART_ATTEMPT=1) should reach its
    first step via the AOT store.  env is already applied by the
    launcher's child bootstrap."""
    import os

    attempt = int(os.environ.get("DDP_RESTART_ATTEMPT", "0"))
    _warm_start_child(
        f"attempt{attempt}", store_dir,
        os.path.join(out_dir, f"attempt{attempt}.json"), {},
    )
    if attempt == 0:
        raise SystemExit(1)


def bench_warm_start() -> dict:
    """Warm-start subsystem (training.warm_start): first-step latency of
    the SAME GPT-2 124M train step acquired three ways — cold compile,
    persistent-cache hit, and AOT executable load — each in a fresh
    process on an 8-device virtual CPU mesh.  The done bar: cache-hit or
    AOT-load at least 5x faster to the first step than the cold compile.
    With DDP_BENCH_SLOW set, also measures restart-to-first-step latency
    under the PR 1 supervisor (spawn max_restarts=1): incarnation 0
    compiles + saves + dies, incarnation 1 must come back via AOT."""
    import json as _json
    import multiprocessing as mp
    import os
    import tempfile

    root = tempfile.mkdtemp(prefix="ddp_bench_warm_")
    cache_dir = os.path.join(root, "cache")
    store_a = os.path.join(root, "aot_a")
    store_b = os.path.join(root, "aot_b")  # stays empty: forces compile
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        # The cold/warm contrast needs a cache that starts empty: the
        # children get their own, placed the way any cache is — from
        # outside, before jax imports.
        "JAX_COMPILATION_CACHE_DIR": cache_dir,
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    }
    ctx = mp.get_context("spawn")
    out = {}
    runs = (
        ("cold", store_a),       # fresh cache + store: full compile + save
        ("cache_hit", store_b),  # warm cache, empty store: cached compile
        ("aot", store_a),        # populated store: deserialize, no trace
    )
    for mode, store in runs:
        out_path = os.path.join(root, f"{mode}.json")
        p = ctx.Process(
            target=_warm_start_child,
            args=(mode, store, out_path, env),
        )
        p.start()
        p.join(timeout=420)
        if p.is_alive():
            p.terminate()
            p.join()
            out[mode] = {"error": "child timed out"}
        elif p.exitcode != 0 or not os.path.exists(out_path):
            out[mode] = {"error": f"child exit {p.exitcode}"}
        else:
            with open(out_path) as fh:
                out[mode] = _json.load(fh)
    try:
        cold_s = out["cold"]["acquire_s"]
        out["cache_hit_speedup"] = round(
            cold_s / out["cache_hit"]["acquire_s"], 2
        )
        out["aot_speedup"] = round(cold_s / out["aot"]["acquire_s"], 2)
        out["modes"] = [out[m]["mode"] for m, _ in runs]
    except (KeyError, TypeError, ZeroDivisionError):
        pass  # a child failed; its error record is already in out

    if os.environ.get("DDP_BENCH_SLOW"):
        from distributeddataparallel_tpu.runtime.launcher import spawn

        r_root = os.path.join(root, "restart")
        os.makedirs(r_root, exist_ok=True)
        # Launcher children apply ``env`` after jax is imported, too late
        # to place its cache; they inherit this process's environment at
        # birth, so the restart pair's own (empty) cache goes there.
        r_env = {
            **env, "JAX_COMPILATION_CACHE_DIR": os.path.join(r_root, "cache"),
        }
        saved = {k: os.environ.get(k) for k in r_env}
        os.environ.update(r_env)
        try:
            spawn(
                _restart_latency_worker,
                args=(os.path.join(r_root, "aot"), r_root),
                nprocs=1, max_restarts=1, restart_backoff_s=0.1,
            )
            att = {}
            for a in (0, 1):
                with open(
                    os.path.join(r_root, f"attempt{a}.json")
                ) as fh:
                    att[a] = _json.load(fh)
            out["restart_latency"] = {
                f"attempt{a}": {
                    k: att[a][k] for k in (
                        "mode", "acquire_s", "start_to_ready_s"
                    )
                }
                for a in (0, 1)
            }
            out["restart_latency"]["restart_speedup"] = round(
                att[0]["start_to_ready_s"] / att[1]["start_to_ready_s"], 2
            )
        except Exception as e:  # noqa: BLE001 — keep the fast numbers
            out["restart_latency"] = {"error": repr(e)}
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    else:
        out["restart_latency"] = {"skipped": "set DDP_BENCH_SLOW=1"}
    return out


def bench_elastic_resize() -> dict:
    """Elastic gang resize vs supervised cold restart, head to head: the
    SAME 8-fake-device CPU gang loses one worker mid-run (chaos), once
    with ``--elastic`` (in-process resize to 7, no checkpoint read) and
    once under the fixed-size supervisor (whole-gang respawn, AOT warm
    start — the strongest restart baseline this repo has).  Downtime is
    measured the same way on both sides, from the timeline each run
    leaves behind: first post-recovery step-span ts minus the
    chaos_inject ts.  Headlines: ``resize_downtime_s`` (lower-better)
    and ``restart_reclaimed_s`` = cold restart minus resize downtime
    (ends in _s but HIGHER is better — seconds given back; perf_gate's
    _HIGHER_BETTER knows the suffix)."""
    import os
    import subprocess
    import sys
    import tempfile

    from distributeddataparallel_tpu.observability.events import (
        load_timeline,
    )

    here = os.path.dirname(os.path.abspath(__file__))
    root = tempfile.mkdtemp(prefix="ddp_bench_elastic_")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    env.pop("_DDP_SUPERVISED", None)
    env.pop("DDP_ELASTIC_WORLD", None)
    base = [
        sys.executable, os.path.join(here, "dpp.py"),
        "--model", "mlp", "--fake-devices", "8", "--batch-size", "4",
        "--epochs", "1", "--steps-per-epoch", "12",
    ]
    runs = {
        # in-process resize: kill rank 5 at step 4, keep training at 7
        "resize": ["--elastic", "--chaos", "worker-kill@4:5"],
        # fixed-size baseline: same loss at the same step, whole-gang
        # respawn through the supervisor (checkpoint-dir is required by
        # --max-restarts and hosts the chaos marker files that keep the
        # preempt from re-firing in the respawn)
        "restart": ["--chaos", "preempt@4", "--max-restarts", "1"],
    }
    out = {}
    records = {}
    for mode, extra in runs.items():
        ev = os.path.join(root, f"ev_{mode}")
        cc = os.path.join(root, f"cc_{mode}")
        cmd = base + extra + ["--events-dir", ev, "--compile-cache", cc]
        if mode == "restart":
            cmd += ["--checkpoint-dir", os.path.join(root, "ckpt")]
        try:
            proc = subprocess.run(
                cmd, env=env, cwd=here, timeout=420,
                capture_output=True, text=True,
            )
        except subprocess.TimeoutExpired:
            out[mode] = {"error": "timed out"}
            continue
        recs = load_timeline(ev) if os.path.isdir(ev) else []
        records[mode] = recs
        out[mode] = {
            "exit": proc.returncode,
            "n_records": len(recs),
            "kinds": sorted({r.get("kind") for r in recs
                             if r.get("kind") in (
                                 "gang_resize", "restart_attempt",
                                 "resize_downtime")}),
        }
        if proc.returncode != 0:
            out[mode]["error"] = (proc.stderr or "")[-400:]

    def downtime(recs, disrupt_prefix, recover_kind):
        """First step-span ts at or after the recovery marker, minus the
        chaos_inject ts — the wall seconds training stood still."""
        dis = next((r["ts"] for r in recs
                    if r.get("kind") == "chaos_inject"
                    and str(r.get("entry", "")).startswith(disrupt_prefix)),
                   None)
        mark = next((r["ts"] for r in recs
                     if r.get("kind") == recover_kind), None)
        if dis is None or mark is None:
            return None
        rec = min((r["ts"] for r in recs
                   if r.get("kind") == "span" and r.get("name") == "step"
                   and r["ts"] >= mark), default=None)
        return None if rec is None else round(rec - dis, 3)

    rd = downtime(records.get("resize", []), "worker-kill", "gang_resize")
    cd = downtime(records.get("restart", []), "preempt", "restart_attempt")
    out["resize_downtime_s"] = rd
    out["cold_restart_s"] = cd
    if rd is not None and cd is not None:
        out["restart_reclaimed_s"] = round(cd - rd, 3)
        out["resize_beats_restart"] = rd < cd
    # the done bar of the elastic subsystem: the resize path must never
    # have fallen back to supervision, and vice versa
    out["resize_clean"] = (
        "restart_attempt" not in out.get("resize", {}).get("kinds", ())
        and "gang_resize" in out.get("resize", {}).get("kinds", ())
    )
    return out


def _observability_child(out_path, events_dir, env):
    """Telemetry-overhead measurement in a fresh 8-device CPU-mesh
    interpreter (same isolation rationale as _warm_start_child: the
    measurement needs no chip, and the CPU mesh is the acceptance
    target).  Three answers into out_path:

    - step_s_off / step_s_on: the SAME compiled GPT-2 124M step timed
      with observability disabled, then wired exactly as dpp.py wires it
      (per-step span, profiler hooks, steps_total counter,
      --metrics-every export cadence, the PR 5 attribution layer: MFU
      meter + memory sampling at the window boundary, and the alert
      engine evaluated at that same boundary);
    - syncs_off / syncs_on: jax.block_until_ready call counts in each
      loop — the telemetry-on loop must add ZERO;
    - telemetry_us_per_step: the per-step telemetry work microbenchmarked
      alone (2000 reps), the high-resolution form of the same overhead —
      differencing two multi-second step loops cannot resolve a
      sub-millisecond cost, the micro number can.
    """
    import os

    os.environ.update(env)
    import json
    import time

    import jax

    import bench as _bench
    from distributeddataparallel_tpu.observability import (
        AlertEngine,
        EventLog,
        JsonlExporter,
        MemoryTelemetry,
        MetricsRegistry,
        MFUMeter,
        ProfilerOrchestrator,
        Tracer,
        events_path,
        train_step_flops,
        transformer_fwd_flops,
        validate_file,
    )
    from distributeddataparallel_tpu.training.train_step import (
        make_train_step,
    )

    mesh, loss_fn, state, batch = _bench._gpt2_setup(
        "xla", per_chip_batch=2, seq_len=64
    )
    step = make_train_step(loss_fn, mesh=mesh, donate=False)
    key = jax.random.PRNGKey(0)

    # Count EVERY host sync either loop performs.
    real_block = jax.block_until_ready
    syncs = {"n": 0}

    def counting_block(x):
        syncs["n"] += 1
        return real_block(x)

    jax.block_until_ready = counting_block
    try:
        real_block(step(state, batch, key)[0].params)  # compile + warm
        # 2 iterations suffice: the loop exists to COUNT syncs (exact at
        # any length) and sanity-check the wall clock; the resolution
        # question is answered by the micro-benchmark below.  On a
        # 1-core host the 8-device virtual mesh runs one GPT-2 step in
        # ~1 min, so the loop length is the child's time budget.
        ITERS = 2

        def loop(tracer=None, prof=None, registry=None, metrics_every=100,
                 steps_total=None, mfu_meter=None, mem_tel=None,
                 alert_engine=None):
            syncs["n"] = 0
            s = state
            t0 = time.perf_counter()
            for i in range(ITERS):
                if prof is not None:
                    prof.on_step_start(i)
                if tracer is not None:
                    with tracer.span("step", step=i):
                        s, _ = step(s, batch, key)
                else:
                    s, _ = step(s, batch, key)
                if prof is not None:
                    prof.on_step_end(i)
                if steps_total is not None:
                    steps_total.inc()
                if registry is not None and i % metrics_every == 0:
                    registry.export(step=i)
            jax.block_until_ready(s.params)  # the one boundary drain
            dt = (time.perf_counter() - t0) / ITERS
            # The PR 5 attribution work runs exactly where dpp.py runs
            # it: AT the boundary where the loop already drained.  Kept
            # inside the counted region so syncs_on would expose any
            # device round-trip the meters sneaked in.
            att = sample = None
            if mfu_meter is not None:
                att = mfu_meter.on_reading(
                    {"steps_per_s": 1.0 / dt}, step=ITERS
                )
            if mem_tel is not None:
                sample = mem_tel.sample(ITERS)
            if alert_engine is not None:
                # Same contract as dpp.py: the engine sees only host
                # floats this boundary already computed, inside the
                # counted region so any device read it sneaked in would
                # show up in syncs_on.
                alert_engine.observe(
                    step=ITERS,
                    step_s=dt,
                    mfu=att["mfu"] if att else None,
                    live_hwm_bytes=(
                        sample.get("live_hwm_bytes") if sample else None
                    ),
                    restarts=0,
                )
            return dt, syncs["n"]

        step_s_off, syncs_off = loop()

        events = EventLog(events_path(events_dir, 0), 0)
        events.emit("run_start", argv=["bench_observability"])
        registry = MetricsRegistry()
        registry.add_exporter(JsonlExporter(events))
        registry.bind("faults", lambda: {"nonfinite_steps": 0})
        tracer = Tracer(events, registry)
        prof = ProfilerOrchestrator(None, events=events)  # disabled dir
        steps_total = registry.counter("steps_total")
        # Same cost model dpp.py --mfu builds: the fixture IS gpt2_124m
        # at per-chip batch 2, seq 64 (loss applies tokens[:, :-1]).
        from distributeddataparallel_tpu.models import gpt2_124m

        cfg = gpt2_124m(max_seq_len=64)
        fwd = transformer_fwd_flops(
            cfg, batch=2 * len(jax.devices()), seq_len=63
        )
        mfu_meter = MFUMeter(
            train_step_flops(fwd, remat=getattr(cfg, "remat", False)),
            n_chips=len(jax.devices()),
            peak_flops_per_chip=None,  # virtual CPU mesh: FLOP/s only
            registry=registry,
            events=events,
        )
        mem_tel = MemoryTelemetry(registry, events, jax.local_devices())
        alert_engine = AlertEngine(events=events, registry=registry)
        step_s_on, syncs_on = loop(
            tracer, prof, registry,
            steps_total=steps_total, mfu_meter=mfu_meter, mem_tel=mem_tel,
            alert_engine=alert_engine,
        )
        events.emit("run_end", status="ok")

        # Micro: the per-step telemetry work alone, at default cadence —
        # including the PR 5 boundary work (MFU arithmetic + live-array
        # walk) and the alert-rule evaluation at a window-ish cadence
        # of 100.
        REPS = 2000
        t0 = time.perf_counter()
        for i in range(REPS):
            prof.on_step_start(i)
            with tracer.span("step", step=i):
                pass
            prof.on_step_end(i)
            steps_total.inc()
            if i % 100 == 0:
                registry.export(step=i)
                att = mfu_meter.on_reading({"steps_per_s": 1.0}, step=i)
                sample = mem_tel.sample(i)
                alert_engine.observe(
                    step=i, step_s=1.0, mfu=att["mfu"],
                    live_hwm_bytes=(
                        sample.get("live_hwm_bytes") if sample else None
                    ),
                    restarts=0,
                )
        telemetry_us = (time.perf_counter() - t0) / REPS * 1e6
        events.close()
    finally:
        jax.block_until_ready = real_block

    problems = validate_file(events_path(events_dir, 0))
    with open(out_path, "w") as fh:
        json.dump({
            "step_s_off": round(step_s_off, 4),
            "step_s_on": round(step_s_on, 4),
            "overhead_frac_loop": round(step_s_on / step_s_off - 1.0, 4),
            "syncs_off": syncs_off,
            "syncs_on": syncs_on,
            "telemetry_us_per_step": round(telemetry_us, 1),
            "overhead_frac_micro": round(
                telemetry_us / 1e6 / step_s_off, 6
            ),
            "events_valid": not problems,
            "events_problems": problems[:5],
        }, fh)


def bench_observability() -> dict:
    """Observability done bar (PR 3 harness, extended with the PR 5
    attribution layer): with --events-dir, the steps_total counter, the
    MFU meter and memory sampling all wired at default cadence, step
    throughput on the 8-device CPU mesh (GPT-2 124M) stays within 2% of
    telemetry-off, with zero extra host syncs and a schema-valid event
    file."""
    import json as _json
    import multiprocessing as mp
    import os
    import tempfile

    root = tempfile.mkdtemp(prefix="ddp_bench_obs_")
    out_path = os.path.join(root, "out.json")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    ctx = mp.get_context("spawn")
    p = ctx.Process(
        target=_observability_child,
        args=(out_path, os.path.join(root, "events"), env),
    )
    p.start()
    # Unlike the warm-start children (compile only), this child runs
    # the compiled step 2×ITERS+1 times; on a 1-core host that is
    # minutes, not seconds.
    p.join(timeout=900)
    if p.is_alive():
        p.terminate()
        p.join()
        return {"error": "child timed out"}
    if p.exitcode != 0 or not os.path.exists(out_path):
        return {"error": f"child exit {p.exitcode}"}
    with open(out_path) as fh:
        out = _json.load(fh)
    out["zero_extra_syncs"] = out.get("syncs_on") == out.get("syncs_off")
    out["within_2pct"] = (
        out.get("overhead_frac_micro", 1.0) < 0.02
        and out["zero_extra_syncs"]
    )
    return out


def _zero_sharding_child(out_path, env):
    """ZeRO-2/3 memory-delta measurement in a fresh 8-device CPU-mesh
    interpreter (the acceptance target of the sharded-update work is the
    8-device CPU mesh, and the live-array walk must not see another
    section's leftovers).  For dp / zero2 / zero3 on the SAME GPT-2 124M
    fixture it records, into out_path:

    - perdevice_hwm_bytes: busiest-device live-array high-water mark
      across warm steps (``live_array_bytes_per_device`` — the only view
      that can see the sharding win; global nbytes cannot);
    - step_s: mean warm step time (zero2/3 must stay within 10% of dp);
    - exec memory_analysis of the compiled step (the compiler's own
      per-device budget, the mesh-sim counterpart of the measured HWM).

    Each variant rebuilds params from the same seed and drops every
    handle before sampling, so a replicated tree from one variant can
    never inflate the next one's HWM.
    """
    import gc
    import os

    os.environ.update(env)
    import json
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data.loader import shard_batch
    from distributeddataparallel_tpu.models import TransformerLM
    from distributeddataparallel_tpu.models.transformer import gpt2_124m
    from distributeddataparallel_tpu.observability.memory import (
        MemoryTelemetry,
        executable_memory_analysis,
    )
    from distributeddataparallel_tpu.ops import lm_cross_entropy
    from distributeddataparallel_tpu.parallel.zero import zero_state
    from distributeddataparallel_tpu.training.train_step import (
        make_train_step,
    )

    SEQ, PER_CHIP, STEPS = 128, 1, 3
    mesh = ddp.make_mesh(("data",))
    n = len(jax.devices())
    cfg = gpt2_124m(max_seq_len=SEQ, scan_layers=True)
    model = TransformerLM(cfg)
    init = jax.jit(model.init)

    def loss_fn(p, batch, rng):
        toks = batch["tokens"]
        logits = model.apply({"params": p}, toks[:, :-1],
                             deterministic=True)
        return lm_cross_entropy(logits, toks[:, 1:]), {}

    npr = np.random.default_rng(0)
    batch = shard_batch(
        {"tokens": npr.integers(
            0, cfg.vocab_size, size=(PER_CHIP * n, SEQ + 1)
        ).astype(np.int32)},
        mesh,
    )
    key = jax.random.PRNGKey(0)

    results = {}
    for name, level in (("dp", 0), ("zero2", 2), ("zero3", 3)):
        params = init(
            jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32)
        )["params"]
        tx = optax.adamw(3e-4)
        if level:
            s = zero_state(apply_fn=model.apply, params=params, tx=tx,
                           mesh=mesh, level=level)
        else:
            s = ddp.broadcast_params(
                ddp.TrainState.create(
                    apply_fn=model.apply, params=params, tx=tx
                ),
                mesh,
            )
        # the unsharded init tree must die before sampling or it bills
        # ~500 MB to one device under every variant alike
        del params
        gc.collect()

        step = make_train_step(loss_fn, mesh=mesh, zero=level or False)
        compiled = step.lower(s, batch, key).compile()
        mem_tel = MemoryTelemetry()
        s, _ = step(s, batch, key)  # warm (donates the init state)
        jax.block_until_ready(jax.tree.leaves(s.params)[0])
        t0 = time.perf_counter()
        for i in range(STEPS):
            s, _ = step(s, batch, key)
            jax.block_until_ready(jax.tree.leaves(s.params)[0])
            mem_tel.sample(i)
        dt = (time.perf_counter() - t0) / STEPS
        results[name] = {
            "step_s": round(dt, 4),
            "perdevice_hwm_bytes": mem_tel.live_perdevice_hwm_bytes,
            "exec_memory": executable_memory_analysis(compiled),
        }
        del s, step, compiled
        gc.collect()

    with open(out_path, "w") as fh:
        json.dump(results, fh)


def bench_zero_sharding() -> dict:
    """Sharded weight update done bar: on the 8-device CPU mesh,
    GPT-2 124M per-device live-array HWM drops >=25% at zero2 vs dp
    (further at zero3) while step time stays within 10% of dp."""
    import json as _json
    import multiprocessing as mp
    import os
    import tempfile

    root = tempfile.mkdtemp(prefix="ddp_bench_zero_")
    out_path = os.path.join(root, "out.json")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_zero_sharding_child, args=(out_path, env))
    p.start()
    # three variants x (compile + 4 steps) of GPT-2 on a virtual
    # 8-device mesh: minutes on a 1-core host, like bench_observability
    p.join(timeout=900)
    if p.is_alive():
        p.terminate()
        p.join()
        return {"error": "child timed out"}
    if p.exitcode != 0 or not os.path.exists(out_path):
        return {"error": f"child exit {p.exitcode}"}
    with open(out_path) as fh:
        out = _json.load(fh)
    dp_hwm = out.get("dp", {}).get("perdevice_hwm_bytes") or 0
    dp_s = out.get("dp", {}).get("step_s") or 0.0
    for v in ("zero2", "zero3"):
        rec = out.get(v)
        if not rec or not dp_hwm:
            continue
        rec["hwm_drop_vs_dp"] = round(
            1.0 - rec["perdevice_hwm_bytes"] / dp_hwm, 4
        )
        if dp_s:
            rec["step_over_dp"] = round(rec["step_s"] / dp_s, 3)
    out["meets_25pct_drop"] = bool(
        out.get("zero2", {}).get("hwm_drop_vs_dp", 0.0) >= 0.25
    )
    return out


def _autotune_child(out_path, env):
    """Autotuner acceptance run in a fresh 8-device CPU-mesh
    interpreter: a small but real search over GPT-2 124M (short seq)
    with the hand-picked default as the measured baseline.  Writes the
    winner, the baseline, and the gain to out_path.

    The baseline is what a careful human would type on this box —
    per-chip batch 1 with remat on — so ``gain_frac`` is the honest
    answer to "did the tuner beat me", not a strawman.
    """
    import os

    os.environ.update(env)
    import json
    import tempfile

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.tuning import (
        SearchSpace,
        TrialConfig,
        TuningStore,
        search_model,
    )

    mesh = ddp.make_mesh(("data",))
    space = SearchSpace(
        batch_per_chip=(1, 2), accum_steps=(1,), remat=(False, True),
        zero=(0, 1), moment_dtype=("f32",),
    )
    baseline = TrialConfig(batch_per_chip=1, accum_steps=1, remat=True)
    tmp = tempfile.mkdtemp(prefix="ddp_bench_tune_")
    summary = search_model(
        "gpt2-small", mesh=mesh, seq=64, space=space, baseline=baseline,
        top_k=2, warmup_steps=1, measure_steps=2, seed=0,
        tune_store=TuningStore(os.path.join(tmp, "tuned")),
    )
    out = {
        "winner": summary["winner"],
        "baseline": summary["baseline"],
        "gain_frac": summary["gain_frac"],
        "records": summary["records"],
        "store_path": summary["store_path"],
    }
    with open(out_path, "w") as fh:
        json.dump(out, fh)


def bench_autotune() -> dict:
    """Autotune done bar: on the 8-device CPU mesh, the searched config
    for GPT-2 124M beats the hand-picked default (tune_gain_frac > 0),
    and the winner is persisted for ``--autotune apply`` to replay."""
    import json as _json
    import multiprocessing as mp
    import os
    import tempfile

    root = tempfile.mkdtemp(prefix="ddp_bench_autotune_")
    out_path = os.path.join(root, "out.json")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_autotune_child, args=(out_path, env))
    p.start()
    # 3 measured candidates x (compile + 3 steps) of GPT-2 on a virtual
    # 8-device mesh: minutes on a 1-core host, like bench_zero_sharding
    p.join(timeout=1200)
    if p.is_alive():
        p.terminate()
        p.join()
        return {"error": "child timed out"}
    if p.exitcode != 0 or not os.path.exists(out_path):
        return {"error": f"child exit {p.exitcode}"}
    with open(out_path) as fh:
        out = _json.load(fh)
    w = out.get("winner") or {}
    out["tuned_step_s"] = w.get("measured_step_s")
    out["tune_gain_frac"] = out.get("gain_frac")
    out["tuner_beats_default"] = bool(
        (out.get("gain_frac") or 0.0) > 0.0
    )
    return out


def _serving_child(out_path, events_dir, env):
    """Continuous-batching vs static-batch serving on the 8-device CPU
    mesh, in a fresh interpreter (the serving acceptance target; the
    parent process holds the chip).

    Both sides serve the SAME seeded Poisson trace on the SAME tiny
    model with greedy decoding:

    - **continuous**: the serving engine (paged KV, slot batch,
      chunked prefill) in wall-clock mode — requests admitted the step
      they arrive, retired the step they hit max_new_tokens;
    - **static**: the pre-engine serving idiom this subsystem replaces —
      collect arrivals into fixed batches of num_slots, pad every
      prompt to the trace max, run ONE compiled ``generate()`` for the
      trace-max new tokens, deliver everything at batch end.  Same
      fixed shapes (one executable, compiled before timing), so the
      contrast is pure scheduling: padding waste + tail-token waste +
      convoy TTFT, not compile counts.

    Both sides pay compilation before their timed region.  tok/s counts
    only REQUESTED tokens on both sides (the static batch generates
    trace-max tokens for every row; the excess is waste, not credit).
    """
    import os

    os.environ.update(env)
    import json
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddataparallel_tpu.models import TransformerLM, generate
    from distributeddataparallel_tpu.models.transformer import tiny_lm
    from distributeddataparallel_tpu.observability.events import (
        EventLog,
        events_path,
        merge_timeline,
    )
    from distributeddataparallel_tpu.observability.registry import (
        MetricsRegistry,
    )
    from distributeddataparallel_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        LoadConfig,
        make_trace,
        run_load,
    )

    # Scaled-up tiny config: ~12 ms decode steps, so a reachable
    # arrival rate saturates the server (the stock tiny_lm outruns any
    # honest rate on this host and both sides just measure the trace).
    cfg = tiny_lm(
        num_layers=4, d_model=256, d_ff=1024, num_heads=8,
        max_seq_len=128,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]

    # Saturating load (arrivals outpace drain: ~1200 tok/s offered vs
    # ~675 tok/s engine capacity measured at full slots): at a gentle
    # rate both sides are arrival-bound and tok/s measures the trace,
    # not the server; under saturation the static batch's padding waste
    # (every row generates the trace-max tokens) shows up as the real
    # tok/s gap while the convoy effect shows up in TTFT.
    lcfg = LoadConfig(
        rate_rps=120.0, duration_s=1.0, prompt_len=(4, 24),
        output_len=(4, 16), vocab_size=cfg.vocab_size, seed=0,
    )
    trace = make_trace(lcfg)
    n_slots = 8

    # -- continuous batching (the engine) -----------------------------
    os.makedirs(events_dir, exist_ok=True)
    events = EventLog(events_path(events_dir, 0), 0)
    events.emit("run_start", argv=["bench_serving"], role="serve")
    registry = MetricsRegistry()
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=n_slots, num_blocks=64, block_size=16,
                     prefill_chunk=32),
        events=events, registry=registry,
    )
    # Warmup: compile both programs (prefill + decode) outside the
    # timed region, leaving the engine drained.
    engine.submit(np.arange(4, dtype=np.int32) % cfg.vocab_size, 4)
    engine.run()
    engine.completed.clear()  # warmup must not count in the summary
    t0 = time.perf_counter()
    cb = run_load(engine, trace)
    cb_wall = time.perf_counter() - t0
    events.emit("metrics", snapshot=registry.snapshot())
    events.emit("run_end", status="ok")
    events.close()
    merge_timeline(events_dir)

    # -- static batching (generate() on fixed shapes) -----------------
    p_max = max(len(r["prompt"]) for r in trace)
    n_max = max(r["max_new_tokens"] for r in trace)
    pad_prompt = np.zeros((n_slots, p_max), np.int32)
    warm = generate(model, params, jnp.asarray(pad_prompt), n_max)
    assert int(jnp.sum(warm)) >= 0  # compile + fence

    t0 = time.perf_counter()
    done_at = {}
    for lo in range(0, len(trace), n_slots):
        group = trace[lo:lo + n_slots]
        # The batch cannot launch before its last member arrives.
        launch = max(r["arrival_s"] for r in group)
        now = time.perf_counter() - t0
        if now < launch:
            time.sleep(launch - now)
        batch = np.zeros((n_slots, p_max), np.int32)
        for i, r in enumerate(group):
            batch[i, :len(r["prompt"])] = r["prompt"]
        out = generate(model, params, jnp.asarray(batch), n_max)
        assert int(jnp.sum(out)) >= 0  # fence: tokens delivered now
        end = time.perf_counter() - t0
        for r in group:
            done_at[id(r)] = end
    static_wall = time.perf_counter() - t0
    static_tokens = sum(r["max_new_tokens"] for r in trace)
    static_ttft = sorted(
        done_at[id(r)] - r["arrival_s"] for r in trace
    )

    def pct(vals, q):
        return float(np.percentile(vals, q)) if vals else None

    out = {
        "requests": len(trace),
        "completed": cb["completed"],
        "num_slots": n_slots,
        "rate_rps": lcfg.rate_rps,
        "serve_tok_s": cb["serve_tok_s"],
        "serve_p50_ttft_s": cb["serve_p50_ttft_s"],
        "serve_p99_ttft_s": cb["serve_p99_ttft_s"],
        "cb_wall_s": round(cb_wall, 3),
        "static_tok_s": round(static_tokens / static_wall, 1),
        "static_p50_ttft_s": round(pct(static_ttft, 50), 4),
        "static_p99_ttft_s": round(pct(static_ttft, 99), 4),
        "static_wall_s": round(static_wall, 3),
        "cb_tok_s_speedup": round(
            cb["serve_tok_s"] / (static_tokens / static_wall), 3
        ),
        "cb_p99_ttft_improvement": round(
            pct(static_ttft, 99) / max(cb["serve_p99_ttft_s"], 1e-9), 3
        ),
        "preemptions": cb["preemptions"],
        "evictions": cb["evictions"],
    }
    with open(out_path, "w") as fh:
        json.dump(out, fh)


def _integrity_child(out_path, env):
    """Digest-on vs digest-off step timing in a fresh 8-device CPU-mesh
    interpreter (same isolation as the other CPU-mesh children: the
    acceptance target is the fake-device mesh, not the chip).

    Headline arm replicates dpp.py's production dispatch: cadence-length
    step windows where the single cadence step runs the digest-armed
    program and the rest run the bit-identical plain program, against
    plain-only windows.  A cadence-1 worst case (EVERY timed step pays
    the digest + all_gather) rides along as detail.  Tiny model on
    purpose: a 1-core host runs a GPT-2 step in ~40 s, which cannot
    resolve a 1% delta; a ~100 ms step can.  The two arms run
    INTERLEAVED and the minimum per-arm time is compared (min-of-reps
    is robust to the host's additive noise, and interleaving cancels
    thermal/load drift that back-to-back loops would bake into one
    side).  Also runs one flip round-trip as a correctness canary so
    the perf number can never come from a digest that stopped
    detecting.
    """
    import os

    os.environ.update(env)
    import json
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.data.loader import shard_batch
    from distributeddataparallel_tpu.models import TransformerLM, tiny_lm
    from distributeddataparallel_tpu.ops import lm_cross_entropy
    from distributeddataparallel_tpu.training import integrity as integ
    from distributeddataparallel_tpu.training.train_step import (
        make_train_step,
    )

    SEQ = 64
    mesh = ddp.make_mesh(("data",))
    n = len(jax.devices())
    cfg = tiny_lm(max_seq_len=SEQ, num_layers=4, d_model=64, d_ff=128)
    model = TransformerLM(cfg)

    def loss_fn(p, batch, rng):
        logits = model.apply({"params": p}, batch["tokens"][:, :-1],
                             deterministic=True)
        return lm_cross_entropy(logits, batch["tokens"][:, 1:]), {}

    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32)
    )["params"]
    state = ddp.broadcast_params(
        ddp.TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adamw(3e-4)
        ),
        mesh,
    )
    npr = np.random.default_rng(0)
    batch = shard_batch(
        {"tokens": npr.integers(
            0, cfg.vocab_size, size=(2 * n, SEQ + 1)
        ).astype(np.int32)},
        mesh,
    )
    key = jax.random.PRNGKey(0)

    # Both arms arm the nonfinite guard — the recommended production
    # config (dpp.py runs --nan-guard alongside --integrity-every), and
    # the config whose cost model the train step optimizes for: the SDC
    # verdict folds into the guard's existing whole-state skip select,
    # so the digest-on arm's marginal cost is the cadence-gated digest
    # + all_gather alone, which is exactly what this A/B measures.
    CADENCE = 50  # a representative production cadence
    step_off = make_train_step(
        loss_fn, mesh=mesh, donate=False, nonfinite_guard=True
    )
    step_on1 = make_train_step(
        loss_fn, mesh=mesh, donate=False, nonfinite_guard=True,
        integrity_every=1,
    )
    step_onN = make_train_step(
        loss_fn, mesh=mesh, donate=False, nonfinite_guard=True,
        integrity_every=CADENCE,
    )

    def once(step, s_in):
        t0 = time.perf_counter()
        s, m = step(s_in, batch, key)
        jax.block_until_ready(s)
        return time.perf_counter() - t0, (s, m)

    for _ in range(2):  # compile + warm all three programs
        once(step_off, state)
        once(step_on1, state)
        once(step_onN, state)

    # Under dpp.py's dual-program dispatch the CADENCE-1 off-cadence
    # steps ARE the digest-off executable — their marginal cost is zero
    # by construction, not by measurement.  What a production window
    # pays extra is (a) the one cadence step running the digest-armed
    # program instead of the plain one and (b) the following plain step
    # consuming state produced by a different executable (a possible
    # relayout at the program switch).  Both are single-step deltas, so
    # they are measured as tightly-interleaved singles (min-of-reps
    # kills the host's additive noise; whole-window A/B timing on this
    # box has a ~3% noise floor that swamps a 0.2% effect) and
    # amortized over the cadence for the headline.
    s_digest = once(step_onN, state)[1][0]  # digest-program-made state
    m_on = once(step_on1, state)[1][1]      # clean-run cadence metrics
    REPS = 25
    times = {"plain": [], "digest": [], "switch": []}
    arms = [
        ("plain", step_off, state),
        ("digest", step_onN, state),
        ("switch", step_off, s_digest),
    ]
    for i in range(REPS):
        for name, fn, s_in in arms[i % 3:] + arms[: i % 3]:
            t, _ = once(fn, s_in)
            times[name].append(t)
    w_off = min(times["plain"])
    w_on = min(times["digest"])
    switch_s = max(0.0, min(times["switch"]) - w_off)
    amortized = ((w_on - w_off) + switch_s) / (CADENCE * w_off)

    # canary: the timed digest still detects a real flip
    flipped = integ.apply_bitflip(state, rank=3, mesh=mesh)
    _, m = step_on1(flipped, batch, key)
    mat = np.asarray(jax.device_get(m["sdc_digest"]))
    verdict = integ.vote(mat)

    with open(out_path, "w") as fh:
        json.dump({
            "cadence": CADENCE,
            "integrity_overhead_frac": round(amortized, 5),
            "digest_step_s_off": round(w_off, 5),
            "digest_step_s_on": round(w_on, 5),
            "digest_step_overhead_frac": round((w_on - w_off) / w_off, 4),
            "program_switch_s": round(switch_s, 5),
            "clean_mismatch": float(m_on["sdc_mismatch"]),
            "canary_detected": bool(
                not verdict.ok and verdict.corrupt == (3,)
            ),
        }, fh)


def bench_integrity() -> dict:
    """SDC-digest overhead (--integrity-every): the claim is <= 1%
    amortized step-time cost at a production cadence.  Headline
    ``integrity_overhead_frac`` compares cadence-length step windows
    under dpp.py's dual-program dispatch (exactly one digest step per
    window, plain program elsewhere) and is gated lower-better by
    perf_gate's ``_frac`` suffix rule; the cadence-1 worst case rides
    along as ``digest_step_overhead_frac``."""
    import json as _json
    import multiprocessing as mp
    import os
    import tempfile

    root = tempfile.mkdtemp(prefix="ddp_bench_integrity_")
    out_path = os.path.join(root, "out.json")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_integrity_child, args=(out_path, env))
    p.start()
    p.join(timeout=900)
    if p.is_alive():
        p.terminate()
        p.join()
        return {"error": "child timed out"}
    if p.exitcode != 0 or not os.path.exists(out_path):
        return {"error": f"child exit {p.exitcode}"}
    with open(out_path) as fh:
        out = _json.load(fh)
    out["within_1pct"] = (
        out.get("integrity_overhead_frac", 1.0) <= 0.01
        and out.get("canary_detected", False)
        and out.get("clean_mismatch") == 0.0
    )
    return out



def bench_serving() -> dict:
    """Serving done bar: on the 8-device CPU mesh, the continuous-
    batching engine beats static-batch generate() on the same Poisson
    trace in BOTH tok/s and p99 TTFT; headline keys serve_tok_s /
    serve_p99_ttft_s are gated by perf_gate."""
    import json as _json
    import multiprocessing as mp
    import os
    import tempfile

    root = tempfile.mkdtemp(prefix="ddp_bench_serve_")
    out_path = os.path.join(root, "out.json")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    ctx = mp.get_context("spawn")
    p = ctx.Process(
        target=_serving_child,
        args=(out_path, os.path.join(root, "events"), env),
    )
    p.start()
    p.join(timeout=600)
    if p.is_alive():
        p.terminate()
        p.join()
        return {"error": "child timed out"}
    if p.exitcode != 0 or not os.path.exists(out_path):
        return {"error": f"child exit {p.exitcode}"}
    with open(out_path) as fh:
        out = _json.load(fh)
    out["cb_beats_static"] = bool(
        out.get("cb_tok_s_speedup", 0) > 1.0
        and out.get("cb_p99_ttft_improvement", 0) > 1.0
    )
    return out


def _serving_fastpath_child(out_path, env):
    """Serving fast path (refcounted radix prefix cache + speculative
    decoding) vs the plain engine, in a fresh interpreter.

    Both sides serve the SAME seeded shared-prefix Zipf trace (a pool
    of hot system-prompt-like prefixes, Zipf rank weights, random
    suffixes) on the SAME scaled-up tiny model, wall-clock, greedy:

    - **base**: the engine as benched above — every admitted request
      prefills its full context, one token per decode dispatch;
    - **fast**: ``prefix_cache=True`` maps the shared prefix blocks
      out of the radix cache (skipping their prefill FLOPs entirely)
      and ``spec_k=4`` drafts 4 tokens per slot per step through the
      fixed-shape verify program, emitting every accepted prefix
      token in one dispatch.

    Greedy outputs are bitwise-identical by construction (pinned by
    tests/test_serving.py), so the contrast is pure scheduling/compute:
    avoided prefill chunks + multi-token decode steps.  Headline keys
    spec_tok_s_speedup / prefix_hit_frac / prefill_flops_avoided_frac
    gate higher-is-better; fastpath_p99_ttft_s lower-is-better.
    """
    import os

    os.environ.update(env)
    import json
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddataparallel_tpu.models import TransformerLM
    from distributeddataparallel_tpu.models.transformer import tiny_lm
    from distributeddataparallel_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        LoadConfig,
        make_trace,
        run_load,
    )

    cfg = tiny_lm(
        num_layers=4, d_model=256, d_ff=1024, num_heads=8,
        max_seq_len=128,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]

    # Long shared prefixes (48 of 56-63 prompt tokens) + saturating
    # arrivals + long generations (48-64): the base side pays chunked
    # prefill for every hot prefix AND one dispatch per output token —
    # the radix cache attacks the former, speculation the latter.  The
    # two compose: the cache alone leaves the run decode-bound, which
    # is exactly the regime where multi-token verify dispatches pay.
    lcfg = LoadConfig(
        rate_rps=60.0, duration_s=1.0, prompt_len=(56, 63),
        output_len=(48, 64), vocab_size=cfg.vocab_size, seed=0,
        prefix_pool=4, prefix_len=48, zipf_alpha=1.1,
    )
    trace = make_trace(lcfg)

    def run_side(prefix_cache, spec_k):
        engine = InferenceEngine(
            model, params,
            EngineConfig(num_slots=8, num_blocks=96, block_size=16,
                         prefill_chunk=32, prefix_cache=prefix_cache,
                         spec_k=spec_k),
        )
        # Warmup compiles every program this side dispatches (prefill +
        # decode or verify) outside the timed region; the warmup
        # request's stats must not count.
        engine.submit(np.arange(40, dtype=np.int32) % cfg.vocab_size, 4)
        engine.run()
        engine.completed.clear()
        for attr in ("prefix_admits", "prefix_hits", "prefix_hit_tokens",
                     "prefix_ctx_tokens", "cow_copies", "spec_rows",
                     "spec_drafted", "spec_accepted"):
            setattr(engine, attr, 0)
        t0 = time.perf_counter()
        out = run_load(engine, trace)
        out["wall_s"] = round(time.perf_counter() - t0, 3)
        return out

    base = run_side(False, 0)
    fast = run_side(True, 4)

    out = {
        "requests": len(trace),
        "completed": fast["completed"],
        "rate_rps": lcfg.rate_rps,
        "prefix_pool": lcfg.prefix_pool,
        "prefix_len": lcfg.prefix_len,
        "zipf_alpha": lcfg.zipf_alpha,
        "base_tok_s": round(base["serve_tok_s"], 1),
        "base_p50_ttft_s": round(base["serve_p50_ttft_s"], 4),
        "base_p99_ttft_s": round(base["serve_p99_ttft_s"], 4),
        "base_wall_s": base["wall_s"],
        "fast_tok_s": round(fast["serve_tok_s"], 1),
        "fast_p50_ttft_s": round(fast["serve_p50_ttft_s"], 4),
        "fastpath_p99_ttft_s": round(fast["serve_p99_ttft_s"], 4),
        "fast_wall_s": fast["wall_s"],
        "spec_tok_s_speedup": round(
            fast["serve_tok_s"] / max(base["serve_tok_s"], 1e-9), 3
        ),
        "fastpath_p99_ttft_improvement": round(
            base["serve_p99_ttft_s"]
            / max(fast["serve_p99_ttft_s"], 1e-9), 3
        ),
        "prefix_hit_frac": round(fast.get("prefix_hit_frac", 0.0), 3),
        "prefill_flops_avoided_frac": round(
            fast.get("prefill_flops_avoided_frac", 0.0), 3
        ),
        "spec_accept_mean": round(fast.get("spec_accept_mean", 0.0), 3),
        "cow_copies": fast.get("cow_copies", 0),
        "preemptions": fast["preemptions"],
        "evictions": fast["evictions"],
    }
    with open(out_path, "w") as fh:
        json.dump(out, fh)


def bench_serving_fastpath() -> dict:
    """Fast-path done bar: on the shared-prefix Zipf trace the engine
    with prefix cache + speculation sustains >1.5x the plain engine's
    tok/s and drops p99 TTFT, with >0.5 of admissions hitting the
    radix cache; headline keys spec_tok_s_speedup / prefix_hit_frac /
    prefill_flops_avoided_frac are gated higher-is-better."""
    import json as _json
    import multiprocessing as mp
    import os
    import tempfile

    root = tempfile.mkdtemp(prefix="ddp_bench_fastpath_")
    out_path = os.path.join(root, "out.json")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_serving_fastpath_child, args=(out_path, env))
    p.start()
    p.join(timeout=600)
    if p.is_alive():
        p.terminate()
        p.join()
        return {"error": "child timed out"}
    if p.exitcode != 0 or not os.path.exists(out_path):
        return {"error": f"child exit {p.exitcode}"}
    with open(out_path) as fh:
        out = _json.load(fh)
    out["fastpath_beats_base"] = bool(
        out.get("spec_tok_s_speedup", 0) > 1.5
        and out.get("fastpath_p99_ttft_improvement", 0) > 1.0
        and out.get("prefix_hit_frac", 0) > 0.5
    )
    return out


def _serving_fleet_child(out_path, env):
    """Disaggregated fleet (1 prefill + 2 decode engines, KV-block
    handoff, session-affinity router) vs 3 identical MONOLITHIC engines
    behind the same router, in a fresh interpreter.

    Both sides serve the SAME seeded multi-turn trace (every base
    request seeds a 2-turn session whose follow-up extends the prior
    prompt) on the SAME scaled-up tiny model, wall-clock, greedy —
    ``ServingFleet`` with ``prefill=0`` IS the monolithic baseline
    (the router load-balances decode engines that each prefill their
    own requests, one chunk per step, interleaved with decode).

    Why disaggregation wins here: (a) TTFT decouples from decode-slot
    occupancy — the first token is produced on the prefill tier, so a
    full decode batch of long generations no longer delays a new
    prompt's first token; (b) the prefill tier runs 4 chunks per step
    with no decode batch to protect; (c) decode work concentrates on
    fewer engines, so each fixed-shape decode dispatch carries more
    active slots (tokens per dispatch), which is the whole cost model
    of the padded (num_slots, 1) program.

    A THIRD run re-serves the trace on the fleet with one decode
    engine killed mid-drive: its requests (and in-flight handoffs to
    it) must drain-and-requeue onto the survivor with zero dropped —
    that run feeds ``dropped_req_total`` (hard-zero in perf_gate), not
    the perf headlines.
    """
    import os

    os.environ.update(env)
    import json
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddataparallel_tpu.models import TransformerLM
    from distributeddataparallel_tpu.models.transformer import tiny_lm
    from distributeddataparallel_tpu.serving import (
        EngineConfig,
        LoadConfig,
        make_trace,
        run_load,
    )
    from distributeddataparallel_tpu.serving.fleet import (
        FleetConfig,
        ServingFleet,
    )

    cfg = tiny_lm(
        num_layers=4, d_model=256, d_ff=1024, num_heads=8,
        max_seq_len=256,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    ecfg = EngineConfig(
        num_slots=8, num_blocks=128, block_size=16, prefill_chunk=32,
        prefix_cache=True,
    )
    # Long prompts (prefill-heavy admissions) + long outputs (decode
    # occupancy that delays monolithic admissions) + 2-turn sessions
    # (affinity traffic for the router) at a saturating rate.
    lcfg = LoadConfig(
        rate_rps=30.0, duration_s=1.0, prompt_len=(72, 96),
        output_len=(32, 48), vocab_size=cfg.vocab_size, seed=0,
        turns=2, turn_gap_s=0.3, turn_tokens=(8, 16),
    )
    trace = make_trace(lcfg)

    def build(prefill, decode, events=None):
        fleet = ServingFleet(
            model, params, ecfg,
            FleetConfig(prefill=prefill, decode=decode,
                        prefill_chunks_per_step=4),
            events=events,
        )
        # Warm every engine's programs outside the timed region, then
        # reset the stats the summary reads.  Each jitted program lives
        # per-ENGINE, so the warmup must walk every compile the timed
        # trace will hit: prompt lengths spanning the trace's handoff
        # block counts (set_pool_blocks compiles per count), and
        # sessioned follow-up turns so the DECODE tier's prefill
        # program compiles too (affinity hits prefill there — injected
        # requests alone never would).
        rng = np.random.default_rng(123)
        lens = [int(x) for x in np.linspace(
            lcfg.prompt_len[0],
            lcfg.prompt_len[1] + lcfg.turn_tokens[1] + 1,
            max(8, 2 * (prefill + decode)),
        )]
        for i, n in enumerate(lens):
            p = rng.integers(0, cfg.vocab_size, n).tolist()
            fleet.submit(p, 4, session=f"warm-{i}")
            while fleet.has_work():
                fleet.step()
            fleet.submit(
                p + rng.integers(0, cfg.vocab_size, 8).tolist(), 4,
                session=f"warm-{i}",
            )
        while fleet.has_work():
            fleet.step()
        fleet.completed.clear()
        fleet.dropped.clear()
        fleet.handoffs = 0
        fleet.handoff_bytes = 0
        fleet.handoff_s_sum = 0.0
        fleet.router.routed = 0
        fleet.router.affinity_hits = 0
        fleet.router._affinity.clear()
        for eng in fleet.engines.values():
            eng.completed.clear()
            for attr in ("prefix_admits", "prefix_hits",
                         "prefix_hit_tokens", "prefix_ctx_tokens",
                         "cow_copies"):
                setattr(eng, attr, 0)
        return fleet

    def timed(fleet):
        t0 = time.perf_counter()
        out = run_load(fleet, trace)
        out["wall_s"] = round(time.perf_counter() - t0, 3)
        return out

    mono = timed(build(0, 3))
    # The disagg run records its span timeline so the TTFT
    # decomposition headlines come from the SAME trace the perf
    # numbers do (warmup fids are filtered out below).
    from distributeddataparallel_tpu.observability import critical_path
    from distributeddataparallel_tpu.observability.events import (
        EventLog,
        read_events,
    )

    span_log_path = os.path.join(
        os.path.dirname(out_path), "events-fleet.jsonl"
    )
    span_log = EventLog(span_log_path, "bench-fleet")
    fleet = build(1, 2, events=span_log)
    disagg = timed(fleet)
    span_log.close()
    timed_fids = set(fleet.completed)
    decomps = [
        d for d in critical_path.request_decompositions(
            read_events(span_log_path)
        )
        if d["req"] in timed_fids
    ]
    droll = critical_path.ttft_rollup(decomps)

    # Robustness run: same trace, one decode engine killed mid-drive.
    kfleet = build(1, 2)
    i = 0
    t0 = time.perf_counter()
    killed = False
    while i < len(trace) or kfleet.has_work():
        now = time.perf_counter() - t0
        while i < len(trace) and trace[i]["arrival_s"] <= now:
            r = trace[i]
            kfleet.submit(
                r["prompt"], r["max_new_tokens"],
                session=r.get("session"),
            )
            i += 1
        if not killed and i >= len(trace) // 2:
            kfleet.kill_engine("decode-1")
            killed = True
        if kfleet.has_work():
            kfleet.step()
        else:
            time.sleep(0.0002)

    out = {
        "requests": len(trace),
        "completed": disagg["completed"],
        "rate_rps": lcfg.rate_rps,
        "turns": lcfg.turns,
        "mono_tok_s": round(mono["serve_tok_s"], 1),
        "mono_p50_ttft_s": round(mono["serve_p50_ttft_s"], 4),
        "mono_p99_ttft_s": round(mono["serve_p99_ttft_s"], 4),
        "mono_wall_s": mono["wall_s"],
        "fleet_tok_s": round(disagg["serve_tok_s"], 1),
        "fleet_p50_ttft_s": round(disagg["serve_p50_ttft_s"], 4),
        "fleet_p99_ttft_s": round(disagg["serve_p99_ttft_s"], 4),
        "fleet_wall_s": disagg["wall_s"],
        "fleet_tok_s_speedup": round(
            disagg["serve_tok_s"] / max(mono["serve_tok_s"], 1e-9), 3
        ),
        "fleet_p99_ttft_improvement": round(
            mono["serve_p99_ttft_s"]
            / max(disagg["serve_p99_ttft_s"], 1e-9), 3
        ),
        "handoffs": disagg["handoffs"],
        "handoff_bytes": disagg["handoff_bytes"],
        "handoff_s": round(disagg["handoff_s"], 5),
        "re_handoff_blocks": disagg["re_handoff_blocks"],
        "affinity_hits": disagg["affinity_hits"],
        "affinity_frac": round(
            disagg["affinity_hits"] / max(disagg["routed"], 1), 3
        ),
        "tiers": disagg.get("tiers"),
        # TTFT decomposition over the disagg run's span timeline:
        # share fractions + the span-tree self-consistency error
        # (all lower-better in perf_gate via _share_frac/_decomp_err).
        "ttft_queue_share_frac": round(
            droll.get("ttft_queue_share_frac", 0.0), 4
        ),
        "ttft_handoff_share_frac": round(
            droll.get("ttft_handoff_share_frac", 0.0), 4
        ),
        "ttft_decomp_err_frac": round(
            droll.get("ttft_decomp_err_frac", 1.0), 4
        ),
        "ttft_decomp_requests": droll.get("requests", 0),
        # Kill run (robustness, not perf): every request must still
        # complete — dropped_req_total is hard-zero in perf_gate.
        "dropped_req_total": len(kfleet.dropped),
        "kill_completed": len(kfleet.completed),
        "kill_requeued": kfleet.requeued,
        "kill_handoffs": kfleet.handoffs,
    }
    with open(out_path, "w") as fh:
        json.dump(out, fh)


def bench_serving_fleet() -> dict:
    """Fleet done bar: the 1:2 disaggregated fleet beats 3 monolithic
    engines on p99 TTFT while holding tokens/s, and the engine-kill
    run drains with zero dropped requests.  Headline keys
    fleet_tok_s_speedup (higher-better via _speedup$), fleet_p99_ttft_s
    / handoff_s (lower-better via _s$), dropped_req_total (lower-better
    + hard-zero), plus the TTFT decomposition from the disagg run's
    span timeline: ttft_queue_share_frac / ttft_handoff_share_frac /
    ttft_decomp_err_frac (all lower-better via the _share_frac /
    _decomp_err_frac row)."""
    import json as _json
    import multiprocessing as mp
    import os
    import tempfile

    root = tempfile.mkdtemp(prefix="ddp_bench_fleet_")
    out_path = os.path.join(root, "out.json")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_serving_fleet_child, args=(out_path, env))
    p.start()
    p.join(timeout=600)
    if p.is_alive():
        p.terminate()
        p.join()
        return {"error": "child timed out"}
    if p.exitcode != 0 or not os.path.exists(out_path):
        return {"error": f"child exit {p.exitcode}"}
    with open(out_path) as fh:
        out = _json.load(fh)
    out["fleet_beats_mono"] = bool(
        out.get("fleet_tok_s_speedup", 0) >= 1.0
        and out.get("fleet_p99_ttft_improvement", 0) > 1.0
        and out.get("dropped_req_total", 1) == 0
        and out.get("kill_completed", 0) == out.get("requests", -1)
    )
    return out


def _run(fn, label: str) -> dict:
    """Run a bench section, with one retry.  A section that fails twice
    leaves an error record so the other sections still report — and
    main() exits non-zero once the JSON is out."""
    for attempt in (1, 2):
        t0 = time.perf_counter()
        try:
            out = fn()
            out["wall_s"] = round(time.perf_counter() - t0, 1)
            return out
        except Exception as e:  # noqa: BLE001
            import sys
            import traceback

            traceback.print_exc()
            print(f"[bench] {label} attempt {attempt} failed: {e}",
                  file=sys.stderr)
    return {"error": f"{label} failed twice"}


def main() -> None:
    import os

    import jax

    from distributeddataparallel_tpu.training.warm_start import (
        resolve_compile_cache,
    )

    resolve_compile_cache()

    dev = jax.devices()[0]
    resnet = _run(bench_resnet50, "resnet50")
    gpt2 = _run(bench_gpt2, "gpt2")
    llama = _run(bench_llama, "llama")
    decode = _run(bench_decode, "decode")
    moe = _run(bench_moe_scaling, "moe_scaling")
    cp_ring = _run(bench_cp_ring, "cp_ring")
    overlap = _run(bench_overlap, "overlap")
    pp_zb = _run(bench_pipeline_zb, "pipeline_zb")
    pp_bubble = pp_zb.get("analytic", {})  # roofline column rides along
    input_pipe = _run(bench_input_pipeline, "input_pipeline")
    warm = _run(bench_warm_start, "warm_start")
    elastic = _run(bench_elastic_resize, "elastic_resize")
    obs = _run(bench_observability, "observability")
    integrity = _run(bench_integrity, "integrity")
    zshard = _run(bench_zero_sharding, "zero_sharding")
    serving = _run(bench_serving, "serving")
    fastpath = _run(bench_serving_fastpath, "serving_fastpath")
    fleet = _run(bench_serving_fleet, "serving_fleet")
    autotune = _run(bench_autotune, "autotune")
    # Config 3's done bar: can the host pipeline feed the device?
    if "host_gather_img_s" in input_pipe and "img_s_chip" in resnet:
        dev_rate = resnet["img_s_chip"] * len(jax.devices())
        input_pipe["device_img_s"] = round(dev_rate, 1)
        input_pipe["host_over_device"] = round(
            input_pipe["host_gather_img_s"] / max(dev_rate, 1e-9), 3
        )

    # Token-pipeline done-bar (mirrors the image one above).
    if "token_gather_tok_s" in input_pipe and "tokens_s_chip" in gpt2:
        tok_dev = gpt2["tokens_s_chip"] * len(jax.devices())
        input_pipe["device_tok_s"] = round(tok_dev, 1)
        input_pipe["token_host_over_device"] = round(
            input_pipe["token_gather_tok_s"] / max(tok_dev, 1e-9), 3
        )

    img_s_chip = resnet.get("img_s_chip", 0.0)
    target = TARGET_FRACTION * A100_DDP_RESNET50_IMG_S
    full = {
        "metric": "img/s/chip (resnet50_imagenet_dp)",
        "value": img_s_chip,
        "unit": "img/s/chip",
        "vs_baseline": round(img_s_chip / target, 4),
        "extras": {
            "peaks": _device_peaks(),
            "device_kind": dev.device_kind,
            "platform": dev.platform,
            "n_devices": len(jax.devices()),
            "resnet50": resnet,
            "gpt2_124m": gpt2,
            "llama_0p6b": llama,
            "decode_gpt2": decode,
            "moe_token_choice": moe,
            "cp_ring_block": cp_ring,
            "overlap_gpt2_dp": overlap,
            "pipeline_1f1b_bubble": pp_bubble,
            "pipeline_zb": pp_zb,
            "input_pipeline": input_pipe,
            "warm_start": warm,
            "elastic_resize": elastic,
            "observability": obs,
            "integrity": integrity,
            "zero_sharding": zshard,
            "serving": serving,
            "serving_fastpath": fastpath,
            "serving_fleet": fleet,
            "autotune": autotune,
        },
    }
    # Full detail: stdout (live readers) + a file next to this script —
    # the driver persists only a 2 KB stdout TAIL, which round 4 proved
    # loses the headline sections (VERDICT r4 missing 3).
    print(json.dumps(full))
    detail_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json"
    )
    with open(detail_path, "w") as fh:
        json.dump(full, fh, indent=1)

    # LAST line: a compact headline summary sized to always fit the
    # driver's tail, so every README perf claim is auditable from
    # BENCH_r{N}.json alone.
    def _sched(rep):
        if not isinstance(rep, dict):
            return {"error": "missing"}
        if "error" in rep:
            return {"error": str(rep["error"])[:60]}
        return {
            "windows": rep["n_async_windows"],
            "sync": rep["n_sync_collectives"],
            "frac_compute": rep["overlapped_frac_of_compute"],
            "async_bytes_frac": rep["async_bytes_frac"],
        }

    headline = {
        "metric": full["metric"],
        "value": img_s_chip,
        "unit": "img/s/chip",
        "vs_baseline": full["vs_baseline"],
        "headline": {
            "device": dev.device_kind,
            "resnet50_img_s_chip": img_s_chip,
            "resnet50_mfu": resnet.get("mfu_est"),
            "gpt2_tok_s_chip": gpt2.get("tokens_s_chip"),
            "gpt2_mfu": gpt2.get("mfu_est"),
            "gpt2_attn_winner": gpt2.get("attn_winner"),
            "llama_tok_s_chip": llama.get("tokens_s_chip"),
            "llama_mfu": llama.get("mfu_est"),
            "decode_tok_s_chip_b256": (
                decode.get("per_batch", {}).get("256", {})
                .get("decode_tokens_s_chip")
            ),
            "decode_hbm_util_b8": decode.get("hbm_util_b8"),
            "decode_int8_llama_step_speedup": decode.get(
                "int8_llama_0p6b", {}
            ).get("step_speedup_int8"),
            "decode_int8_gpt2_b8_step_speedup": decode.get(
                "int8_b8", {}
            ).get("step_speedup_int8"),
            "moe_e16_over_e4": moe.get("e16_over_e4"),
            "moe_roofline": moe.get("e16_over_e4_weight_traffic_roofline"),
            "moe_ep_shard_frac_measured": moe.get("ep_memory", {}).get(
                "measured_expert_shard_frac"
            ),
            "flash_vs_xla_block_speedup": cp_ring.get("flash_speedup"),
            "overlap_real_gpt2": _sched(
                overlap.get("real_step_schedule_gpt2")
            ),
            "overlap_real_llama": _sched(
                overlap.get("real_step_schedule_llama")
            ),
            "pp_interleaved_bubble_v4_over_v1": (
                pp_bubble.get("stages8_mb32", {}).get("v4_over_v1_bubble")
            ),
            # flat keys (perf_gate contract): *_frac / *_s suffixes make
            # both lower-is-better; measured from the compiled zb
            # schedule's phase counters, not the tick model
            "zb_bubble_frac": pp_zb.get("zb_bubble_frac"),
            "zb_step_s": pp_zb.get("zb_step_s"),
            "zb_beats_1f1b": pp_zb.get("zb_beats_1f1b_analytic"),
            "input_host_gather_img_s": input_pipe.get("host_gather_img_s"),
            "input_host_over_device": input_pipe.get("host_over_device"),
            "token_gather_tok_s": input_pipe.get("token_gather_tok_s"),
            "token_host_over_device": input_pipe.get(
                "token_host_over_device"
            ),
            "warm_start_s": {
                "cold": warm.get("cold", {}).get("acquire_s"),
                "cache": warm.get("cache_hit", {}).get("acquire_s"),
                "aot": warm.get("aot", {}).get("acquire_s"),
                "aot_x": warm.get("aot_speedup"),
            },
            # flat on purpose (perf_gate): resize_downtime_s is
            # lower-better via _s$; restart_reclaimed_s is the seconds
            # the elastic path gave back vs a cold restart — HIGHER is
            # better (_HIGHER_BETTER's reclaimed_s$ override)
            "resize_downtime_s": elastic.get("resize_downtime_s"),
            "restart_reclaimed_s": elastic.get("restart_reclaimed_s"),
            # flat on purpose (perf_gate): the _frac suffix makes the
            # SDC-digest step-time cost lower-is-better; measured at
            # cadence 1, the worst case — production cadence N pays 1/N
            "integrity_overhead_frac": integrity.get(
                "integrity_overhead_frac"
            ),
            "integrity_ok": integrity.get("within_1pct"),
            "obs": {
                "ovh": obs.get("overhead_frac_micro"),
                "sync0": obs.get("zero_extra_syncs"),
                "ok": obs.get("within_2pct"),
            },
            # flat keys on purpose: perf_gate gates top-level numerics,
            # and the *_bytes / *_s suffixes make them lower-is-better
            "z2_hwm_bytes": zshard.get("zero2", {}).get(
                "perdevice_hwm_bytes"
            ),
            "z3_hwm_bytes": zshard.get("zero3", {}).get(
                "perdevice_hwm_bytes"
            ),
            "z2_step_s": zshard.get("zero2", {}).get("step_s"),
            "z2_hwm_drop": zshard.get("zero2", {}).get("hwm_drop_vs_dp"),
            # flat on purpose (same perf_gate contract as above); the
            # rate suffixes hit _HIGHER_BETTER, the _ttft_s ones are
            # latency -> lower-better
            "serve_tok_s": serving.get("serve_tok_s"),
            "serve_p99_ttft_s": serving.get("serve_p99_ttft_s"),
            "serve_cb_speedup": serving.get("cb_tok_s_speedup"),
            "serve_beats_static": serving.get("cb_beats_static"),
            # flat on purpose (perf_gate): _speedup / _hit_frac /
            # _avoided_frac hit _HIGHER_BETTER's win-share overrides;
            # fastpath_p99_ttft_s stays lower-better via _s$
            "spec_tok_s_speedup": fastpath.get("spec_tok_s_speedup"),
            "prefix_hit_frac": fastpath.get("prefix_hit_frac"),
            "prefill_flops_avoided_frac": fastpath.get(
                "prefill_flops_avoided_frac"
            ),
            "fastpath_p99_ttft_s": fastpath.get("fastpath_p99_ttft_s"),
            # flat on purpose (perf_gate): _speedup$ makes the fleet
            # tok/s ratio higher-better; fleet_p99_ttft_s / handoff_s
            # are lower-better via _s$; dropped_req_total is the
            # hard-zero loss counter (_HARD_ZERO) — nonzero fails the
            # gate regardless of baseline
            "fleet_tok_s_speedup": fleet.get("fleet_tok_s_speedup"),
            "fleet_p99_ttft_s": fleet.get("fleet_p99_ttft_s"),
            "handoff_s": fleet.get("handoff_s"),
            "dropped_req_total": fleet.get("dropped_req_total"),
            # flat on purpose (perf_gate): the tracing rollup's
            # _share_frac / _decomp_err_frac row pins all three
            # lower-better
            "ttft_queue_share_frac": fleet.get("ttft_queue_share_frac"),
            "ttft_handoff_share_frac": fleet.get(
                "ttft_handoff_share_frac"
            ),
            "ttft_decomp_err_frac": fleet.get("ttft_decomp_err_frac"),
            # (fleet_beats_mono stays in extras.serving_fleet — the
            # headline only carries what perf_gate can gate, and the
            # 1.9KB tail budget is nearly full)
            # flat on purpose (perf_gate): tuned_step_s is lower-better
            # via _s$; tune_gain_frac is the autotuner's win over the
            # hand-picked default — HIGHER is better (_HIGHER_BETTER's
            # gain_frac$ override beats the _frac$ waste-share rule)
            "tuned_step_s": autotune.get("tuned_step_s"),
            "tune_gain_frac": autotune.get("tune_gain_frac"),
            "tuner_beats_default": autotune.get("tuner_beats_default"),
            "detail": "BENCH_DETAIL.json (full sections)",
        },
    }
    line = json.dumps(headline)
    assert len(line) < 1900, f"headline line {len(line)}B > 1.9KB tail budget"
    print(line)
    failed = [
        name for name, section in full["extras"].items()
        if isinstance(section, dict)
        and str(section.get("error", "")).endswith("failed twice")
    ]
    if failed:
        raise SystemExit(f"bench: section(s) failed twice: {failed}")


if __name__ == "__main__":
    main()
