#!/usr/bin/env python
"""Llama-3 8B memory-fit analysis (BASELINE config 5 evidence).

Strategy: XLA's compile-time memory assignment is exact, but the full 8B
config exceeds this chip's HBM and XLA refuses to compile it (its OOM
message reports only a lower bound).  With ``scan_layers=True`` peak
memory is affine in the layer count L (scanned layers stack parameters;
remat keeps one layer's backward live at a time) and in the vocab size V
(embedding + lm_head params and the f32 logits buffer), with no L x V
cross term.  So the full config's peak is recovered by measuring configs
that compile on this chip — the REAL shapes (d_model 4096, d_ff 14336,
GQA 32/8, full 128256 vocab, seq as given; bf16 compute, f32 params,
remat + scan, donated state — the exact step ``dpp.py`` runs), just
fewer layers — and extrapolating only the layer direction:

    peak(32, mb) = peak_measured(L0, full_vocab, mb) + (32-L0)*dL(mb)

The grid runs with STATELESS sgd; optimizer state is then added
analytically (its exact bytes from ``tx.init``'s abstract shapes — the
donated update is elementwise, so opt state is purely additional
resident memory).  Three validation points are measured and reported:
the L midpoint (affinity in L), the full-vocab column (affinity in V),
and an sgd+momentum compile (the optimizer-bytes additivity).

Nothing is allocated at any point — compile-only on the real TPU
backend.  Run: ``python memfit.py [--seq-len 4096]``; output committed
as MEMFIT.md.
"""

from __future__ import annotations

import argparse

# Usable HBM XLA reports when a program exceeds it ("Used ... of 15.75G
# hbm", v5e): the fallback where memory_stats() gives no bytes_limit.
V5E_HBM_BYTES = int(15.75 * (1 << 30))
V5P_HBM_BYTES = 95 * (1 << 30)  # BASELINE config 5's platform


def gb(x: float) -> float:
    return round(x / (1 << 30), 2)


def _abstract_state(model, tx):
    import jax
    import jax.numpy as jnp

    import distributeddataparallel_tpu as ddp

    def make():
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
        )["params"]
        return ddp.TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    return jax.eval_shape(make)


def _tree_bytes(tree) -> int:
    import jax

    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


def _peak_bytes(model, tx, mb: int, seq_len: int) -> int:
    """AOT-compile the DP train step; return XLA's peak memory figure."""
    import jax
    import jax.numpy as jnp

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.ops import lm_cross_entropy

    astate = _abstract_state(model, tx)

    def loss_fn(params, batch, rng):
        toks = batch["tokens"]
        logits = model.apply({"params": params}, toks[:, :-1])
        return lm_cross_entropy(logits, toks[:, 1:]), {}

    mesh = ddp.make_mesh(("data",), devices=jax.devices()[:1])
    step = ddp.make_train_step(loss_fn, mesh=mesh)
    akey = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    abatch = {"tokens": jax.ShapeDtypeStruct((mb, seq_len + 1), jnp.int32)}
    ma = step.lower(astate, abatch, akey).compile().memory_analysis()
    return ma.peak_memory_in_bytes or (
        ma.argument_size_in_bytes
        + ma.temp_size_in_bytes
        + ma.output_size_in_bytes
        - ma.alias_size_in_bytes
    )


def analyze(seq_len: int, microbatches=(1, 2)) -> dict:
    import jax
    import optax

    from distributeddataparallel_tpu.models import TransformerLM, llama3_8b

    sgd = optax.sgd(1e-3)  # stateless: isolates model memory
    L0, L1, Lmid = 2, 4, 3
    V0 = 16032  # small vocab for the layer direction (keeps L=4 on-chip)

    full_cfg = llama3_8b(max_seq_len=seq_len)
    target_layers, target_vocab = full_cfg.num_layers, full_cfg.vocab_size

    def model_at(L, V):
        return TransformerLM(
            llama3_8b(max_seq_len=seq_len, num_layers=L, vocab_size=V)
        )

    def peak(L, V, mb, tx=sgd):
        return _peak_bytes(model_at(L, V), tx, mb, seq_len)

    # The FULL-vocab base is measured directly at L0 (it fits on-chip) —
    # no extrapolation in V at all (vocab-coupled memory is not quite
    # affine: XLA pads/lays out the big logits buffer differently at
    # 128256 than at small vocabs; measured 17% off in an earlier affine
    # attempt).  Only the layer direction, which IS affine under scan
    # (validated below), is extrapolated.
    peak_model, checks = {}, []
    for mb in microbatches:
        a = peak(L0, V0, mb)
        dL = (peak(L1, V0, mb) - a) / (L1 - L0)
        base_full = peak(L0, target_vocab, mb)
        peak_model[mb] = base_full + (target_layers - L0) * dL
        if mb == microbatches[0]:
            # Validation 1: affinity in L — the midpoint must sit on the line.
            mid_pred = a + (Lmid - L0) * dL
            mid_meas = peak(Lmid, V0, mb)
            checks.append({
                "what": f"L affinity (L={Lmid}, V={V0}, mb={mb})",
                "predicted_gb": gb(mid_pred), "measured_gb": gb(mid_meas),
                "rel_err": round(abs(mid_pred - mid_meas) / mid_meas, 4),
            })
            # Validation 2: dL is vocab-independent (no L x V cross term) —
            # the L2->L3 delta at FULL vocab must equal dL measured at V0.
            try:
                l3_pred = base_full + (Lmid - L0) * dL
                l3_meas = peak(Lmid, target_vocab, mb)
                checks.append({
                    "what": f"dL vocab-independence (L={Lmid}, "
                            f"V={target_vocab}, mb={mb})",
                    "predicted_gb": gb(l3_pred), "measured_gb": gb(l3_meas),
                    "rel_err": round(abs(l3_pred - l3_meas) / l3_meas, 4),
                })
            except Exception as e:  # noqa: BLE001 — validation point OOM
                checks.append({
                    "what": f"dL vocab-independence (L={Lmid}): "
                            f"did not fit on this chip ({type(e).__name__})",
                    "predicted_gb": None, "measured_gb": None,
                    "rel_err": None,
                })
            # Validation 3: optimizer state adds exactly its bytes.
            mom = optax.sgd(1e-3, momentum=0.9)
            mom_bytes = _tree_bytes(
                _abstract_state(model_at(L0, V0), mom).opt_state
            )
            mom_pred = a + mom_bytes
            mom_meas = peak(L0, V0, mb, tx=mom)
            checks.append({
                "what": f"opt-state additivity (sgd+momentum, L={L0}, V={V0})",
                "predicted_gb": gb(mom_pred), "measured_gb": gb(mom_meas),
                "rel_err": round(abs(mom_pred - mom_meas) / mom_meas, 4),
            })

    b0, b1 = microbatches
    slope = (peak_model[b1] - peak_model[b0]) / (b1 - b0)
    model_fixed = peak_model[b0] - b0 * slope

    dev = jax.local_devices()[0]
    hbm = (dev.memory_stats() or {}).get("bytes_limit") or V5E_HBM_BYTES

    full_model = TransformerLM(full_cfg)
    params_bytes = _tree_bytes(_abstract_state(full_model, sgd).params)

    def max_mb(limit, fixed_bytes):
        if slope <= 0:
            return None
        return max(0, int((limit - fixed_bytes) // slope))

    # TP-8: Megatron layout shards the layer params (q/k/v/o, MLP) over 8
    # chips — exact byte fractions from the spec tree; embeddings/norms
    # stay replicated.  Params AND grads shard; opt state mirrors params.
    # The activation slope is kept unsharded (a conservative upper bound:
    # TP also divides attention/MLP activations, which we cannot measure
    # on one chip).
    from distributeddataparallel_tpu.parallel.tensor_parallel import (
        tp_param_specs,
    )

    def sharded_bytes(tree) -> int:
        specs = tp_param_specs(tree)
        return sum(
            l.size * l.dtype.itemsize
            for l, s in zip(jax.tree.leaves(tree), jax.tree.leaves(specs))
            if any(s)
        )

    TPN = 8
    FSDPN = 8
    # FSDP byte math (optimizer-independent parts, parallel/fsdp.py):
    # stored = per-chip params shards; the gathered non-layer flat and
    # ~2 gathered layers (current + backward regather) live full.
    from distributeddataparallel_tpu.parallel.fsdp import _Meta

    meta = _Meta(full_cfg, FSDPN)
    layer_elems = sum(l.size for l in jax.tree.leaves(meta.layer_template))
    rest_elems = meta.rest_chunk * FSDPN
    # v2 gathers ride bf16 (gather_dtype) and the rest flat is
    # checkpointed around its two uses, so the transient is the LARGER
    # of (gathered rest) and (~2 gathered layers), not their sum.
    fsdp_transient = max(2 * rest_elems, 2 * 2 * layer_elems)
    fsdp_stored = 4 * (meta.L * meta.layer_chunk + meta.rest_chunk)
    FSDPN32 = 32
    fsdp32_stored = fsdp_stored * FSDPN / FSDPN32
    rows = []
    for name, tx in (
        ("sgd", sgd),
        ("sgd_momentum", optax.sgd(1e-3, momentum=0.9)),
        ("adamw", optax.adamw(3e-4)),
    ):
        ast = _abstract_state(full_model, tx)
        opt_bytes = _tree_bytes(ast.opt_state)
        fixed = model_fixed + opt_bytes
        # params + grads each drop their sharded fraction (N-1)/N; opt
        # state drops its own sharded fraction.
        sharded_opt = sharded_bytes(ast.opt_state)
        tp_saving = (
            2 * sharded_bytes(ast.params) + sharded_opt
        ) * (TPN - 1) / TPN
        tp_fixed = fixed - tp_saving
        # TP-8 x ZeRO-1x8 (a DP(8) x TP(8) pod slice): params/grads keep
        # the TP fractions; the flat opt state is built from each
        # position's LOCAL Megatron shard and then 1/8-sharded again over
        # the data axis (parallel/zero.py zero_state(tp_axis=...)).
        tp_local_opt = (opt_bytes - sharded_opt) + sharded_opt / TPN
        tp_zero_fixed = tp_fixed - tp_local_opt + tp_local_opt / 8
        # FSDP-8: params, grads, and opt state all 1/8 resident; plus the
        # full gathered non-layer flat, ~2 gathered layers, AND the same
        # measured non-param residual (model_fixed - params - grads, the
        # XLA/framework overhead ~10 GB) every other column inherits —
        # without it the FSDP column would not be comparable.
        opt_mult = opt_bytes / max(params_bytes, 1)  # 0 sgd, 1 mom, 2 adamw
        residual = max(model_fixed - 2 * params_bytes, 0)
        fsdp_fixed = (
            fsdp_stored * (2 + opt_mult) + fsdp_transient + residual
        )
        fsdp32_fixed = (
            fsdp32_stored * (2 + opt_mult) + fsdp_transient + residual
        )
        rows.append({
            "optimizer": name,
            "opt_state_gb": gb(opt_bytes),
            "peak8b_gb": {mb: gb(p + opt_bytes) for mb, p in peak_model.items()},
            "fixed_gb": gb(fixed),
            "max_mb_v5e": max_mb(hbm, fixed),
            "max_mb_v5p": max_mb(V5P_HBM_BYTES, fixed),
            # ZeRO-1 over N chips keeps 1/N of the opt state per chip
            # (parallel/zero.py); nothing else changes.
            "zero1x8_fixed_gb": gb(model_fixed + opt_bytes / 8),
            "zero1x8_max_mb_v5p": max_mb(
                V5P_HBM_BYTES, model_fixed + opt_bytes / 8
            ),
            "tp8_fixed_gb": gb(tp_fixed),
            "tp8_max_mb_v5p": max_mb(V5P_HBM_BYTES, tp_fixed),
            "tp8_max_mb_v5e": max_mb(hbm, tp_fixed),
            "tp8_zero8_fixed_gb": gb(tp_zero_fixed),
            "tp8_zero8_max_mb_v5p": max_mb(V5P_HBM_BYTES, tp_zero_fixed),
            "fsdp8_fixed_gb": gb(fsdp_fixed),
            "fsdp8_max_mb_v5p": max_mb(V5P_HBM_BYTES, fsdp_fixed),
            "fsdp8_max_mb_v5e": max_mb(hbm, fsdp_fixed),
            "fsdp32_fixed_gb": gb(fsdp32_fixed),
            "fsdp32_max_mb_v5e": max_mb(hbm, fsdp32_fixed),
        })

    # ZeRO-level ladder (sharded weight update, adamw, N=8 data shards):
    # per-chip PEAK coefficient on params bytes P and between-step STORED
    # state, from the parallel/zero.py byte model.  Peak counts params +
    # grads + opt-state residency; zero1 and zero2 share a peak line (opt
    # at 1/N) — zero2's win over zero1 is the scatter TRANSIENT (one
    # ~1 MiB bucket instead of a full P-byte flat f32 grad copy) and is
    # below the GB resolution of this table.  zero3 is peak-honest for
    # the implemented full-gather step: the gathered param tree is live
    # at peak, so peak EXCEEDS zero2 by P/N while stored drops to
    # (params + opt)/N — the stored column is what checkpoint/resident
    # HWM telemetry sees (mesh_sim).
    ZN = 8
    P = params_bytes
    adamw_opt = _tree_bytes(
        _abstract_state(full_model, optax.adamw(3e-4)).opt_state
    )
    opt_coeff = adamw_opt / max(P, 1)  # 2.0 for adamw
    zero_levels = []
    for name, peak_coeff, stored in (
        ("dp", 2.0 + opt_coeff, P + adamw_opt),
        ("zero1", 2.0 + opt_coeff / ZN, P + adamw_opt / ZN),
        ("zero2", 2.0 + opt_coeff / ZN, P + adamw_opt / ZN),
        ("zero3", 2.0 + (1 + opt_coeff) / ZN, (P + adamw_opt) / ZN),
    ):
        fixed = peak_coeff * P + residual
        headroom = max(V5P_HBM_BYTES - residual - slope, 0)
        zero_levels.append({
            "level": name,
            "stored_gb": gb(stored),
            "fixed_gb": gb(fixed),
            "max_mb_v5e": max_mb(hbm, fixed),
            "max_mb_v5p": max_mb(V5P_HBM_BYTES, fixed),
            # largest f32 param count whose mb=1 step still fits a v5p
            # chip: invert fixed(P) = coeff*P + residual at one act row
            "max_params_b_v5p_mb1": round(
                headroom / peak_coeff / 4 / 1e9, 2
            ),
        })

    return {
        "device_kind": dev.device_kind,
        "seq_len": seq_len,
        "hbm_gb": gb(hbm),
        "params_gb": gb(params_bytes),
        "act_gb_per_row": gb(slope),
        "model_fixed_gb": gb(model_fixed),
        "validations": checks,
        "optimizers": rows,
        "zero_levels": zero_levels,
    }


def main() -> None:
    from distributeddataparallel_tpu.training.warm_start import (
        resolve_compile_cache,
    )

    # Reruns reuse the measured grid's binaries.
    resolve_compile_cache()

    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=4096)
    args = p.parse_args()

    r = analyze(args.seq_len)
    print(f"# Llama-3 8B memory fit — measured on {r['device_kind']} "
          f"({r['hbm_gb']} GB HBM), seq {r['seq_len']}, remat+scan, "
          f"bf16 compute / f32 params, donated state\n")
    print(f"Params: {r['params_gb']} GB f32; model fixed cost "
          f"{r['model_fixed_gb']} GB (params + grads + residue); "
          f"activations {r['act_gb_per_row']} GB per batch row.  Peaks "
          f"are XLA's exact compile-time memory assignment (AOT, nothing "
          f"allocated): the full-128256-vocab base is measured directly "
          f"at 2 layers, then extrapolated in the layer direction only "
          f"(affine under scan, validated below); optimizer state adds "
          f"its exact byte size.  v5p columns project onto a 95 GB chip "
          f"(BASELINE config 5's platform).\n")
    print("Regression validations (each predicted from the regression "
          "basis, then measured directly):\n")
    for c in r["validations"]:
        print(f"- {c['what']}: predicted {c['predicted_gb']} GB, measured "
              f"{c['measured_gb']} GB, rel err {c['rel_err']}")
    print()
    print("| optimizer | opt state | 8B peak @mb=1 | 8B peak @mb=2 | "
          "max mb (v5e 16G) | max mb (v5p 95G) | ZeRO-1x8 fixed | "
          "ZeRO-1x8 max mb (v5p) | TP-8 fixed | TP-8 max mb (v5p) | "
          "TP-8 x ZeRO-1x8 fixed | TP-8 x ZeRO max mb (v5p) | "
          "FSDP-8 fixed | FSDP-8 max mb (v5p) | FSDP-8 max mb (v5e 16G) | "
          "FSDP-32 fixed | FSDP-32 max mb (v5e 16G) |  "
          "(FSDP columns assume --fsdp-gather bf16; f32 gathers double "
          "the transient term)")
    print("|---|---|---|---|---|---|---|---|---|---|---|---|---|---|---|---|---|")
    for row in r["optimizers"]:
        mbs = sorted(row["peak8b_gb"])
        print(
            f"| {row['optimizer']} | {row['opt_state_gb']} GB "
            f"| {row['peak8b_gb'][mbs[0]]} GB | {row['peak8b_gb'][mbs[1]]} GB "
            f"| {row['max_mb_v5e']} | {row['max_mb_v5p']} "
            f"| {row['zero1x8_fixed_gb']} GB | {row['zero1x8_max_mb_v5p']} "
            f"| {row['tp8_fixed_gb']} GB | {row['tp8_max_mb_v5p']} "
            f"| {row['tp8_zero8_fixed_gb']} GB "
            f"| {row['tp8_zero8_max_mb_v5p']} "
            f"| {row['fsdp8_fixed_gb']} GB | {row['fsdp8_max_mb_v5p']} "
            f"| {row['fsdp8_max_mb_v5e']} "
            f"| {row['fsdp32_fixed_gb']} GB | {row['fsdp32_max_mb_v5e']} |"
        )
    print()
    print("## Sharded weight update (ZeRO ladder, adamw, N=8 data "
          "shards)\n")
    print("Per-chip byte model of the parallel/zero.py update path.  "
          "'Stored' is the between-step resident state (what HWM "
          "telemetry and checkpoints see); 'peak' adds the transient "
          "gradients (and for zero3 the gathered param tree, which the "
          "implemented full-gather step keeps live at peak — zero3 "
          "trades a slightly higher peak for 1/N stored params).  zero1 "
          "and zero2 share a peak line: zero2's win is the scatter "
          "transient (one ~1 MiB bucket, not a full flat f32 grad "
          "copy), below this table's GB resolution.\n")
    print("| level | stored / chip | peak fixed | max mb (v5e 16G) | "
          "max mb (v5p 95G) | max f32 params @mb=1 (v5p) |")
    print("|---|---|---|---|---|---|")
    for z in r["zero_levels"]:
        print(
            f"| {z['level']} | {z['stored_gb']} GB | {z['fixed_gb']} GB "
            f"| {z['max_mb_v5e']} | {z['max_mb_v5p']} "
            f"| {z['max_params_b_v5p_mb1']} B |"
        )
    import json
    print("\n```json")
    print(json.dumps(r))
    print("```")


if __name__ == "__main__":
    main()
