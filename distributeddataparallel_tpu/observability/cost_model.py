"""Analytic per-step FLOP accounting + MFU/HFU meters.

The north-star efficiency number ("Scalable Training of Language Models
using JAX pjit and TPUv4", PAPERS.md) is **MFU** — model FLOPs per
second over the hardware's peak — and computing it needs a numerator
nobody measures at runtime: how many useful FLOPs one optimizer step
represents.  This module derives that number analytically from the
model configuration (matmul terms only, the MFU convention: embedding
lookups, norms, softmax, and other VPU work are excluded from the
numerator on purpose), and cross-checks it against XLA's own
``jax.jit(...).lower(...).cost_analysis()`` in the tests — the two
agree within a few percent on the repo's configs, which is what makes
the analytic number trustworthy on hardware where cost analysis is
unavailable.

Conventions (PaLM appendix B / the pjit-TPUv4 paper):

- train step FLOPs = 3x forward (forward + ~2x backward);
- **MFU** counts model FLOPs only; **HFU** additionally counts the
  recompute that rematerialization performs (one extra forward, so 4x);
- gradient accumulation splits the batch into microbatches, it does NOT
  multiply the work — per-step FLOPs are accumulation-invariant, and
  the train-step factory's ``flop_signature`` handoff records that so
  the meter can't be wired wrong;
- attention scores/values are counted over the FULL S×S square (no
  causal halving) — the Pallas/XLA kernels here compute the full
  square, so that is the work the chip actually does.

Module-import rule: stdlib only at module scope — ``MFUMeter`` feeds
gauges that export from import-light contexts; jax is imported inside
the few helpers that need it.
"""

from __future__ import annotations

# Peak dense matmul throughput per chip, FLOP/s (bf16 where the MXU has
# a bf16 path; the models here run bf16 matmuls on TPU).  Same contract
# as utils.metrics.ICI_PEAK_BYTES_PER_S: denominators for a *relative*
# utilization number — record which one was used.  "cpu" is a loopback
# ballpark so MFU stays a meaningful (small, nonzero) fraction in the
# 8-fake-device CI runs.
PEAK_FLOPS_PER_CHIP = {
    "tpu v5 lite": 197e12,
    "tpu v5e": 197e12,
    "tpu v5p": 459e12,
    "tpu v4": 275e12,
    "cpu": 5e10,
}


def peak_flops_for(device) -> float | None:
    """Known peak FLOP/s for the device kind, or None (unknown hardware —
    better no MFU than one against a wrong denominator)."""
    kind = getattr(device, "device_kind", "cpu").lower()
    for key, peak in PEAK_FLOPS_PER_CHIP.items():
        if key in kind:
            return peak
    return None


def transformer_fwd_flops(cfg, *, batch: int, seq_len: int) -> int:
    """Matmul FLOPs of one forward pass at global ``batch`` x ``seq_len``.

    ``cfg`` is a ``models.transformer.TransformerConfig`` (duck-typed:
    only the size fields are read, so a plain namespace works in tests).
    Covers MHA/GQA, gelu (2-mat) and swiglu (3-mat) MLPs, and MoE blocks
    in both dispatch modes: dense dispatch (``moe_capacity_factor == 0``)
    runs every token through every expert (FLOPs scale with E), token-
    choice dispatch scales with top-k (capacity-dropped tokens still
    occupy their slot's FLOPs — the chip does the work whether or not
    the token keeps the result).
    """
    T = batch * seq_len
    d = cfg.d_model
    heads = cfg.num_heads
    head_dim = cfg.head_dim or d // heads
    kv_heads = getattr(cfg, "num_kv_heads", None) or heads
    attn_dim = heads * head_dim

    qkv = 2 * T * d * (attn_dim + 2 * kv_heads * head_dim)
    scores_values = 2 * 2 * batch * heads * seq_len * seq_len * head_dim
    out_proj = 2 * T * attn_dim * d

    if getattr(cfg, "activation", "gelu") == "swiglu":
        mlp_mats = 3  # gate, up, down
    else:
        mlp_mats = 2  # up, down
    mlp_one = mlp_mats * 2 * T * d * cfg.d_ff

    moe_experts = getattr(cfg, "moe_experts", 0)
    if moe_experts:
        router = 2 * T * d * moe_experts
        if getattr(cfg, "moe_capacity_factor", 0.0) > 0:
            # Token-choice: each token occupies top-k expert slots.
            mlp = getattr(cfg, "moe_top_k", 1) * mlp_one + router
        else:
            # Dense einsum dispatch: every token through every expert.
            mlp = moe_experts * mlp_one + router
    else:
        mlp = mlp_one

    logits = 2 * T * d * cfg.vocab_size
    return cfg.num_layers * (qkv + scores_values + out_proj + mlp) + logits


def ssd_cost(batch: int, seq: int, heads: int, head_dim: int, state: int,
             groups: int, chunk: int) -> dict:
    """FLOPs and least HBM bytes of one layer's state-space scan
    (``ops.ssd``) in one train step, from shapes alone.  The chunked
    form's four products, forward (``C B^T`` 2 L N a group, the masked
    matrix on ``x`` 2 L H P, ``B^T x`` and ``C h`` 2 N H P each, a token),
    times 3 for forward and backward; bytes: ``x`` and ``y`` (bf16),
    ``B`` and ``C`` (bf16), ``dt`` (f32) and their gradients, once each."""
    chunk = min(chunk, seq)
    tokens = batch * chunk * -(-seq // chunk)
    inner = heads * head_dim
    per_token = 2 * chunk * (groups * state + inner) + 4 * state * inner
    bytes_ = batch * seq * 2 * (
        2 * 2 * inner + 2 * 2 * groups * state + 4 * heads
    )
    return {"flops": 3 * tokens * per_token, "bytes": bytes_}


def moe_cost(rows: int, d: int, f: int, groups: int) -> dict:
    """FLOPs and least HBM bytes of one expert layer's grouped products
    (``ops.moe.grouped_matmul``: gate, up and down of a gated MLP,
    ``d -> f -> d``) in one train step, from shapes alone — whatever
    implements them.  ``rows`` (token, choice) pairs over ``groups`` held
    experts: three products of ``2 rows d f`` forward, times 3 for forward
    and backward; bytes: the ``groups`` experts' three matrices read
    forward, read backward and their gradients written (bf16), the rows
    in and out and their gradients (bf16), once each."""
    return {
        "flops": 3 * 3 * 2 * rows * d * f,
        "bytes": 2 * (3 * 3 * groups * d * f + 4 * rows * d),
    }


def eva_pair_counts(seq: int, window: int, chunk: int) -> tuple[int, int]:
    """``(local, remote)`` (query, key) and (query, summary) pairs of one
    head and one sequence under ``ops.eva``'s rule: a query sees its own
    block's keys up to itself, and one summary a chunk of every earlier
    block.  At 16,384 positions in blocks of 2,048 and chunks of 16:
    16,785,408 and 7,340,032."""
    if seq <= window:
        return seq * (seq + 1) // 2, 0
    n = seq // window
    return (n * window * (window + 1) // 2,
            window * (window // chunk) * n * (n - 1) // 2)


def eva_cost(batch: int, seq: int, heads: int, head_dim: int, window: int,
             chunk: int) -> dict:
    """FLOPs and least HBM bytes of one layer's attention over the
    summaries (``ops.eva.remote_attention``) in one train step, from
    shapes alone — whatever implements it.  The score and the value
    product over the (query, summary) pairs, times 3 for forward and
    backward; bytes: the queries past the first block, their result and
    the gradients of both (bf16), the summaries of all blocks but the
    last, keys and values, and their gradients (bf16), the row statistic
    and its gradient (float32), once each."""
    _, pairs = eva_pair_counts(seq, window, chunk)
    rows = max(seq - window, 0)
    kept = rows // chunk
    return {
        "flops": 3 * 2 * 2 * batch * heads * head_dim * pairs,
        "bytes": batch * heads * (
            2 * head_dim * (4 * rows + 4 * kept) + 4 * 2 * rows),
    }


def simple_cnn_fwd_flops(
    *,
    batch: int,
    image_shape: tuple[int, ...],
    widths: tuple[int, ...] = (32, 64),
    num_classes: int = 10,
    kernel: int = 3,
) -> int:
    """Matmul/conv FLOPs of one ``models.SimpleCNN`` forward pass.

    SAME-padded kxk convs at full resolution followed by 2x2 max-pool
    per block, then a global-mean head — mirrors the module exactly so
    the analytic number tracks the real program within conv-padding
    noise (the tests pin the tolerance against ``cost_analysis()``).
    """
    h, w, c_in = image_shape
    flops = 0
    for c_out in widths:
        flops += 2 * batch * h * w * kernel * kernel * c_in * c_out
        h, w, c_in = h // 2, w // 2, c_out
    flops += 2 * batch * c_in * num_classes
    return flops


def mlp_fwd_flops(
    *,
    batch: int,
    in_features: int,
    features: tuple[int, ...] = (128, 128),
    num_classes: int = 10,
) -> int:
    """Dense FLOPs of one ``models.TinyMLP`` forward pass."""
    flops, fan_in = 0, in_features
    for f in features:
        flops += 2 * batch * fan_in * f
        fan_in = f
    return flops + 2 * batch * fan_in * num_classes


def train_step_flops(
    fwd_flops: int, *, remat: bool = False, flop_signature: dict | None = None
) -> dict:
    """Per-optimizer-step FLOPs from one full-batch forward count.

    ``flop_signature`` is the train-step factory's handoff
    (``make_train_step(...).flop_signature``): it records that the
    factory's microbatching divides the batch rather than repeating it
    (``microbatch_fraction``) — so N accumulation microbatches of B/N
    tokens cost exactly one batch of B, and this function deliberately
    takes the FULL-batch forward count and ignores the accumulation
    degree.  ``model_flops`` is the MFU numerator (3x forward);
    ``hardware_flops`` is the HFU numerator (4x under remat: the
    backward replays the forward).
    """
    mult = 3
    if flop_signature is not None:
        mult = flop_signature.get("train_flop_multiplier", mult)
    return {
        "model_flops": mult * fwd_flops,
        "hardware_flops": (mult + 1 if remat else mult) * fwd_flops,
    }


#: assumed achievable fraction of peak for analytic step-time
#: prediction — deliberately a single scalar, not a tuned model: the
#: autotuner uses predictions only to RANK candidates (a shared
#: efficiency factor cancels in the ranking), and ddp_report's
#: predicted-vs-measured drift table shows how wrong it was.
DEFAULT_EFFICIENCY = 0.35


def predict_step_s(
    hardware_flops: float,
    *,
    n_chips: int,
    peak_flops_per_chip: float | None,
    efficiency: float = DEFAULT_EFFICIENCY,
) -> float | None:
    """Analytic step-time prediction: hardware FLOPs over assumed
    achieved throughput.  None when the peak is unknown (better no
    prediction than one against a made-up denominator — same policy as
    ``peak_flops_for``).  This is the autotuner's pruning/ranking
    signal; measured windows are the ground truth it drifts against.
    """
    if not peak_flops_per_chip or hardware_flops <= 0:
        return None
    return float(hardware_flops) / (
        peak_flops_per_chip * max(1, n_chips) * efficiency
    )


def xla_cost_analysis(lowered) -> dict | None:
    """Normalize ``jax.stages.Lowered.cost_analysis()`` across jax
    versions (dict vs one-element list of dicts) into
    ``{"flops": float, "bytes_accessed": float}``; None when the
    backend doesn't implement cost analysis."""
    try:
        ca = lowered.cost_analysis()
    # ddplint: allow[broad-except] — cost analysis is best-effort per
    # backend; absence must degrade to "no cross-check", not a crash
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }


class MFUMeter:
    """Turns throughput readings into MFU/HFU gauges and events.

    Construction is pure host work; ``on_reading`` runs only at the
    StepTimer's window boundaries (where the loop already drained), so
    the meter adds zero per-step cost and zero device syncs.  With an
    unknown peak (``peak_flops_per_chip`` None) the meter still reports
    absolute model FLOP/s — an honest number beats a made-up fraction.
    """

    def __init__(
        self,
        step_flops: dict,
        *,
        n_chips: int,
        peak_flops_per_chip: float | None,
        registry=None,
        events=None,
    ):
        self.model_flops = float(step_flops["model_flops"])
        self.hardware_flops = float(
            step_flops.get("hardware_flops", step_flops["model_flops"])
        )
        self.n_chips = n_chips
        self.peak = peak_flops_per_chip
        self.registry = registry
        self.events = events

    def on_reading(self, reading: dict, *, step: int) -> dict:
        """Consume one StepTimer reading; returns (and records) the
        MFU numbers for that throughput window."""
        steps_per_s = reading["steps_per_s"]
        out = {
            "model_flops_per_s": steps_per_s * self.model_flops,
            "mfu": None,
            "hfu": None,
        }
        if self.peak:
            denom = self.peak * self.n_chips
            out["mfu"] = steps_per_s * self.model_flops / denom
            out["hfu"] = steps_per_s * self.hardware_flops / denom
        if self.registry is not None:
            g = self.registry.gauge
            g("model_flops_per_s").set(round(out["model_flops_per_s"], 1))
            if out["mfu"] is not None:
                g("mfu").set(round(out["mfu"], 6))
                g("hfu").set(round(out["hfu"], 6))
        if self.events is not None:
            self.events.emit(
                "mfu",
                step=step,
                mfu=out["mfu"],
                hfu=out["hfu"],
                model_flops_per_s=out["model_flops_per_s"],
                peak_flops_per_chip=self.peak,
                n_chips=self.n_chips,
            )
        return out
