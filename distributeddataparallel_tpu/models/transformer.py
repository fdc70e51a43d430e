"""Decoder-only transformer LM family: one stack, GPT-2 and Llama configs.

The reference's model layer is a torchvision ResNet (ref dpp.py:11-18);
the LM models here exist for BASELINE configs 4 (GPT-2 124M pure DP) and
5 (Llama-3 8B, grad accumulation + overlapped all-reduce).  One
``TransformerLM`` covers both families through ``TransformerConfig``:

==============  =====================  =========================
feature         GPT-2                  Llama-3
==============  =====================  =========================
norm            LayerNorm (pre-LN)     RMSNorm
positional      learned embeddings     RoPE (theta 500000)
MLP             GELU, 4×d              SwiGLU, 3 mats
attention       MHA                    GQA (8 kv heads)
embeddings      tied in/out            untied
==============  =====================  =========================

TPU-first choices:

- bf16 activations/matmuls (MXU), f32 norms/softmax/logits (VPU);
  params stay f32 (optimizer math), cast per-use.
- ``scan_layers``: homogeneous blocks run under ``flax.linen.scan`` — one
  layer trace instead of L, an order-of-magnitude compile-time cut for the
  32-layer 8B config.
- ``remat``: per-block ``nn.remat`` (checkpoint) trades recompute for HBM,
  required to fit 8B pure-DP per chip (SURVEY.md §7 hard-part 3).
- attention dispatches through ``ops.attention.attention`` (Pallas flash
  kernel on TPU when shapes allow, XLA reference otherwise).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributeddataparallel_tpu.observability import scopes
from distributeddataparallel_tpu.ops.attention import (
    apply_rope,
    attention,
    repeat_kv,
    rope_frequencies,
)
from distributeddataparallel_tpu.parallel.tensor_parallel import (
    copy_to_tp,
    reduce_from_tp,
    tp_size,
)


#: attention kinds beside "attention" (cfg.positional on every layer, no
#: window): a window of ``sliding_window`` keys with rotated q and k, and
#: every earlier key with no positions at all
SLIDING, FULL = "sliding_attention", "full_attention"
#: exact attention inside blocks of ``sliding_window`` positions and one
#: learned summary per ``eva_chunk`` keys of every earlier block, under one
#: softmax (``ops.eva``); q and k rotated where ``positional`` is "rope"
EVA = "eva_attention"
LAYER_KINDS = frozenset({"attention", "mamba", SLIDING, FULL, EVA})


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int
    num_layers: int
    num_heads: int
    d_model: int
    d_ff: int
    max_seq_len: int
    num_kv_heads: int | None = None  # None -> MHA (= num_heads)
    head_dim: int | None = None      # None -> d_model // num_heads
    norm: str = "layernorm"          # "layernorm" | "rmsnorm"
    activation: str = "gelu"         # "gelu" | "swiglu"
    positional: str = "learned"      # "learned" | "rope" | "none"
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    dtype: Any = jnp.float32         # activation/matmul dtype
    remat: bool = False
    scan_layers: bool = False
    attn_impl: str = "auto"          # "auto" | "xla" | "pallas"
    dropout_rate: float = 0.0        # residual-branch dropout (GPT-2 style)
    use_bias: bool = True            # proj biases: GPT-2 yes, Llama no
    # Context parallelism: name of the mesh axis the sequence dimension is
    # sharded over.  When set, the model must run inside shard_map with
    # that axis bound; attention becomes collective over the axis and
    # positions default to each shard's global offsets.  ``cp_impl``
    # picks the collective: "ring" (blockwise ppermute ring — memory
    # O(S/N), scales past the head count) or "ulysses" (two all_to_alls
    # to a head-sharded layout — local attention sees the full sequence
    # and can use the Pallas flash kernel; requires num_heads % N == 0).
    cp_axis: str | None = None
    cp_impl: str = "ring"            # "ring" | "ulysses"
    # Tensor parallelism: name of the mesh axis attention heads and MLP
    # hidden units are sharded over (Megatron column/row split, see
    # parallel.tensor_parallel).  When set, the model must run inside
    # shard_map with that axis bound and params sharded by
    # ``tp_param_specs``; unbound (init / direct apply) it degrades to
    # the full unsharded shapes.
    tp_axis: str | None = None
    # Autoregressive decoding: attention layers keep a KV cache sized
    # max_seq_len in the "cache" variable collection and attend against
    # it.  The caller passes explicit global ``positions`` per apply
    # (prefill: arange(P); decode: the single next position) and makes
    # the collection mutable — see ``models.generate``.  Mutually
    # exclusive with cp_axis (sequence-sharded training) and remat.
    decode: bool = False
    # Mixture-of-experts: replace every block's MLP with `moe_experts`
    # expert MLPs routed top-`moe_top_k` (1 = switch, 2 = Mixtral-style
    # with renormalized gates).  `ep_axis` shards the expert dimension
    # over a mesh axis (parallel.expert_parallel).
    #
    # Dispatch is picked by `moe_capacity_factor`:
    # - 0.0 (default): dense einsum dispatch — every token through every
    #   local expert, a (B, S, E) combine tensor blends.  No
    #   gather/scatter, ideal at tiny E; FLOPs scale with E.
    # - > 0: token-choice dispatch (GShard/Switch, ops.moe) — each token
    #   occupies at most K capacity-bounded expert slots, overflow drops
    #   through the residual.  FLOPs scale with K, not E.  Under EP the
    #   token slots are exchanged with a real all_to_all over the
    #   expert axis.
    moe_experts: int = 0
    moe_top_k: int = 1
    ep_axis: str | None = None
    moe_capacity_factor: float = 0.0
    # Data-parallel grad sync INSIDE the backward scan: name of the mesh
    # axis the scanned blocks' param gradients are pmean'd over, per scan
    # iteration, via an identity-with-all-reduce-VJP on the param reads
    # (``parallel.data_parallel.sync_grad_in_backward``).  Scanned models
    # otherwise hold every layer grad inside the backward while-loop,
    # and a reduction after the loop has no backward left to run beside.
    # Requires ``scan_layers``; the train step must skip these leaves in
    # its own sync (``make_train_step(presynced=scanned_param_paths)``).
    # Backward passes must then run inside shard_map with the axis bound.
    grad_sync_axis: str | None = None
    # bf16 comm-hook for the in-scan reduction: the per-layer cotangents
    # cross the wire in bfloat16 (see data_parallel.all_reduce_gradients
    # ``compress``).  Only meaningful with grad_sync_axis.
    grad_sync_compress: str | None = None
    # int8 weight-only serving (ops.quant): the scanned blocks
    # dequantize their per-layer param slice INSIDE the scan body so the
    # int8 stack stays HBM-resident (set by models.generate for
    # quantized decode; see _ScanBlock).
    quant_serving: bool = False
    # Hybrid stacks: the kind of each layer's mixer, "attention" or
    # "mamba" (a Mamba-2 mixer, ``Mamba2Mixer``), in order; None is
    # "attention" throughout.  Layers of two kinds are not a scan's one
    # body, so ``scan_layers`` (and with it PP and FSDP) is refused.
    layer_types: tuple[str, ...] | None = None
    # The Mamba-2 mixer's sizes: H heads of P channels (d_inner = H * P),
    # an N-wide state, G groups that share B and C, a causal depthwise
    # convolution of ``ssm_conv`` taps, and the scan's chunk (ops.ssd).
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # Granite's four scalars (1 / None leave the step as it was):
    # embeddings times ``embedding_multiplier``; each residual branch
    # times ``residual_multiplier``; attention scores times
    # ``attention_multiplier`` in place of 1/sqrt(head_dim); logits
    # divided by ``logits_scaling``.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None
    logits_scaling: float = 1.0
    # Window and full attention in one stack (``layer_types`` of
    # "sliding_attention" | "full_attention"): the first sees its own key
    # and the ``sliding_window - 1`` before it and, where ``positional`` is
    # "rope", rotates q and k; the second sees every earlier key and
    # carries no positions.  Each of the next three is skipped at False:
    # RMSNorm over each head's dims of q and k (before the rotation);
    # sigmoid(x W_gate) times the attention's result, before ``o_proj``;
    # a norm after each branch too, before its residual add.
    sliding_window: int | None = None
    qk_norm: bool = False
    attn_output_gate: bool = False
    post_norms: bool = False
    # Expert layers as the published sparse models route them (all at
    # their neutral values leave ``MoEMLP`` as it was).  The first
    # ``num_dense_layers`` layers keep the dense MLP of ``d_ff``; experts
    # are ``moe_d_ff`` wide (None: ``d_ff``); ``moe_score_func`` turns the
    # router's logits into scores ("softmax" | "sigmoid");
    # ``moe_expert_bias`` adds a per-expert bias to the scores for the
    # selection only; ``moe_route_norm`` divides the chosen scores by
    # their sum (None: where ``moe_top_k`` > 1) and ``moe_route_scale``
    # multiplies them; ``moe_shared_experts`` gated MLPs of ``moe_d_ff``
    # run on every token beside the routed ones.
    num_dense_layers: int = 0
    moe_d_ff: int | None = None
    moe_score_func: str = "softmax"
    moe_expert_bias: bool = False
    moe_route_norm: bool | None = None
    moe_route_scale: float = 1.0
    moe_shared_experts: int = 0
    # The share of the router's ``moe_experts`` held here, (first, count):
    # one position of an expert-parallel deployment, run without its
    # exchange.  The layer routes over all experts, computes every
    # (token, choice) whose expert it holds — dropless: sorted by expert,
    # one grouped product over the held experts' row groups, a weighted
    # scatter-add back — and leaves out what the others would have added.
    # None keeps the dispatches chosen by ``moe_capacity_factor``.
    moe_experts_held: tuple[int, int] | None = None
    # A byte-level model's four (each skipped at its neutral value).
    # ``layer_types`` of "eva_attention": blocks of ``sliding_window``
    # positions, chunks of ``eva_chunk`` keys, and two learned vectors a
    # head (``ops.eva``).  ``norm_unit_offset``: an RMSNorm scales by
    # ``1 + offset``, its parameter drawn round 0.  ``fp32_residual``: the
    # residual stream is kept and added in float32 while the branches
    # compute in ``dtype``.  ``num_pred_heads`` P > 1: the head gives P x
    # vocab logits a position, viewed (B, S, P, vocab); head h is scored on
    # the token ``1 + h`` ahead (``ops.losses.multi_token_cross_entropy``).
    eva_chunk: int = 0
    norm_unit_offset: bool = False
    fp32_residual: bool = False
    num_pred_heads: int = 1

    def __post_init__(self):
        if self.scan_layers and self.num_dense_layers:
            raise ValueError("scan_layers runs one kind of FFN only")
        if self.moe_experts_held is not None:
            first, count = self.moe_experts_held  # a JSON list
            object.__setattr__(self, "moe_experts_held", (first, count))
            if not (0 <= first and 0 < count and first + count <= self.moe_experts):
                raise ValueError(
                    f"moe_experts_held {(first, count)} is no share of "
                    f"{self.moe_experts} experts"
                )
        kinds = self.layer_types
        if kinds is None:
            return
        object.__setattr__(self, "layer_types", tuple(kinds))  # a JSON list
        if len(kinds) != self.num_layers or set(kinds) - LAYER_KINDS:
            raise ValueError(
                f"layer_types must name {self.num_layers} layers, each one "
                f"of {sorted(LAYER_KINDS)}; got {kinds!r}"
            )
        if self.scan_layers and set(kinds) != {"attention"}:
            raise ValueError("scan_layers runs attention layers only")
        if SLIDING in kinds and not self.sliding_window:
            raise ValueError("sliding_attention layers need sliding_window")
        if EVA in kinds and not (
                self.sliding_window and self.eva_chunk
                and self.sliding_window % self.eva_chunk == 0):
            raise ValueError(
                "eva_attention layers need sliding_window, a whole number "
                "of chunks of eva_chunk"
            )

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def dims_per_head(self) -> int:
        return self.head_dim or self.d_model // self.num_heads


# --- Named configs (sizes per the public GPT-2 / Llama-3 papers) ---------

def gpt2_124m(**overrides) -> TransformerConfig:
    """GPT-2 small: 12L/12H/768d, 4×d GELU MLP, 50257 vocab, tied embs."""
    base = dict(
        vocab_size=50257, num_layers=12, num_heads=12, d_model=768,
        d_ff=3072, max_seq_len=1024, norm="layernorm", activation="gelu",
        positional="learned", tie_embeddings=True,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def llama3_8b(**overrides) -> TransformerConfig:
    """Llama-3 8B: 32L/32H(8kv)/4096d, 14336 SwiGLU, 128256 vocab, RoPE."""
    base = dict(
        vocab_size=128256, num_layers=32, num_heads=32, num_kv_heads=8,
        d_model=4096, d_ff=14336, max_seq_len=8192, norm="rmsnorm",
        activation="swiglu", positional="rope", rope_theta=500000.0,
        tie_embeddings=False, dtype=jnp.bfloat16, remat=True,
        scan_layers=True, use_bias=False,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def granite_4_0_h_micro(**overrides) -> TransformerConfig:
    """Granite 4.0-H Micro (``granitemoehybrid``, no experts): 40 layers
    in periods of ten — nine Mamba-2 mixers and, sixth, one GQA attention
    layer (32 heads on 8, D 64) — d 2048, a gated SiLU MLP of 8192, no
    positions, 100352 ids tied, and the four multipliers."""
    period = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    base = dict(
        vocab_size=100352, num_layers=40, num_heads=32, num_kv_heads=8,
        head_dim=64, d_model=2048, d_ff=8192, max_seq_len=131072,
        norm="rmsnorm", activation="swiglu", positional="none",
        tie_embeddings=True, use_bias=False, layer_types=period * 4,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
        ssm_conv=4, ssm_chunk=256, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=1.0 / 64,
        logits_scaling=8.0,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def trinity_mini(**overrides) -> TransformerConfig:
    """Trinity-Mini (``afmoe``, 26B-A3B): 32 layers in periods of three
    window layers (2048 keys, RoPE) and one full layer (no positions), 32
    query heads on 4 of 128 with q/k norms and an output gate, four norms a
    layer, d 2048; two dense layers of 6144, then 128 sigmoid-routed experts
    of 1024, 8 a token, normalised and scaled by 2.826, beside one shared
    expert; embeddings times sqrt(2048), untied head over 200192 ids."""
    base = dict(
        vocab_size=200192, num_layers=32, num_heads=32, num_kv_heads=4,
        head_dim=128, d_model=2048, d_ff=6144, max_seq_len=131072,
        norm="rmsnorm", activation="swiglu", positional="rope",
        rope_theta=10000.0, tie_embeddings=False, use_bias=False,
        layer_types=((SLIDING,) * 3 + (FULL,)) * 8, sliding_window=2048,
        qk_norm=True, attn_output_gate=True, post_norms=True,
        embedding_multiplier=2048 ** 0.5, num_dense_layers=2,
        moe_experts=128, moe_top_k=8, moe_d_ff=1024,
        moe_score_func="sigmoid", moe_expert_bias=True, moe_route_norm=True,
        moe_route_scale=2.826, moe_shared_experts=1,
        moe_experts_held=(0, 128),
    )
    base.update(overrides)
    return TransformerConfig(**base)


def evabyte(**overrides) -> TransformerConfig:
    """EvaByte 6.5B (``evabyte``, ``attention_class`` "eva"): 32 layers of
    chunk-summarised attention (blocks of 2048, chunks of 16; 32 heads of
    128, rotated at theta 1e5) and a gated SiLU MLP of 11008 at d 4096, no
    bias; RMSNorms that scale by ``1 + offset``; a float32 residual stream;
    320 byte and special ids, untied, eight prediction heads."""
    base = dict(
        vocab_size=320, num_layers=32, num_heads=32, d_model=4096,
        d_ff=11008, max_seq_len=32768, norm="rmsnorm", activation="swiglu",
        positional="rope", rope_theta=100000.0, tie_embeddings=False,
        use_bias=False, sliding_window=2048, eva_chunk=16,
        norm_unit_offset=True, fp32_residual=True, num_pred_heads=8,
    )
    base.update(overrides)
    base.setdefault("layer_types", (EVA,) * base["num_layers"])
    return TransformerConfig(**base)


def tiny_lm(**overrides) -> TransformerConfig:
    """Test-sized config (fast CPU init/compile)."""
    base = dict(
        vocab_size=256, num_layers=2, num_heads=2, d_model=32, d_ff=64,
        max_seq_len=128, norm="rmsnorm", activation="swiglu",
        positional="rope", tie_embeddings=True,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def moe_aux_from_intermediates(col) -> Any:
    """Mean of the per-layer sown switch load-balance terms (sow wraps
    each in a tuple; scan stacks them) — layer-count independent.  ONE
    definition shared by every loss path (CP / plain LM / pipeline)."""
    terms = jax.tree.leaves(col)
    return sum(jnp.mean(t) for t in terms) / max(len(terms), 1)


class RMSNorm(nn.Module):
    """Llama-style RMS normalization; stats in f32, scale param f32.
    ``unit_offset``: the learned parameter is ``offset``, drawn round 0,
    and the scale ``1 + offset``.  ``out_dtype`` (None: the input's) is
    what the result is cast to: a float32 residual stream feeds branches
    that compute in a narrower type."""

    epsilon: float = 1e-5
    unit_offset: bool = False
    out_dtype: Any = None

    @nn.compact
    def __call__(self, x):
        dtype = self.out_dtype or x.dtype
        x = x.astype(jnp.float32)
        if self.unit_offset:
            scale = 1.0 + self.param(
                "offset", nn.initializers.zeros, (x.shape[-1],))
        else:
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.epsilon)
        return (x * scale).astype(dtype)


def _make_norm(cfg: TransformerConfig, name: str):
    if cfg.norm == "rmsnorm":
        return RMSNorm(
            name=name, unit_offset=cfg.norm_unit_offset,
            out_dtype=cfg.dtype if cfg.fp32_residual else None,
        )
    if cfg.norm_unit_offset or cfg.fp32_residual:
        raise ValueError("norm_unit_offset and fp32_residual are RMSNorm's")
    # LayerNorm math in f32 regardless of activation dtype.
    return nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name=name)


class _RowParallelOut(nn.Module):
    """Row-parallel output projection (attention o / MLP down).

    Parameter names and full shapes are identical to the DenseGeneral /
    Dense it replaces (``kernel``, optional ``bias``) so checkpoints and
    weight-io never see TP.  Under TP the kernel's leading (input) dims
    are sharded; the partial product is completed with ``reduce_from_tp``
    and the bias — replicated — is added AFTER the psum (adding it per
    position would count it tp× times).
    """

    features: int
    kernel_shape: tuple  # full kernel shape, batch-axes first
    contract_ndim: int   # how many trailing input dims the kernel eats
    use_bias: bool
    dtype: Any
    kernel_init: Any
    tp_axis: Any = None

    @nn.compact
    def __call__(self, x):
        n_tp = tp_size(self.tp_axis)
        shape = (self.kernel_shape[0] // n_tp,) + tuple(self.kernel_shape[1:])
        kernel = self.param("kernel", self.kernel_init, shape, jnp.float32)
        cdims = tuple(range(x.ndim - self.contract_ndim, x.ndim))
        kdims = tuple(range(self.contract_ndim))
        y = jax.lax.dot_general(
            x.astype(self.dtype), kernel.astype(self.dtype),
            ((cdims, kdims), ((), ())),
        )
        if self.tp_axis is not None and n_tp > 1:
            y = reduce_from_tp(y, self.tp_axis)
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros, (self.features,), jnp.float32
            )
            y = y + bias.astype(self.dtype)
        return y


def _output_gate(out, gate):
    """The attention's result times sigmoid(gate), element-wise, before
    ``o_proj``; the sigmoid in f32."""
    return (out * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)


def _select(scores, bias, k: int):
    """``(gates, idx)``: the ``k`` experts with the largest ``scores + bias``
    and their bare scores — the bias decides the selection only."""
    _, idx = jax.lax.top_k(scores + bias, k)
    return jnp.take_along_axis(scores, idx, axis=-1), idx


class Attention(nn.Module):
    cfg: TransformerConfig
    kind: str = "attention"  # or SLIDING / FULL, of cfg.layer_types

    @nn.compact
    def __call__(self, x, *, positions=None, rope=None, deterministic=True):
        cfg = self.cfg
        new = (self.kind != "attention" or cfg.qk_norm
               or cfg.attn_output_gate)
        if new and (cfg.decode or cfg.tp_axis is not None
                    or cfg.cp_axis is not None):
            raise ValueError(
                "window / full / eva attention kinds, qk_norm and "
                "attn_output_gate run data-parallel training only "
                "(no decode, tp_axis or cp_axis)"
            )
        window = cfg.sliding_window if self.kind == SLIDING else None
        B, S, _ = x.shape
        H, Hkv, D = cfg.num_heads, cfg.kv_heads, cfg.dims_per_head
        n_tp = tp_size(cfg.tp_axis)
        if H % n_tp or Hkv % n_tp:
            raise ValueError(
                f"tp={n_tp} must divide num_heads={H} and kv_heads={Hkv}"
            )
        Hl, Hkvl = H // n_tp, Hkv // n_tp  # per-position head counts
        if cfg.tp_axis is not None and n_tp > 1:
            x = copy_to_tp(x, cfg.tp_axis)
        dense = lambda feats, name: nn.DenseGeneral(
            feats, axis=-1, dtype=cfg.dtype, name=name, use_bias=cfg.use_bias,
            kernel_init=nn.initializers.normal(0.02),
        )
        q = dense((Hl, D), "q_proj")(x)
        k = dense((Hkvl, D), "k_proj")(x)
        v = dense((Hkvl, D), "v_proj")(x)
        if cfg.qk_norm:
            q = RMSNorm(name="q_norm")(q)
            k = RMSNorm(name="k_norm")(k)
        if cfg.positional == "rope" and self.kind != FULL:
            # Tables are computed once in TransformerLM and passed down so
            # they sit outside the scanned/remat'd block body.
            cos, sin = rope if rope is not None else rope_frequencies(
                D, cfg.max_seq_len, theta=cfg.rope_theta
            )
            q = apply_rope(q, cos, sin, positions=positions)
            k = apply_rope(k, cos, sin, positions=positions)
        if cfg.decode:
            # KV-cache attention: insert this call's k/v at the caller's
            # global positions, attend q against the whole cache with a
            # positional mask (static shapes: the cache is always
            # max_seq_len long; future slots sit behind NEG_INF).
            if positions is None:
                raise ValueError(
                    "decode=True requires explicit positions "
                    "(models.generate passes them)"
                )
            from distributeddataparallel_tpu.ops.attention import (
                NEG_INF,
                causal_mask_bias,
                dot_product_attention,
            )

            ck = self.variable(
                "cache", "cached_key", jnp.zeros,
                (B, cfg.max_seq_len, Hkvl, D), k.dtype,
            )
            cv = self.variable(
                "cache", "cached_value", jnp.zeros,
                (B, cfg.max_seq_len, Hkvl, D), v.dtype,
            )
            if positions.ndim == 2:
                # Per-row positions (B, S): continuous-batching decode
                # where every slot sits at its own length (serving
                # engine).  S == 1 is the classic one-token step; S > 1
                # is a speculative-verify window — each row inserts S
                # tokens at ITS OWN contiguous positions and row i
                # attends causally through position[b, i].  Rows past a
                # slot's position hold stale/garbage values, which the
                # finite NEG_INF bias zeroes exactly in the softmax.
                if positions.shape != (B, S):
                    raise ValueError(
                        f"per-row positions must be ({B}, {S}), got "
                        f"{positions.shape}"
                    )
                row = jnp.arange(B)[:, None]  # (B, 1) broadcast index
                ck.value = ck.value.at[row, positions].set(k)
                cv.value = cv.value.at[row, positions].set(v)
                kf = repeat_kv(ck.value, Hl // Hkvl)
                vf = repeat_kv(cv.value, Hl // Hkvl)
                kv_pos = jnp.arange(cfg.max_seq_len)
                bias = jnp.where(
                    kv_pos[None, None, None, :]
                    <= positions[:, None, :, None],
                    0.0, NEG_INF,
                ).astype(jnp.float32)  # (B, 1, S, max_seq_len)
                out = dot_product_attention(
                    q, kf, vf, causal=False, bias=bias,
                    scale=cfg.attention_multiplier,
                )
            else:
                pos = positions.reshape(-1)  # (S,) global token positions
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k, (0, pos[0], 0, 0)
                )
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v, (0, pos[0], 0, 0)
                )
                kf = repeat_kv(ck.value, Hl // Hkvl)
                vf = repeat_kv(cv.value, Hl // Hkvl)
                # Positions are contiguous from pos[0] (the insert
                # offset), so the cache mask is the ordinary causal bias
                # at that q offset.
                bias = causal_mask_bias(
                    S, cfg.max_seq_len, q_offset=pos[0]
                )
                out = dot_product_attention(
                    q, kf, vf, causal=False, bias=bias[None, None],
                    scale=cfg.attention_multiplier,
                )
        elif self.kind == EVA:
            from distributeddataparallel_tpu.ops.eva import eva_attention

            if Hkvl != Hl:
                raise ValueError("eva_attention layers share no kv heads")
            # one learned query and one key offset a head, for the pooling;
            # drawn as the other matrices are, not by the published init_fn
            init = nn.initializers.normal(0.02)
            phi = self.param("adaptive_phi", init, (H, D), jnp.float32)
            mu = self.param("adaptive_mu_k", init, (H, D), jnp.float32)
            out = eva_attention(
                q, k, v, phi, mu, window=cfg.sliding_window,
                chunk=cfg.eva_chunk, scale=cfg.attention_multiplier,
                impl=cfg.attn_impl,
            )
        elif cfg.cp_axis is not None and cfg.attention_multiplier is not None:
            raise ValueError(
                "attention_multiplier is not carried through the "
                "context-parallel attentions"
            )
        elif cfg.cp_axis is not None and cfg.cp_impl == "ulysses":
            from distributeddataparallel_tpu.parallel.context_parallel import (
                ulysses_attention,
            )

            # GQA-native: ulysses exchanges kv at its own head count when
            # the axis divides it, expanding internally otherwise.
            out = ulysses_attention(
                q, k, v, axis_name=cfg.cp_axis, causal=True,
                impl=cfg.attn_impl,
            )
        elif cfg.cp_axis is not None:
            if cfg.cp_impl != "ring":
                raise ValueError(f"unknown cp_impl {cfg.cp_impl!r}")
            from distributeddataparallel_tpu.parallel.context_parallel import (
                ring_attention,
            )

            # Ring attention contracts q and kv headwise: expand GQA here.
            k = repeat_kv(k, Hl // Hkvl)
            v = repeat_kv(v, Hl // Hkvl)
            out = ring_attention(
                q, k, v, axis_name=cfg.cp_axis, causal=True,
                impl=cfg.attn_impl,
            )
        else:
            # GQA kv stays at its own head count: the flash kernel indexes
            # the shared head natively; the XLA path expands internally.
            out = attention(
                q, k, v, causal=True, impl=cfg.attn_impl,
                scale=cfg.attention_multiplier, window=window,
            )
        if cfg.attn_output_gate:
            out = _output_gate(out, dense((Hl, D), "gate_proj")(x))
        return _RowParallelOut(
            features=cfg.d_model,
            kernel_shape=(H, D, cfg.d_model),
            contract_ndim=2,
            use_bias=cfg.use_bias,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(
                0.02 / (2 * cfg.num_layers) ** 0.5
            ),
            tp_axis=cfg.tp_axis,
            name="o_proj",
        )(out)


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        n_tp = tp_size(cfg.tp_axis)
        if cfg.d_ff % n_tp:
            raise ValueError(f"tp={n_tp} must divide d_ff={cfg.d_ff}")
        ffl = cfg.d_ff // n_tp  # per-position hidden width
        if cfg.tp_axis is not None and n_tp > 1:
            x = copy_to_tp(x, cfg.tp_axis)
        dense = lambda feats, name: nn.Dense(
            feats, dtype=cfg.dtype, name=name, use_bias=cfg.use_bias,
            kernel_init=nn.initializers.normal(0.02),
        )
        if cfg.activation == "swiglu":
            gate = dense(ffl, "gate_proj")(x)
            up = dense(ffl, "up_proj")(x)
            h = nn.silu(gate) * up
        elif cfg.activation == "gelu":
            h = nn.gelu(dense(ffl, "up_proj")(x), approximate=True)
        else:
            raise ValueError(f"unknown activation {cfg.activation!r}")
        return _RowParallelOut(
            features=cfg.d_model,
            kernel_shape=(cfg.d_ff, cfg.d_model),
            contract_ndim=1,
            use_bias=cfg.use_bias,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02),
            tp_axis=cfg.tp_axis,
            name="down_proj",
        )(h)


class MoEMLP(nn.Module):
    """Top-k-routed mixture-of-experts MLP.

    Routing: ``cfg.moe_top_k == 1`` is the Switch convention (the raw
    top probability gates the output — that dependence is what trains
    the router); ``k > 1`` is Mixtral-style (probabilities renormalized
    over the selected k, gradients flow through the renormalization).

    Two dispatch strategies (picked by ``cfg.moe_capacity_factor``):

    **Dense einsum** (capacity_factor 0): every token's hidden state is
    pushed through each LOCAL expert as one batched einsum (MXU-friendly
    — no gather/scatter) and a dense (B, S, E) combine-weight tensor
    blends the outputs.  Under EP each mesh position computes its E/n
    experts over ALL tokens and the combine is one psum
    (``reduce_from_tp``).  FLOPs scale with E — right for tiny E, wrong
    at Mixtral scale.

    **Token-choice** (capacity_factor > 0, ``ops.moe``): each token
    occupies at most K slots in a ``(E, capacity)`` buffer; overflow
    drops through the residual.  FLOPs scale with K, not E.  Under EP
    each position routes ITS 1/n slice of the tokens, exchanges slot
    buffers with one ``all_to_all`` over the expert axis (tokens travel
    to their experts — the GShard dataflow), computes its local experts
    on all sources' slots, all_to_alls back, combines its slice, and
    restores replication with an ``all_gather``.

    **Dropless, a held share** (``cfg.moe_experts_held = (first, count)``,
    ``ops.moe.dropless``): the layer is one position of an expert-parallel
    deployment run without its exchange.  It routes over all
    ``moe_experts``, holds ``count`` of them, and computes every (token,
    choice) whose expert it holds: a sort by expert, one grouped product
    over the held experts' row groups, a gate-weighted scatter-add back.
    What the experts it does not hold would have added is left out.  Its
    parts carry ``scopes.MOE_SCOPES``; the rows each held expert received
    are sown as ``moe_load``.

    Routing is read from the config: ``moe_score_func``, a selection bias
    (``moe_expert_bias``: in ``top_k``'s argument only, never in the gate),
    ``moe_route_norm``, ``moe_route_scale``; ``moe_shared_experts`` gated
    MLPs run on every token beside the routed ones.

    Gradient completeness: replicated params' grads must come out
    complete and identical on every expert-axis position so the
    data-axis sync needs no EP-awareness.  The dense path achieves this
    with ``copy_to_tp`` (backward psum) on its replicated inputs; the
    token-choice path instead uses the slice/all_gather conjugate pair
    ``ep_shard_tokens``/``ep_unshard_tokens`` — a psum there would
    overcount n× because each position only handles its token slice
    (see parallel.expert_parallel).
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from distributeddataparallel_tpu.parallel.tensor_parallel import (
            copy_to_tp,
            reduce_from_tp,
            tp_size,
        )

        cfg = self.cfg
        E, K = cfg.moe_experts, cfg.moe_top_k
        n_ep = tp_size(cfg.ep_axis)
        if E % n_ep:
            raise ValueError(f"ep={n_ep} must divide moe_experts={E}")
        if not 1 <= K <= E:
            raise ValueError(f"moe_top_k={K} must be in [1, {E}]")
        held = cfg.moe_experts_held
        if held is not None and (n_ep > 1 or cfg.tp_axis is not None):
            raise ValueError(
                "a held share of the experts (moe_experts_held) runs "
                "without its exchange: no ep_axis or tp_axis"
            )
        El = held[1] if held is not None else E // n_ep
        d, f = cfg.d_model, cfg.moe_d_ff or cfg.d_ff

        # Router runs replicated (its params are tiny); f32 for stable
        # scores.
        with jax.named_scope(scopes.MOE_ROUTER):
            logits = nn.Dense(
                E, dtype=jnp.float32, use_bias=False, name="router",
                kernel_init=nn.initializers.normal(0.02),
            )(x.astype(jnp.float32))
            if cfg.moe_score_func == "softmax":
                probs = jax.nn.softmax(logits, axis=-1)    # (B, S, E)
            elif cfg.moe_score_func == "sigmoid":
                probs = jax.nn.sigmoid(logits)
            else:
                raise ValueError(
                    f"unknown moe_score_func {cfg.moe_score_func!r}"
                )
            if cfg.moe_expert_bias:
                # in the selection only: the gate reads the bare score
                bias = self.param(
                    "expert_bias", nn.initializers.zeros, (E,), jnp.float32
                )
                vals, idx = _select(probs, bias, K)
            else:
                vals, idx = jax.lax.top_k(probs, K)        # (B, S, K)
            if (K > 1 if cfg.moe_route_norm is None else cfg.moe_route_norm):
                vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
            if cfg.moe_route_scale != 1.0:
                vals = vals * cfg.moe_route_scale

        init = nn.initializers.normal(0.02)
        w_up = self.param("experts_up", init, (El, d, f), jnp.float32)
        w_down = self.param("experts_down", init, (El, f, d), jnp.float32)
        w_gate = (
            self.param("experts_gate", init, (El, d, f), jnp.float32)
            if cfg.activation == "swiglu"
            else None
        )

        def shared(out):
            if not cfg.moe_shared_experts:
                return out
            with jax.named_scope(scopes.MOE_SHARED):
                wide = dataclasses.replace(
                    cfg, d_ff=f * cfg.moe_shared_experts
                )
                return out + MLP(wide, name="shared")(x)

        if held is not None:
            return shared(self._dropless(x, vals, idx, w_gate, w_up, w_down))

        sel = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (B, S, K, E)

        # Load-balance auxiliary (Fedus et al. / GShard): E * sum f_e*P_e,
        # f_e = fraction of routing slots assigned to expert e (stop-grad
        # via top_k), P_e = mean router probability.  Minimized at
        # uniform routing; exposed through sow — loss_fns opt in with
        # apply(..., mutable=["intermediates"]) and add moe_aux * weight
        # (the dpp.py CLI does).
        frac = jnp.mean(sel, axis=(0, 1, 2))           # sums to 1/... per slot
        self.sow(
            "intermediates", "moe_aux",
            E * jnp.sum(frac * probs.mean(axis=(0, 1))),
        )

        def experts(z):
            """Batched expert MLP: (El, n, d) -> (El, n, d)."""
            h = jnp.einsum("end,edf->enf", z, w_up.astype(cfg.dtype))
            if w_gate is not None:
                g = jnp.einsum("end,edf->enf", z, w_gate.astype(cfg.dtype))
                h = nn.silu(g) * h
            else:
                h = nn.gelu(h, approximate=True)
            return jnp.einsum("enf,efd->end", h, w_down.astype(cfg.dtype))

        if cfg.moe_capacity_factor > 0:
            return shared(self._token_choice(x, vals, idx, experts, n_ep))

        # --- Dense einsum dispatch ---------------------------------------
        # Dense combine weights: w[b,s,e] = this token's gate for expert
        # e (0 off the top-k).
        w = jnp.sum(sel * vals[..., None], axis=2)     # (B, S, E)
        if cfg.ep_axis is not None and n_ep > 1:
            x = copy_to_tp(x, cfg.ep_axis)
            w = copy_to_tp(w, cfg.ep_axis)
        xe = x.astype(cfg.dtype)
        # Kept as bsd,edf einsums rather than experts() on a broadcast
        # (El, B*S, d) operand: the einsum guarantees x is never
        # materialised El times in HBM.
        h = jnp.einsum("bsd,edf->ebsf", xe, w_up.astype(cfg.dtype))
        if w_gate is not None:
            g = jnp.einsum("bsd,edf->ebsf", xe, w_gate.astype(cfg.dtype))
            h = nn.silu(g) * h
        else:
            h = nn.gelu(h, approximate=True)
        y = jnp.einsum(
            "ebsf,efd->ebsd", h, w_down.astype(cfg.dtype)
        )  # (El, B, S, d)

        # Local combine: this position's experts are global
        # [ep_rank*El, (ep_rank+1)*El); slice the weight tensor to match
        # and blend, then complete the partial sum over the expert axis.
        first = (
            jax.lax.axis_index(cfg.ep_axis) * El
            if cfg.ep_axis is not None and n_ep > 1
            else 0
        )
        w_local = jax.lax.dynamic_slice_in_dim(w, first, El, axis=2)
        out = jnp.einsum(
            "ebsd,bse->bsd", y, w_local.astype(cfg.dtype)
        )
        if cfg.ep_axis is not None and n_ep > 1:
            out = reduce_from_tp(out, cfg.ep_axis)
        return shared(out)

    def _dropless(self, x, vals, idx, w_gate, w_up, w_down):
        """The held experts' part (``ops.moe.dropless``)."""
        from distributeddataparallel_tpu.ops import grouped_matmul as gm
        from distributeddataparallel_tpu.ops import moe

        cfg = self.cfg
        B, S, d = x.shape
        first, count = cfg.moe_experts_held
        xt = x.reshape(B * S, d).astype(cfg.dtype)
        w_up, w_down = w_up.astype(cfg.dtype), w_down.astype(cfg.dtype)

        def experts(rows, layout):
            h = gm.grouped_matmul(rows, w_up, layout)
            if w_gate is not None:
                h = nn.silu(gm.grouped_matmul(
                    rows, w_gate.astype(cfg.dtype), layout)) * h
            else:
                h = nn.gelu(h, approximate=True)
            return gm.grouped_matmul(h, w_down, layout)

        out, sizes = moe.dropless(
            xt, vals.reshape(B * S, -1), idx.reshape(B * S, -1),
            cfg.moe_experts, first, count, experts, gm.row_tile(xt, w_up),
        )
        self.sow("intermediates", "moe_load", sizes)
        return out.reshape(B, S, d)

    def _token_choice(self, x, vals, idx, experts, n_ep):
        """Capacity-bounded token-choice dispatch (ops.moe)."""
        from distributeddataparallel_tpu.ops.moe import (
            combine,
            dispatch,
            moe_capacity,
            token_choice_slots,
        )

        cfg = self.cfg
        E, K, El = cfg.moe_experts, cfg.moe_top_k, cfg.moe_experts // n_ep
        B, S, d = x.shape
        T = B * S
        ep = cfg.ep_axis if n_ep > 1 else None
        if ep is not None and T % n_ep:
            raise ValueError(
                f"token-choice EP needs tokens ({T}) divisible by the "
                f"expert-axis size ({n_ep})"
            )
        Tl = T // n_ep
        xt = x.reshape(T, d)
        vt = vals.reshape(T, K)
        it = idx.reshape(T, K)
        if ep is not None:
            # Conjugate entry (parallel.expert_parallel.ep_shard_tokens):
            # slice forward, all_gather backward — x and the gate values
            # carry gradients for upstream replicated params and the
            # router, which must come out complete and identical on
            # every expert-axis position.
            from distributeddataparallel_tpu.parallel.expert_parallel import (
                ep_shard_tokens,
            )

            xt = ep_shard_tokens(xt, ep)
            vt = ep_shard_tokens(vt, ep)
            r = jax.lax.axis_index(ep)
            it = jax.lax.dynamic_slice_in_dim(it, r * Tl, Tl, 0)
        C = moe_capacity(Tl, E, K, cfg.moe_capacity_factor)

        tok_for_slot, gate_for_slot = token_choice_slots(it, vt, E, C)
        z = dispatch(xt.astype(cfg.dtype), tok_for_slot)  # (E*C, d)
        if ep is not None:
            # Tokens travel to their experts: slot buffers for expert
            # block j go to position j; received leading dim indexes the
            # SOURCE position.
            z = jax.lax.all_to_all(
                z.reshape(n_ep, El, C, d), ep, split_axis=0, concat_axis=0
            )
            z = z.transpose(1, 0, 2, 3).reshape(El, n_ep * C, d)
        else:
            z = z.reshape(E, C, d)
        y = experts(z)
        if ep is not None:
            y = y.reshape(El, n_ep, C, d).transpose(1, 0, 2, 3)
            # Outputs travel back: piece s returns to source position s,
            # restoring this position's original (E, C) slot order.
            y = jax.lax.all_to_all(y, ep, split_axis=0, concat_axis=0)
        out = combine(
            y.reshape(E * C, d), tok_for_slot, gate_for_slot, Tl
        )
        if ep is not None:
            # Conjugate exit: all_gather forward restores replication;
            # backward keeps each position's own chunk of the
            # (replicated-identical) cotangent.
            from distributeddataparallel_tpu.parallel.expert_parallel import (
                ep_unshard_tokens,
            )

            out = ep_unshard_tokens(out, ep)
        return out.reshape(B, S, d)


class GatedRMSNorm(RMSNorm):
    """Mamba-2's output norm: ``RMSNorm(y * silu(z))`` over the whole
    inner width (one group); the gate too in f32."""

    def __call__(self, y, z):
        gated = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        return super().__call__(gated).astype(y.dtype)


def a_log_init(key, shape, dtype=jnp.float32):
    """``A = -exp(A_log)`` drawn uniform in [-16, -1] (the public Mamba-2
    initialisation)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus(dt_bias)`` drawn log-uniform in [1e-3, 0.1]: a decay
    near 1, so a state remembers hundreds of steps."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(1e-3), jnp.log(0.1)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse


def conv_init(taps: int):
    """The depthwise convolution's taps and bias, uniform in
    +-1/sqrt(taps) (a depthwise Conv1d's default, which the public
    Mamba-2 code keeps): ``x``, ``B`` and ``C`` come out of order 1.
    With normal(0, 0.02) taps they are ~0.03 and the scan adds nothing
    to a ``y`` that ``D * x`` carries (PERF.md section 2, PR 28)."""
    bound = taps ** -0.5

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class Mamba2Mixer(nn.Module):
    """Mamba-2 mixer (Dao & Gu 2024) as ``GraniteMoeHybrid`` lays it out:
    one input projection to the gate ``z``, the convolved ``x | B | C``
    and ``dt``; a causal depthwise convolution and SiLU
    (``ops.causal_conv``); the state-space scan (``ops.ssd``); the gated
    norm; the output projection.  Its five parts carry
    ``scopes.MIXER_SCOPES``.  Data-parallel training only: no tensor or
    context parallelism, and no decoding state yet."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u):
        from distributeddataparallel_tpu.ops import causal_conv, ssd

        cfg = self.cfg
        if cfg.decode or cfg.tp_axis is not None or cfg.cp_axis is not None:
            raise ValueError(
                "mamba layers run data-parallel training only "
                "(no decode, tp_axis or cp_axis)"
            )
        B_, S, _ = u.shape
        H, P = cfg.ssm_heads, cfg.ssm_head_dim
        G, N, K = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv
        inner, bc = H * P, G * N
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, dtype=cfg.dtype, name=name, use_bias=False,
            kernel_init=nn.initializers.normal(0.02),
        )
        with jax.named_scope(scopes.SSM_IN_PROJ):
            # z | x B C | dt; the convolution reads its part in place
            proj = dense(2 * inner + 2 * bc + H, "in_proj")(u)
            z, dt = proj[..., :inner], proj[..., 2 * inner + 2 * bc:]
        with jax.named_scope(scopes.SSM_CONV):
            taps = self.param(
                "conv_kernel", conv_init(K), (K, inner + 2 * bc), jnp.float32,
            )
            bias = self.param(
                "conv_bias", conv_init(K), (inner + 2 * bc,), jnp.float32,
            )
            x, Bm, Cm = causal_conv.causal_conv_silu(
                proj, taps, bias, (inner, bc, bc), start=inner
            )
        with jax.named_scope(scopes.SSD):
            dt_bias = self.param("dt_bias", dt_bias_init, (H,), jnp.float32)
            a_log = self.param("A_log", a_log_init, (H,), jnp.float32)
            skip = self.param("D", nn.initializers.ones, (H,), jnp.float32)
            y = ssd.ssd_chunked(
                x.reshape(B_, S, H, P),
                jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log),
                Bm.reshape(B_, S, G, N), Cm.reshape(B_, S, G, N),
                skip, chunk=cfg.ssm_chunk,
            ).reshape(B_, S, inner)
        with jax.named_scope(scopes.SSM_GATE_NORM):
            y = GatedRMSNorm(name="norm")(y, z)
        with jax.named_scope(scopes.SSM_OUT_PROJ):
            return dense(cfg.d_model, "out_proj")(y)


class DecoderBlock(nn.Module):
    cfg: TransformerConfig
    kind: str = "attention"  # this layer's mixer, of cfg.layer_types
    index: int = 0           # its place in the stack (cfg.num_dense_layers)

    @nn.compact
    def __call__(self, x, positions=None, rope=None, deterministic=True):
        cfg = self.cfg
        drop = nn.Dropout(cfg.dropout_rate, deterministic=deterministic)

        def add(x, branch):
            if cfg.residual_multiplier != 1.0:
                branch = branch * cfg.residual_multiplier
            if cfg.fp32_residual:  # the branch computed narrow, added wide
                branch = branch.astype(jnp.float32)
            return x + drop(branch)

        def after(branch, name):
            return _make_norm(cfg, name)(branch) if cfg.post_norms else branch

        if self.kind == "mamba":
            y = _make_norm(cfg, "mamba_norm")(x)
            x = add(x, Mamba2Mixer(cfg, name="mamba")(y))
        else:
            y = _make_norm(cfg, "attn_norm")(x)
            x = add(x, after(Attention(cfg, self.kind, name="attn")(
                y, positions=positions, rope=rope, deterministic=deterministic
            ), "post_attn_norm"))
        y = _make_norm(cfg, "mlp_norm")(x)
        mlp = (
            MoEMLP(cfg, name="mlp")
            if cfg.moe_experts > 0 and self.index >= cfg.num_dense_layers
            else MLP(cfg, name="mlp")
        )
        return add(x, after(mlp(y), "post_mlp_norm"))


class _ScanBlock(nn.Module):
    """DecoderBlock adapted to linen.scan's (carry, *broadcast) shape.

    Under ``cfg.grad_sync_axis`` the block's params are read through
    ``sync_grad_in_backward``: forward identity, backward pmean over the
    data axis — so each scan iteration's param-slice gradient is reduced
    inside the backward while-loop body, with the trip's remaining
    backward compute beside it (after the loop there is none left).

    Under ``cfg.quant_serving`` (int8 weight-only decode, ops.quant) the
    per-layer param SLICE is dequantized here, inside the scan body —
    nn.scan splits the stacked ``QuantLeaf`` nodes along the layer dim
    like any pytree, so each trip dequantizes only its own layer and the
    int8 stack stays HBM-resident.  Dequantizing the whole stack before
    the scan instead measures SLOWER than bf16 (full-stack bf16
    materialization per decode step: +2x the byte traffic it was meant
    to save).
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, rope, deterministic):
        cls = DecoderBlock
        trans = []
        if self.cfg.grad_sync_axis is not None:
            from distributeddataparallel_tpu.parallel.data_parallel import (
                sync_grad_in_backward,
            )

            axis = self.cfg.grad_sync_axis
            comp = self.cfg.grad_sync_compress
            trans.append(
                lambda vs: sync_grad_in_backward(vs, axis, compress=comp)
            )
        if self.cfg.quant_serving:
            from distributeddataparallel_tpu.ops.quant import dequantize

            dt = self.cfg.dtype
            trans.append(lambda vs: dequantize(vs, dt))
        if trans:
            def chain(vs, _fns=tuple(trans)):
                for f in _fns:
                    vs = f(vs)
                return vs

            cls = nn.map_variables(
                DecoderBlock,
                "params",
                trans_in_fn=(
                    (lambda vs: vs) if self.is_initializing() else chain
                ),
                init=self.is_initializing(),
            )
        x = cls(self.cfg, name="block")(
            x, positions, rope, deterministic
        )
        return x, None


def scanned_layer_cls(cfg: TransformerConfig, length: int | None = None):
    """The scan-transformed decoder-block class — ONE construction shared
    by TransformerLM and the pipeline-parallel stage runner, so a slice
    of the stacked params always applies under identical scan settings
    (remat wrapper, rng splitting, partition metadata).

    ``length`` overrides the layer count (a PP stage runs
    ``num_layers / n_stages`` of the stack).
    """
    scan_block = (
        nn.remat(_ScanBlock, prevent_cse=False, static_argnums=(4,))
        if cfg.remat
        else _ScanBlock
    )
    return nn.scan(
        scan_block,
        # intermediates: MoE blocks sow their load-balance aux per layer;
        # stacked along the scan dim when the caller makes it mutable
        # (a no-op for dense models / immutable applies).  cache: per-layer
        # KV caches under decode, stacked the same way.
        variable_axes={"params": 0, "intermediates": 0, "cache": 0},
        split_rngs={"params": True, "dropout": True},
        in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
        length=length if length is not None else cfg.num_layers,
        metadata_params={nn.PARTITION_NAME: "layers"},
    )


class LMHead(nn.Module):
    """Untied output projection: params identical to a bias-free Dense
    (``{"kernel": (d_model, vocab)}`` f32, so checkpoints/weight-io are
    unchanged), but the matmul takes ``compute_dtype`` operands with f32
    MXU accumulation instead of casting operands to f32."""

    vocab_size: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.normal(0.02),
            (x.shape[-1], self.vocab_size), jnp.float32,
        )
        return jax.lax.dot_general(
            x.astype(self.compute_dtype), kernel.astype(self.compute_dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


class TransformerLM(nn.Module):
    """Decoder-only LM: tokens (B, S) int32 -> logits (B, S, vocab) f32."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, *, positions=None, deterministic=True):
        cfg = self.cfg
        B, S = tokens.shape
        if cfg.decode and (cfg.cp_axis is not None or cfg.remat):
            # The KV cache is a mutable collection: remat can't replay it
            # and sequence sharding has no cache layout; generate() builds
            # a decode twin config with both off.
            raise ValueError("decode does not compose with cp_axis/remat")
        # Under CP the model sees a local shard: the bound check must use
        # the GLOBAL length, or out-of-range RoPE/pos_embed lookups get
        # silently clamped by XLA's gather semantics instead of erroring.
        # psum of a literal over a named axis is a trace-time constant
        # (the axis size); outside shard_map the axis is unbound -> treat
        # as unsharded (direct single-device apply / init).
        n_seq_shards = 1
        if cfg.cp_axis is not None:
            try:
                n_seq_shards = int(jax.lax.psum(1, cfg.cp_axis))
            except NameError:
                n_seq_shards = 1
        if S * n_seq_shards > cfg.max_seq_len:
            detail = (
                f"global seq len {S * n_seq_shards} ({S} local x "
                f"{n_seq_shards} {cfg.cp_axis!r} shards)"
                if n_seq_shards > 1
                else f"seq len {S}"
            )
            raise ValueError(f"{detail} > max_seq_len {cfg.max_seq_len}")
        if cfg.cp_axis is not None and positions is None:
            from distributeddataparallel_tpu.parallel.context_parallel import (
                cp_positions,
            )

            # Sequence-sharded run: this shard's global token offsets.
            positions = cp_positions(S, cfg.cp_axis)
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, name="token_embed",
            embedding_init=nn.initializers.normal(0.02),
            param_dtype=jnp.float32,
        )
        with jax.named_scope(scopes.EMBED):
            x = embed(tokens)
            if not cfg.fp32_residual:  # else the stream stays float32
                x = x.astype(cfg.dtype)
            if cfg.embedding_multiplier != 1.0:
                x = x * cfg.embedding_multiplier
            if cfg.positional == "learned":
                pos = positions if positions is not None else jnp.arange(S)
                pos_embed = self.param(
                    "pos_embed", nn.initializers.normal(0.02),
                    (cfg.max_seq_len, cfg.d_model), jnp.float32,
                )
                x = x + pos_embed[pos].astype(cfg.dtype)
        x = nn.Dropout(cfg.dropout_rate, deterministic=deterministic)(x)

        rope = None
        if cfg.positional == "rope":
            rope = rope_frequencies(
                cfg.dims_per_head, cfg.max_seq_len, theta=cfg.rope_theta
            )
        if cfg.grad_sync_axis is not None and not cfg.scan_layers:
            # Unrolled layers emit per-leaf grads at top level, where the
            # train step's own bucketed reduction already overlaps; the
            # in-body sync exists for the scan case only.
            raise ValueError("grad_sync_axis requires scan_layers=True")
        if cfg.scan_layers:
            # One traced layer instead of L (compile time); under scan,
            # remat wraps the scan body (prevent_cse must be False there).
            x, _ = scanned_layer_cls(cfg)(cfg, name="layers")(
                x, positions, rope, deterministic
            )
        else:
            block_cls = (
                nn.remat(DecoderBlock, static_argnums=(4,))
                if cfg.remat
                else DecoderBlock
            )
            kinds = cfg.layer_types or ("attention",) * cfg.num_layers
            if cfg.num_dense_layers and cfg.moe_experts == 0:
                raise ValueError("num_dense_layers counts layers before experts")
            for i, kind in enumerate(kinds):
                # the index is told only where it decides the layer's FFN
                place = {"index": i} if cfg.num_dense_layers else {}
                x = block_cls(cfg, kind, name=f"layer_{i}", **place)(
                    x, positions, rope, deterministic
                )

        x = _make_norm(cfg, "final_norm")(x)
        # Logits in f32 (loss precision; analog of the ResNet head rule),
        # but the matmul runs with cfg.dtype OPERANDS and f32 MXU
        # accumulation (preferred_element_type): f32 operands would run
        # the vocab-sized matmul at 1/4 MXU rate — measured ~25% of the
        # whole GPT-2 train step.  Under cfg.dtype=float32 (tests, CPU)
        # the casts are no-ops and this is exactly the f32 matmul.
        with jax.named_scope(scopes.HEAD):
            if cfg.tie_embeddings:
                w = embed.embedding.astype(cfg.dtype)  # (V, D)
                logits = jax.lax.dot_general(
                    x.astype(cfg.dtype), w,
                    (((x.ndim - 1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            else:
                logits = LMHead(
                    cfg.vocab_size * cfg.num_pred_heads, cfg.dtype,
                    name="lm_head",
                )(x)
            if cfg.logits_scaling != 1.0:
                logits = logits / cfg.logits_scaling
            if cfg.num_pred_heads > 1:
                logits = logits.reshape(
                    B, S, cfg.num_pred_heads, cfg.vocab_size)
        return logits
