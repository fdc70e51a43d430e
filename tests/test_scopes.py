"""What the compiled step, the profiler's trace and the program's spans
are named by (PR 25): the ``jax.named_scope`` names of
``observability/scopes.py`` reach the HLO ``op_name`` of the train step,
the three Pallas kernels have three names, ``Tracer`` spans enter the
profiler's trace as ``ddp:<name>`` and nest per thread, the loader spans
where the work happens, and ``dpp.py --profile-steps`` captures them."""

import collections
import functools
import os
import re
import sys
import threading
import types

import jax
import jax.numpy as jnp
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import distributeddataparallel_tpu as ddp  # noqa: E402
from benchmarks import scope_reduce  # noqa: E402
from distributeddataparallel_tpu.data import SyntheticClassification  # noqa: E402
from distributeddataparallel_tpu.data.loader import DataLoader, shard_batch  # noqa: E402
from distributeddataparallel_tpu.models.transformer import (  # noqa: E402
    TransformerLM,
    gpt2_124m,
)
from distributeddataparallel_tpu.observability import (  # noqa: E402
    Tracer,
    get_tracer,
    scopes,
    set_tracer,
)
from distributeddataparallel_tpu.ops import accuracy, lm_cross_entropy  # noqa: E402

# ------------------------------------------------- scopes in the compiled step

#: instructions that do the step's work; the rest (bitcasts, constants,
#: parameters, tuples) takes no device time
_WORK = re.compile(
    r" (?:fusion|dot|convolution|reduce|reduce-window|all-reduce|"
    r"all-gather|reduce-scatter|custom-call|scatter|gather)\("
)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _compiled_step_text(n_devices: int, scan_layers: bool,
                        attn_impl: str = "xla", seq: int = 16,
                        remat: bool = False) -> str:
    cfg = gpt2_124m(
        vocab_size=128, d_model=32, num_layers=2, num_heads=2, d_ff=64,
        max_seq_len=seq, scan_layers=scan_layers, attn_impl=attn_impl,
        remat=remat,
    )
    model = TransformerLM(cfg)
    mesh = ddp.make_mesh(("data",), devices=jax.devices()[:n_devices])

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["tokens"][:, :-1])
        targets = batch["tokens"][:, 1:]
        return lm_cross_entropy(logits, targets), {
            "accuracy": accuracy(logits, targets)
        }

    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    state = ddp.broadcast_params(
        ddp.TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adamw(1e-3)
        ),
        mesh,
    )
    step = ddp.make_train_step(loss_fn, mesh=mesh, grad_clip=1.0)
    batch = shard_batch(
        {"tokens": jnp.zeros((2 * n_devices, seq + 1), jnp.int32)}, mesh
    )
    return step.lower(state, batch, jax.random.PRNGKey(0)).compile().as_text()


@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["unrolled", "scan_layers"])
@pytest.mark.parametrize("n_devices", [1, 4])
def test_compiled_step_carries_every_scope(devices, n_devices, scan_layers):
    text = _compiled_step_text(n_devices, scan_layers)
    found = collections.Counter()
    work = collections.Counter()
    for line in text.splitlines():
        scope = _OP_NAME.search(line)
        scope = scope.group(1) if scope else ""
        bucket = scope_reduce.bucket_of(scope)
        found[bucket] += 1
        if _WORK.search(line) and " = " in line:
            work[bucket] += 1
    expected = {"embed", "head", "loss", "metrics", "grad_clip", "optimizer",
                "attn", "mlp", "norm", "block"}
    if n_devices > 1:
        expected.add("grad_sync")
    assert expected <= set(found), sorted(expected - set(found))
    # on one device the exchange is a collective over one replica, which
    # the TPU compiler drops: grad_sync is asked for across devices only
    if n_devices > 1:
        exchanged = [ln for ln in text.splitlines() if " all-reduce(" in ln]
        assert exchanged and all(
            scope_reduce.bucket_of(_OP_NAME.search(ln).group(1))
            in ("grad_sync", "metrics", "grad_clip")
            for ln in exchanged
        )
    # forward and backward are told apart by what JAX itself writes
    assert "transpose(jvp(" in text and "/jvp(" in text
    total = sum(work.values())
    assert total > 20
    assert work[scope_reduce.OTHER] <= 0.10 * total, work


def test_every_scope_constant_falls_in_exactly_one_bucket():
    for name in scopes.STEP_SCOPES + scopes.KERNEL_NAMES:
        for path in (f"jit(step)/jvp(M)/{name}/add",
                     f"jit(step)/shard_map/transpose(jvp({name}))/mul"):
            hits = [b for b, rx in scope_reduce.BUCKETS
                    if re.search(rx, path)]
            assert len(hits) == 1, (name, path, hits)
    table = dict(scope_reduce.BUCKETS)
    assert set(scope_reduce.UPDATE_BUCKETS) == {
        scopes.GRAD_SYNC, scopes.GRAD_CLIP, scopes.OPTIMIZER
    } and set(scope_reduce.UPDATE_BUCKETS) <= set(table)
    # what Flax names itself, relied on and not re-wrapped
    for path, bucket in [
        ("jit(s)/jvp(M)/layer_1/attn/q_proj/dot_general", "attn"),
        ("jit(s)/transpose(jvp(M))/layer_1/mlp/up_proj/reduce_sum", "mlp"),
        ("jit(s)/jvp(M)/layer_0/attn_norm/rsqrt", "norm"),
        ("jit(s)/jvp(M)/final_norm/add", "norm"),
        ("jit(s)/jvp(M)/layer_0/add", "block"),
        ("jit(s)/jvp(M)/while/body/closed_call/layers/block/add", "block"),
        ("jit(s)/jvp(M)/while/body/dynamic_slice", "block"),
        ("jit(s)/jvp(M)/layer_3/attn/flash_bwd_dkv/pallas_call",
         "attn_kernel.dkv"),
        ("jit(s)/shard_map", "other"), ("", "other"),
    ]:
        assert scope_reduce.bucket_of(path) == bucket, path
    assert scope_reduce.phase_of("a/transpose(jvp(M))/mlp/x", "mlp") == "bwd"
    assert scope_reduce.phase_of("a/jvp(M)/mlp/x", "mlp") == "fwd"
    assert scope_reduce.phase_of("a/optimizer/x", "optimizer") == "update"


def _tiny_hybrid_step_text(recompute_all=False, **overrides):
    """The compiled train step of a two-layer hybrid (``mamba``,
    ``attention``) under remat, as HLO text.  ``recompute_all``: the
    ``mamba`` block under the policy-free wrapper too, so that its second
    forward runs every part again.  With the projections kept, the CPU's
    XLA merges the second ``conv_fwd`` launch, which reads the same kept
    ``proj``, into the first (the chip's compile keeps both:
    ``tests/test_attention.py``)."""
    from unittest import mock

    import flax.linen as nn

    from distributeddataparallel_tpu.models import transformer
    from distributeddataparallel_tpu.models.transformer import (
        granite_4_0_h_micro,
    )

    if recompute_all:
        with mock.patch.object(transformer, "RematBlock", nn.remat(
                transformer.DecoderBlock, static_argnums=(4,))):
            return _tiny_hybrid_step_text(**overrides)

    cfg = granite_4_0_h_micro(**{**dict(
        vocab_size=128, num_layers=2, layer_types=("mamba", "attention"),
        d_model=32, num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
        max_seq_len=16, ssm_heads=4, ssm_head_dim=16, ssm_state=16,
        ssm_chunk=8, attn_impl="xla", remat=True,
    ), **overrides})
    model = TransformerLM(cfg)
    mesh = ddp.make_mesh(("data",), devices=jax.devices()[:1])

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["tokens"][:, :-1])
        return lm_cross_entropy(logits, batch["tokens"][:, 1:]), {}

    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    state = ddp.broadcast_params(
        ddp.TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adamw(1e-3)
        ),
        mesh,
    )
    batch = shard_batch({"tokens": jnp.zeros((2, 17), jnp.int32)}, mesh)
    return ddp.make_train_step(loss_fn, mesh=mesh).lower(
        state, batch, jax.random.PRNGKey(0)
    ).compile().as_text()


def test_mixer_scopes_fall_in_one_row_and_reach_the_compiled_step(devices):
    """The Mamba-2 mixer's five scopes (PR 28) are a tuple of their own,
    read by ``benchmarks/mixer_scopes.py``'s table and not by
    ``scope_reduce.BUCKETS``; a compiled hybrid step carries each of them,
    forward and backward."""
    from benchmarks import mixer_scopes

    assert not set(scopes.MIXER_SCOPES) & set(scopes.STEP_SCOPES)
    assert [name for name, _ in mixer_scopes.PARTS] == list(scopes.MIXER_SCOPES)
    for name in scopes.MIXER_SCOPES:
        for path in (f"jit(step)/jvp(M)/layer_0/mamba/{name}/add",
                     f"jit(s)/transpose(jvp(M))/layer_3/mamba/{name}/mul"):
            hits = [part for part, rx in mixer_scopes.PARTS
                    if re.search(rx, path)]
            assert hits == [name], (path, hits)
            assert mixer_scopes.part_of(path) == name
    assert mixer_scopes.part_of("jit(s)/jvp(M)/layer_0/mamba/reshape") == (
        mixer_scopes.REST)
    assert mixer_scopes.part_of("jit(s)/jvp(M)/layer_0/mamba_norm/mul") is None
    assert mixer_scopes.part_of("jit(s)/jvp(M)/layer_0/attn/q_proj/dot") is None

    found = collections.Counter()
    for scope in _OP_NAME.findall(_tiny_hybrid_step_text()):
        part = mixer_scopes.part_of(scope)
        if part is not None:
            found[part, scope_reduce.phase_of(scope, "")] += 1
    for name in scopes.MIXER_SCOPES:
        assert found[name, "fwd"] and found[name, "bwd"], (name, found)
    # the accepted table still has a row for all of it
    assert scope_reduce.bucket_of(
        "jit(s)/jvp(M)/layer_0/mamba/ssd/dot_general") == "block"


@pytest.mark.parametrize("module,entry,names,part", [
    ("ssd", "ssd_chunked", scopes.SSD_KERNEL_NAMES, "ssd"),
    ("causal_conv", "causal_conv_silu", scopes.CONV_KERNEL_NAMES, "ssm_conv"),
], ids=["ssd", "conv"])
def test_mixer_kernels_carry_their_parts_scope_in_both_phases(
    devices, module, entry, names, part
):
    """``ssd_fwd`` / ``ssd_bwd`` (PR 30) and ``conv_fwd`` / ``conv_bwd``
    (PR 32) are launched from jitted functions of their own and from a
    ``custom_vjp``: their operations must still carry ``.../mamba/ssd/...``
    and ``.../mamba/ssm_conv/...``, which is where ``train_ssd_scan_ms``,
    ``ssd_scan_roofline`` and ``train_ssm_conv_ms`` look for them — the
    forward in the forward pass (and again under remat), the backward
    under ``transpose(``.  The kernels are forced through the interpreter;
    on the chip each is one custom call under the same name.  The ``ssd``
    case reads the step the model runs; the ``conv`` case the policy-free
    wrapper's (``_tiny_hybrid_step_text``), and the model's own step on
    the chip holds ``conv_fwd``'s second launches
    (``tests/test_attention.py::
    test_the_granite_step_holds_the_conv_kernels_and_no_copy_round_them``)."""
    import functools
    import importlib
    from unittest import mock

    from benchmarks import mixer_scopes

    assert set(scopes.SSD_KERNEL_NAMES) == {"ssd_fwd", "ssd_bwd"}
    assert set(scopes.CONV_KERNEL_NAMES) == {"conv_fwd", "conv_bwd"}
    op = importlib.import_module(f"distributeddataparallel_tpu.ops.{module}")
    with mock.patch.object(
        op, entry, functools.partial(getattr(op, entry), _interpret=True)
    ):
        text = _tiny_hybrid_step_text(recompute_all=module == "causal_conv")
    fwd, bwd = names
    found = collections.Counter()
    for scope in _OP_NAME.findall(text):
        for name in names:
            if f"/{name}/" in scope:
                assert mixer_scopes.part_of(scope) == part, scope
                found[name, scope_reduce.phase_of(scope, "")] += 1
    assert found[fwd, "fwd"] and found[fwd, "bwd"], found
    assert found[bwd, "bwd"] and not found[bwd, "fwd"], found


def _tiny_afmoe_step_text(**overrides):
    """The compiled train step of a three-layer afmoe stack (a dense
    sliding layer, a sliding and a full layer with experts, a share of 4
    of 8 held) under remat, as HLO text."""
    from distributeddataparallel_tpu.models.transformer import trinity_mini

    cfg = trinity_mini(**{**dict(
        vocab_size=128, num_layers=3, num_dense_layers=1,
        layer_types=("sliding_attention", "sliding_attention",
                     "full_attention"),
        d_model=32, num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
        moe_d_ff=32, max_seq_len=16, sliding_window=4, moe_experts=8,
        moe_top_k=2, moe_experts_held=(0, 4), attn_impl="xla", remat=True,
    ), **overrides})
    model = TransformerLM(cfg)
    mesh = ddp.make_mesh(("data",), devices=jax.devices()[:1])

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["tokens"][:, :-1])
        return lm_cross_entropy(logits, batch["tokens"][:, 1:]), {}

    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    state = ddp.broadcast_params(
        ddp.TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adamw(1e-3)
        ),
        mesh,
    )
    batch = shard_batch({"tokens": jnp.zeros((2, 17), jnp.int32)}, mesh)
    return ddp.make_train_step(loss_fn, mesh=mesh).lower(
        state, batch, jax.random.PRNGKey(0)
    ).compile().as_text()


def test_moe_scopes_fall_in_one_row_and_reach_the_compiled_step(devices):
    """The expert FFN's five scopes (PR 33) are a tuple of their own, read
    by ``benchmarks/moe_scopes.py``'s table and not by
    ``scope_reduce.BUCKETS``, which counts all of it under ``mlp``; a
    compiled afmoe step carries each of them, forward and backward, and
    the dense layer's FFN none."""
    from benchmarks import moe_scopes

    assert scopes.MOE_SCOPES == (
        "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
        "moe_shared")
    assert not set(scopes.MOE_SCOPES) & set(
        scopes.STEP_SCOPES + scopes.MIXER_SCOPES)
    # the grouped kernels' names; ``tests/test_attention.py`` finds them in
    # the cell's compiled step, under ``moe_experts`` in both phases
    assert scopes.MOE_KERNEL_NAMES == ("moe_gmm", "moe_tgmm")
    assert [name for name, _ in moe_scopes.PARTS] == list(scopes.MOE_SCOPES)
    for name in scopes.MOE_SCOPES:
        for path in (f"jit(step)/jvp(M)/layer_1/mlp/{name}/add",
                     f"jit(s)/transpose(jvp(M))/layer_3/mlp/{name}/cond/mul"):
            hits = [part for part, rx in moe_scopes.PARTS
                    if re.search(rx, path)]
            assert hits == [name], (path, hits)
            assert moe_scopes.part_of(path) == name
            assert scope_reduce.bucket_of(path) == "mlp"
    assert moe_scopes.part_of("jit(s)/jvp(M)/layer_0/mlp/up_proj/dot") is None
    assert moe_scopes.part_of("jit(s)/jvp(M)/layer_1/mlp_norm/mul") is None

    found = collections.Counter()
    dense = 0
    for scope in _OP_NAME.findall(_tiny_afmoe_step_text()):
        part = moe_scopes.part_of(scope)
        if part is not None:
            assert "/layer_0/" not in scope, scope
            found[part, scope_reduce.phase_of(scope, "")] += 1
        elif "/layer_0/mlp/" in scope:
            dense += 1
    assert dense
    for name in scopes.MOE_SCOPES:
        assert found[name, "fwd"] and found[name, "bwd"], (name, found)


def _tiny_evabyte_step_text(seq=64, **overrides):
    """The compiled train step of a two-layer evabyte stack (windows of
    16 in chunks of 4, eight heads' loss) under remat, as HLO text."""
    from distributeddataparallel_tpu.models.transformer import evabyte
    from distributeddataparallel_tpu.ops import multi_token_cross_entropy

    cfg = evabyte(**{**dict(
        num_layers=2, d_model=32, num_heads=2, d_ff=64, max_seq_len=seq,
        sliding_window=16, eva_chunk=4, attn_impl="xla", remat=True,
    ), **overrides})
    model = TransformerLM(cfg)
    mesh = ddp.make_mesh(("data",), devices=jax.devices()[:1])

    def loss_fn(params, batch, rng):
        ids = batch["tokens"]
        logits = model.apply({"params": params}, ids[:, :-1])
        return multi_token_cross_entropy(logits, ids), {}

    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    state = ddp.broadcast_params(
        ddp.TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adamw(1e-3)
        ),
        mesh,
    )
    batch = shard_batch({"tokens": jnp.zeros((1, seq + 1), jnp.int32)}, mesh)
    return ddp.make_train_step(loss_fn, mesh=mesh).lower(
        state, batch, jax.random.PRNGKey(0)
    ).compile().as_text()


def test_eva_scopes_fall_in_one_row_and_reach_the_compiled_step(devices):
    """Chunk-summarised attention's four scopes (PR 36) are a tuple of
    their own, read by ``benchmarks/eva_scopes.py``'s table and not by
    ``scope_reduce.BUCKETS``, which counts them under ``attn`` and the
    flash kernels under their own names wherever they run; a compiled
    evabyte step carries each of them, forward and backward, and the
    projections none."""
    from benchmarks import eva_scopes

    assert scopes.EVA_SCOPES == (
        "eva_local", "eva_summaries", "eva_remote", "eva_merge")
    assert not set(scopes.EVA_SCOPES) & set(
        scopes.STEP_SCOPES + scopes.MIXER_SCOPES + scopes.MOE_SCOPES)
    # the local part's kernels are a row of their own, before their scope's
    assert [name for name, _ in eva_scopes.PARTS] == [
        "eva_local.kernels", *scopes.EVA_SCOPES]
    for name in scopes.EVA_SCOPES:
        for path in (f"jit(step)/jvp(M)/layer_1/attn/{name}/add",
                     f"jit(s)/transpose(jvp(M))/layer_3/attn/{name}/mul"):
            assert eva_scopes.part_of(path) == name
            assert scope_reduce.bucket_of(path) == "attn"
    for kernel, bucket in zip(scopes.KERNEL_NAMES,
                              scope_reduce.KERNEL_BUCKETS):
        local = (f"jit(s)/jvp(M)/layer_0/attn/eva_local/jit(_fwd_launch)/"
                 f"{kernel}/pallas_call")
        assert eva_scopes.part_of(local) == "eva_local.kernels"
        assert eva_scopes.part_of(
            local.replace("eva_local", "eva_remote")) == "eva_remote"
        assert scope_reduce.bucket_of(local) == bucket
    assert eva_scopes.part_of("jit(s)/jvp(M)/layer_0/attn/q_proj/dot") is None
    assert eva_scopes.part_of("jit(s)/jvp(M)/layer_1/attn_norm/mul") is None

    found = collections.Counter()
    projections = 0
    for scope in _OP_NAME.findall(_tiny_evabyte_step_text()):
        part = eva_scopes.part_of(scope)
        if part is not None:
            assert "/attn/" in scope, scope
            found[part, scope_reduce.phase_of(scope, "")] += 1
        elif "/attn/q_proj/" in scope:
            projections += 1
    assert projections
    for name in scopes.EVA_SCOPES:
        assert found[name, "fwd"] and found[name, "bwd"], (name, found)


def test_eva_kernels_carry_their_parts_scope_in_both_phases(devices):
    """On the kernel path (forced through the interpreter) the three flash
    kernels run under ``eva_local`` and again under ``eva_remote``:
    ``flash_fwd`` forward and, under remat, once more in the backward
    pass; the two backward kernels under ``transpose(`` alone."""
    from unittest import mock

    from benchmarks import eva_scopes
    from distributeddataparallel_tpu.ops import pallas_attention

    flash = pallas_attention.flash_attention

    def interpreted(q, k, v, causal=True, interpret=False, *args, **kw):
        return flash(q, k, v, causal, True, *args, **kw)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(pallas_attention, "flash_attention",
                              interpreted):
        text = _tiny_evabyte_step_text(
            seq=512, sliding_window=256, eva_chunk=2, attn_impl="auto",
            num_layers=1, head_dim=16)
    found = collections.Counter()
    for scope in _OP_NAME.findall(text):
        for name in scopes.KERNEL_NAMES:
            if f"/{name}/" in scope:
                found[eva_scopes.part_of(scope), name,
                      scope_reduce.phase_of(scope, "")] += 1
    fwd, dq, dkv = scopes.KERNEL_NAMES
    for part in ("eva_local.kernels", "eva_remote"):
        assert found[part, fwd, "fwd"] and found[part, fwd, "bwd"], found
        for name in (dq, dkv):
            assert found[part, name, "bwd"], found
            assert not found[part, name, "fwd"], found


def test_flash_kernels_carry_attn_scope_and_phase_in_a_compiled_step(devices):
    """The backward kernels are launched from a jitted ``_bwd_launch`` (PR
    35) as the forward is from ``_fwd_launch`` (PR 26), inside a
    ``custom_vjp``: their operations must still carry ``.../attn/...`` and
    their own name, which is where ``train_attn_ms`` and the four
    ``flash_*_roofline`` readers look for them — ``flash_fwd`` in the
    forward pass, ``flash_bwd_dq`` / ``flash_bwd_dkv`` under ``transpose(``
    and nowhere else.  The kernels are forced through the interpreter; on
    the chip each is one custom call under the same name."""
    from unittest import mock

    from distributeddataparallel_tpu.ops import pallas_attention

    flash = pallas_attention.flash_attention
    # "auto" with the backend's say left out: the model's init, at 8
    # tokens, takes the XLA path as it would on the chip
    with mock.patch.object(pallas_attention, "supported",
                           lambda q, k, v: q.shape[1] % 128 == 0), \
            mock.patch.object(
                pallas_attention, "flash_attention",
                lambda q, k, v, causal, interpret, scale, window, **kw: flash(
                    q, k, v, causal, True, scale, window, **kw)):
        text = _compiled_step_text(1, False, attn_impl="auto", seq=128)
    buckets = dict(zip(scopes.KERNEL_NAMES, scope_reduce.KERNEL_BUCKETS))
    found = collections.Counter()
    for scope in _OP_NAME.findall(text):
        for name in scopes.KERNEL_NAMES:
            if f"/{name}/" in scope:
                assert scope_reduce.bucket_of(scope) == buckets[name], scope
                assert "/attn/" in scope, scope
                found[name, scope_reduce.phase_of(scope, "")] += 1
    fwd, dq, dkv = scopes.FLASH_FWD, scopes.FLASH_BWD_DQ, scopes.FLASH_BWD_DKV
    assert found[fwd, "fwd"] and not found[fwd, "bwd"], found
    for name in (dq, dkv):
        assert found[name, "bwd"] and not found[name, "fwd"], found
    assert "jit(_bwd_launch)" in text and "jit(_fwd_launch)" in text


def test_three_pallas_calls_have_three_names():
    from distributeddataparallel_tpu.ops.pallas_attention import (
        flash_attention,
    )

    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, True).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    kernels = re.findall(r"\bname=(\w+)", text)
    assert sorted(set(kernels) & set(scopes.KERNEL_NAMES)) == sorted(
        scopes.KERNEL_NAMES
    ), kernels
    assert text.count("pallas_call[") == 3


# ------------------------------------------ remat's second forward, by name

#: the remat cells' models at a tiny size, and the scopes under which
#: some recomputed operation must lie.  Of them only GPT-2 scans its
#: layers (``scan_layers`` runs attention layers only)
_REMAT_MODELS = {
    # a ``mamba`` block keeps the stream its output projection is added
    # to: nothing of ``out_proj`` is run again
    # (``test_a_mamba_blocks_second_forward_runs_no_projection``)
    "hybrid": ("attn", "mlp", "mamba") + tuple(
        s for s in scopes.MIXER_SCOPES if s != scopes.SSM_OUT_PROJ),
    "afmoe": ("attn", "mlp") + scopes.MOE_SCOPES,
    "evabyte": ("attn", "mlp") + scopes.EVA_SCOPES,
    "gpt2.unrolled": ("attn", "mlp", r"layer_\d+"),
    "gpt2.scan_layers": ("attn", "mlp", "layers"),
}


@functools.lru_cache(maxsize=None)
def _remat_step_text(model: str, remat: bool) -> str:
    if model == "hybrid":
        return _tiny_hybrid_step_text(remat=remat)
    if model == "afmoe":
        return _tiny_afmoe_step_text(remat=remat)
    if model == "evabyte":
        return _tiny_evabyte_step_text(remat=remat)
    return _compiled_step_text(1, model.endswith("scan_layers"), remat=remat)


@pytest.mark.parametrize("model", list(_REMAT_MODELS))
def test_remat_second_forward_carries_the_word_under_every_scope(
    devices, model
):
    """JAX writes ``scopes.RECOMPUTE`` into every operation that remat runs
    a second time, inside ``transpose(`` (so the accepted phase split
    counts it as backward), and the model's own scopes nest under it."""
    from benchmarks import remat_scopes

    # a reduction's own adder runs as no operation of its own and carries
    # the name from ``checkpoint/`` on: the step's operations start at jit(
    recomputed = [scope for scope in _OP_NAME.findall(
        _remat_step_text(model, True))
        if scopes.RECOMPUTE in scope and scope.startswith("jit(")]
    assert recomputed
    for scope in recomputed:
        assert remat_scopes._RECOMPUTE.search(scope), scope
        assert scope.index("transpose(") < scope.index(scopes.RECOMPUTE)
        assert scope_reduce.phase_of(scope, "") == "bwd"
    for name in _REMAT_MODELS[model]:
        under = re.compile(scope_reduce._under(name))
        found = [s for s in recomputed if under.search(s)]
        assert found, (model, name)
        assert all(s.index(scopes.RECOMPUTE) < under.search(s).start()
                   for s in found), (model, name)


def test_a_mamba_blocks_second_forward_runs_no_projection(devices):
    """Under remat a ``mamba`` block keeps what its mixer's two
    projections made: no recomputed ``dot`` lies under ``ssm_in_proj`` or
    ``ssm_out_proj``, while the MLP's and the attention's run again."""
    recomputed = [
        scope for line in _remat_step_text("hybrid", True).splitlines()
        if " dot(" in line and scopes.RECOMPUTE in line
        for scope in _OP_NAME.findall(line)]
    assert not [s for s in recomputed
                if f"/{scopes.SSM_IN_PROJ}/" in s
                or f"/{scopes.SSM_OUT_PROJ}/" in s], recomputed
    for under in ("/mlp/", "/attn/"):
        assert any(under in s for s in recomputed), (under, recomputed)


@pytest.mark.parametrize("model", list(_REMAT_MODELS))
def test_no_operation_carries_the_word_without_remat(devices, model):
    text = _remat_step_text(model, False)
    assert "transpose(jvp(" in text
    assert scopes.RECOMPUTE not in text


def _interpreted_kernels_text(kernel: str) -> str:
    """A tiny remat step whose ``kernel`` runs as the chip would run it,
    its Pallas body through the interpreter."""
    from unittest import mock

    from distributeddataparallel_tpu.ops import (
        causal_conv,
        grouped_matmul,
        pallas_attention,
        ssd,
    )

    if kernel in (scopes.SSD_FWD, scopes.CONV_FWD):
        # ``conv_fwd``'s second launch needs the policy-free wrapper on the
        # CPU (``_tiny_hybrid_step_text``); ``ssd_fwd``'s is read on the
        # step the model runs
        with mock.patch.object(ssd, "ssd_chunked", functools.partial(
                ssd.ssd_chunked, _interpret=True)), \
                mock.patch.object(causal_conv, "causal_conv_silu",
                                  functools.partial(causal_conv.causal_conv_silu,
                                                    _interpret=True)):
            return _tiny_hybrid_step_text(
                recompute_all=kernel == scopes.CONV_FWD)
    if kernel == scopes.MOE_GMM:
        # the kernels' row tile wants both weight axes a multiple of 128
        with mock.patch.object(grouped_matmul, "supported", lambda r, w: True), \
                mock.patch.object(grouped_matmul, "grouped_matmul",
                                  functools.partial(grouped_matmul.grouped_matmul,
                                                    _interpret=True)):
            return _tiny_afmoe_step_text(d_model=128, moe_d_ff=128)
    flash = pallas_attention.flash_attention

    def interpreted(q, k, v, causal=True, interpret=False, *args, **kw):
        return flash(q, k, v, causal, True, *args, **kw)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(pallas_attention, "flash_attention", interpreted):
        return _tiny_evabyte_step_text(
            seq=512, sliding_window=256, eva_chunk=2, attn_impl="auto",
            num_layers=1, head_dim=16)


@pytest.mark.parametrize("kernel", [
    scopes.SSD_FWD, scopes.FLASH_FWD, scopes.MOE_GMM, scopes.CONV_FWD,
])
def test_a_kernels_second_forward_launch_carries_the_word(devices, kernel):
    """The forward kernels' launches inside remat's second forward carry
    the word and their first launches do not; no backward kernel carries
    it.  ``ssd_fwd`` and ``conv_fwd`` are read on the hybrid's step; the
    chip's compile of the granite step holds both second launches
    (``tests/test_attention.py::
    test_the_granite_step_holds_the_conv_kernels_and_no_copy_round_them``)."""
    from benchmarks import remat_scopes

    text = _interpreted_kernels_text(kernel)
    forward = (kernel,)
    backward = {scopes.SSD_FWD: scopes.SSD_KERNEL_NAMES[1:],
                scopes.CONV_FWD: scopes.CONV_KERNEL_NAMES[1:],
                scopes.FLASH_FWD: scopes.KERNEL_NAMES[1:],
                scopes.MOE_GMM: scopes.MOE_KERNEL_NAMES[1:]}[kernel]
    found = collections.Counter()
    for scope in _OP_NAME.findall(text):
        for name in forward + backward:
            if f"/{name}/" not in scope:
                continue
            again = scopes.RECOMPUTE in scope
            found[name, again, "transpose(" in scope] += 1
            if again and name in forward:
                assert remat_scopes.kernel_of("k.1 custom-call", scope) == name
    for name in forward:
        assert found[name, True, True], (name, found)      # the second launch
        assert found[name, False, False], (name, found)    # the first
        assert not found[name, True, False], found
    for name in backward:
        assert found[name, False, True], (name, found)
        assert not found[name, True, True] and not found[name, True, False]
    if kernel != scopes.MOE_GMM:
        # launched in the backward pass only by remat
        for name in forward:
            assert not found[name, False, True], (name, found)
    else:
        # ``moe_gmm`` also takes the backward's d rows times the transpose
        assert found[kernel, False, True], found


def test_the_remat_reader_keeps_its_own_copy_of_the_names():
    from benchmarks import remat_scopes

    assert remat_scopes.RECOMPUTE == scopes.RECOMPUTE
    assert remat_scopes.KERNELS == (
        scopes.FLASH_FWD, scopes.SSD_FWD, scopes.CONV_FWD, scopes.MOE_GMM)
    assert remat_scopes.kernel_of(
        "fusion.3", "a/rematted_computation/x/flash_fwd/pallas_call") is None
    assert remat_scopes.kernel_of(
        "attn.7 custom-call", "a/x/flash_bwd_dq/pallas_call") is None


def _hand_scoped_trace(ops):
    """The scoped form (``scope_reduce.load_xplane``) of one chip running
    ``ops`` [(event name, scope, ns)] one after another in the window."""
    names = [["bench:window", ""]] + [[n, scope] for n, scope, _ in ops]
    events, at = [], 1000
    for i, (_, _, ns) in enumerate(ops):
        events.append([i + 1, at, ns])
        at += ns
    return {"names": names, "planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [[0, 0, at + 1000]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events}]},
    ]}


def test_remat_readers_read_the_recompute_and_are_silent_without_it():
    from benchmarks import harness, remat_scopes

    fwd = "jit(s)/jvp(M)/"
    bwd = "jit(s)/transpose(jvp(M))/"
    again = bwd + "jvp(M)/checkpoint/rematted_computation/"
    flash = "attn/jit(_fwd_launch)/flash_fwd/pallas_call"
    gmm = "mlp/moe_experts/jit(_gmm_launch)/moe_gmm/pallas_call"
    trace = _hand_scoped_trace([
        ("fusion.1", again + "layer_0/attn/q_proj/dot_general", 3_000_000),
        ("fusion.2", again + "layer_0/mlp/up_proj/dot_general", 5_000_000),
        ("attn.3 custom-call", again + "layer_0/" + flash, 2_000_000),
        ("copy-done.4", again + "layer_0/" + flash, 500_000),
        ("mlp.5 custom-call", again + "layer_1/" + gmm, 1_000_000),
        # the first forward, the true backward: not recompute
        ("attn.6 custom-call", fwd + "layer_0/" + flash, 1_900_000),
        ("mlp.7 custom-call", fwd + "layer_1/" + gmm, 900_000),
        ("mlp.8 custom-call", bwd + "layer_1/" + gmm, 1_500_000),
        ("attn.9 custom-call", bwd + "layer_0/attn/flash_bwd_dq/pallas_call",
         4_000_000),
        ("fusion.10", fwd + "layer_0/mlp/up_proj/dot_general", 6_000_000),
        ("fusion.11", bwd + "layer_0/mlp/up_proj/dot_general", 7_000_000),
    ])
    reduced = remat_scopes.reduce(trace, 1)
    assert reduced["bucket_kind_s"] == {
        "attn": {"fusion": 0.003},
        "mlp": {"fusion": 0.005, "mlp custom-call": 0.001},
        "attn_kernel.fwd": {"attn custom-call": 0.002, "copy-done": 0.0005},
    }
    assert reduced["kernel_s"] == {"flash_fwd": 0.002, "moe_gmm": 0.001}
    assert reduced["first_kernel_s"] == {"flash_fwd": 0.0019, "moe_gmm": 0.0009}
    assert "flash_fwd" in remat_scopes.table(reduced, steps=2)
    ctx = {"remat_reduced": reduced, "measured": {"steps": 2}, "chips": 1}
    metric = lambda name: harness.load_module("layer_metrics", name).read  # noqa: E731
    assert metric("train_remat_ms")(ctx) == pytest.approx(5.75)
    assert metric("train_remat_kernels_ms")(ctx) == pytest.approx(1.5)
    # the same work counted by the accepted phase split: all of it bwd
    phases = scope_reduce.reduce(trace, 1)["phase_s"]
    assert phases["bwd"] == pytest.approx(0.024)
    # a step without remat (cells 1 and 3): no number, no error
    plain = _hand_scoped_trace([op for op in (
        ("fusion.10", fwd + "layer_0/mlp/up_proj/dot_general", 6_000_000),
        ("attn.6 custom-call", fwd + "layer_0/" + flash, 1_900_000))])
    assert remat_scopes.reduce(plain, 1) is None
    assert remat_scopes.reduce({"names": [], "planes": []}, 1) is None
    silent = dict(ctx, remat_reduced=None)
    for name in ("train_remat_ms", "train_remat_kernels_ms"):
        assert metric(name)(silent) is None
    # recompute with no kernel under it: the kernels' reader is silent
    no_kernel = remat_scopes.reduce(_hand_scoped_trace([
        ("fusion.1", again + "layer_0/attn/q_proj/dot_general", 3_000_000)]), 1)
    assert metric("train_remat_kernels_ms")(
        dict(ctx, remat_reduced=no_kernel)) is None
    assert metric("train_remat_ms")(
        dict(ctx, remat_reduced=no_kernel)) == pytest.approx(1.5)


# ---------------------------------------------------------------- the tracer

class _Recorder:
    """Stands in for an ``EventLog``: keeps what a tracer emits."""

    def __init__(self):
        self.records = []

    def emit(self, kind, **fields):
        self.records.append(dict(fields, kind=kind))

    def names(self):
        return collections.Counter(r["name"] for r in self.records)

    def parents(self):
        return {r["name"]: r["parent"] for r in self.records}


def test_tracer_keeps_parent_and_depth_and_stores_nothing_itself():
    rec = _Recorder()
    tr = Tracer(rec)
    for i in range(50):
        with tr.span("step", step=i):
            with tr.span("settle"):
                assert tr.depth == 2
    assert tr.depth == 0
    assert rec.names() == {"step": 50, "settle": 50}
    last, before = rec.records[-1], rec.records[-2]
    assert (last["name"], last["parent"], last["depth"]) == ("step", None, 0)
    assert (before["name"], before["parent"], before["depth"]) == (
        "settle", "step", 1)
    assert last["step"] == 49 and last["dur_s"] >= before["dur_s"] >= 0
    # with a registry, one histogram per span name
    from distributeddataparallel_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    tr2 = Tracer(None, reg)
    with tr2.span("loader.batch"):
        pass
    assert reg.snapshot()["span_loader_batch_s"]["count"] == 1
    # with nowhere to write, a span leaves nothing behind in the tracer:
    # no ring, no counters, nothing that grows over a long run
    bare = Tracer()
    before = dict(vars(bare))
    for _ in range(50):
        with bare.span("step"):
            pass
    assert vars(bare) == before and set(before) == {
        "events", "registry", "_local"}


def test_tracer_nests_per_thread():
    rec = _Recorder()
    tr = Tracer(rec)
    inside = threading.Event()
    release = threading.Event()

    def producer():
        with tr.span("loader.batch"):
            inside.set()
            release.wait(5)

    t = threading.Thread(target=producer)
    with tr.span("epoch"):
        t.start()
        assert inside.wait(5)
        # the other thread's open span is not this thread's parent
        with tr.span("step"):
            assert tr.depth == 2
        release.set()
        t.join()
    assert rec.parents() == {
        "loader.batch": None, "step": "epoch", "epoch": None}


def test_tracer_enters_a_trace_annotation_when_jax_is_loaded(monkeypatch):
    entered = []

    class FakeAnnotation:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, attrs

        def __enter__(self):
            entered.append(("in", self.name, self.attrs))

        def __exit__(self, *exc):
            entered.append(("out", self.name))
            return False

    fake = types.ModuleType("jax")
    fake.profiler = types.SimpleNamespace(TraceAnnotation=FakeAnnotation)
    monkeypatch.setitem(sys.modules, "jax", fake)
    tr = Tracer()
    with tr.span("step", step=3):
        with tr.span("settle"):
            pass
    assert entered == [
        ("in", "ddp:step", {"step": 3}), ("in", "ddp:settle", {}),
        ("out", "ddp:settle"), ("out", "ddp:step"),
    ]
    # without jax in the process a span is two clock reads, no import
    monkeypatch.delitem(sys.modules, "jax")
    entered.clear()
    with tr.span("step"):
        pass
    assert entered == [] and "jax" not in sys.modules


def test_spans_from_many_threads_lose_nothing(tmp_path):
    """The loader's producer emits its spans beside the train loop's: the
    event log's ``seq`` stays a total order and no line is torn, with
    more threads than cores and a short switch interval."""
    from distributeddataparallel_tpu.observability import (
        EventLog,
        read_events,
        validate_file,
    )

    path = str(tmp_path / "events-p0.jsonl")
    n_threads, per_thread = 2 * (os.cpu_count() or 4), 200
    previous = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with EventLog(path, 0) as ev:
            logged = Tracer(ev)

            def work():
                for i in range(per_thread):
                    with logged.span("loader.batch", i=i):
                        pass

            threads = [threading.Thread(target=work) for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(previous)
    records = read_events(path)
    assert len(records) == n_threads * per_thread
    assert sorted(r["seq"] for r in records) == list(range(len(records)))
    assert all(r["depth"] == 0 and r["parent"] is None for r in records)
    assert validate_file(path) == []


# ---------------------------------------------------------------- the loader

@pytest.mark.parametrize("workers", [0, 1], ids=["inline", "threaded"])
def test_loader_emits_one_batch_span_a_batch(devices, workers):
    mesh = ddp.make_mesh(("data",))
    ds = SyntheticClassification(num_examples=256, shape=(4, 4, 1), seed=0)
    loader = DataLoader(ds, per_replica_batch=4, mesh=mesh, workers=workers)
    previous = get_tracer()
    rec = _Recorder()
    set_tracer(Tracer(rec))
    try:
        batches = list(loader)
    finally:
        set_tracer(previous)
    assert len(batches) == len(loader) == 8
    # the one span of the loader, once a batch, on whichever thread makes
    # the batch (the producer's own stack: no parent)
    assert rec.names() == {"loader.batch": 8}
    assert rec.parents() == {"loader.batch": None}


# -------------------------------------------------------------------- dpp.py

def test_profile_steps_alone_has_a_live_tracer_and_captures_its_spans(
    devices, tmp_path, monkeypatch
):
    import dpp
    from benchmarks import trace_reduce
    from distributeddataparallel_tpu import observability

    installed = []
    monkeypatch.setattr(
        observability, "set_tracer",
        lambda tracer: installed.append(set_tracer(tracer)) or installed[-1],
    )
    previous = get_tracer()
    args = dpp.parse_args([
        "--device", "cpu", "--dataset", "synthetic", "--model", "mlp",
        "--num-examples", "256", "--batch-size", "4", "--epochs", "1",
        "--log-every", "1000", "--profile-steps", "2:5",
        "--profile-dir", str(tmp_path / "xprof"),
    ])
    try:
        dpp.train(args)
        # the run's tracer left with the run
        assert get_tracer() is installed[-1] is not installed[0]
    finally:
        set_tracer(previous)
    # installed once, inside the run, and with no event log behind it
    assert len(installed) == 2 and installed[0] is not previous
    assert installed[0].events is None and installed[0].registry is None
    trace = trace_reduce.load_xplane(
        trace_reduce.find_xplane(str(tmp_path / "xprof"))
    )
    host = collections.Counter(
        name for plane in trace["planes"] for line in plane["lines"]
        for name, _, _ in line["events"]
    )
    assert host["ddp:step"] == 3, host["ddp:step"]  # steps 2, 3, 4
    assert host["ddp:loader.batch"] >= 1 and host["ddp:settle"] >= 1
    assert any(name.startswith("PjitFunction(") for name in host)


def test_a_run_that_fails_in_set_up_leaves_no_tracer_installed(
    devices, monkeypatch
):
    import dpp
    from distributeddataparallel_tpu import observability
    from distributeddataparallel_tpu.data import loader as loader_mod

    installed = []
    monkeypatch.setattr(
        observability, "set_tracer",
        lambda tracer: installed.append(set_tracer(tracer)) or installed[-1],
    )

    def broken(*args, **kwargs):
        raise RuntimeError("no loader today")

    monkeypatch.setattr(loader_mod.DataLoader, "__init__", broken)
    previous = get_tracer()
    args = dpp.parse_args([
        "--device", "cpu", "--dataset", "synthetic", "--model", "mlp",
        "--num-examples", "64", "--batch-size", "4", "--epochs", "1",
    ])
    try:
        with pytest.raises(RuntimeError, match="no loader today"):
            dpp.train(args)
        assert installed == [] and get_tracer() is previous
    finally:
        set_tracer(previous)
        ddp.destroy_process_group()  # set-up had opened it
