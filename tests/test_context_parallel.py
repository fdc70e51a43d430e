"""Context-parallelism tests: ring attention numerics vs full attention,
global positions, and an end-to-end DP×CP LM train step equivalence."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import distributeddataparallel_tpu as ddp
from distributeddataparallel_tpu.data import shard_lm_batch
from distributeddataparallel_tpu.models import TransformerLM, tiny_lm
from distributeddataparallel_tpu.ops import lm_cross_entropy
from distributeddataparallel_tpu.ops.attention import dot_product_attention
from distributeddataparallel_tpu.parallel import (
    cp_positions,
    make_cp_train_step,
    ring_attention,
)


def _ring_on_mesh(q, k, v, mesh, causal):
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name="seq", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    )
    return jax.jit(fn)(q, k, v)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal, devices):
    mesh = ddp.make_mesh(("seq",))
    B, S, H, D = 2, 64, 2, 8  # S sharded 8-way -> 8 tokens per device
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(kk, (B, S, H, D))
        for kk in jax.random.split(key, 3)
    )
    ref = dot_product_attention(q, k, v, causal=causal)
    out = _ring_on_mesh(q, k, v, mesh, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_cp_positions(devices):
    mesh = ddp.make_mesh(("seq",))
    fn = jax.shard_map(
        lambda: cp_positions(4, "seq").reshape(1, 4),
        mesh=mesh,
        in_specs=(),
        out_specs=P("seq"),
        check_vma=False,
    )
    got = np.asarray(jax.jit(fn)()).reshape(-1)
    np.testing.assert_array_equal(got, np.arange(32))


def test_cp_lm_forward_matches_single_device(devices):
    """Sequence-sharded forward (ring attention + global RoPE positions)
    must reproduce the unsharded model's logits."""
    mesh = ddp.make_mesh(("seq",))
    cfg = tiny_lm(max_seq_len=64)
    cfg_cp = tiny_lm(max_seq_len=64, cp_axis="seq")
    model = TransformerLM(cfg)
    model_cp = TransformerLM(cfg_cp)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]

    ref = model.apply({"params": params}, toks)

    fn = jax.shard_map(
        lambda p, t: model_cp.apply({"params": p}, t),
        mesh=mesh,
        in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    )
    out = jax.jit(fn)(params, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_cp_train_step_matches_dp(devices):
    """DP×CP (4 data × 2 seq) one train step == single-device step on the
    same global batch: same loss, same updated params."""
    mesh = ddp.make_mesh(("data", "seq"), shape=(4, 2))
    cfg = tiny_lm(max_seq_len=32)
    cfg_cp = tiny_lm(max_seq_len=32, cp_axis="seq")
    model = TransformerLM(cfg)
    model_cp = TransformerLM(cfg_cp)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, size=(8, 33)).astype(np.int32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32)
    )["params"]
    tx = optax.sgd(0.1)

    # Reference: single-device full-batch step.
    def ref_loss(p):
        logits = model.apply({"params": p}, jnp.asarray(tokens[:, :-1]))
        return lm_cross_entropy(logits, jnp.asarray(tokens[:, 1:]))

    loss_ref, grads_ref = jax.value_and_grad(ref_loss)(params)
    updates, _ = tx.update(grads_ref, tx.init(params), params)
    params_ref = optax.apply_updates(params, updates)

    # DP×CP step.
    def loss_fn(p, batch, rng):
        logits = model_cp.apply({"params": p}, batch["inputs"])
        return lm_cross_entropy(logits, batch["targets"]), {}

    state = ddp.TrainState.create(apply_fn=model_cp.apply, params=params, tx=tx)
    state = ddp.broadcast_params(state, mesh)
    step = make_cp_train_step(loss_fn, mesh=mesh)
    batch = shard_lm_batch(tokens, mesh)
    state, metrics = step(state, batch, jax.random.PRNGKey(0))

    assert float(metrics["loss"]) == pytest.approx(float(loss_ref), rel=1e-5)
    for a, b in zip(
        jax.tree.leaves(state.params), jax.tree.leaves(params_ref)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_cp_global_seq_len_guard(devices):
    """The max_seq_len bound must be checked against the GLOBAL length
    under CP: 16 local x 8 shards = 128 > 64 must raise instead of
    letting XLA clamp out-of-range RoPE/pos_embed lookups silently."""
    mesh = ddp.make_mesh(("seq",))
    cfg_cp = tiny_lm(max_seq_len=64, cp_axis="seq")
    model_cp = TransformerLM(cfg_cp)
    toks = jnp.zeros((1, 64), jnp.int32)  # 8 tokens/shard: global 64, fits
    params = TransformerLM(tiny_lm(max_seq_len=64)).init(
        jax.random.PRNGKey(0), toks
    )["params"]

    def apply_sharded(t):
        fn = jax.shard_map(
            lambda p, x: model_cp.apply({"params": p}, x),
            mesh=mesh,
            in_specs=(P(), P(None, "seq")),
            out_specs=P(None, "seq"),
            check_vma=False,
        )
        return jax.jit(fn)(params, t)

    apply_sharded(toks)  # global 64 == max_seq_len: fine
    with pytest.raises(ValueError, match="global seq len 128"):
        apply_sharded(jnp.zeros((1, 128), jnp.int32))  # 16/shard: global 128


def test_cp_accum_matches_plain_cp(devices):
    """CP × gradient accumulation: accumulating 2 microbatches must equal
    the single-step CP run on the same global batch (no_sync boundary
    semantics compose with sequence sharding)."""
    mesh = ddp.make_mesh(("data", "seq"), shape=(4, 2))
    cfg_cp = tiny_lm(max_seq_len=32, cp_axis="seq")
    model_cp = TransformerLM(cfg_cp)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 256, size=(8, 33)).astype(np.int32)
    params = TransformerLM(tiny_lm(max_seq_len=32)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32)
    )["params"]

    def loss_fn(p, batch, rng):
        logits = model_cp.apply({"params": p}, batch["inputs"])
        return lm_cross_entropy(logits, batch["targets"]), {}

    def run(accum):
        state = ddp.TrainState.create(
            apply_fn=model_cp.apply, params=params, tx=optax.sgd(0.1)
        )
        state = ddp.broadcast_params(state, mesh)
        step = make_cp_train_step(
            loss_fn, mesh=mesh, accum_steps=accum, donate=False
        )
        state, metrics = step(
            state, shard_lm_batch(tokens, mesh), jax.random.PRNGKey(0)
        )
        return float(metrics["loss"]), state.params

    loss1, p1 = run(1)
    loss2, p2 = run(2)
    assert loss1 == pytest.approx(loss2, rel=1e-6)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_cp_zero_matches_plain_cp(devices):
    """CP × ZeRO-1: the sharded-optimizer update under sequence sharding
    must reproduce the replicated CP step exactly (adam state included)."""
    mesh = ddp.make_mesh(("data", "seq"), shape=(4, 2))
    cfg_cp = tiny_lm(max_seq_len=32, cp_axis="seq")
    model_cp = TransformerLM(cfg_cp)
    rng = np.random.default_rng(2)
    tokens = [
        rng.integers(0, 256, size=(8, 33)).astype(np.int32) for _ in range(2)
    ]
    params = TransformerLM(tiny_lm(max_seq_len=32)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32)
    )["params"]
    tx = optax.adam(1e-2)

    def loss_fn(p, batch, rng):
        logits = model_cp.apply({"params": p}, batch["inputs"])
        return lm_cross_entropy(logits, batch["targets"]), {}

    # Replicated CP baseline, two steps.
    state = ddp.TrainState.create(
        apply_fn=model_cp.apply, params=params, tx=tx
    )
    state = ddp.broadcast_params(state, mesh)
    step = make_cp_train_step(loss_fn, mesh=mesh, donate=False)
    for t in tokens:
        state, _ = step(state, shard_lm_batch(t, mesh), jax.random.PRNGKey(0))

    # ZeRO-1 CP, same two steps.
    zstate = ddp.zero_state(
        apply_fn=model_cp.apply, params=ddp.broadcast_params(params, mesh),
        tx=tx, mesh=mesh,
    )
    zstep = make_cp_train_step(loss_fn, mesh=mesh, zero=True, donate=False)
    for t in tokens:
        zstate, _ = zstep(
            zstate, shard_lm_batch(t, mesh), jax.random.PRNGKey(0)
        )

    for a, b in zip(
        jax.tree.leaves(state.params), jax.tree.leaves(zstate.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-6
        )


# --- Flash-kernel ring (Pallas per kv-hop) ------------------------------


@pytest.mark.parametrize("n_ring", [2, 4])
def test_flash_ring_matches_xla_ring(n_ring, devices):
    """flash_ring_attention (Pallas kernel per kv-hop, logsumexp merge,
    ring-flash manual backward) == the XLA-einsum ring, forward AND
    gradients, across wrap-masked hops.  Interpret mode: the kernel math
    runs as plain jax on CPU."""
    from jax.sharding import Mesh

    from distributeddataparallel_tpu.parallel.context_parallel import (
        flash_ring_attention,
    )

    mesh = Mesh(np.array(jax.devices()[:n_ring]), ("seq",))
    B, S, H, D = 1, 128 * n_ring, 2, 32
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    weight = 1 + jnp.arange(q.size, dtype=jnp.float32).reshape(q.shape) % 7

    def run(fn):
        def loss(q, k, v, w):
            return jnp.sum(fn(q, k, v) * w)

        sharded = jax.shard_map(
            jax.value_and_grad(loss, argnums=(0, 1, 2)),
            mesh=mesh,
            in_specs=(P(None, "seq"),) * 4,
            out_specs=(P(), (P(None, "seq"),) * 3),
            check_vma=False,
        )
        return jax.jit(sharded)(q, k, v, weight)

    l_x, g_x = run(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq", impl="xla")
    )
    l_f, g_f = run(
        lambda q, k, v: flash_ring_attention(q, k, v, "seq", True)
    )
    assert float(l_f) == pytest.approx(float(l_x), rel=1e-5)
    for name, a, b in zip("qkv", g_x, g_f):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=2e-4, err_msg=name
        )


def test_flash_ring_forward_matches_single_device(devices):
    """The ring forward merges its hops by the lse the flash forward
    returns ((B*H, 8, S), every sublane the row's value): out and the
    merged lse on a 4-device mesh == one device over the whole sequence."""
    from jax.sharding import Mesh

    from distributeddataparallel_tpu.ops.pallas_attention import _flash_fwd_impl
    from distributeddataparallel_tpu.parallel.context_parallel import (
        _flash_ring_fwd_impl,
    )

    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    B, S, H, D = 1, 512, 2, 32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(26), 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H, D), jnp.float32)

    ring = jax.shard_map(
        lambda q, k, v: _flash_ring_fwd_impl(q, k, v, "seq", True),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3,
        out_specs=(P(None, "seq"), P(None, None, "seq")),
        check_vma=False,
    )
    out, lse = jax.jit(ring)(q, k, v)
    one_out, one_lse8 = _flash_fwd_impl(q, k, v, causal=True, interpret=True)
    with jax.default_matmul_precision("highest"):
        ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(one_out), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(one_lse8[:, 0, :].reshape(B, H, S)),
        atol=2e-5,
    )


def test_ring_impl_dispatch(devices):
    """impl='pallas' off-TPU/odd shapes raises; impl='xla' never touches
    the kernel; 'auto' silently stays on the XLA path on CPU."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    q = jnp.zeros((1, 64, 2, 16))  # 32-per-shard: below any flash block

    def call(impl):
        f = jax.shard_map(
            lambda q: ring_attention(q, q, q, axis_name="seq", impl=impl),
            mesh=mesh, in_specs=P(None, "seq"), out_specs=P(None, "seq"),
            check_vma=False,
        )
        return jax.jit(f)(q)

    call("xla")
    call("auto")  # CPU -> supported() False -> XLA fallback
    with pytest.raises(ValueError, match="pallas ring"):
        call("pallas")
