"""Attention op tests: RoPE properties, causal masking, GQA expansion, and
the Pallas flash kernel vs the XLA reference (interpret mode on CPU)."""

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddataparallel_tpu.ops.attention import (
    NEG_INF,
    apply_rope,
    attention,
    causal_mask_bias,
    dot_product_attention,
    repeat_kv,
    rope_frequencies,
)
from distributeddataparallel_tpu.ops import pallas_attention


def _qkv(key, B=2, S=16, H=4, D=8, Hkv=None, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    Hkv = Hkv or H
    q = jax.random.normal(kq, (B, S, H, D), dtype)
    k = jax.random.normal(kk, (B, S, Hkv, D), dtype)
    v = jax.random.normal(kv, (B, S, Hkv, D), dtype)
    return q, k, v


def test_causal_masking_blocks_future():
    """Perturbing a future token must not change earlier outputs."""
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out = dot_product_attention(q, k, v, causal=True)
    k2 = k.at[:, -1].add(100.0)
    v2 = v.at[:, -1].add(100.0)
    out2 = dot_product_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(out[:, :-1], out2[:, :-1], atol=1e-5)
    assert not np.allclose(out[:, -1], out2[:, -1])


def test_attention_matches_manual_softmax():
    q, k, v = _qkv(jax.random.PRNGKey(1), B=1, S=6, H=2, D=4)
    out = dot_product_attention(q, k, v, causal=False)
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(4)
    w = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    expected = np.einsum("bhqk,bkhd->bqhd", w, v)
    np.testing.assert_allclose(out, expected, atol=1e-5)


def test_causal_mask_bias_offsets():
    # Chunk at global q offset 4 attending to kv chunk at offset 0: all visible.
    bias = causal_mask_bias(4, 4, q_offset=4, kv_offset=0)
    assert (bias == 0).all()
    # kv chunk strictly in the future: all masked.
    bias = causal_mask_bias(4, 4, q_offset=0, kv_offset=4)
    assert (bias < -1e29).all()
    # Diagonal chunk: lower triangle visible.
    bias = causal_mask_bias(4, 4, q_offset=0, kv_offset=0)
    expected = np.where(np.tril(np.ones((4, 4))), 0, -1e30).astype(np.float32)
    assert (np.asarray(bias) == expected).all()


def test_rope_preserves_norm_and_relative_phase():
    cos, sin = rope_frequencies(8, 32)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 8))
    rx = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(rx), axis=-1),
        rtol=1e-5,
    )
    # Rotation at position 0 is the identity.
    np.testing.assert_allclose(rx[:, 0], x[:, 0], atol=1e-6)


def test_rope_relative_position_invariance():
    """q·k after RoPE depends only on relative distance."""
    cos, sin = rope_frequencies(8, 64)
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, 8))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 1, 8))

    def dot_at(pq, pk):
        rq = apply_rope(q, cos, sin, positions=jnp.array([pq]))
        rk = apply_rope(k, cos, sin, positions=jnp.array([pk]))
        return float(jnp.sum(rq * rk))

    assert dot_at(5, 3) == pytest.approx(dot_at(12, 10), rel=1e-5)
    assert dot_at(5, 3) != pytest.approx(dot_at(5, 4), rel=1e-3)


def test_rope_explicit_positions_match_offset_slice():
    """RoPE on a shard with explicit positions == slice of full-seq RoPE
    (the property sequence-parallel shards rely on)."""
    cos, sin = rope_frequencies(8, 64)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 2, 8))
    full = apply_rope(x, cos, sin)
    shard = apply_rope(x[:, 8:], cos, sin, positions=jnp.arange(8, 16))
    np.testing.assert_allclose(full[:, 8:], shard, atol=1e-6)


def test_repeat_kv_gqa():
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 4, 2, 8))
    r = repeat_kv(x, 3)
    assert r.shape == (2, 4, 6, 8)
    np.testing.assert_allclose(r[:, :, 0], x[:, :, 0])
    np.testing.assert_allclose(r[:, :, 2], x[:, :, 0])
    np.testing.assert_allclose(r[:, :, 3], x[:, :, 1])


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(7), B=2, S=256, H=2, D=16)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = pallas_attention.flash_attention(q, k, v, causal, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_grads_match_reference():
    q, k, v = _qkv(jax.random.PRNGKey(8), B=1, S=128, H=2, D=16)

    def loss_flash(q, k, v):
        return jnp.sum(pallas_attention.flash_attention(q, k, v, True, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_causal_decode_shapes_see_full_context():
    """Sq != Skv: queries align to the END of the kv sequence, so a 1-token
    query attends over the whole cache (not just position 0)."""
    q, k, v = _qkv(jax.random.PRNGKey(9), B=1, S=8, H=2, D=4)
    full = dot_product_attention(q, k, v, causal=True)
    last = dot_product_attention(q[:, -1:], k, v, causal=True)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, -1:]), atol=1e-5)


def test_flash_causal_decode_shapes():
    q, k, v = _qkv(jax.random.PRNGKey(10), B=1, S=256, H=2, D=16)
    full = pallas_attention.flash_attention(q, k, v, True, True)
    half = pallas_attention.flash_attention(q[:, 128:], k, v, True, True)
    np.testing.assert_allclose(
        np.asarray(half), np.asarray(full[:, 128:]), atol=2e-5
    )


def test_flash_supported_gating():
    q = jnp.zeros((1, 256, 2, 16))
    # CPU backend in tests → native kernel not supported (interpret only).
    assert not pallas_attention.supported(q, q, q)
    assert pallas_attention._pick_block(256) == 256
    assert pallas_attention._pick_block(384) == 128
    assert pallas_attention._pick_block(100) is None


def test_flash_decode_shape_grads_match_reference():
    """Sq != Skv backward: the blockwise kernels' q_offset must align query
    rows to the END of the kv sequence, matching the XLA reference."""
    q, k, v = _qkv(jax.random.PRNGKey(11), B=1, S=256, H=2, D=16)
    qh = q[:, 128:]  # 128 queries against 256 kv positions

    def loss_flash(qh, k, v):
        return jnp.sum(pallas_attention.flash_attention(qh, k, v, True, True) ** 2)

    def loss_ref(qh, k, v):
        return jnp.sum(dot_product_attention(qh, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(qh, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(qh, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_backward_memory_is_linear_in_seq():
    """Long-context guarantee: backward peak temp memory must scale O(S),
    not O(S²) — the blockwise kernels never materialize the (S, S)
    probability matrix (an O(S²) backward at S=4096 needs >500 MB here;
    the blockwise one a few MB).  Read from S=1024 up: at S=512 the
    forward's grid is a single q block, whose loop XLA:CPU unrolls, and
    the constant that leaves behind would sit in the first increment."""

    def temp_bytes(S):
        def loss(q, k, v):
            return jnp.sum(
                pallas_attention.flash_attention(q, k, v, True, True) ** 2
            )

        args = [jax.ShapeDtypeStruct((1, S, 2, 16), jnp.float32)] * 3
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).compile()
        analysis = compiled.memory_analysis()
        if analysis is None:
            pytest.skip("backend exposes no memory analysis")
        return analysis.temp_size_in_bytes

    m1024, m2048, m4096 = temp_bytes(1024), temp_bytes(2048), temp_bytes(4096)
    # Linear growth: each doubling adds ~2x the previous increment.
    # Quadratic growth would multiply increments by ~4 and blow past this.
    assert m4096 - m2048 < 3 * (m2048 - m1024) + (1 << 20), (m1024, m2048, m4096)
    assert m4096 < 8 * m1024, (m1024, m4096)


def test_flash_rejects_causal_sq_gt_skv():
    """Causal Sq > Skv leaves query rows with no visible keys (undefined
    softmax) — must be rejected, not silently garbage."""
    q = jnp.zeros((1, 256, 2, 16))
    kv = jnp.zeros((1, 128, 2, 16))
    assert not pallas_attention.supported(q, kv, kv)
    with pytest.raises(ValueError, match="Sq <= Skv"):
        pallas_attention.flash_attention(q, kv, kv, True, True)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gqa_matches_repeated_reference(causal):
    """GQA-native flash (kv at Hkv < H, indexed per group in the kernel)
    must equal the reference on repeat_kv-expanded kv."""
    q, k, v = _qkv(jax.random.PRNGKey(12), B=2, S=256, H=4, D=16, Hkv=2)
    ref = dot_product_attention(
        q, repeat_kv(k, 2), repeat_kv(v, 2), causal=causal
    )
    out = pallas_attention.flash_attention(q, k, v, causal, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_gqa_grads_match_repeated_reference():
    """GQA backward: dk/dv accumulate over the whole query-head group
    (the dkv grid walks every (q block, group member) pair per kv head)."""
    q, k, v = _qkv(jax.random.PRNGKey(13), B=1, S=128, H=4, D=16, Hkv=2)

    def loss_flash(q, k, v):
        return jnp.sum(pallas_attention.flash_attention(q, k, v, True, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            dot_product_attention(
                q, repeat_kv(k, 2), repeat_kv(v, 2), causal=True
            ) ** 2
        )

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, err_msg=f"d{name}"
        )


# --- the forward's tile plan (PR 26) -------------------------------------


def _ref_out_lse(q, k, v, causal):
    """``dot_product_attention`` at "highest", and the log-sum-exp of the
    same scaled, masked scores as (B, H, Sq)."""
    group = q.shape[2] // k.shape[2]
    k, v = repeat_kv(k, group), repeat_kv(v, group)
    Sq, Skv, D = q.shape[1], k.shape[1], q.shape[3]
    with jax.default_matmul_precision("highest"):
        out = dot_product_attention(q, k, v, causal=causal)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        s = s + causal_mask_bias(Sq, Skv, q_offset=Skv - Sq)
    return out, jax.nn.logsumexp(s, axis=-1)


#: name -> (B, Sq, Skv, H, Hkv, D): the shapes where the plan changes
#: character
_PLAN_SHAPES = {
    "one_tile": (1, 128, 128, 2, 2, 16),
    "3x3_of_128": (1, 384, 384, 2, 2, 16),
    "cell_d64": (1, 1024, 1024, 2, 2, 64),
    "d128": (1, 1024, 1024, 1, 1, 128),
    "decode_block_multiple": (1, 512, 1024, 2, 2, 16),   # q_offset 512
    "decode_inside_a_block": (1, 384, 1024, 2, 2, 16),   # q_offset 640
    "decode_one_q_tile": (1, 128, 512, 2, 2, 16),        # q_offset 384
    "gqa_4_to_1": (2, 256, 256, 4, 1, 16),
    "gqa_8_to_1": (1, 256, 256, 8, 1, 16),
}


def _plan_inputs(name):
    B, Sq, Skv, H, Hkv, D = _PLAN_SHAPES[name]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(26), 3)
    return (
        jax.random.normal(kq, (B, Sq, H, D), jnp.float32),
        jax.random.normal(kk, (B, Skv, Hkv, D), jnp.float32),
        jax.random.normal(kv, (B, Skv, Hkv, D), jnp.float32),
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("name", list(_PLAN_SHAPES))
def test_flash_forward_out_and_lse_match_reference(name, causal):
    q, k, v = _plan_inputs(name)
    B, Sq, H, _ = q.shape
    out, lse = pallas_attention._flash_fwd_impl(
        q, k, v, causal=causal, interpret=True
    )
    ref_out, ref_lse = _ref_out_lse(q, k, v, causal)
    assert lse.shape == (B * H, 8, Sq) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5)
    # every one of the 8 sublanes carries the row's lse
    np.testing.assert_allclose(
        np.asarray(lse.reshape(B, H, 8, Sq)),
        np.broadcast_to(np.asarray(ref_lse)[:, :, None], (B, H, 8, Sq)),
        atol=2e-5,
    )


@contextlib.contextmanager
def _backward_path(path):
    """Steer the backward's plan at a small shape: ``whole`` is what the
    shape gets (operands a row at a time, the walk unrolled), ``loops``
    the same with the walk as loops (what S 2048 and up get), ``grid`` the
    clamped grid (what does not fit the fetch budget gets: one 128-row
    tile of D=16 float32 fits it here).  The jitted launches keep their
    traces by shape, so they are dropped before and after."""
    patch = {"whole": {}, "loops": {"_UNROLL_TILES": 0},
             "grid": {"_KV_FETCH_BYTES": 128 * 16 * 4}}[path]

    def drop():
        pallas_attention._fwd_launch.clear_cache()
        pallas_attention._bwd_launch.clear_cache()

    with contextlib.ExitStack() as stack:
        for name, value in patch.items():
            stack.enter_context(mock.patch.object(pallas_attention, name, value))
        if patch:
            drop()
            stack.callback(drop)
        yield


@pytest.mark.parametrize("path", ["whole", "loops", "grid"])
@pytest.mark.parametrize("name", [
    "3x3_of_128", "decode_inside_a_block",
    # PR 35, where the backward's plan changes: GQA groups of 4 and 8 (the
    # dk/dv kernel walks every member), queries at the end of the keys
    "gqa_4_to_1", "gqa_8_to_1", "decode_block_multiple",
])
def test_flash_grads_match_reference_where_plan_changes(name, path):
    """The backward kernels read the forward's lse, on each of their
    paths."""
    q, k, v = _plan_inputs(name)
    B, Sq, Skv, H, Hkv, D = _PLAN_SHAPES[name]
    with _backward_path(path):
        plan = pallas_attention._bwd_plan(Sq, Skv, D, 4, H // Hkv)
        assert (plan.dq_whole, plan.dkv_whole) == (path != "grid",) * 2
        assert plan.unrolled == (path == "whole")

        def loss_flash(q, k, v):
            return jnp.sum(
                pallas_attention.flash_attention(q, k, v, True, True) ** 2)

        def loss_ref(q, k, v):
            k, v = repeat_kv(k, H // Hkv), repeat_kv(v, H // Hkv)
            return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for n, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, err_msg=f"d{n}"
        )


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_kv_fetched_in_several_blocks(causal, monkeypatch):
    """K/V longer than one fetch: the kv grid axis is back, the inner
    loops clip to the fetched block, and a step above the diagonal names
    the block already there.  Steered here by shrinking the fetch budget
    to one 128-row tile of D=16 float32."""
    monkeypatch.setattr(pallas_attention, "_KV_FETCH_BYTES", 128 * 16 * 4)
    q, k, v = _plan_inputs("3x3_of_128")
    plan = pallas_attention._fwd_plan(384, 384, 16, 4)
    assert plan == (128, 128, 128)
    assert pallas_attention.fwd_tile_counts(384, 384, causal, 0, plan).steps == 9
    # the jitted launch keeps its traces by shape: drop the one-fetch
    # trace of this shape before, and this test's trace after
    pallas_attention._fwd_launch.clear_cache()
    try:
        jaxpr = str(jax.make_jaxpr(
            lambda q, k, v: pallas_attention._flash_fwd_impl(
                q, k, v, causal=causal, interpret=True
            )
        )(q, k, v))
        assert "grid=(2, 3, 3)" in jaxpr, "the kv axis is not in the grid"
        out, lse = pallas_attention._flash_fwd_impl(
            q, k, v, causal=causal, interpret=True
        )
    finally:
        pallas_attention._fwd_launch.clear_cache()
    ref_out, ref_lse = _ref_out_lse(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(lse[:, 0].reshape(ref_lse.shape)), np.asarray(ref_lse),
        atol=2e-5,
    )


@pytest.mark.parametrize(
    "Sq,Skv,D,itemsize,causal,plan,counts",
    [
        # the benchmark cell: 1 / 2 / 1 of 4 tiles, 2 steps a row (the
        # parent launched 4: 512 a call of 128 rows, now 256)
        (1024, 1024, 64, 2, True, (512, 512, 1024), (1, 2, 1, 2)),
        (1024, 1024, 64, 2, False, (512, 512, 1024), (4, 0, 0, 2)),
        # decode: 128 queries at the end of 1024 keys (q_offset 896)
        (128, 1024, 64, 2, True, (128, 512, 1024), (1, 1, 0, 1)),
        (384, 384, 64, 2, True, (128, 128, 384), (3, 3, 3, 3)),
        # K/V past the fetch budget (1 MiB each): two fetches a q block
        (2048, 8192, 128, 2, True, (512, 512, 4096), (54, 4, 6, 8)),
    ],
)
def test_fwd_tile_plan_counts(Sq, Skv, D, itemsize, causal, plan, counts):
    got = pallas_attention._fwd_plan(Sq, Skv, D, itemsize)
    assert got == plan
    assert pallas_attention.fwd_tile_counts(
        Sq, Skv, causal, Skv - Sq, got
    ) == counts


def test_fwd_tile_counts_cover_the_grid_and_agree_with_predicates():
    """unmasked + masked + skipped is every (q block, kv block) pair, and
    the closed-form span is the two predicates counted tile by tile."""
    pa = pallas_attention
    for Sq, Skv in [(128, 128), (384, 384), (1024, 1024), (128, 1024),
                    (384, 1024), (256, 768), (512, 2048), (1024, 1536)]:
        for causal in (True, False):
            plan = pa._fwd_plan(Sq, Skv, 64, 2)
            geom = dict(causal=causal, block_q=plan.block_q,
                        block_k=plan.block_k, q_offset=Skv - Sq)
            n_q, n_k = Sq // plan.block_q, Skv // plan.block_k
            live = [[bool(pa._block_live(i, j, **geom)) for j in range(n_k)]
                    for i in range(n_q)]
            free = [[bool(pa._block_unmasked(i, j, **geom)) for j in range(n_k)]
                    for i in range(n_q)]
            for i in range(n_q):
                full, end = pa._kv_span(i, n_k=n_k, **geom)
                assert free[i] == [j < full for j in range(n_k)]
                assert live[i] == [j < end for j in range(n_k)]
                assert end >= 1  # kv tile 0 opens every q block
            c = pa.fwd_tile_counts(Sq, Skv, causal, Skv - Sq, plan)
            assert c.unmasked == sum(map(sum, free))
            assert c.unmasked + c.masked == sum(map(sum, live))
            assert c.unmasked + c.masked + c.skipped == n_q * n_k


# --- a window on the left (PR 33) ----------------------------------------
# key j is visible to query i iff j <= i and i - j < window


def _visible(Sq, Skv, window):
    i = (Skv - Sq) + np.arange(Sq)[:, None]
    j = np.arange(Skv)[None, :]
    return (j <= i) & (i - j < window)


@pytest.mark.parametrize("window", [1, 5, 16, 100])
def test_window_on_the_xla_path_is_the_mask_written_from_positions(window):
    from distributeddataparallel_tpu.ops.attention import attention

    q, k, v = _qkv(jax.random.PRNGKey(33))
    got = attention(q, k, v, impl="xla", window=window)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = np.where(_visible(16, 16, window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(got, want, atol=2e-6)
    if window >= 16:  # every earlier key: causal, to the bit
        np.testing.assert_array_equal(got, attention(q, k, v, impl="xla"))
    with pytest.raises(ValueError, match="causal"):
        attention(q, k, v, impl="xla", causal=False, window=window)


@pytest.mark.parametrize("shape,window", [
    ((1, 384, 384, 4, 2, 16), 1),
    ((1, 384, 384, 4, 2, 16), 100),      # inside a tile of 128
    ((1, 384, 384, 4, 2, 16), 128),      # a tile
    ((1, 384, 384, 4, 2, 16), 200),      # a tile and a part
    ((1, 384, 384, 4, 2, 16), 384),      # the sequence: causal
    ((1, 384, 384, 4, 2, 16), 1000),     # past it: causal
    ((1, 1024, 1024, 2, 1, 16), 700),    # tiles of 512, fetches of one tile
    ((1, 2048, 2048, 1, 1, 16), 1024),   # fetches of 512 of 2048: 3 a q block
    ((1, 256, 768, 2, 2, 16), 300),      # queries at the end of the keys
    # PR 35: the dk/dv kernel's walk of (q block, member) pairs under a window
    ((1, 384, 384, 4, 1, 16), 200),      # a group of 4
    ((1, 384, 384, 8, 1, 16), 130),      # a group of 8
    ((1, 256, 768, 8, 2, 16), 100),      # groups of 4, columns no query sees
], ids=lambda x: str(x).replace(" ", ""))
def test_flash_kernels_with_a_window_match_the_xla_path(shape, window):
    """All three kernels through the interpreter, values and gradients,
    against the XLA path's bias; a window that reaches the first key is
    causal attention."""
    from distributeddataparallel_tpu.ops.attention import attention

    B, Sq, Skv, H, Hkv, D = shape
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(33), 3)
    args = (jax.random.normal(kq, (B, Sq, H, D)),
            jax.random.normal(kk, (B, Skv, Hkv, D)),
            jax.random.normal(kv, (B, Skv, Hkv, D)))

    def both(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))), argnums=(0, 1, 2)
        )(*args)

    flash = lambda w: lambda q, k, v: pallas_attention.flash_attention(  # noqa: E731
        q, k, v, True, True, None, w)
    got = both(flash(window))
    with jax.default_matmul_precision("highest"):
        want = both(lambda q, k, v: attention(
            q, k, v, impl="xla", window=window))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())))
    if window >= Skv:
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(both(flash(None)))):
            np.testing.assert_allclose(a, b, atol=2e-6)
    with pytest.raises(ValueError, match="causal"):
        pallas_attention.flash_attention(*args, False, True, None, window)


def test_fwd_tile_counts_with_a_window():
    """The trinity cell's sliding layers: S 8192, tiles of 512, window
    2048.  A q block from the fifth on has 5 live tiles of 16 — one the
    window's edge crosses, three whole, the diagonal's — and the row's
    three fetches of 1024 keys cover them."""
    pa = pallas_attention
    plan = pa._fwd_plan(8192, 8192, 128, 2, 2048)
    assert plan == (512, 512, 1024)
    assert pa._fwd_plan(8192, 8192, 128, 2) == (512, 512, 4096)
    geom = dict(block_q=512, block_k=512, q_offset=0)
    for i in range(16):
        start, lo = pa._kv_window(i, window=2048, **geom)
        full, live = pa._kv_span(i, causal=True, n_k=16, **geom)
        assert (start, lo, full, live) == (max(i - 4, 0), max(i - 3, 0), i, i + 1)
    assert pa.fwd_tile_counts(8192, 8192, True, 0, plan, 2048) == (
        6 + 12 * 3, 4 + 12 * 2, 256 - 70, 16 * 3)
    assert pa.fwd_tile_counts(8192, 8192, True, 0, pa._fwd_plan(
        8192, 8192, 128, 2)) == (120, 16, 120, 32)
    # closed forms against the predicates, tile by tile, by row and column
    for Sq, Skv, window in [(384, 384, 1), (384, 384, 100), (384, 384, 128),
                            (384, 384, 200), (1024, 1024, 700), (256, 768, 300),
                            (2048, 2048, 512), (1024, 1024, 5000)]:
        plan = pa._fwd_plan(Sq, Skv, 64, 2, window)
        geom = dict(block_q=plan.block_q, block_k=plan.block_k,
                    q_offset=Skv - Sq)
        n_q, n_k = Sq // plan.block_q, Skv // plan.block_k
        live = np.array([[bool(pa._block_live(
            i, j, causal=True, window=window, **geom)) for j in range(n_k)]
            for i in range(n_q)])
        free = np.array([[bool(pa._block_unmasked(
            i, j, causal=True, window=window, **geom)) for j in range(n_k)]
            for i in range(n_q)])
        seen = _visible(Sq, Skv, window)
        for i in range(n_q):
            start, lo = pa._kv_window(i, window=window, **geom)
            full, end = pa._kv_span(i, causal=True, n_k=n_k, **geom)
            assert list(live[i]) == [start <= j < end for j in range(n_k)]
            assert list(free[i]) == [lo <= j < full for j in range(n_k)]
            for j in range(n_k):
                tile = seen[i * plan.block_q:(i + 1) * plan.block_q,
                            j * plan.block_k:(j + 1) * plan.block_k]
                assert live[i, j] == tile.any() and free[i, j] == tile.all()
        for j in range(n_k):
            first, last = pa._q_span(j, window=window, n_q=n_q, **geom)
            assert [i for i in range(n_q) if live[i, j]] == list(
                range(first, last + 1)) or not live[:, j].any()
        c = pa.fwd_tile_counts(Sq, Skv, True, Skv - Sq, plan, window)
        assert (c.unmasked, c.unmasked + c.masked) == (free.sum(), live.sum())
        assert c.unmasked + c.masked + c.skipped == n_q * n_k


# --- the backward's plan and its counter (PR 35) ---------------------------


@pytest.mark.parametrize(
    "Sq,Skv,D,group,window,plan,dq,dkv",
    [
        # the GPT-2 cells: a row arrives whole, 1 / 2 / 1 of 4 tiles, one
        # step a row (the parent launched 4 and 4 a row, one of each dead)
        (1024, 1024, 64, 1, None, (512, 512, True, True, True),
         (1, 2, 1, 1), (1, 2, 1, 1)),
        # the granite cell: dq whole (K, V, q, do 512 KiB each), dk/dv not
        # (a group of 4: 2 MiB of q) — its grid walks 8 blocks x 8 x 4
        (4096, 4096, 64, 4, None, (512, 512, True, False, False),
         (28, 8, 28, 1), (112, 32, 112, 8 * 8 * 4)),
        # the trinity cell's full layer: K / V of 2 MiB do not fit
        (8192, 8192, 128, 8, None, (512, 512, False, False, False),
         (120, 16, 120, 16 * 16), (960, 128, 960, 16 * 16 * 8)),
        # its sliding layers: 42 / 28 / 186 of 256, five steps a q block
        # or a kv block and member
        (8192, 8192, 128, 8, 2048, (512, 512, False, False, False),
         (42, 28, 186, 16 * 5), (336, 224, 1488, 16 * 5 * 8)),
        # decode: 128 queries at the end of 1024 keys, groups of 4
        (128, 1024, 128, 4, None, (128, 512, True, True, True),
         (1, 1, 0, 1), (4, 4, 0, 1)),
        # few rows, many tiles: whole, walked by loops
        (2048, 2048, 64, 4, None, (512, 512, True, True, False),
         (6, 4, 6, 1), (24, 16, 24, 1)),
    ],
)
def test_bwd_tile_plan_counts(Sq, Skv, D, group, window, plan, dq, dkv):
    pa = pallas_attention
    got = pa._bwd_plan(Sq, Skv, D, 2, group, window)
    assert got == plan
    assert pa.bwd_tile_counts(
        Sq, Skv, True, Skv - Sq, got, window, group) == (dq, dkv)


def test_bwd_tile_counts_cover_the_grid_and_agree_with_predicates():
    """unmasked + masked + skipped is every (q block, kv block) pair for
    both kernels, the tiles are the forward's, and the column walk's
    closed form (``_q_walk``) is the two predicates read by column."""
    pa = pallas_attention
    for Sq, Skv, window in [
        (128, 128, None), (384, 384, None), (1024, 1024, None),
        (128, 1024, None), (384, 1024, None), (256, 768, None),
        (512, 2048, None), (1024, 1536, None), (384, 384, 100),
        (384, 384, 200), (1024, 1024, 700), (256, 768, 300),
        (2048, 2048, 512), (256, 768, 100),
    ]:
        for causal, group in [(True, 1), (True, 4), (False, 2)]:
            if window is not None and not causal:
                continue
            plan = pa._bwd_plan(Sq, Skv, 64, 2, group, window)
            geom = dict(block_q=plan.block_q, block_k=plan.block_k,
                        q_offset=Skv - Sq)
            n_q, n_k = Sq // plan.block_q, Skv // plan.block_k
            live = np.array([[bool(pa._block_live(
                i, j, causal=causal, window=window, **geom))
                for j in range(n_k)] for i in range(n_q)])
            free = np.array([[bool(pa._block_unmasked(
                i, j, causal=causal, window=window, **geom))
                for j in range(n_k)] for i in range(n_q)])
            for j in range(n_k):
                first, lo, end = pa._q_walk(
                    j, causal=causal, window=window, n_q=n_q, **geom)
                assert [i for i in range(n_q) if live[i, j]] == list(
                    range(first, end))
                if window is None:  # below ``lo`` the diagonal crosses
                    assert list(free[:, j]) == [i >= lo for i in range(n_q)]
            c = pa.bwd_tile_counts(Sq, Skv, causal, Skv - Sq, plan, window, group)
            fwd = pa.fwd_tile_counts(
                Sq, Skv, causal, Skv - Sq,
                pa._fwd_plan(Sq, Skv, 64, 2, window), window)
            assert c.dq[:3] == fwd[:3]
            assert c.dkv[:3] == tuple(group * t for t in fwd[:3])
            assert (c.dq.unmasked, c.dq.unmasked + c.dq.masked) == (
                free.sum(), live.sum())
            assert sum(c.dq[:3]) == n_q * n_k
            if plan.dq_whole:
                assert (c.dq.steps, c.dkv.steps) == (1, 1)
            else:  # the grid has room for the widest row and column
                assert c.dq.steps == n_q * live.sum(1).max()
                assert c.dkv.steps == n_k * max(live.sum(0).max(), 1) * group


# --- the row statistic handed out, and the staircase (PR 36) --------------

@pytest.mark.parametrize("B,Sq,Skv,H,Hkv,D,causal,window", [
    (1, 256, 256, 2, 2, 16, True, None),     # a row whole, unrolled
    (1, 128, 256, 4, 2, 16, True, None),     # decode alignment, GQA
    (1, 256, 256, 2, 1, 16, True, 128),      # a window: the clamped grid
], ids=["square", "gqa-decode", "window"])
def test_flash_lse_and_its_cotangent_match_the_xla_path(
        B, Sq, Skv, H, Hkv, D, causal, window):
    """``return_lse``: ``(out, lse)`` and the gradients of a loss that
    reads both, against ``jax.grad`` of the ``xla`` attention (whose lse is
    a logsumexp JAX differentiates by itself)."""
    ks = jax.random.split(jax.random.PRNGKey(36), 5)
    q = jax.random.normal(ks[0], (B, Sq, H, D))
    k, v = (jax.random.normal(ks[1 + i], (B, Skv, Hkv, D)) for i in range(2))
    do = jax.random.normal(ks[3], (B, Sq, H, D))
    dl = jax.random.normal(ks[4], (B, Sq, H))

    def through(f):
        def loss(q, k, v):
            out, lse = f(q, k, v)
            return jnp.sum(out * do) + jnp.sum(lse * dl), (out, lse)
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)

    flash = through(lambda q, k, v: pallas_attention.flash_attention(
        q, k, v, causal, True, None, window, return_lse=True))
    rep = H // Hkv
    plain = through(lambda q, k, v: dot_product_attention(
        q, repeat_kv(k, rep), repeat_kv(v, rep), causal=causal,
        window=window, return_lse=True))
    with jax.default_matmul_precision("highest"):
        got, (out, lse) = flash(q, k, v)
        want, (ref_out, ref_lse) = plain(q, k, v)
    assert lse.shape == (B, Sq, H) and lse.dtype == jnp.float32
    np.testing.assert_allclose(out, ref_out, atol=2e-5)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)
    # and through the dispatcher, on the path a CPU takes
    out2, lse2 = attention(q, k, v, causal=causal, impl="xla", window=window,
                           return_lse=True)
    np.testing.assert_allclose(out2, ref_out, atol=1e-6)
    np.testing.assert_allclose(lse2, ref_lse, atol=1e-6)


def _equations(jaxpr, acc):
    for eqn in jaxpr.eqns:
        acc[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _equations(inner, acc)
    return acc


@pytest.mark.parametrize("B,S,Skv,H,Hkv,D,causal,window,total,subs,exps", [
    (1, 256, 256, 2, 2, 16, True, None, 318, 17, 8),
    (1, 512, 512, 2, 1, 16, True, 128, 677, 57, 12),
    (1, 128, 256, 2, 2, 16, False, None, 188, 9, 5),
], ids=["square", "window", "non-causal"])
def test_flash_without_lse_traces_the_parents_program(
        B, S, Skv, H, Hkv, D, causal, window, total, subs, exps):
    """Without ``return_lse`` and without a staircase, forward and backward
    trace to what PR 36's parent traced: the counts of all equations, of
    subtractions (``delta - d lse`` would be one more) and of exponentials,
    taken on the parent commit (its whole jaxpr text was compared then,
    equal to the last character outside source lines)."""
    import collections

    q = jnp.zeros((B, S, H, D), jnp.bfloat16)
    k = jnp.zeros((B, Skv, Hkv, D), jnp.bfloat16)

    def loss(q, k, v):
        return pallas_attention.flash_attention(
            q, k, v, causal, True, None, window).astype(jnp.float32).sum()

    acc = _equations(jax.make_jaxpr(
        jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, k).jaxpr,
        collections.Counter())
    assert (sum(acc.values()), acc["sub"], acc["exp"]) == (total, subs, exps)
    assert acc["pallas_call"] == 3
    forward = str(jax.make_jaxpr(loss)(q, k, k))
    assert "name=flash_attention" in forward  # the call's name, as ever


@pytest.mark.parametrize("Sq,Skv,stair,path", [
    (768, 384, (256, 128), "grid"),
    (512, 256, (256, 128), "whole"),
    (512, 256, (256, 128), "loops"),
], ids=["clamped-grid", "row-whole-unrolled", "row-whole-loops"])
def test_flash_kernels_under_a_staircase_match_the_mask(
        Sq, Skv, stair, path):
    """The staircase rule — query i sees keys ``[0, (i // q_step + 1) *
    k_step)`` — through all three kernels on each of the backward plan's
    paths, against the mask written from positions; and Sq > Skv, which
    the causal kernels refuse, is this rule's usual shape."""
    ks = jax.random.split(jax.random.PRNGKey(37), 5)
    q = jax.random.normal(ks[0], (2, Sq, 2, 16))
    k, v = (jax.random.normal(ks[1 + i], (2, Skv, 2, 16)) for i in range(2))
    do = jax.random.normal(ks[3], (2, Sq, 2, 16))
    dl = jax.random.normal(ks[4], (2, Sq, 2))
    seen = (jnp.arange(Skv)[None, :]
            < (jnp.arange(Sq)[:, None] // stair[0] + 1) * stair[1])
    bias = jnp.where(seen, 0.0, NEG_INF)[None, None]

    def through(f):
        def loss(q, k, v):
            out, lse = f(q, k, v)
            return jnp.sum(out * do) + jnp.sum(lse * dl), (out, lse)
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)

    plan = pallas_attention._bwd_plan(Sq, Skv, 16, 4, 1, None, stair)
    with jax.default_matmul_precision("highest"), _backward_path(path):
        assert (plan.dq_whole, plan.unrolled) == (True, True)  # unsteered
        got, (out, lse) = through(
            lambda q, k, v: pallas_attention.flash_attention(
                q, k, v, False, True, None, None, return_lse=True,
                stair=stair))(q, k, v)
        want, (ref_out, ref_lse) = through(
            lambda q, k, v: dot_product_attention(
                q, k, v, causal=False, bias=bias, return_lse=True))(q, k, v)
    np.testing.assert_allclose(out, ref_out, atol=2e-5)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)
    with pytest.raises(ValueError, match="rule of its own"):
        pallas_attention.flash_attention(q, k, v, True, True, stair=stair)


# --- the forward kernel through the chip's own compiler (no chip) --------
# Interpret mode cannot see what Mosaic refuses (a misaligned slice, too
# much VMEM, a layout it cannot make); an AOT compile for a described v5e
# can, at the real widths, in about a second a shape.


@pytest.fixture(scope="module")
def v5e_chip():
    try:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        return SingleDeviceSharding(topo.devices[0])
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e!r}")


@pytest.fixture()
def no_compile_cache():
    """An entry written without a chip cannot be read back: keep these
    compiles out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "B,Sq,Skv,H,Hkv,D,dtype,causal",
    [
        (8, 1024, 1024, 16, 16, 64, jnp.bfloat16, True),    # the cell
        (2, 1024, 1024, 12, 12, 64, jnp.float32, True),     # chip_smoke
        (2, 1024, 1024, 8, 8, 64, jnp.bfloat16, False),     # a ring hop
        (2, 384, 384, 4, 4, 64, jnp.bfloat16, True),        # 128-tiles
        (2, 128, 1024, 8, 2, 128, jnp.bfloat16, True),      # GQA decode
        (1, 2048, 8192, 4, 4, 128, jnp.bfloat16, True),     # two fetches
        (1, 1024, 1024, 4, 4, 200, jnp.bfloat16, True),     # D off the lanes
        (1, 1024, 1024, 4, 4, 256, jnp.bfloat16, True),     # D of two vregs
    ],
)
def test_flash_forward_compiles_for_v5e(
    v5e_chip, no_compile_cache, B, Sq, Skv, H, Hkv, D, dtype, causal
):
    q = jax.ShapeDtypeStruct((B, Sq, H, D), dtype, sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((B, Skv, Hkv, D), dtype, sharding=v5e_chip)
    text = jax.jit(
        lambda q, k, v: pallas_attention._flash_fwd_impl(
            q, k, v, causal=causal, interpret=False
        )
    ).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "flash_fwd" in text


@pytest.mark.parametrize(
    "B,Sq,Skv,H,Hkv,D,dtype,causal,scale,window",
    [
        # the four shapes the cells reach
        (8, 1024, 1024, 16, 16, 64, jnp.bfloat16, True, None, None),
        (2, 4096, 4096, 32, 8, 64, jnp.bfloat16, True, 1 / 64, None),
        (1, 8192, 8192, 32, 4, 128, jnp.bfloat16, True, None, 2048),
        (1, 8192, 8192, 32, 4, 128, jnp.bfloat16, True, None, None),
        # and the plan's other corners
        (2, 1024, 1024, 12, 12, 64, jnp.float32, True, None, None),   # chip_smoke
        (2, 1024, 1024, 8, 8, 64, jnp.bfloat16, False, None, None),   # a ring hop
        (2, 128, 1024, 8, 2, 128, jnp.bfloat16, True, None, None),    # GQA decode
        (1, 8192, 8192, 2, 2, 64, jnp.bfloat16, True, None, None),    # 1 MiB operands whole, loops
        (1, 2048, 2048, 2, 2, 128, jnp.float32, True, None, None),    # the same in f32, unrolled
        (1, 1024, 1024, 4, 4, 200, jnp.bfloat16, True, None, None),   # D off the lanes
    ],
)
def test_flash_backward_compiles_for_v5e(
    v5e_chip, no_compile_cache, B, Sq, Skv, H, Hkv, D, dtype, causal, scale,
    window,
):
    """Both backward kernels through the chip's own compiler, on each of
    the plan's paths (a row whole and unrolled, whole and looped, the
    clamped grid with and without a window)."""
    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)

    q, kv = sds((B, Sq, H, D)), sds((B, Skv, Hkv, D))
    text = jax.jit(
        lambda q, k, v, out, lse, do: pallas_attention._bwd(
            causal, False, scale, window, (q, k, v, out, lse), do)
    ).lower(q, kv, kv, q, sds((B * H, 8, Sq), jnp.float32), q
            ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text


@pytest.mark.parametrize(
    "b,s,h,p,g,n,chunk,dtype",
    [
        (2, 4096, 64, 64, 1, 128, 256, jnp.bfloat16),   # the granite cell
        (1, 512, 16, 64, 2, 128, 256, jnp.float32),     # two groups, f32
        (1, 256, 8, 128, 1, 256, 128, jnp.bfloat16),    # one sub-tile a chunk
    ],
)
def test_ssd_kernels_compile_for_v5e(
    v5e_chip, no_compile_cache, b, s, h, p, g, n, chunk, dtype
):
    """The state-space scan's forward and backward kernels (``ops/ssd.py``,
    PR 30), kept in this file because only the process that described the
    topology may compile for it.  Mosaic refused one version of the
    backward that every interpret-mode test had passed (a lane slice of
    a row it held replicated)."""
    from distributeddataparallel_tpu.ops import ssd

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)

    f32 = jnp.float32
    args = (sds((b, s, h, p), dtype), sds((b, s, h), f32), sds((h,), f32),
            sds((b, s, g, n), dtype), sds((b, s, g, n), dtype), sds((h,), f32))
    assert ssd._plan(chunk, h // g, p).heads in (8, 16)

    def loss(*a):
        # _interpret=False and a CPU backend: what `supported` would allow
        # on the chip is asked for by hand
        with mock.patch.object(ssd, "supported", lambda *_: True):
            return ssd.ssd_chunked(*a, chunk=chunk).astype(f32).sum()

    text = jax.jit(
        jax.value_and_grad(loss, argnums=range(6))
    ).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "ssd_fwd" in text and "ssd_bwd" in text


@pytest.mark.parametrize(
    "s,width,start,dtype",
    [
        (4096, 8512, 4096, jnp.bfloat16),   # the granite cell
        (2048, 4352, 0, jnp.float32),       # xbc an array of its own, f32
        (8192, 8512, 4096, jnp.bfloat16),   # 64 rows a block
    ],
)
def test_conv_kernels_compile_for_v5e(
    v5e_chip, no_compile_cache, s, width, start, dtype
):
    """The causal convolution's forward and backward kernels
    (``ops/causal_conv.py``, PR 32) at the cell's channels: lane rotates of
    a chunk with its halo, dynamic row groups, narrow stores of the taps'
    gradients — what the interpreter takes and Mosaic might not."""
    from distributeddataparallel_tpu.ops import causal_conv

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)

    f32 = jnp.float32
    splits = (4096, 128, 128)
    args = (sds((2, s, width), dtype), sds((4, 4352), f32), sds((4352,), f32))

    def loss(*a):
        # a CPU backend: what `supported` would allow on the chip is asked
        # for by hand, after it has said so for the described chip
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            assert causal_conv.supported(*a[:2], splits, start)
            parts = causal_conv.causal_conv_silu(*a, splits, start=start)
        return sum(p.astype(f32).sum() for p in parts)

    text = jax.jit(
        jax.value_and_grad(loss, argnums=range(3))
    ).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "conv_fwd" in text and "conv_bwd" in text


@pytest.mark.parametrize("m,k,n", [
    (16384 + 4096, 2048, 1024),   # the trinity cell's usual buffer: up, gate
    (16384 + 4096, 1024, 2048),   # and down
    (65536 + 4096, 2048, 1024),   # its worst case
])
def test_grouped_kernels_compile_for_v5e(v5e_chip, no_compile_cache, m, k, n):
    """The expert layer's grouped kernels (``ops/grouped_matmul.py``,
    PR 33) at the trinity cell's widths, 16 experts, bf16: ``moe_gmm`` plain
    and transposed and ``moe_tgmm``, kept in this file because only the
    process that described the topology may compile for it."""
    from distributeddataparallel_tpu.ops import grouped_matmul as gm

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=v5e_chip)
    args = (sds((m, k), jnp.bfloat16), sds((16, k, n), jnp.bfloat16),
            sds((m, n), jnp.bfloat16), sds((m // gm.ROW_TILE,), jnp.int32),
            sds((), jnp.int32))

    def both(rows, w, dy, tile_group, live):
        out, vjp = jax.vjp(
            lambda r, w: gm._gmm(r, w, tile_group, live, False), rows, w)
        return out, vjp(dy)

    text = jax.jit(both).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "moe_gmm" in text and "moe_tgmm" in text


def test_eva_attention_compiles_for_v5e(v5e_chip, no_compile_cache):
    """``ops.eva.eva_attention`` forward and backward at the cell's shape,
    (1, 16384, 32, 128) bf16 in windows of 2,048 and chunks of 16, through
    the chip's own compiler: three kernels on eight folded rows of 2,048
    under ``eva_local`` (MHA at D 128, a row whole and unrolled) and three
    under ``eva_remote`` on the staircase (14,336 queries, 896 summaries,
    (512, 128) tiles), each under its scope and phase."""
    from distributeddataparallel_tpu.ops import eva

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)

    def both(q, k, v, phi, mu, do):
        out, vjp = jax.vjp(lambda *a: eva.eva_attention(
            *a, window=2048, chunk=16, impl="pallas"), q, k, v, phi, mu)
        return out, vjp(do)

    x, p = sds((1, 16384, 32, 128)), sds((32, 128), jnp.float32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(both).lower(x, x, x, p, p, x).compile().as_text()
    assert text.count("tpu_custom_call") == 6
    calls = [scope for op, _, scope in _instructions(text).values()
             if op == "custom-call" and "/pallas_call" in scope]
    for part in ("eva_local", "eva_remote"):
        assert sorted(
            (s.split("/")[-2], "transpose(" in s) for s in calls if part in s
        ) == [("flash_bwd_dkv", True), ("flash_bwd_dq", True),
              ("flash_fwd", False)], (part, calls)


def _instructions(text):
    """``{name: (op, [operand names], op_name)}`` of a compiled module's
    text."""
    import re

    out = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = .*? ([\w\-]+)\(([^)]*)\)", line)
        if m:
            scope = re.search(r'op_name="([^"]*)"', line)
            out[m.group(1)] = (
                m.group(2), re.findall(r"%([\w.\-]+)", m.group(3)),
                scope.group(1) if scope else "",
            )
    return out


def test_the_granite_step_holds_the_conv_kernels_and_no_copy_round_them(
    v5e_chip, no_compile_cache
):
    """The granite cell's whole step, compiled for the described chip (what
    ``benchmarks/aot_fit_hybrid.py`` prints, ~1 min): nine ``mamba`` layers
    launch ``conv_fwd`` twice (remat) and ``conv_bwd`` once beside the 31
    custom calls there were; ``conv_fwd`` reads ``in_proj``'s own result
    and the scan's kernels read ``conv_fwd``'s, through bitcasts alone — XLA
    copies a *slice* for a custom call, which is why the op takes the
    projection whole and hands back three arrays."""
    import types

    from benchmarks import aot_fit, harness

    cell = harness.load_cell("granite-4.0-h-micro.train-s4096")
    seen = {}
    topo = types.SimpleNamespace(devices=sorted(
        v5e_chip.device_set, key=lambda d: d.id))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(
                aot_fit, "describe",
                lambda name, compiled, hbm: seen.update(
                    text=compiled.as_text())):
        aot_fit.fit_train(
            {"cell": cell, "config": cell["config"],
             "traffic": cell["traffic"], "hbm": 16e9}, topo,
        )
    ins = _instructions(seen["text"])
    # PR 33 put a window through the three flash kernels: with none given the
    # step is the parent's, instruction for instruction (24,849 of them);
    # PR 35 rewrote the two backward kernels and left it so (with a
    # ``CostEstimate`` on them XLA prefetches one more operand: 24,852)
    assert sum(" = " in line for line in seen["text"].splitlines()) == 24_849

    def calls(kernel):
        return [n for n, (op, _, scope) in ins.items()
                if op == "custom-call" and f"/{kernel}/pallas_call" in scope]

    def source(name):  # through what moves no byte
        while ins[name][0] in ("bitcast", "get-tuple-element"):
            name = ins[name][1][0]
        return name

    conv_fwd, conv_bwd = calls("conv_fwd"), calls("conv_bwd")
    assert seen["text"].count("tpu_custom_call") == 31 + 27
    assert len(conv_fwd) == 18 and len(conv_bwd) == 9
    for call in conv_fwd + conv_bwd:
        op, _, scope = ins[source(ins[call][1][0])]
        assert op == "fusion" and "/ssm_in_proj/" in scope, (call, op, scope)
    scans = calls("ssd_fwd") + calls("ssd_bwd")
    assert len(scans) == 27
    for call in scans:
        x = source(ins[call][1][1])  # operands: D, x, ...
        assert x in conv_fwd, (call, x, ins[x])


def _step_text(v5e_chip, cell, fit, **overrides):
    """The optimized HLO of ``cell``'s train step at its published widths,
    compiled for the described chip, with ``overrides`` of depth."""
    import types

    config = dict(cell["config"], overrides=dict(
        cell["config"]["overrides"], **overrides))
    topo = types.SimpleNamespace(devices=sorted(
        v5e_chip.device_set, key=lambda d: d.id))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return fit({"cell": cell, "config": config,
                    "traffic": cell["traffic"], "hbm": 16e9}, topo)


def test_a_gpt2_step_has_not_noticed_the_window(v5e_chip, no_compile_cache):
    """Two layers of cell 1's step at its widths: 4,863 instructions and 6
    custom calls, as the parent of PR 33 compiles it (the whole 24-layer step
    was compared text against text when the window went in: equal but for the
    source lines in the kernels' bodies) and as PR 35 left it, whose backward
    kernels changed inside the custom calls alone (with a ``CostEstimate`` on
    them XLA slices one more prefetch to VMEM, 4,872, and re-tiles the matmul
    that reads it)."""
    from benchmarks import aot_fit, harness

    seen = {}
    with mock.patch.object(
            aot_fit, "describe",
            lambda name, compiled, hbm: seen.update(text=compiled.as_text())):
        _step_text(v5e_chip, harness.load_cell("gpt2-medium.train-b8x1024"),
                   aot_fit.fit_train, num_layers=2)
    assert sum(" = " in line for line in seen["text"].splitlines()) == 4_863
    assert seen["text"].count("tpu_custom_call") == 6


def test_the_trinity_step_holds_its_kernels_and_mosaic_takes_them(
    v5e_chip, no_compile_cache
):
    """The trinity cell's step at its published widths and its own length,
    cut to two layers (the dense sliding layer and a full layer with
    experts; ``benchmarks/aot_fit_moe.py`` compiles all five, ~3 min): the
    chip's own compiler takes the windowed flash kernels and the grouped
    kernels, each under the scope its reader looks for.  A layer launches
    ``flash_fwd`` twice (remat) and the two backward kernels once.  The
    expert layer's usual buffer launches ``moe_gmm`` 6 times forward (3
    products, remat) and 3 backward, and ``moe_tgmm`` 3 times; its
    worst-case buffer, which keeps nothing for its backward but its
    arguments, 3 more forward."""
    from benchmarks import aot_fit_moe, harness, moe_scopes

    text = _step_text(
        v5e_chip, harness.load_cell("trinity-mini.train-s8192"),
        aot_fit_moe.fit, num_layers=2,
        layer_types=["sliding_attention", "full_attention"],
    ).as_text()
    ins = _instructions(text)

    def calls(kernel):
        return [scope for op, _, scope in ins.values()
                if op == "custom-call" and f"/{kernel}/pallas_call" in scope]

    assert text.count("tpu_custom_call") == 2 * 4 + 12 + 15
    assert len(calls("flash_fwd")) == 4
    assert len(calls("flash_bwd_dq")) == len(calls("flash_bwd_dkv")) == 2
    gmm, tgmm = calls("moe_gmm"), calls("moe_tgmm")
    assert (len(gmm), len(tgmm)) == (9 + 12, 3 + 3)
    for scope in gmm + tgmm:
        assert moe_scopes.part_of(scope) == "moe_experts", scope
    # remat's second forward runs in the backward pass, beside d rows
    assert sum("transpose(" in s for s in gmm) == 6 + 9
    assert all("transpose(" in s for s in tgmm)
    assert "ragged-dot" not in text


def test_the_evabyte_step_holds_its_kernels_and_mosaic_takes_them(
    v5e_chip, no_compile_cache
):
    """The EvaByte cell's step at its published widths and its own length,
    cut to one layer (``benchmarks/aot_fit_eva.py`` compiles all four, ~1
    min, 32 custom calls): the chip's own compiler takes the folded local
    kernels and the staircase, each under the scope its reader looks for.
    A layer launches ``flash_fwd`` twice in each part (remat) and the two
    backward kernels once."""
    from benchmarks import aot_fit_eva, eva_scopes, harness

    text = _step_text(
        v5e_chip, harness.load_cell("evabyte.train-s16384"),
        aot_fit_eva.fit, num_layers=1,
    ).as_text()
    scopes_of = [scope for op, _, scope in _instructions(text).values()
                 if op == "custom-call" and "/pallas_call" in scope]
    assert text.count("tpu_custom_call") == 8 == len(scopes_of)
    parts = [eva_scopes.part_of(s) for s in scopes_of]
    assert parts.count("eva_local.kernels") == 4
    assert parts.count("eva_remote") == 4
    assert sum("transpose(" in s for s in scopes_of) == 2 + 4
