"""The mixer's causal convolution (``ops/causal_conv.py``, PR 32): the two
kernels through the interpreter against the plain form and a step-by-step
loop, values and every gradient; causality; which shapes take which path."""

import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributeddataparallel_tpu.ops import causal_conv as cc  # noqa: E402

f32 = jnp.float32


def inputs(b, s, width, c, K, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    bound = K ** -0.5  # the mixer's own initialiser: outputs of order 1
    return (
        jax.random.normal(k[0], (b, s, width)).astype(dtype),
        jax.random.uniform(k[1], (K, c), f32, -bound, bound),
        jax.random.uniform(k[2], (c,), f32, -bound, bound),
    )


def step_by_step(xbc, taps, bias):
    """One step at a time, the last K - 1 inputs carried: (b, s, c) f32."""
    K = taps.shape[0]
    x = jnp.swapaxes(xbc.astype(f32), 0, 1)                    # (s, b, c)

    def step(past, x_t):
        window = jnp.concatenate([past, x_t[None]])            # (K, b, c)
        pre = bias + jnp.einsum("kbc,kc->bc", window, taps)
        return window[1:], pre * jax.nn.sigmoid(pre)

    _, y = jax.lax.scan(step, jnp.zeros((K - 1,) + x.shape[1:], f32), x)
    return jnp.swapaxes(y, 0, 1)


def through_sin(fn, args):
    """Gradients of all three operands under a cotangent that differs from
    part to part and from step to step."""
    def loss(*a):
        return sum(jnp.sum(jnp.sin((i + 1) * p.astype(f32)))
                   for i, p in enumerate(fn(*a)))

    return jax.grad(loss, argnums=(0, 1, 2))(*args)


@pytest.fixture()
def short_chunks():
    """Lane chunks of 256, so that a test-sized sequence has several; the
    launches are traced anew, for they read the constant while tracing."""
    def clear():
        cc._fwd_launch.clear_cache()
        cc._bwd_launch.clear_cache()

    clear()
    with mock.patch.object(cc, "_CHUNK", 256):
        yield
    clear()


CASES = {
    # b, s, width, start, splits, K, dtype
    "f32-whole-chunks": (2, 512, 640, 128, (256, 128, 128), 4, f32),
    "f32-ragged-chunk": (1, 384, 640, 128, (256, 128, 128), 4, f32),
    "f32-two-taps": (1, 384, 512, 0, (256, 128, 128), 2, f32),
    "f32-the-cells-channels": (1, 256, 8512, 4096, (4096, 128, 128), 4, f32),
    "f32-odd-sizes": (2, 36, 296, 128, (128, 16, 16), 4, f32),
    "f32-one-part": (1, 40, 24, 0, (24,), 3, f32),
    "bf16-ragged-chunk": (1, 384, 640, 128, (256, 128, 128), 4, jnp.bfloat16),
    "bf16-two-taps": (2, 256, 512, 0, (256, 128, 128), 2, jnp.bfloat16),
    "bf16-odd-sizes": (2, 36, 296, 128, (128, 16, 16), 4, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_are_the_loop_and_the_plain_form(short_chunks, case):
    """``conv_fwd`` and ``conv_bwd`` through the interpreter: the parts and
    the gradients of ``xbc``, ``taps`` and ``bias``.  f32 against the
    step-by-step loop and the plain form at 2e-6 of each tensor's largest
    entry (the same f32 arithmetic in another order; the taps' gradients sum
    up to 1024 products).  bf16 against the plain form under the same casts:
    both round ``y`` and ``d xbc`` once, so an entry differs by at most one
    rounding (2 ** -8 of it) where the f32 values straddle a tie."""
    b, s, width, start, splits, K, dtype = CASES[case]
    c = sum(splits)
    args = inputs(b, s, width, c, K, dtype)
    bounds = list(np.cumsum(splits)[:-1])

    def kernels(*a):
        return cc.causal_conv_silu(*a, splits, start=start, _interpret=True)

    def plain(*a):
        return cc.causal_conv_silu(*a, splits, start=start)

    def loop(proj, taps, bias):
        y = step_by_step(proj[..., start:start + c], taps, bias)
        return jnp.split(y, bounds, axis=-1)

    got, g_got = kernels(*args), through_sin(kernels, args)
    assert [p.shape for p in got] == [(b, s, n) for n in splits]
    assert all(p.dtype == dtype for p in got)
    assert [g.shape for g in g_got] == [a.shape for a in args]
    # nothing comes back to the channels the convolution does not read
    outside = np.ones(width, bool)
    outside[start:start + c] = False
    assert not np.asarray(g_got[0], np.float32)[..., outside].any()
    if dtype == f32:
        for fn in (loop, plain):
            for a, w in zip(got, fn(*args)):
                np.testing.assert_allclose(
                    a, w, atol=2e-6 * float(jnp.abs(w).max()))
            for a, w in zip(g_got, through_sin(fn, args)):
                np.testing.assert_allclose(
                    a, w, atol=2e-6 * float(jnp.abs(w).max()))
        return
    pairs = list(zip(got, plain(*args))) + list(
        zip(g_got, through_sin(plain, args)))
    for a, w in pairs:
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(a, w, rtol=2 ** -7, atol=1e-6 * np.abs(w).max())
        assert np.linalg.norm(a - w) <= 2e-3 * np.linalg.norm(w)


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_a_step_sees_itself_and_the_three_before_it(path):
    """The output at step t does not change when inputs after t do, changes
    when the input K - 1 steps before does, and the first steps see zeros
    to their left."""
    K, c, s, t = 4, 128, 256, 131
    proj, taps, bias = inputs(1, s, c, c, K, f32)
    conv = lambda p: cc.causal_conv_silu(  # noqa: E731
        p, taps, bias, (c,), _interpret=path == "kernels")[0]
    want = conv(proj)
    later = conv(proj.at[:, t + 1:].add(1.0))
    np.testing.assert_array_equal(later[:, :t + 1], want[:, :t + 1])
    assert float(jnp.abs(later[:, t + 1] - want[:, t + 1]).min()) > 0
    earlier = conv(proj.at[:, t - (K - 1)].add(1.0))
    np.testing.assert_array_equal(earlier[:, :t - (K - 1)], want[:, :t - (K - 1)])
    assert float(jnp.abs(earlier[:, t] - want[:, t]).min()) > 0
    np.testing.assert_array_equal(
        conv(proj.at[:, t - K].add(1.0))[:, t], want[:, t])
    # step 0 reads the last tap alone, step 1 the last two
    silu = lambda v: v * jax.nn.sigmoid(v)  # noqa: E731
    np.testing.assert_allclose(
        want[:, 0], silu(bias + taps[3] * proj[:, 0]), atol=1e-6)
    np.testing.assert_allclose(
        want[:, 1], silu(bias + taps[3] * proj[:, 1] + taps[2] * proj[:, 0]),
        atol=1e-6)


def test_the_kernels_take_the_cells_shapes_and_others_the_plain_form():
    """``supported`` reads backend, shapes and dtype, nothing else; a shape
    it refuses never reaches the kernels."""
    def shapes(s=4096, width=8512, c=4352, K=4, dtype=jnp.bfloat16):
        return (jax.ShapeDtypeStruct((2, s, width), dtype),
                jax.ShapeDtypeStruct((K, c), f32))

    cell = (4096, 128, 128)
    assert not cc.supported(*shapes(), cell, 4096)   # the CPU the tests run on
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert cc.supported(*shapes(), cell, 4096)   # the cell
        assert cc.supported(*shapes(dtype=f32, s=2048), cell, 4096)
        assert cc.supported(*shapes(width=4352), cell)
        assert cc.supported(*shapes(s=8192), cell, 4096)        # 64 rows a block
        assert not cc.supported(*shapes(s=65536), cell, 4096)   # no row block fits
        assert not cc.supported(*shapes(s=4100), cell, 4096)    # lanes not whole
        assert not cc.supported(*shapes(c=4128), (4096, 16, 16), 4096)
        assert not cc.supported(*shapes(), cell, 64)            # start in a block
        assert not cc.supported(*shapes(K=130), cell, 4096)
        assert not cc.supported(*shapes(dtype=jnp.float16), cell, 4096)
        # refused: the plain form's own answer, the kernels never entered
        proj, taps, bias = inputs(2, 36, 296, 160, 4, f32)
        with mock.patch.object(cc, "_conv", side_effect=AssertionError):
            got = cc.causal_conv_silu(
                proj, taps, bias, (128, 16, 16), start=128)
        want = cc._plain(proj[..., 128:288], taps, bias)
        np.testing.assert_array_equal(jnp.concatenate(got, axis=-1), want)
    assert cc._plan(4096, 2, 4, 4096, cell) == (128, 16, cc._CHUNK, 128, 128)
    assert cc._plan(8192, 2, 4, 4096, cell).rows == 64
    assert cc._plan(36, 4, 4, 128, (128, 16, 16)) == (16, 16, 36, 3, 36)


def test_operands_that_do_not_fit_are_refused():
    proj, taps, bias = inputs(1, 8, 32, 24, 4, f32)
    for splits, start in (((16, 4), 0), ((16, 8), 16)):
        with pytest.raises(ValueError, match="do not describe"):
            cc.causal_conv_silu(proj, taps, bias, splits, start=start)
    with pytest.raises(ValueError, match="do not describe"):
        cc.causal_conv_silu(proj, taps, bias[:-1], (16, 8))
