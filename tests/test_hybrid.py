"""The hybrid (Mamba-2 / attention) path of ``TransformerLM`` (PR 28): the
chunked scan against the recurrence, the model against the benchmark's
plain reference, the tiny hybrid cell end to end with its control and its
faults, the benchmark's counts against the program's, and what a
GPT-2-shaped configuration must not have noticed."""

import contextlib
import functools
import json
import os
import shutil
import sys
from unittest import mock

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

jax.devices()  # the suite's eight CPU devices, before anything asks for four
with mock.patch.object(jax.config, "update"):
    # benchmarks/tests/conftest.py sets its own device count on import
    from benchmarks.tests.conftest import ROOT, make_tiny_root

from benchmarks import harness, hybrid_flops, readings_hybrid  # noqa: E402
from benchmarks.reference import granite_hybrid  # noqa: E402
from distributeddataparallel_tpu.models import transformer as tfm  # noqa: E402
from distributeddataparallel_tpu.observability import cost_model  # noqa: E402
from distributeddataparallel_tpu.ops import (  # noqa: E402
    causal_conv,
    pallas_attention,
    ssd,
)
from distributeddataparallel_tpu.ops.attention import attention  # noqa: E402

DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
CELL_CONFIG = os.path.join(
    ROOT, "benchmarks", "configs", "granite-4.0-h-micro-10l.json"
)


def read(path):
    with open(path) as fh:
        return json.load(fh)


# ------------------------------------------------------------- the scan

def scan_inputs(b=2, s=20, h=4, p=8, g=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    return (
        jax.random.normal(k[0], (b, s, h, p)),
        jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) - 2.0),
        -jnp.exp(jax.random.normal(k[2], (h,))),
        jax.random.normal(k[3], (b, s, g, n)),
        jax.random.normal(k[4], (b, s, g, n)),
        jax.random.normal(k[5], (h,)),
    )


@pytest.mark.parametrize("chunk", [4, 8, 20, 256], ids=lambda c: f"chunk{c}")
def test_chunked_scan_is_the_recurrence(chunk):
    """S = 20: five chunks of 4, two and a padded half of 8, one chunk
    exactly, one chunk larger than the sequence.  f32 on both sides; the
    two orders of summation differ by rounding, 1e-5 of the largest y."""
    args = scan_inputs()
    want = granite_hybrid.ssm_scan(*args)
    got = ssd.ssd_chunked(*args, chunk=chunk)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=1e-5 * float(jnp.abs(want).max()))

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=range(6))(*args)

    for g_got, g_want in zip(
        through(lambda *a: ssd.ssd_chunked(*a, chunk=chunk)),
        through(granite_hybrid.ssm_scan),
    ):
        np.testing.assert_allclose(
            g_got, g_want, atol=2e-5 * float(jnp.abs(g_want).max())
        )


def test_a_dropped_chunk_carry_shows_only_past_the_first_chunk():
    args = scan_inputs()
    want = ssd.ssd_chunked(*args, chunk=8)
    with readings_hybrid.FAULTS["fault_no_chunk_carry"]():
        got = ssd.ssd_chunked(*args, chunk=8)
    np.testing.assert_array_equal(got[:, :8], want[:, :8])
    assert float(jnp.abs(got[:, 8:] - want[:, 8:]).max()) > 1e-2


# ------------------------------------------------- the scan's two kernels

def through_sin(fn, args, argnums):
    return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32))),
                    argnums=argnums)(*args)


@pytest.mark.parametrize("inputs,chunk,skip,dtype", [
    (dict(g=1), 8, True, jnp.float32),             # one group of four heads
    (dict(g=2), 8, True, jnp.float32),             # two groups, padded tail
    (dict(g=2), 4, True, jnp.float32),             # whole chunks
    (dict(g=2), 256, True, jnp.float32),           # one chunk of S = 20
    (dict(g=1), 8, False, jnp.float32),            # D=None
    (dict(b=1, s=300, h=2, g=1), 256, True, jnp.float32),  # 128-sub-tiles, padded
    (dict(g=2), 8, True, jnp.bfloat16),
    (dict(b=1, s=300, h=2, g=1), 256, False, jnp.bfloat16),
], ids=["g1-r4", "g2-padded", "g2-whole", "g2-one-chunk", "no-skip",
        "sub-tiles", "bf16", "bf16-sub-tiles-no-skip"])
def test_scan_kernels_are_the_recurrence_and_the_plain_form(
    inputs, chunk, skip, dtype
):
    """``ssd_fwd`` and ``ssd_bwd`` through the interpreter: ``y`` and every
    gradient.  f32 against the recurrence at the chunked form's own
    tolerances (``test_chunked_scan_is_the_recurrence``; the sub-tiled case
    sums 256 steps where those sum 20, five times the rounding).  bf16
    against the plain form under the same casts: both round the tile and
    ``dy`` to eight bits (2 ** -9 a rounding), in different places of the
    backward, so the worst entry agrees to 2 % of the largest and the whole
    to 1 % in norm."""
    args = scan_inputs(**inputs)
    if not skip:
        args = args[:5]
    argnums = range(len(args))
    cast = lambda a: tuple(  # noqa: E731
        v.astype(dtype) if i in (0, 3, 4) else v for i, v in enumerate(a))
    kernels = lambda *a: ssd.ssd_chunked(  # noqa: E731
        *cast(a), chunk=chunk, _interpret=True)
    plain = lambda *a: ssd.ssd_chunked(*cast(a), chunk=chunk)  # noqa: E731
    got, g_got = kernels(*args), through_sin(kernels, args, argnums)
    assert got.dtype == dtype and got.shape == args[0].shape
    if dtype == jnp.float32:
        wide = 5 if args[0].shape[1] > 20 else 1
        recurrence = granite_hybrid.ssm_scan if skip else (
            lambda *a: granite_hybrid.ssm_scan(*a, jnp.zeros(a[1].shape[-1])))
        for fn in (recurrence, plain):
            want = fn(*args)
            np.testing.assert_allclose(
                got, want, atol=wide * 1e-5 * float(jnp.abs(want).max()))
            for a, b in zip(g_got, through_sin(fn, args, argnums)):
                np.testing.assert_allclose(
                    a, b, atol=wide * 2e-5 * float(jnp.abs(b).max()))
        return
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    want = plain(*args)
    pairs = [(got, want)] + list(zip(g_got, through_sin(plain, args, argnums)))
    for a, b in pairs:
        a, b = f32(a), f32(b)
        np.testing.assert_allclose(a, b, atol=2e-2 * np.abs(b).max())
        assert np.linalg.norm(a - b) <= 1e-2 * np.linalg.norm(b)


@contextlib.contextmanager
def on_the_kernel_path():
    """``ssd.ssd_chunked`` and ``causal_conv.causal_conv_silu`` with the
    kernels forced through the interpreter, patched in under their own names
    so that the benchmark's fault hooks, which patch ``ssd_chunked`` and
    ``_carry_states``, find them."""
    with mock.patch.object(
        ssd, "ssd_chunked",
        functools.partial(ssd.ssd_chunked, _interpret=True),
    ), mock.patch.object(
        causal_conv, "causal_conv_silu",
        functools.partial(causal_conv.causal_conv_silu, _interpret=True),
    ):
        yield


def test_the_benchmarks_faults_bite_on_the_kernel_path():
    args = scan_inputs()
    with on_the_kernel_path():
        want = ssd.ssd_chunked(*args, chunk=8)
        with readings_hybrid.FAULTS["fault_no_chunk_carry"]():
            got = ssd.ssd_chunked(*args, chunk=8)
        np.testing.assert_array_equal(got[:, :8], want[:, :8])
        assert float(jnp.abs(got[:, 8:] - want[:, 8:]).max()) > 1e-2
        with readings_hybrid.FAULTS["fault_no_skip"]():
            got = ssd.ssd_chunked(*args, chunk=8)
        np.testing.assert_array_equal(
            got, ssd.ssd_chunked(*args[:5], chunk=8))
        np.testing.assert_allclose(
            want - got, args[5][:, None] * args[0], atol=1e-5)
    # and both are the plain form's answers
    np.testing.assert_allclose(
        want, ssd.ssd_chunked(*args, chunk=8),
        atol=1e-5 * float(jnp.abs(want).max()))
    # through the mixer, the convolution's kernels interpreted too: the
    # faults patch names the mixer still calls, with what it still passes
    _, model = tiny_model()
    params = tiny_weights(model)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 36), 0, 256)
    logits = lambda: model.apply({"params": params}, tokens)  # noqa: E731
    plain = logits()
    size = float(jnp.abs(plain).max())
    with on_the_kernel_path():
        sound = logits()
        np.testing.assert_allclose(sound, plain, atol=2e-5 * size)
        for fault in ("fault_no_chunk_carry", "fault_no_skip"):
            with readings_hybrid.FAULTS[fault]():
                # 7.8e-3 and 1.5e-2 of the largest logit; a sound run 2e-7
                assert float(jnp.abs(logits() - sound).max()) > 1e-3 * size


def test_the_kernels_take_the_cells_shapes_and_count_their_tiles():
    """``supported`` reads backend, shapes and dtype, nothing else."""
    def shapes(s=4096, h=64, p=64, g=1, n=128, dtype=jnp.bfloat16):
        return (jax.ShapeDtypeStruct((2, s, h, p), dtype),
                jax.ShapeDtypeStruct((2, s, g, n), dtype))

    assert not ssd.supported(*shapes(), 256)        # the CPU the tests run on
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert ssd.supported(*shapes(), 256)        # the cell
        assert ssd.supported(*shapes(dtype=jnp.float32), 256)
        assert ssd.supported(*shapes(s=8192), 128)
        assert not ssd.supported(*shapes(), 20)     # no 128-square sub-tile
        assert not ssd.supported(*shapes(s=20), 256)
        assert not ssd.supported(*shapes(n=16), 256)   # the tests' state
        assert not ssd.supported(*shapes(p=8), 256)
        assert not ssd.supported(*shapes(h=8, g=2), 256)  # 4 heads a block
        assert not ssd.supported(*shapes(dtype=jnp.float16), 256)
    # L 256 = 2 x 128: three of a chunk tile's four sub-tiles are live
    tiles = 2 * 16 * 64
    assert ssd.tile_counts(2, 4096, 64, 256) == (3 * tiles, tiles)
    assert ssd.tile_counts(2, 4096, 64, 128) == (2 * tiles, 0)
    assert ssd.tile_counts(2, 20, 4, 8) == (2 * 3 * 4, 0)
    assert ssd._plan(256, 64, 64) == (128, 16)


# ----------------------------------------- the model against the reference

def tiny_model(**overrides):
    config = read(os.path.join(DATA, "tiny-hybrid.json"))
    cfg = getattr(tfm, config["constructor"])(
        **{**config["overrides"], "attn_impl": "xla", **overrides}
    )
    return config, tfm.TransformerLM(cfg)


def tiny_weights(model, seed=3):
    """Every leaf random, none left at 0 or 1, the decays long."""
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    kind = harness.load_module("kinds", "train_hybrid")
    return kind.mamba_draws(
        harness.make_weights(shapes, seed, model.cfg.num_layers, jnp.float32, one),
        seed, one,
    )


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "kernels"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_matches_the_plain_reference(remat, kernels):
    """Logits, loss and every leaf's gradient, f32, pattern m m a m, a
    sequence of 36 (four chunks of 8 and a padded one).  Tolerance 2e-5 of
    each tensor's largest entry: f32 rounding through four layers, in two
    different orders of summation (chunked products against the
    recurrence); a dropped term reads 1e-2 and more.  ``kernels``: the
    mixer's convolution and scan through their kernels, interpreted."""
    config, model = tiny_model(remat=remat)
    params = tiny_weights(model)
    flat = harness.flatten(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 37), 0, 256)

    def program_loss(p):
        logits = model.apply({"params": p}, tokens[:, :-1])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        ), logits

    def reference_loss(w):
        logits = granite_hybrid.forward(w, tokens[:, :-1], config)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        ), logits

    with on_the_kernel_path() if kernels else contextlib.nullcontext():
        (loss, logits), grads = jax.value_and_grad(
            program_loss, has_aux=True
        )(params)
    (ref_loss, ref_logits), ref_grads = jax.value_and_grad(
        reference_loss, has_aux=True
    )(flat)
    assert float(jnp.abs(ref_logits).max()) > 0.1
    np.testing.assert_allclose(
        logits, ref_logits, atol=2e-5 * float(jnp.abs(ref_logits).max())
    )
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    grads = harness.flatten(grads)
    assert set(grads) == set(ref_grads)
    for leaf, want in ref_grads.items():
        assert float(jnp.abs(want).max()) > 0, leaf
        np.testing.assert_allclose(
            grads[leaf], want, atol=2e-5 * float(jnp.abs(want).max()),
            err_msg=leaf,
        )


def test_layer_types_are_checked():
    with pytest.raises(ValueError, match="layer_types"):
        tfm.granite_4_0_h_micro(num_layers=3)
    with pytest.raises(ValueError, match="layer_types"):
        tfm.tiny_lm(layer_types=("attention", "conv"))
    with pytest.raises(ValueError, match="scan_layers"):
        tfm.tiny_lm(layer_types=("attention", "mamba"), ssm_heads=4,
                    scan_layers=True)
    _, model = tiny_model(decode=True)
    with pytest.raises(ValueError, match="data-parallel training only"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                   positions=jnp.arange(8))


# ------------------------------------------------- the tiny cell, end to end

@pytest.fixture()
def hybrid_root(tmp_path):
    """``make_tiny_root`` with the tiny hybrid configuration, mix and cell
    dropped in as new files and entries."""
    root = make_tiny_root(str(tmp_path / "checkout"))
    shutil.copy(os.path.join(DATA, "tiny-hybrid.json"),
                os.path.join(root, "benchmarks", "configs"))
    shutil.copy(os.path.join(DATA, "tiny-train-hybrid.json"),
                os.path.join(root, "benchmarks", "traffic"))
    path = os.path.join(root, "BENCHMARK.json")
    bench = read(path)
    bench["configs"].append({
        "name": "tiny-hybrid", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/configs/tiny-hybrid.json",
    })
    bench["workloads"].append({
        "name": "tiny.hybrid", "config": "tiny-hybrid",
        "traffic": "tiny-train-hybrid", "chips": 1, "why": "test",
    })
    bench["end_to_end"][0]["workloads"].append("tiny.hybrid")
    with open(path, "w") as fh:
        json.dump(bench, fh)
    return root


def run(root, seed=2 ** 31 + 77):
    return harness.run_cell("tiny.hybrid", seed, 0.3, False, root=root,
                            require_chip=False)[0]


def test_the_tiny_hybrid_cell_is_correct(hybrid_root):
    result = run(hybrid_root)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"train_tokens_s_chip", "setup_s"}
    assert set(result["compared"]) == {
        "loss_gap", "grad_norm_gap", "update_norm_gap"}
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("fault", sorted(readings_hybrid.FAULTS))
def test_a_faulty_hybrid_model_is_not_correct(hybrid_root, fault):
    """Each fault is in the program, at the tiny cell's own limits: one
    term of the mixer left out, or one multiplier wrong."""
    with readings_hybrid.FAULTS[fault]() as overrides:
        mix = os.path.join(hybrid_root, "benchmarks", "traffic",
                           "tiny-train-hybrid.json")
        traffic = read(mix)
        traffic["model_overrides"].update(overrides)
        with open(mix, "w") as fh:
            json.dump(traffic, fh)
        assert run(hybrid_root)["correct"] is False


def test_the_fp8_control_is_not_correct(hybrid_root):
    from benchmarks import readings

    cell = harness.load_cell("tiny.hybrid", hybrid_root)
    kind = harness.load_module("kinds", "train_hybrid", hybrid_root)
    env = {"cell": cell, "config": cell["config"], "traffic": cell["traffic"],
           "devices": jax.devices()[:1], "seed": 5, "root": hybrid_root,
           "spans": harness.Spans(), "window_s": 0.2}
    session = kind.setup(env)
    session.release()
    control = readings.as_program(
        session.reference(quant=granite_hybrid.fake_fp8))
    compared = kind.compare(control, session.reference(),
                            cell["traffic"]["limits"])
    assert any(value > limit for _, value, limit in compared)
    assert all(value <= limit for _, value, limit in session.check())


# ------------------------------------------ the counts, against the program

def test_param_count_matches_the_programs_tree_leaf_for_leaf():
    """772,160,448 at the cell's configuration, by ``jax.eval_shape``:
    nothing is allocated."""
    config = read(CELL_CONFIG)
    train = harness.load_module("kinds", "train")
    env = {"config": config,
           "traffic": {"model_overrides": {"attn_impl": "xla"}}}
    shapes = harness.flatten(
        train.param_shapes(tfm.TransformerLM(train.model_config(env)))
    )
    assert sum(int(x.size) for x in shapes.values()) == 772_160_448
    assert hybrid_flops.param_count(config) == 772_160_448
    z = hybrid_flops.sizes(config)
    for i, kind in enumerate(z["kinds"]):
        mixer = "mamba" if kind == "mamba" else "attn"
        leaves = {k: v for k, v in shapes.items()
                  if k.startswith(f"layer_{i}/{mixer}/")}
        assert sum(int(v.size) for v in leaves.values()) == (
            hybrid_flops._mixer_matmul(z, kind)
            + hybrid_flops._mixer_other(z, kind)
        ), (i, kind)
        assert sum(int(v.size) for k, v in leaves.items()
                   if k.endswith("_proj/kernel")) == hybrid_flops._mixer_matmul(z, kind)
    assert hybrid_flops.matmul_param_count(config) == sum(
        int(v.size) for k, v in shapes.items()
        if k.endswith("_proj/kernel") or k == "token_embed/embedding"
    )


def test_the_constructor_holds_the_published_values():
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl"
    )] if os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl") else []
    published = next(
        (r["config"] for r in rows if r["name"] == "granite-4.0-h-micro"), None
    )
    config = read(CELL_CONFIG)
    if published is not None:
        changed = {k for k, v in published.items() if config.get(k) != v}
        assert changed == set(config["reduced"])
    cfg = tfm.granite_4_0_h_micro()
    assert (cfg.num_layers, cfg.vocab_size) == (40, 100352)
    assert list(cfg.layer_types[:10]) == config["layer_types"]
    assert cfg.layer_types == cfg.layer_types[:10] * 4
    for ours, theirs in [
        ("d_model", "hidden_size"), ("d_ff", "shared_intermediate_size"),
        ("num_heads", "num_attention_heads"),
        ("num_kv_heads", "num_key_value_heads"),
        ("ssm_heads", "mamba_n_heads"), ("ssm_head_dim", "mamba_d_head"),
        ("ssm_state", "mamba_d_state"), ("ssm_groups", "mamba_n_groups"),
        ("ssm_conv", "mamba_d_conv"), ("ssm_chunk", "mamba_chunk_size"),
        ("embedding_multiplier", "embedding_multiplier"),
        ("residual_multiplier", "residual_multiplier"),
        ("attention_multiplier", "attention_multiplier"),
        ("logits_scaling", "logits_scaling"),
    ]:
        assert getattr(cfg, ours) == config[theirs], ours


@pytest.mark.parametrize("shape", [
    (2, 4096, 64, 64, 128, 1, 256), (1, 100, 8, 16, 16, 2, 8),
    (3, 8, 4, 8, 16, 1, 256),
])
def test_ssd_cost_is_the_benchmarks_copy(shape):
    assert cost_model.ssd_cost(*shape) == hybrid_flops.ssd_cost(*shape)
    if shape[1] == 4096:  # the cell: 12.8 MFLOP a token a layer
        per_token = cost_model.ssd_cost(*shape)["flops"] / (2 * 4096)
        assert per_token == 3 * 4_259_840


def test_step_flops_of_the_cell():
    config = read(CELL_CONFIG)
    assert hybrid_flops.matmul_param_count(config) == 771_883_008
    fwd = hybrid_flops.forward_flops(config, 2, 4096, causal=True)
    scan = hybrid_flops.scan_cost(config, 2, 4096)["flops"] / 3
    attn = 2 * hybrid_flops.attention_flops(config, 4096, 4096) * 4097 / 8192
    assert fwd == 2 * 8192 * 771_883_008 + scan + attn
    assert hybrid_flops.train_step_flops(config, 2, 4096) == 3 * fwd


# ------------------------------------------------ the mixer's trace readers

def _scoped_trace(scopes_ns):
    """A scoped trace (``scope_reduce.load_xplane``'s form) of one chip:
    back-to-back operations ``[(scope, ns), ...]`` inside a window."""
    names = [["bench:window", ""]] + [
        [f"fusion.{i}", scope] for i, (scope, _) in enumerate(scopes_ns)
    ]
    events, at = [], 1000
    for i, (_, ns) in enumerate(scopes_ns):
        events.append([i + 1, at, ns])
        at += ns
    return {"names": names, "planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [[0, 0, at + 1000]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events}]},
    ]}


def test_mixer_readers_read_the_mixer_and_are_silent_without_one():
    from benchmarks import mixer_scopes

    fwd, bwd = "jit(s)/jvp(M)/layer_0/", "jit(s)/transpose(jvp(M))/layer_0/"
    trace = _scoped_trace([
        (fwd + "mamba/ssd/dot_general", 3_000_000),
        (bwd + "mamba/ssd/dot_general", 5_000_000),
        (fwd + "mamba/ssm_in_proj/in_proj/dot_general", 2_000_000),
        (fwd + "mamba/ssm_conv/jit(_fwd_launch)/conv_fwd/pallas_call", 600_000),
        (bwd + "mamba/ssm_conv/jit(_bwd_launch)/conv_bwd/pallas_call", 900_000),
        (fwd + "mamba/reshape", 500_000),
        (fwd + "mlp/up_proj/dot_general", 7_000_000),
    ])
    reduced = mixer_scopes.reduce(trace, 1)
    assert reduced["part_s"]["ssd"] == {"fwd": 0.003, "bwd": 0.005}
    assert reduced["part_s"]["ssm_in_proj"] == {"fwd": 0.002}
    assert reduced["part_s"][mixer_scopes.REST] == {"fwd": 0.0005}
    assert "ssd" in mixer_scopes.table(reduced, steps=2)
    config = read(CELL_CONFIG)
    ctx = {
        "mixer_reduced": reduced, "measured": {"steps": 2}, "chips": 1,
        "config": config, "window_s": 1.0,
        "traffic": {"per_chip_batch": 2, "seq_len": 4096},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }
    metric = lambda name: harness.load_module("layer_metrics", name).read  # noqa: E731
    assert metric("train_ssd_scan_ms")(ctx) == pytest.approx(4.0)
    assert metric("train_ssm_conv_ms")(ctx) == pytest.approx(0.75)
    assert metric("train_ssm_mixer_ms")(ctx) == pytest.approx(6.0)
    cost = hybrid_flops.scan_cost(config, 2, 4096)
    assert metric("ssd_scan_roofline")(ctx) == pytest.approx(
        100 * 2 * cost["flops"] / 197e12 / 0.008)
    assert metric("train_hybrid_step_mfu")(ctx) == pytest.approx(
        100 * 2 * hybrid_flops.train_step_flops(config, 2, 4096) / 197e12)
    # a trace with nothing under mamba (the parent's): no number, no error
    assert mixer_scopes.reduce(
        _scoped_trace([(fwd + "mlp/up_proj/dot_general", 7_000_000)]), 1
    ) is None
    silent = dict(ctx, mixer_reduced=None)
    for name in ("train_ssd_scan_ms", "train_ssm_mixer_ms", "ssd_scan_roofline",
                 "train_ssm_conv_ms"):
        assert metric(name)(silent) is None
    assert mixer_scopes.reduce({"names": [], "planes": []}, 1) is None


def test_gqa_flash_reader_counts_the_attention_layers_alone():
    config = read(CELL_CONFIG)
    cost = hybrid_flops.flash_attention_cost(config, 2, 4096)
    # one attention layer of ten, 32 query heads: 9 causal products a head
    assert cost["flops"] == 2 * 32 * 9 * 4096 * 4097 * 64
    rows = 2 * 4096 * 64 * 2  # one head's bf16 tensor
    assert cost["bytes"] == rows * (9 * 32 + 8 * 8) + 5 * 2 * 4096 * 32 * 4
    ctx = {
        "scope_reduced": {"devices": 1, "bucket_s": {
            "attn_kernel.fwd": 0.004, "attn_kernel.dq": 0.003,
            "attn_kernel.dkv": 0.005, "attn": 0.5}},
        "measured": {"steps": 2}, "chips": 1, "config": config,
        "traffic": {"per_chip_batch": 2, "seq_len": 4096},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }
    read_ = harness.load_module("layer_metrics", "flash_attn_gqa_roofline").read
    assert read_(ctx) == pytest.approx(
        100 * 2 * cost["flops"] / 197e12 / 0.012)
    # no kernel under its own name (the xla path), or no trace: no number
    assert read_(dict(ctx, scope_reduced={
        "devices": 1, "bucket_s": {"attn": 0.5}})) is None
    assert read_(dict(ctx, scope_reduced=None)) is None


# ------------------------- what a GPT-2-shaped configuration must not notice

class _BlockBeforeKinds(nn.Module):
    """``DecoderBlock`` as it was before it learnt kinds and multipliers."""

    cfg: tfm.TransformerConfig
    kind: str = "attention"

    @nn.compact
    def __call__(self, x, positions=None, rope=None, deterministic=True):
        cfg = self.cfg
        drop = nn.Dropout(cfg.dropout_rate, deterministic=deterministic)
        y = tfm._make_norm(cfg, "attn_norm")(x)
        x = x + drop(tfm.Attention(cfg, name="attn")(
            y, positions=positions, rope=rope, deterministic=deterministic))
        y = tfm._make_norm(cfg, "mlp_norm")(x)
        return x + drop(tfm.MLP(cfg, name="mlp")(y))


@pytest.mark.parametrize("family", ["gpt2_124m", "tiny_lm"])
def test_a_model_without_kinds_has_the_parameters_and_logits_it_had(
    family, monkeypatch
):
    cfg = getattr(tfm, family)(
        vocab_size=128, d_model=32, num_layers=2, num_heads=2, d_ff=64,
        max_seq_len=16, attn_impl="xla",
    )
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 128)
    model = tfm.TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    logits = model.apply({"params": params}, tokens)
    explicit = tfm.TransformerLM(
        tfm.dataclasses.replace(cfg, layer_types=("attention",) * 2)
    )
    np.testing.assert_array_equal(
        explicit.apply({"params": params}, tokens), logits)
    monkeypatch.setattr(tfm, "DecoderBlock", _BlockBeforeKinds)
    before = tfm.TransformerLM(cfg)
    params_before = before.init(jax.random.PRNGKey(0), tokens)["params"]
    assert jax.tree.structure(params_before) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params_before), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        before.apply({"params": params}, tokens), logits)


def test_attention_scale_none_is_one_over_sqrt_d():
    k = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(k[0], (2, 128, 4, 16))
    kv = [jax.random.normal(key, (2, 128, 2, 16)) for key in k[1:]]
    today = attention(q, *kv, impl="xla")
    np.testing.assert_array_equal(
        attention(q, *kv, impl="xla", scale=None), today)
    np.testing.assert_array_equal(
        attention(q, *kv, impl="xla", scale=0.25), today)
    # and another scale, through the kernels (interpret mode) and the
    # reference alike: forward and the three gradients
    assert float(jnp.abs(
        attention(q, *kv, impl="xla", scale=1 / 64) - today
    ).max()) > 1e-2

    def loss(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2)
        )(q, *kv)

    want = loss(lambda q, k, v: attention(
        q, k, v, impl="xla", scale=1 / 64))
    got = loss(lambda q, k, v: pallas_attention.flash_attention(
        q, k, v, True, True, 1 / 64))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))
    np.testing.assert_array_equal(
        pallas_attention.flash_attention(q, *kv, True, True, None),
        pallas_attention.flash_attention(q, *kv, True, True),
    )
