"""The ``evabyte`` path of ``TransformerLM`` (PR 36): chunk-summarised
attention (``ops/eva.py``: exact inside blocks of a window, one learned
summary a chunk of every earlier block, one softmax) on the ``jax.numpy``
path and through the flash kernels under the staircase rule, norms with a
unit offset, a float32 residual stream, eight prediction heads and their
loss — the op against the benchmark reference's one-softmax form, the
tiny cell end to end with its control and its faults, and the benchmark's
counts against the program's."""

import json
import os
import shutil
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

jax.devices()  # the suite's eight CPU devices, before anything asks for four
with mock.patch.object(jax.config, "update"):
    # benchmarks/tests/conftest.py sets its own device count on import
    from benchmarks.tests.conftest import ROOT, make_tiny_root

from benchmarks import eva_flops, harness, readings, readings_eva  # noqa: E402
from benchmarks.reference import evabyte  # noqa: E402
from distributeddataparallel_tpu.models import transformer as tfm  # noqa: E402
from distributeddataparallel_tpu.observability import cost_model  # noqa: E402
from distributeddataparallel_tpu.ops import (  # noqa: E402
    attention,
    eva,
    lm_cross_entropy,
    multi_token_cross_entropy,
    pallas_attention,
)

DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
CELL_CONFIG = os.path.join(ROOT, "benchmarks", "configs", "evabyte-4l.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(path):
    with open(path) as fh:
        return json.load(fh)


# ------------------------------- the op against the reference's one softmax

def one_softmax(q, k, v, phi, mu, window, chunk):
    """The published rule written once, with a mask: scores of every query
    over all keys and all summaries side by side, ONE softmax."""
    B, S, H, D = q.shape
    sigma = D ** -0.5
    ksum, vsum = evabyte.summaries(k, v, phi, mu, chunk, sigma)
    i = jnp.arange(S)[:, None]
    t = jnp.arange(S)[None]
    j = jnp.arange(S // chunk)[None]
    seen = jnp.concatenate([
        (t <= i) & (t >= (i // window) * window),
        j < (i // window) * (window // chunk),
    ], axis=-1)
    s = sigma * jnp.concatenate([
        jnp.einsum("bqhd,bkhd->bhqk", q, k),
        jnp.einsum("bqhd,bjhd->bhqj", q, ksum)], axis=-1)
    a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return (jnp.einsum("bhqk,bkhd->bqhd", a[..., :S], v)
            + jnp.einsum("bhqj,bjhd->bqhd", a[..., S:], vsum))


def interpreted_kernels():
    """The flash kernels through the interpreter, as the chip would pick
    them: the backend's say left out of ``supported``."""
    real = pallas_attention.flash_attention

    def interpreted(q, k, v, causal=True, interpret=False, *args, **kw):
        return real(q, k, v, causal, True, *args, **kw)

    return (mock.patch.object(jax, "default_backend", lambda: "tpu"),
            mock.patch.object(pallas_attention, "flash_attention", interpreted))


@pytest.mark.parametrize("shape,window,chunk,impl", [
    ((2, 64, 4, 16), 16, 4, "xla"),
    ((1, 1024, 2, 16), 256, 2, "pallas"),   # 3 q blocks of 256, 3 of 128 keys
    ((2, 1536, 2, 16), 512, 4, "pallas"),   # a batch of two, blocks of 512
], ids=["xla-tiny", "kernels-s1024-w256", "kernels-s1536-w512"])
def test_eva_attention_is_one_softmax_over_keys_and_summaries(
        shape, window, chunk, impl):
    """Forward and the gradients of q, k, v, phi and mu, float32, against
    the rule written once with a mask.  2e-5 of each tensor's largest
    entry: the two softmaxes and their merge in another order of
    summation; a summary seen by the wrong window reads 1e-2 and more."""
    H, D = shape[2:]
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v, do = (jax.random.normal(ks[i], shape) for i in range(4))
    phi, mu = (jax.random.normal(ks[4 + i], (H, D)) for i in range(2))
    args = (q, k, v, phi, mu)

    def through(f):
        return jax.value_and_grad(
            lambda *a: jnp.sum(f(*a) * do), argnums=range(5))

    with jax.default_matmul_precision("highest"):
        want, want_grads = through(
            lambda *a: one_softmax(*a, window, chunk))(*args)
        run = through(lambda *a: eva.eva_attention(
            *a, window=window, chunk=chunk, impl=impl))
        if impl == "pallas":
            backend, kernels = interpreted_kernels()
            with backend, kernels:
                got, got_grads = run(*args)
        else:
            got, got_grads = run(*args)
    assert abs(float(got) - float(want)) < 2e-5 * abs(float(want))
    for name, a, b in zip("q k v phi mu".split(), got_grads, want_grads):
        assert float(jnp.abs(b).max()) > 0.1, name
        np.testing.assert_allclose(
            a, b, atol=2e-5 * float(jnp.abs(b).max()), err_msg=name)


def test_one_window_is_plain_causal_attention():
    """With S = window there is no summarised term: bit for bit
    ``attention(causal)``, and phi and mu take no part."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q, k, v = (jax.random.normal(ks[i], (2, 16, 4, 16)) for i in range(3))
    phi, mu = (jax.random.normal(ks[3 + i], (4, 16)) for i in range(2))
    got = eva.eva_attention(q, k, v, phi, mu, window=16, chunk=4, impl="xla")
    np.testing.assert_array_equal(
        got, attention(q, k, v, causal=True, impl="xla"))
    grads = jax.grad(lambda phi, mu: jnp.sum(eva.eva_attention(
        q, k, v, phi, mu, window=16, chunk=4, impl="xla")), (0, 1))(phi, mu)
    assert not np.any(grads[0]) and not np.any(grads[1])
    with pytest.raises(ValueError, match="whole number of windows"):
        eva.eva_attention(q[:, :12], k[:, :12], v[:, :12], phi, mu,
                          window=8, chunk=4, impl="xla")
    with pytest.raises(ValueError, match="whole number of chunks"):
        eva.eva_attention(q, k, v, phi, mu, window=8, chunk=3, impl="xla")


def test_the_staircase_counts_its_blocks_and_tiles():
    """At the cell's 16,384 positions: 28 of the 64 window-by-window
    blocks are live; the remote launch (14,336 queries on 896 summaries)
    runs 112 of 196 (512, 128) tiles a head, none with a mask, the
    forward in 28 grid steps and the backward kernels on grids of 28 x 7
    and 7 x 28."""
    assert eva.remote_blocks(16384, 2048) == (28, 64)
    assert eva.remote_blocks(4096, 2048) == (1, 4)
    stair = (2048, 128)
    plan = pallas_attention._fwd_plan(14336, 896, 128, 2, None, stair)
    assert plan == (512, 128, 896)
    counts = pallas_attention.fwd_tile_counts(
        14336, 896, False, 896 - 14336, plan, None, stair)
    assert counts == (112, 0, 84, 28)
    bwd = pallas_attention._bwd_plan(14336, 896, 128, 2, 1, None, stair)
    assert (bwd.block_q, bwd.block_k, bwd.dq_whole) == (512, 128, False)
    dq, dkv = pallas_attention.bwd_tile_counts(
        14336, 896, False, 896 - 14336, bwd, None, 1, stair)
    assert dq == (112, 0, 84, 28 * 7) and dkv == (112, 0, 84, 7 * 28)
    # the local part: eight rows of 2,048 a sequence, a row whole, unrolled
    local = pallas_attention._bwd_plan(2048, 2048, 128, 2)
    assert local == (512, 512, True, True, True)


# --------------------------------------------------------- the model's parts

def tiny_model(**overrides):
    config = read(os.path.join(DATA, "tiny-evabyte.json"))
    cfg = tfm.evabyte(**{**config["overrides"], "attn_impl": "xla",
                         **overrides})
    return config, tfm.TransformerLM(cfg)


def tiny_weights(model, seed=3):
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    kind = harness.load_module("kinds", "train_eva")
    return kind.eva_draws(harness.make_weights(
        shapes, seed, model.cfg.num_layers, jnp.float32, one), seed, one)


def test_no_logit_sees_a_later_byte():
    """Changing the id at position ``p`` — the first chunk of a later
    window — moves no logit before ``p`` and moves the ones from ``p`` on:
    a window's own chunks are summarised for later windows only."""
    _, model = tiny_model(num_layers=2)
    params = tiny_weights(model)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 64), 0, 320)
    p = 33  # window 2 (positions 32..47), its first chunk
    other = ids.at[0, p].set((ids[0, p] + 7) % 320)
    apply = jax.jit(lambda t: model.apply({"params": params}, t))
    a, b = apply(ids), apply(other)
    assert a.shape == (1, 64, 8, 320) and a.dtype == jnp.float32
    np.testing.assert_array_equal(a[:, :p], b[:, :p])
    assert float(jnp.abs(a[:, p:] - b[:, p:]).max()) > 1e-3
    assert float(jnp.abs(a[:, 48:] - b[:, 48:]).max()) > 1e-4  # through a summary


def test_the_residual_stream_is_float32_under_bf16_branches():
    """``fp32_residual``: every layer hands on a float32 stream while its
    norms hand bfloat16 to the branches — what no number of the cell's
    comparison can see (a bf16 stream reads like a sound run, PERF.md
    section 2).  Without it the stream has the activations' type."""
    ids = jnp.zeros((1, 32), jnp.int32)

    def outputs(**overrides):
        _, model = tiny_model(num_layers=2, dtype=jnp.bfloat16, **overrides)
        params = jax.eval_shape(
            model.init, jax.random.PRNGKey(0), ids)["params"]
        _, col = jax.eval_shape(lambda p: model.apply(
            {"params": p}, ids, capture_intermediates=True,
            mutable=["intermediates"]), params)
        return col["intermediates"]

    wide = outputs()
    for layer in ("layer_0", "layer_1"):
        assert wide[layer]["__call__"][0].dtype == jnp.float32
        assert wide[layer]["attn_norm"]["__call__"][0].dtype == jnp.bfloat16
        assert wide[layer]["attn"]["__call__"][0].dtype == jnp.bfloat16
        assert wide[layer]["mlp"]["__call__"][0].dtype == jnp.bfloat16
    assert wide["final_norm"]["__call__"][0].dtype == jnp.bfloat16
    narrow = outputs(fp32_residual=False)
    assert narrow["layer_1"]["__call__"][0].dtype == jnp.bfloat16


def test_multi_token_cross_entropy():
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    logits = jax.random.normal(ks[0], (2, 12, 8, 20))
    ids = jax.random.randint(ks[1], (2, 13), 0, 20)
    np.testing.assert_allclose(
        multi_token_cross_entropy(logits[:, :, :1], ids),
        lm_cross_entropy(logits[:, :, 0], ids[:, 1:]), rtol=1e-6)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    heads = []
    for h in range(8):  # head h at t is scored on ids[t + 1 + h]
        terms = [-logp[b, t, h, ids[b, t + 1 + h]]
                 for b in range(2) for t in range(12) if t + 1 + h <= 12]
        assert len(terms) == 2 * (12 - h)
        heads.append(np.mean(terms))
    np.testing.assert_allclose(
        multi_token_cross_entropy(logits, ids), np.mean(heads), rtol=1e-6)


@pytest.mark.parametrize("family,data,held,total,dots,remats", [
    ("trinity_mini", "tiny-afmoe.json", (0, 8), 4133, 219, 5),
    ("evabyte", "tiny-evabyte.json", None, 2415, 175, 4),
], ids=["trinity", "evabyte"])
def test_remat_without_a_mixer_traces_the_parents_program(
        family, data, held, total, dots, remats):
    """The tiny trinity and EvaByte models under remat, forward and
    backward, trace to the equation counts of the parent of the commit
    that let ``mamba`` blocks keep their projections (all equations,
    ``dot_general``s, ``remat2``s; counted there): the policy reaches no
    block of another kind, and nothing is named."""
    import collections

    config = read(os.path.join(DATA, data))
    extra = {"moe_experts_held": held} if held else {}
    model = tfm.TransformerLM(getattr(tfm, family)(**{
        **config["overrides"], "attn_impl": "xla", "remat": True, **extra}))
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    loss = lambda p: model.apply(p, tokens).astype(jnp.float32).mean()  # noqa: E731

    def count(jaxpr, acc):
        for eqn in jaxpr.eqns:
            acc[eqn.primitive.name] += 1
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        count(inner, acc)
        return acc

    acc = count(jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr,
                collections.Counter())
    assert (sum(acc.values()), acc["dot_general"], acc["remat2"]) == (
        total, dots, remats)
    assert acc["name"] == 0


def test_what_the_new_kind_refuses():
    _, model = tiny_model(decode=True)
    with pytest.raises(ValueError, match="data-parallel training only"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                   positions=jnp.arange(8))
    with pytest.raises(ValueError, match="eva_attention layers need"):
        tfm.evabyte(num_layers=1, eva_chunk=0)
    with pytest.raises(ValueError, match="eva_attention layers need"):
        tfm.evabyte(num_layers=1, sliding_window=40)
    with pytest.raises(ValueError, match="RMSNorm's"):
        tfm.TransformerLM(tfm.tiny_lm(norm="layernorm", fp32_residual=True)
                          ).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))


# ------------------------------------------------- the tiny cell, end to end

@pytest.fixture(scope="module")
def eva_root(tmp_path_factory):
    """``make_tiny_root`` with the tiny evabyte configuration, mix and cell
    dropped in as new files and entries."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("eva") / "checkout"))
    shutil.copy(os.path.join(DATA, "tiny-evabyte.json"),
                os.path.join(root, "benchmarks", "configs"))
    shutil.copy(os.path.join(DATA, "tiny-train-eva.json"),
                os.path.join(root, "benchmarks", "traffic"))
    path = os.path.join(root, "BENCHMARK.json")
    bench = read(path)
    bench["configs"].append({
        "name": "tiny-evabyte", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/configs/tiny-evabyte.json",
    })
    bench["workloads"].append({
        "name": "tiny.eva", "config": "tiny-evabyte",
        "traffic": "tiny-train-eva", "chips": 1, "why": "test",
    })
    bench["end_to_end"][0]["workloads"].append("tiny.eva")
    with open(path, "w") as fh:
        json.dump(bench, fh)
    return root


def test_the_tiny_eva_cell_is_correct(eva_root):
    """Three train steps of the model (four layers, S 64: four windows of
    16 in chunks of 4) through ``kinds/train_eva`` against
    ``reference/evabyte.py`` under ``kinds/train.compare``, and a window."""
    result = harness.run_cell("tiny.eva", 2 ** 31 + 77, 0.3, False,
                              root=eva_root, require_chip=False)[0]
    assert result["correct"] is True
    assert set(result["metrics"]) == {"train_tokens_s_chip", "setup_s"}
    assert set(result["compared"]) == {
        "loss_gap", "grad_norm_gap", "update_norm_gap"}
    assert result["attempted"] > 0 and result["failed"] == 0
    for number in result["compared"].values():  # f32 against f32
        assert number["value"] < 0.1 * number["limit"], result["compared"]


@pytest.fixture(scope="module")
def sound(eva_root):
    """One seed's session, its reference and the cell: shared by the
    control and every fault, which are read against the same reference on
    the same rows (``readings_eva.main``'s loop)."""
    cell = harness.load_cell("tiny.eva", eva_root)
    kind = harness.load_module("kinds", "train_eva", eva_root)
    env = {"cell": cell, "config": cell["config"], "traffic": cell["traffic"],
           "devices": jax.devices()[:1], "seed": 5, "root": eva_root,
           "spans": harness.Spans(), "window_s": 0.2}
    session = kind.setup(env)
    session.release()
    return kind, env, session, session.reference()


@pytest.mark.parametrize(
    "case", ["control_fp8", *sorted(readings_eva.FAULTS)])
def test_the_control_and_every_fault_read_above_the_sound_run(sound, case):
    """The reference in fp8, and the program with one term of the model
    left out or one rule wrong, at the tiny cell's own limits: not
    correct, where the sound run is."""
    from benchmarks.readings_hybrid import faulty_env

    kind, env, session, ref = sound
    limits = env["traffic"]["limits"]
    if case == "control_fp8":
        program = readings.as_program(
            session.reference(quant=evabyte.fake_fp8))
    else:
        with readings_eva.FAULTS[case]() as overrides:
            broken = kind.setup(faulty_env(env, overrides))
        broken.release()
        program = broken.program
    read_ = kind.compare(program, ref, limits)
    assert any(value > limit for _, value, limit in read_), read_
    assert all(value <= limit for _, value, limit in
               kind.compare(session.program, ref, limits))


# ------------------------------------------ the counts, against the program

def test_param_count_matches_the_programs_tree_leaf_for_leaf():
    """821,366,784 at the cell's configuration, by ``jax.eval_shape``:
    nothing is allocated.  The issue's table, line for line."""
    config = read(CELL_CONFIG)
    train = harness.load_module("kinds", "train")
    env = {"config": config,
           "traffic": {"model_overrides": {"attn_impl": "xla"}}}
    shapes = harness.flatten(
        train.param_shapes(tfm.TransformerLM(train.model_config(env))))
    count = lambda keep: sum(  # noqa: E731
        int(v.size) for k, v in shapes.items() if keep(k))
    assert count(lambda k: True) == 821_366_784
    assert eva_flops.param_count(config) == 821_366_784
    for i in range(4):
        assert count(lambda k: k.startswith(f"layer_{i}/")) == 202_391_552
        assert count(lambda k: k.startswith(f"layer_{i}/attn/")) == (
            67_108_864 + 8_192)
        assert count(lambda k: k.startswith(f"layer_{i}/mlp/")) == 135_266_304
    assert count(lambda k: not k.startswith("layer_")) == (
        1_310_720 + 10_485_760 + 4_096)
    assert shapes["layer_0/attn/adaptive_phi"].shape == (32, 128)
    assert shapes["layer_0/attn/adaptive_mu_k"].shape == (32, 128)
    assert shapes["lm_head/kernel"].shape == (4096, 8 * 320)
    assert shapes["final_norm/offset"].shape == (4096,)


def test_the_constructor_holds_the_published_values():
    rows = [json.loads(line) for line in open(CATALOG)] if os.path.exists(
        CATALOG) else []
    published = next(
        (r["config"] for r in rows if r["name"] == "EvaByte"), None)
    config = read(CELL_CONFIG)
    if published is not None:
        changed = {k for k, v in published.items() if config.get(k, k) != v}
        assert changed == set(config["reduced"]) == {"num_hidden_layers"}
        assert config["published"]["num_hidden_layers"] == published[
            "num_hidden_layers"]
    cfg = tfm.evabyte()
    assert cfg.layer_types == ("eva_attention",) * 32
    assert (cfg.num_layers, cfg.max_seq_len) == (
        config["published"]["num_hidden_layers"],
        config["max_position_embeddings"])
    assert cfg.kv_heads == config["num_key_value_heads"] == cfg.num_heads
    assert cfg.dims_per_head == 128
    assert (cfg.norm_unit_offset, cfg.fp32_residual) == (
        config["norm_add_unit_offset"], config["fp32_skip_add"])
    assert not cfg.tie_embeddings and not cfg.use_bias
    assert cfg.positional == "rope" and cfg.activation == "swiglu"
    for ours, theirs in [
        ("d_model", "hidden_size"), ("d_ff", "intermediate_size"),
        ("num_heads", "num_attention_heads"), ("vocab_size", "vocab_size"),
        ("sliding_window", "window_size"), ("eva_chunk", "chunk_size"),
        ("num_pred_heads", "num_pred_heads"), ("rope_theta", "rope_theta"),
    ]:
        assert getattr(cfg, ours) == config[theirs], ours
    assert len(tfm.evabyte(**config["overrides"]).layer_types) == 4


@pytest.mark.parametrize("shape", [
    (1, 16384, 32, 128, 2048, 16), (2, 64, 4, 16, 16, 4),
    (1, 2048, 32, 128, 2048, 16), (3, 32768, 32, 128, 2048, 16),
])
def test_eva_cost_is_the_benchmarks_copy(shape):
    assert cost_model.eva_cost(*shape) == eva_flops.eva_cost(*shape)
    pairs = cost_model.eva_pair_counts(shape[1], *shape[4:])
    assert pairs == eva_flops.eva_pair_counts(shape[1], *shape[4:])
    if shape[1] == 16384:  # the cell
        assert pairs == (16_785_408, 7_340_032)
        assert cost_model.eva_cost(*shape)["flops"] == (
            12 * 32 * 128 * 7_340_032)
    if shape[1] == 32768:
        assert pairs == (33_570_816, 31_457_280)
    if shape[1] == 2048:  # one window: no summarised term
        assert pairs == (2048 * 2049 // 2, 0)
        assert cost_model.eva_cost(*shape) == {"flops": 0, "bytes": 0}


def test_step_flops_of_the_cell():
    config = read(CELL_CONFIG)
    per_token = eva_flops.matmul_weights_per_token(config)
    assert per_token == 4 * (67_108_864 + 135_266_304) + 10_485_760
    attn = 4 * 32 * 128 * (4 * (16_785_408 + 7_340_032) + 6 * 16384)
    assert eva_flops.attention_flops(config, 1, 16384) == attn
    fwd = eva_flops.forward_flops(config, 1, 16384)
    assert fwd == 2 * 16384 * per_token + attn
    assert eva_flops.train_step_flops(config, 1, 16384) == 3 * fwd
    assert 85.3e12 < 3 * fwd < 85.4e12
    local = eva_flops.local_cost(config, 1, 16384)
    assert local["flops"] == 4 * 32 * 18 * 128 * 16_785_408
    assert eva_flops.remote_cost(config, 1, 16384)["flops"] == (
        4 * cost_model.eva_cost(1, 16384, 32, 128, 2048, 16)["flops"])
