"""The ``afmoe`` path of ``TransformerLM`` (PR 33): window and full attention
kinds with head norms and an output gate, four norms a layer, a leading
dense layer, and the expert layer that is told which share of the router's
experts it holds — the model against the benchmark's plain reference, the
shares adding up to the uncut layer, dropless under skew, the tiny cell end
to end with its control and its faults, and the benchmark's counts against
the program's."""

import dataclasses
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

from benchmarks import harness, moe_flops, moe_scopes, readings_moe  # noqa: E402
from benchmarks.reference import afmoe  # noqa: E402
from distributeddataparallel_tpu.models import transformer as tfm  # noqa: E402
from distributeddataparallel_tpu.observability import cost_model  # noqa: E402
from distributeddataparallel_tpu.ops import grouped_matmul, moe  # noqa: E402

DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
CELL_CONFIG = os.path.join(
    ROOT, "benchmarks", "configs", "trinity-mini-5l-e16.json"
)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(path):
    with open(path) as fh:
        return json.load(fh)


def tiny_model(held=(0, 8), **overrides):
    config = dict(read(os.path.join(DATA, "tiny-afmoe.json")),
                  experts_held=list(held))
    cfg = tfm.trinity_mini(**{
        **config["overrides"], "attn_impl": "xla", "moe_experts_held": held,
        **overrides,
    })
    return config, tfm.TransformerLM(cfg)


def tiny_weights(model, seed=3):
    """Every leaf random, none left at 0 or 1 (the selection's bias too)."""
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return harness.make_weights(
        shapes, seed, model.cfg.num_layers, jnp.float32, one
    )


# ----------------------------------------- the model against the reference

@pytest.mark.parametrize("held,remat", [
    ((0, 8), True), ((2, 2), True), ((5, 3), False),
], ids=["whole-remat", "share-2-of-8-remat", "share-3-of-8"])
def test_model_matches_the_plain_reference(held, remat):
    """Logits, loss, every leaf's gradient and every expert layer's load,
    f32, five layers in the cell's pattern (a dense sliding layer, then
    sliding, sliding, sliding, full with experts), window 8 at a sequence
    of 32, 8 experts 2 a token.  Tolerance 2e-5 of each tensor's largest
    entry (the hybrid's figure): f32 rounding through five layers in two
    orders of summation (a sort and a grouped product against a loop over
    the experts); a dropped term reads 1e-2 and more.  A share of 2 of 8
    runs the usual buffer (twice the mean load), 3 of 8 the worst case."""
    config, model = tiny_model(held, remat=remat)
    params = tiny_weights(model)
    flat = harness.flatten(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 256)

    def nll(logits):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    def program_loss(p):
        logits, col = model.apply(
            {"params": p}, tokens[:, :-1], mutable=["intermediates"])
        return nll(logits), (logits, col["intermediates"])

    def reference_loss(w):
        logits, loads = afmoe.forward(
            w, tokens[:, :-1], config, with_load=True)
        return nll(logits), (logits, loads)

    (loss, (logits, col)), grads = jax.jit(jax.value_and_grad(
        program_loss, has_aux=True))(params)
    (ref_loss, (ref_logits, ref_loads)), ref_grads = jax.jit(
        jax.value_and_grad(reference_loss, has_aux=True))(flat)
    assert float(jnp.abs(ref_logits).max()) > 0.1
    np.testing.assert_allclose(
        logits, ref_logits, atol=2e-5 * float(jnp.abs(ref_logits).max()))
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    grads = harness.flatten(grads)
    assert set(grads) == set(ref_grads)
    for leaf, want in ref_grads.items():
        if leaf.endswith("expert_bias"):  # top_k's indices carry no gradient
            assert not np.any(want) and not np.any(grads[leaf]), leaf
            continue
        assert float(jnp.abs(want).max()) > 0, leaf
        np.testing.assert_allclose(
            grads[leaf], want, atol=2e-5 * float(jnp.abs(want).max()),
            err_msg=leaf)
    assert sorted(col) == ["layer_1", "layer_2", "layer_3", "layer_4"]
    for i in range(1, 5):
        np.testing.assert_array_equal(
            col[f"layer_{i}"]["mlp"]["moe_load"][0], ref_loads[i])
        assert ref_loads[i].shape == (held[1],)
    assert ref_loads[0].shape == (0,)  # the dense layer


# ------------------------------------------------------ the expert layer

def expert_layer(held):
    """``MoEMLP`` alone at the tiny widths, its weights for all 8 experts,
    and the same weights cut to the share ``held``."""
    cfg = tiny_model(held)[1].cfg
    whole = dataclasses.replace(cfg, moe_experts_held=(0, 8))
    layer = tfm.MoEMLP(whole)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 64))
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"]
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    params = harness.make_weights(shapes, 11, 5, jnp.float32, one)
    first, count = held
    cut = {k: v[first:first + count] if k.startswith("experts_") else v
           for k, v in params.items()}
    return tfm.MoEMLP(cfg), cut, params, x


def as_reference(params):
    return {"mlp/" + k: v for k, v in harness.flatten(params).items()}


@pytest.mark.parametrize("shares", [
    [(0, 4), (4, 4)], [(0, 2), (2, 3), (5, 3)], [(0, 8)],
], ids=["two-halves", "three-uneven", "one-whole"])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """What every share computes of the layer's result, the shared expert
    counted once, is the uncut reference's layer — the test that ties the
    chip's share to the model (``model-configs`` section 4)."""
    config = dict(read(os.path.join(DATA, "tiny-afmoe.json")))
    total = None
    for held in shares:
        layer, cut, params, x = expert_layer(held)
        part, col = layer.apply({"params": cut}, x, mutable=["intermediates"])
        total = part if total is None else total + part
        want_part, want_load = afmoe.expert_ffn(
            x, as_reference(cut), dict(config, experts_held=list(held)), None)
        np.testing.assert_allclose(part, want_part, atol=2e-6)
        np.testing.assert_array_equal(col["intermediates"]["moe_load"][0], want_load)
    ref = as_reference(params)
    shared = afmoe.gated_mlp(
        x, ref["mlp/shared/gate_proj/kernel"], ref["mlp/shared/up_proj/kernel"],
        ref["mlp/shared/down_proj/kernel"], None)
    uncut, load = afmoe.expert_ffn(x, ref, config, None)
    assert int(load.sum()) == 2 * 32 * 2  # every (token, choice) lands somewhere
    np.testing.assert_allclose(
        total - (len(shares) - 1) * shared, uncut,
        atol=2e-6 * max(1.0, float(jnp.abs(uncut).max())))


@pytest.mark.parametrize("held,skew", [
    ((0, 2), 1), ((0, 2), None), ((2, 3), 3), ((0, 8), 5),
], ids=["share-takes-all", "share-even", "share-3-inside-usual", "whole-skewed"])
def test_no_row_is_dropped_under_skew(held, skew):
    """A selection bias of 10 on one expert sends it every token: a share
    that holds it gets T rows for that expert alone, past the usual buffer
    of twice the mean load, and computes them all — result, load and
    gradients against the plain loop."""
    layer, cut, _, x = expert_layer(held)
    config = dict(read(os.path.join(DATA, "tiny-afmoe.json")),
                  experts_held=list(held))
    if skew is not None:
        cut = dict(cut, expert_bias=cut["expert_bias"].at[skew].set(10.0))
    T, K = 64, 2
    usual = moe.dropless_bound(T * K, 8, held[1])

    def program(p, x):
        y, col = layer.apply({"params": p}, x, mutable=["intermediates"])
        return jnp.sum(jnp.sin(y)), col["intermediates"]["moe_load"][0]

    def plain(p, x):
        y, load = afmoe.expert_ffn(x, as_reference(p), config, None)
        return jnp.sum(jnp.sin(y)), load

    (got, load), g = jax.jit(
        jax.value_and_grad(program, (0, 1), has_aux=True))(cut, x)
    (want, ref_load), rg = jax.jit(
        jax.value_and_grad(plain, (0, 1), has_aux=True))(cut, x)
    np.testing.assert_array_equal(load, ref_load)
    if skew is not None and held[0] <= skew < sum(held):
        assert int(load[skew - held[0]]) == T
        if held[1] == 2:  # a buffer of 64 rows, and more than 64 held
            assert usual == T < int(load.sum())  # the worst-case path
    elif held[1] < 8:
        assert int(load.sum()) <= usual                 # the usual one
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(rg)):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * max(float(jnp.abs(b).max()), 1e-3))


@pytest.mark.parametrize("sizes,k,n,spare,dtype", [
    ([300, 0, 17, 256], 128, 256, 1, jnp.float32),   # an empty group inside
    ([0, 0, 5], 256, 128, 2, jnp.float32),           # empty groups first
    ([0, 0, 0], 128, 128, 1, jnp.float32),           # no row at all
    ([257, 1, 600, 0], 256, 1024, 0, jnp.bfloat16),  # no spare tile
], ids=["hole-inside", "empty-first", "no-rows", "bf16-full"])
def test_grouped_kernels_are_the_ragged_product(sizes, k, n, spare, dtype):
    """``moe_gmm`` (plain and transposed) and ``moe_tgmm`` through the
    interpreter against ``lax.ragged_dot`` over the same aligned layout:
    the product, the rows' gradient and the weights' — a group without a
    row gets a zero gradient, a tile past the live ones zero rows."""
    tile = grouped_matmul.ROW_TILE
    sizes = jnp.asarray(sizes, jnp.int32)
    rows_n = int(sum(-(-int(s) // tile) * tile for s in sizes)) + spare * tile
    layout, rank = moe.group_layout(sizes, tile, max(rows_n, tile))
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    filled = (rank >= 0)[:, None]
    rows = jnp.where(filled, jax.random.normal(key[0], (len(rank), k)), 0)
    dy = jnp.where(filled, jax.random.normal(key[2], (len(rank), n)), 0)
    w = 0.05 * jax.random.normal(key[1], (len(sizes), k, n))
    rows, dy, w = rows.astype(dtype), dy.astype(dtype), w.astype(dtype)

    def both(fn):
        out, vjp = jax.vjp(fn, rows, w)
        return (out,) + vjp(dy)

    got = both(lambda r, w: grouped_matmul.grouped_matmul(
        r, w, layout, _interpret=True))
    want = both(lambda r, w: jax.lax.ragged_dot(
        r, w, layout.padded, preferred_element_type=r.dtype))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape and np.isfinite(a).all()
        np.testing.assert_allclose(a, b, atol=tol * max(np.abs(b).max(), 1e-6))
    assert int(layout.live) * tile == int(layout.padded.sum())
    assert not np.asarray(got[2])[np.asarray(sizes) == 0].any()
    # the layout: every rank once, groups at multiples of the tile
    ranks = np.asarray(rank)
    assert sorted(ranks[ranks >= 0]) == list(range(int(sizes.sum())))
    assert grouped_matmul.row_tile(rows, w) == grouped_matmul.PLAIN_TILE  # a CPU
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert grouped_matmul.row_tile(rows, w) == tile
        assert not grouped_matmul.supported(rows[:, :-8], w[:, :-8])
        assert not grouped_matmul.supported(rows.astype(jnp.float16), w)


def test_sort_by_expert_puts_the_held_first_and_counts_them():
    idx = jnp.array([[5, 0], [2, 5], [7, 3], [3, 3]], jnp.int32)
    order, sizes = moe.sort_by_expert(idx, 2, 3)  # holds experts 2, 3, 4
    np.testing.assert_array_equal(sizes, [1, 3, 0])
    np.testing.assert_array_equal(order[:4], [2, 5, 6, 7])  # stable inside one
    assert sorted(order.tolist()) == list(range(8))
    assert moe.dropless_bound(65536, 128, 16) == 16384
    assert moe.dropless_bound(128, 8, 8) == 128
    assert moe.dropless_bound(128, 8, 2) == 64


def test_kinds_and_shares_are_checked():
    with pytest.raises(ValueError, match="sliding_window"):
        tfm.tiny_lm(layer_types=("sliding_attention", "full_attention"))
    with pytest.raises(ValueError, match="no share"):
        tfm.trinity_mini(moe_experts_held=(120, 16))
    with pytest.raises(ValueError, match="scan_layers"):
        tfm.trinity_mini(scan_layers=True)
    with pytest.raises(ValueError, match="scan_layers"):
        tfm.tiny_lm(num_dense_layers=1, moe_experts=4, scan_layers=True)
    tokens = jnp.zeros((1, 8), jnp.int32)
    _, model = tiny_model(decode=True)
    with pytest.raises(ValueError, match="data-parallel training only"):
        model.init(jax.random.PRNGKey(0), tokens, positions=jnp.arange(8))
    _, model = tiny_model(tp_axis="model")
    with pytest.raises(ValueError, match="data-parallel training only"):
        model.init(jax.random.PRNGKey(0), tokens)
    layer = tfm.MoEMLP(dataclasses.replace(
        tiny_model((0, 4))[1].cfg, moe_score_func="tanh"))
    with pytest.raises(ValueError, match="moe_score_func"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))


# ------------------------------------------------- the tiny cell, end to end

@pytest.fixture()
def moe_root(tmp_path):
    """``make_tiny_root`` with the tiny afmoe configuration, mix and cell
    dropped in as new files and entries."""
    root = make_tiny_root(str(tmp_path / "checkout"))
    shutil.copy(os.path.join(DATA, "tiny-afmoe.json"),
                os.path.join(root, "benchmarks", "configs"))
    shutil.copy(os.path.join(DATA, "tiny-train-moe.json"),
                os.path.join(root, "benchmarks", "traffic"))
    path = os.path.join(root, "BENCHMARK.json")
    bench = read(path)
    bench["configs"].append({
        "name": "tiny-afmoe", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/configs/tiny-afmoe.json",
    })
    bench["workloads"].append({
        "name": "tiny.moe", "config": "tiny-afmoe",
        "traffic": "tiny-train-moe", "chips": 1, "why": "test",
    })
    bench["end_to_end"][0]["workloads"].append("tiny.moe")
    with open(path, "w") as fh:
        json.dump(bench, fh)
    return root


def run(root, seed=2 ** 31 + 77):
    return harness.run_cell("tiny.moe", seed, 0.3, False, root=root,
                            require_chip=False)[0]


def test_the_tiny_moe_cell_is_correct_and_counts_its_load(moe_root, capsys):
    result = run(moe_root)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"train_tokens_s_chip", "setup_s"}
    assert set(result["compared"]) == {
        "loss_gap", "grad_norm_gap", "update_norm_gap"}
    assert result["attempted"] > 0 and result["failed"] == 0
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if "load step" in ln]
    assert len(lines) == 3 * 4  # three check steps, four expert layers
    for line in lines:  # the program's count is the reference's
        got, want = line.split("program ")[1].split(" reference ")
        assert got == want.split(" max/mean")[0], line
        assert "share of T*K 1.0000" in line  # the tiny cell holds all 8


@pytest.mark.parametrize("fault", sorted(readings_moe.FAULTS))
def test_a_faulty_moe_model_is_not_correct(moe_root, fault):
    """Each fault is in the program, at the tiny cell's own limits: one
    term of the model left out, or one constant wrong."""
    with readings_moe.FAULTS[fault]() as overrides:
        mix = os.path.join(moe_root, "benchmarks", "traffic",
                           "tiny-train-moe.json")
        traffic = read(mix)
        traffic["model_overrides"].update(overrides)
        with open(mix, "w") as fh:
            json.dump(traffic, fh)
        assert run(moe_root)["correct"] is False


def test_the_fp8_control_is_not_correct(moe_root):
    from benchmarks import readings

    cell = harness.load_cell("tiny.moe", moe_root)
    kind = harness.load_module("kinds", "train_moe", moe_root)
    env = {"cell": cell, "config": cell["config"], "traffic": cell["traffic"],
           "devices": jax.devices()[:1], "seed": 5, "root": moe_root,
           "spans": harness.Spans(), "window_s": 0.2}
    session = kind.setup(env)
    session.release()
    control = readings.as_program(session.reference(quant=afmoe.fake_fp8))
    compared = kind.compare(control, session.reference(),
                            cell["traffic"]["limits"])
    assert any(value > limit for _, value, limit in compared)
    assert all(value <= limit for _, value, limit in session.check())


# ------------------------------------------ the counts, against the program

def test_param_count_matches_the_programs_tree_leaf_for_leaf():
    """705,474,304 at the cell's configuration, by ``jax.eval_shape``:
    nothing is allocated.  (The issue's table reads 705,475,584: it counts
    the two head norms of 128 as 512 a layer, five times 256 too many.)"""
    config = read(CELL_CONFIG)
    train = harness.load_module("kinds", "train")
    env = {"config": config,
           "traffic": {"model_overrides": {"attn_impl": "xla"}}}
    shapes = harness.flatten(
        train.param_shapes(tfm.TransformerLM(train.model_config(env))))
    count = lambda keep: sum(  # noqa: E731
        int(v.size) for k, v in shapes.items() if keep(k))
    assert count(lambda k: True) == 705_474_304
    assert moe_flops.param_count(config) == 705_474_304
    assert count(lambda k: k.startswith("layer_0/")) == 65_020_160
    for i in range(1, 5):
        assert count(lambda k: k.startswith(f"layer_{i}/")) == 134_488_448
        assert count(lambda k: k.startswith(f"layer_{i}/mlp/")) == 107_217_024
    assert count(lambda k: k.startswith("layer_0/attn/")) == (
        moe_flops._attn_matmul(moe_flops.sizes(config)) + 256)
    assert count(lambda k: not k.startswith("layer_")) == 102_500_352
    assert shapes["layer_1/mlp/router/kernel"].shape == (2048, 128)
    assert shapes["layer_1/mlp/experts_up"].shape == (16, 2048, 1024)


def test_the_constructor_holds_the_published_values():
    rows = [json.loads(line) for line in open(CATALOG)] if os.path.exists(
        CATALOG) else []
    published = next(
        (r["config"] for r in rows if r["name"] == "Trinity-Mini"), None)
    config = read(CELL_CONFIG)
    if published is not None:
        changed = {k for k, v in published.items() if config.get(k) != v}
        assert changed == set(config["reduced"])
        assert config["published"]["num_experts"] == published["num_experts"]
        assert config["published"]["vocab_size"] == published["vocab_size"]
    cfg = tfm.trinity_mini()
    assert (cfg.num_layers, cfg.vocab_size, cfg.num_dense_layers) == (
        32, 200192, 2)
    assert cfg.layer_types == (
        ("sliding_attention",) * 3 + ("full_attention",)) * 8
    assert [cfg.layer_types[0], *cfg.layer_types[4:8]] == config["layer_types"]
    assert cfg.embedding_multiplier == 2048 ** 0.5
    assert not cfg.tie_embeddings and cfg.positional == "rope"
    for ours, theirs in [
        ("d_model", "hidden_size"), ("d_ff", "intermediate_size"),
        ("moe_d_ff", "moe_intermediate_size"), ("head_dim", "head_dim"),
        ("num_heads", "num_attention_heads"),
        ("num_kv_heads", "num_key_value_heads"),
        ("sliding_window", "sliding_window"), ("rope_theta", "rope_theta"),
        ("moe_top_k", "num_experts_per_tok"),
        ("moe_shared_experts", "num_shared_experts"),
        ("moe_route_scale", "route_scale"), ("moe_route_norm", "route_norm"),
        ("moe_score_func", "score_func"),
    ]:
        assert getattr(cfg, ours) == config[theirs], ours
    assert cfg.moe_experts == config["published"]["num_experts"]


@pytest.mark.parametrize("shape", [
    (8192, 2048, 1024, 16), (100, 64, 32, 4), (65536, 2048, 1024, 128),
])
def test_moe_cost_is_the_benchmarks_copy(shape):
    assert cost_model.moe_cost(*shape) == moe_flops.moe_cost(*shape)
    if shape[0] == 8192:  # the cell: 309 GFLOP a layer a step
        assert cost_model.moe_cost(*shape)["flops"] == 18 * 8192 * 2048 * 1024


def test_step_flops_of_the_cell():
    config = read(CELL_CONFIG)
    assert moe_flops.visible_pairs(8192, None) == 33_558_528
    assert moe_flops.visible_pairs(8192, 2048) == 14_681_088
    assert moe_flops.visible_pairs(32, 64) == moe_flops.visible_pairs(32, None)
    assert moe_flops.expected_rows(config, 8192) == 8192
    attn = 2 * 2 * 32 * 128 * (4 * 14_681_088 + 33_558_528)
    assert moe_flops.attention_flops(config, 1, 8192) == attn
    per_token = moe_flops.matmul_weights_per_token(config)
    # dense layer 65.0 M, an expert layer 40.1 M (a routed expert once)
    assert per_token == 25024 * 2048 + 5 * 27_262_976 + 37_748_736 + 4 * (
        262_144 + 2 * 6_291_456)
    fwd = moe_flops.forward_flops(config, 1, 8192)
    assert fwd == 2 * 8192 * per_token + attn
    assert moe_flops.train_step_flops(config, 1, 8192) == 3 * fwd
    assert 18.1e12 < 3 * fwd < 18.2e12


# ------------------------------------------------ the expert FFN's readers

def _scoped_trace(scopes_ns):
    names = [["bench:window", ""]] + [
        [f"fusion.{i}", scope] for i, (scope, _) in enumerate(scopes_ns)]
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


def test_moe_readers_read_the_expert_ffn_and_are_silent_without_one():
    fwd, bwd = "jit(s)/jvp(M)/layer_1/", "jit(s)/transpose(jvp(M))/layer_1/"
    trace = _scoped_trace([
        (fwd + "mlp/moe_experts/ragged_dot", 3_000_000),
        (bwd + "mlp/moe_experts/ragged_dot", 5_000_000),
        (fwd + "mlp/moe_router/router/dot_general", 500_000),
        (fwd + "mlp/moe_dispatch/gather", 700_000),
        (bwd + "mlp/moe_combine/gather", 800_000),
        (fwd + "mlp/moe_shared/shared/up_proj/dot_general", 2_000_000),
        ("jit(s)/jvp(M)/layer_0/mlp/up_proj/dot_general", 7_000_000),
    ])
    reduced = moe_scopes.reduce(trace, 1)
    assert reduced["part_s"]["moe_experts"] == {"fwd": 0.003, "bwd": 0.005}
    assert "moe_experts" in moe_scopes.table(reduced, steps=2)
    config = read(CELL_CONFIG)
    ctx = {
        "moe_reduced": reduced, "measured": {"steps": 2}, "chips": 1,
        "config": config, "window_s": 1.0,
        "traffic": {"per_chip_batch": 1, "seq_len": 8192},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }
    metric = lambda name: harness.load_module("layer_metrics", name).read  # noqa: E731
    assert metric("train_moe_experts_ms")(ctx) == pytest.approx(4.0)
    assert metric("train_moe_dispatch_ms")(ctx) == pytest.approx(1.0)
    assert metric("train_moe_ffn_ms")(ctx) == pytest.approx(6.0)  # no dense FFN
    cost = moe_flops.experts_cost(config, 1, 8192)
    assert cost["flops"] == 4 * 18 * 8192 * 2048 * 1024
    assert metric("moe_experts_roofline")(ctx) == pytest.approx(
        100 * 2 * cost["flops"] / 197e12 / 0.008)
    assert metric("train_moe_step_mfu")(ctx) == pytest.approx(
        100 * 2 * moe_flops.train_step_flops(config, 1, 8192) / 197e12)
    # a trace with nothing under the scopes (the parent's): no number, no error
    assert moe_scopes.reduce(_scoped_trace(
        [("jit(s)/jvp(M)/layer_0/mlp/up_proj/dot_general", 7_000_000)]), 1
    ) is None
    silent = dict(ctx, moe_reduced=None)
    for name in ("train_moe_ffn_ms", "train_moe_experts_ms",
                 "train_moe_dispatch_ms", "moe_experts_roofline"):
        assert metric(name)(silent) is None
    assert moe_scopes.reduce({"names": [], "planes": []}, 1) is None


def test_window_flash_reader_counts_the_visible_pairs():
    config = read(CELL_CONFIG)
    cost = moe_flops.flash_attention_cost(config, 1, 8192)
    pairs = 4 * 14_681_088 + 33_558_528
    assert cost["flops"] == 32 * 9 * 2 * 128 * pairs
    rows = 8192 * 128 * 2  # one head's bf16 tensor
    assert cost["bytes"] == 5 * (rows * (9 * 32 + 8 * 4) + 5 * 8192 * 32 * 4)
    ctx = {
        "scope_reduced": {"devices": 1, "bucket_s": {
            "attn_kernel.fwd": 0.04, "attn_kernel.dq": 0.03,
            "attn_kernel.dkv": 0.05, "attn": 0.5}},
        "measured": {"steps": 2}, "chips": 1, "config": config,
        "traffic": {"per_chip_batch": 1, "seq_len": 8192},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }
    read_ = harness.load_module(
        "layer_metrics", "flash_attn_window_roofline").read
    assert read_(ctx) == pytest.approx(100 * 2 * cost["flops"] / 197e12 / 0.12)
    assert read_(dict(ctx, scope_reduced={
        "devices": 1, "bucket_s": {"attn": 0.5}})) is None
    assert read_(dict(ctx, scope_reduced=None)) is None
