"""Zero-bubble (ZB-H1-style) pipeline schedule: the B/W backward split
must be a pure re-bracketing of AD — 1f1b's loss, and its params to
f32 rounding of each leaf's accumulated sum — while the three-scan
rendering reports its own useful-slot counters and the shared tick
arithmetic stays one source of truth across the compiled schedule, the
bubble accounting, and the zb schedule IR.  Plus the dpp CLI's loud
zb-constraint rejections and the events-side measured-bubble
reconstruction."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

import distributeddataparallel_tpu as ddp
import dpp
from distributeddataparallel_tpu.data.loader import shard_batch
from distributeddataparallel_tpu.models import TransformerLM, tiny_lm
from distributeddataparallel_tpu.parallel.pipeline_parallel import (
    _1f1b_ticks,
    _zb_segments,
    interleave_layer_perm,
    make_pp_train_step,
    pp_bubble_fraction,
    shard_state_pp,
)


def _scan_cfg(**over):
    base = dict(
        num_layers=4, num_heads=2, d_model=32, d_ff=64, scan_layers=True,
        max_seq_len=32,
    )
    base.update(over)
    return tiny_lm(**base)


def _run_schedule(cfg, params, token_batches, mesh, microbatches,
                  schedule, virtual=1, tx=None):
    """Run one schedule over len(token_batches) steps; returns the
    per-step losses, the final params, and the last step's metrics."""
    step = make_pp_train_step(
        cfg, mesh=mesh, microbatches=microbatches, donate=False,
        schedule=schedule, virtual=virtual,
    )
    state = shard_state_pp(
        ddp.TrainState.create(apply_fn=None, params=params,
                              tx=tx or optax.adam(1e-2)),
        mesh,
    )
    losses, metrics = [], None
    for i, tokens in enumerate(token_batches):
        batch = shard_batch({"tokens": tokens}, mesh)
        state, metrics = step(state, batch, jax.random.PRNGKey(i))
        losses.append(np.asarray(metrics["loss"]))
    return losses, state.params, metrics


@pytest.mark.parametrize(
    "microbatches,virtual",
    [(8, 1),   # accum-style: M > n, the pp microbatch loop IS --accum
     (4, 1),   # M = n edge: steady state is exactly one group
     (8, 2)],  # interleaved: v > 1 composes with the B/W split
)
def test_zb_parity_with_1f1b_to_f32_rounding(devices, microbatches, virtual):
    """DP(2) x PP(4), 3 SGD steps: zb gives 1f1b's first loss to the bit
    (same params, same forward slot), its later losses within 2 ulp, and
    every parameter leaf within 4 ulp of the leaf's largest element — the
    split backward runs the same per-primitive transposes as the joint
    vjp and sums the same microbatches in the same order, but XLA
    compiles the slot anew in each of zb's three scan bodies and may tile
    a bias gradient's row reduction differently there (measured: at most
    2.0 ulp, ``o_proj/bias``; the schedule's docstring).  SGD, not Adam:
    ``k_proj/bias`` has a gradient that is rounding noise alone (softmax
    does not see a key bias), which Adam turns into steps of the learning
    rate — two correct schedules then differ by 1e-4 of that leaf."""
    cfg = _scan_cfg(num_layers=4 * virtual)
    mesh = ddp.make_mesh(("data", "pipe"), shape=(2, 4))
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32)
    )["params"]
    rng = np.random.default_rng(7)
    batches = [
        rng.integers(0, 256, size=(microbatches * 2, 33)).astype(np.int32)
        for _ in range(3)
    ]

    ref_losses, ref_params, ref_m = _run_schedule(
        cfg, params, batches, mesh, microbatches, "1f1b", virtual,
        tx=optax.sgd(0.1),
    )
    zb_losses, zb_params, zb_m = _run_schedule(
        cfg, params, batches, mesh, microbatches, "zb", virtual,
        tx=optax.sgd(0.1),
    )

    eps = np.finfo(np.float32).eps
    np.testing.assert_array_equal(ref_losses[0], zb_losses[0])
    for a, b in zip(ref_losses[1:], zb_losses[1:]):
        np.testing.assert_allclose(a, b, rtol=2 * eps, atol=0)
    assert ref_losses[-1] < ref_losses[0]  # and it trains
    for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(zb_params)[0],
        jax.tree.leaves(ref_params),
    ):
        b = np.asarray(b)
        np.testing.assert_allclose(
            np.asarray(a), b, rtol=0, atol=4 * eps * np.abs(b).max(),
            err_msg="/".join(str(getattr(k, "key", k)) for k in path),
        )

    # The phase counters are the measured-schedule contract: every
    # stage executed M*v valid F and B slots under both schedules, and
    # M*v separate W slots under zb (W is fused into B under 1f1b).
    M = microbatches
    ref_counts = np.asarray(ref_m["pp_phase_counts"])
    zb_counts = np.asarray(zb_m["pp_phase_counts"])
    assert ref_counts.shape == zb_counts.shape == (4, 3)
    np.testing.assert_array_equal(
        ref_counts, np.tile([M * virtual, M * virtual, 0], (4, 1))
    )
    np.testing.assert_array_equal(
        zb_counts, np.tile([M * virtual] * 3, (4, 1))
    )


# ------------------------------------------------ tick arithmetic edges


def test_1f1b_ticks_edge_cases():
    # n=2, M=n: two groups of nothing — last unit is j=1, T covers
    # warm-up + steady + drain exactly
    assert _1f1b_ticks(2, 2, 1) == (1, 4)
    # M = n at larger n
    assert _1f1b_ticks(4, 4, 1) == (3, 10)
    # M not a multiple of n: the tail group still schedules
    assert _1f1b_ticks(3, 7, 1) == (6, 11)
    # v > 1: groups advance by n*v units
    assert _1f1b_ticks(2, 4, 2) == (7, 12)
    assert _1f1b_ticks(4, 8, 2) == (15, 26)


def test_zb_segments_partition_the_1f1b_scan():
    for n, M, v in [(2, 2, 1), (2, 4, 1), (4, 4, 1), (4, 16, 1),
                    (3, 7, 1), (2, 4, 2), (4, 8, 2), (8, 32, 1)]:
        j_last, T = _1f1b_ticks(n, M, v)
        warm, steady, drain, f_end = _zb_segments(n, M, v)
        # the three segments tile [0, T): zb re-brackets capacity, it
        # never lengthens the critical path
        assert warm + steady + drain == T, (n, M, v)
        assert warm == v * n - 1
        assert f_end == warm + steady == j_last + n
        assert drain == T - f_end >= 0


def test_zb_bubble_accounting_fields():
    for n, M, v in [(4, 16, 1), (8, 32, 1), (2, 4, 2)]:
        acct = pp_bubble_fraction(n, M, v, schedule="zb")
        _, _, _, f_end = _zb_segments(n, M, v)
        assert acct["schedule"] == "zb"
        assert acct["useful_slots"] == 3 * M * v
        assert acct["slot_capacity"] == 3 * f_end
        # the accounting rounds to 4 decimals for telemetry
        assert acct["bubble_fraction"] == pytest.approx(
            1.0 - M * v / f_end, abs=5e-5
        )
        # zb strictly beats 1f1b at the same geometry
        v1 = pp_bubble_fraction(n, M, v)["bubble_fraction"]
        assert acct["bubble_fraction"] < v1


def test_zb_beats_1f1b_v4_roofline_at_bench_geometry():
    # the ISSUE's done bar, as arithmetic: zb v=1 under the analytic
    # 1F1B interleave-v4 fractions the bubble study recorded
    for n, M in [(4, 16), (8, 32)]:
        zb = pp_bubble_fraction(n, M, 1, schedule="zb")["bubble_fraction"]
        v4 = pp_bubble_fraction(n, M, 4)["bubble_fraction"]
        assert zb < v4, (n, M, zb, v4)


def test_interleave_layer_perm_roundtrip():
    for L, n, v in [(8, 4, 2), (8, 2, 2), (12, 2, 3), (16, 4, 2),
                    (8, 4, 1), (6, 3, 2)]:
        perm = interleave_layer_perm(L, n, v)
        assert sorted(perm.tolist()) == list(range(L)), (L, n, v)
        logical = np.arange(L)
        stored = logical[perm]
        # invert with argsort: stored[argsort(perm)] == logical
        np.testing.assert_array_equal(stored[np.argsort(perm)], logical)
        # stage s's contiguous block is its v round-robin chunks in
        # chunk-major order
        Lc = L // (n * v)
        block = stored[: v * Lc]
        expect = np.concatenate(
            [np.arange(c * n * Lc, c * n * Lc + Lc) for c in range(v)]
        )
        np.testing.assert_array_equal(block, expect)


# ------------------------------------------------ loud rejections


def test_factory_rejects_bad_zb_compositions(devices):
    mesh = ddp.make_mesh(("data", "pipe"), shape=(2, 4))
    with pytest.raises(ValueError, match="cp_axis"):
        make_pp_train_step(
            _scan_cfg(cp_axis="seq"), mesh=mesh, microbatches=4,
            schedule="zb",
        )
    with pytest.raises(ValueError, match="aux"):
        make_pp_train_step(
            _scan_cfg(moe_experts=2), mesh=mesh, microbatches=4,
            schedule="zb", moe_aux_weight=0.01,
        )
    with pytest.raises(ValueError, match="schedule"):
        make_pp_train_step(
            _scan_cfg(), mesh=mesh, microbatches=4, schedule="zb2",
        )
    # gpipe still rejects virtual; 1f1b/zb accept it
    with pytest.raises(ValueError, match="virtual"):
        make_pp_train_step(
            _scan_cfg(num_layers=8), mesh=mesh, microbatches=4,
            schedule="gpipe", virtual=2,
        )


def test_dpp_cli_zb_validation():
    base = ["--device", "cpu", "--fake-devices", "8", "--model", "gpt2",
            "--dataset", "synthetic-lm", "--pp", "4"]
    # microbatch minimum: fewer microbatches than stages has no steady
    # state for W to fill
    with pytest.raises(SystemExit, match="--pp-microbatches >= --pp"):
        dpp.validate_args(dpp.parse_args(
            base + ["--pp-schedule", "zb", "--pp-microbatches", "2"]
        ))
    # unsupported composition: context parallel
    with pytest.raises(SystemExit, match="does not compose with --cp"):
        dpp.validate_args(dpp.parse_args(
            base + ["--pp-schedule", "zb", "--cp", "2"]
        ))
    # unsupported composition: MoE aux loss (default aux weight is on)
    with pytest.raises(SystemExit, match="MoE aux loss"):
        dpp.validate_args(dpp.parse_args(
            base + ["--pp-schedule", "zb", "--moe-experts", "4"]
        ))
    # layer divisibility extends to pp x virtual
    with pytest.raises(SystemExit, match="divisible by --pp"):
        dpp.validate_args(dpp.parse_args(
            base + ["--pp-schedule", "zb", "--layers", "6"]
        ))
    # virtual now composes with zb (and still rejects gpipe)
    dpp.validate_args(dpp.parse_args(
        base + ["--pp-schedule", "zb", "--pp-virtual", "2",
                "--layers", "8"]
    ))
    with pytest.raises(SystemExit, match="--pp-schedule 1f1b or zb"):
        dpp.validate_args(dpp.parse_args(
            base + ["--pp-schedule", "gpipe", "--pp-virtual", "2"]
        ))
    # the happy path validates clean
    dpp.validate_args(dpp.parse_args(
        base + ["--pp-schedule", "zb", "--pp-microbatches", "8"]
    ))


# ------------------------------------------------ measured reconstruction


def test_measured_bubble_roundtrip_through_events(devices, tmp_path):
    """Close the loop the way a real run does: compiled zb step ->
    phase counters -> pp_phase event -> merged timeline ->
    measured_bubble_fraction; measured must equal the factory's
    analytic number exactly (same schedule, zero drift)."""
    from distributeddataparallel_tpu.observability.events import (
        EventLog,
        events_path,
        load_timeline,
    )
    from distributeddataparallel_tpu.observability.pipeline import (
        measured_bubble_fraction,
        phase_counts_payload,
    )
    from distributeddataparallel_tpu.observability.schema import (
        validate_file,
    )

    cfg = _scan_cfg()
    mesh = ddp.make_mesh(("data", "pipe"), shape=(2, 4))
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32)
    )["params"]
    tokens = np.random.default_rng(0).integers(
        0, 256, size=(8, 33)
    ).astype(np.int32)
    step = make_pp_train_step(
        cfg, mesh=mesh, microbatches=4, donate=False, schedule="zb"
    )
    state = shard_state_pp(
        ddp.TrainState.create(apply_fn=None, params=params,
                              tx=optax.sgd(0.1)),
        mesh,
    )
    _, metrics = step(state, shard_batch({"tokens": tokens}, mesh),
                      jax.random.PRNGKey(0))

    edir = str(tmp_path / "events")
    with EventLog(events_path(edir, 0), proc=0) as log:
        log.emit("pp_phase", **phase_counts_payload(
            jax.device_get(metrics["pp_phase_counts"]),
            schedule="zb", n_stages=4, virtual=1, microbatches=4,
            accounting=step.bubble_accounting,
        ))
    assert validate_file(events_path(edir, 0)) == []

    rec = measured_bubble_fraction(load_timeline(edir))
    assert rec is not None
    acct = step.bubble_accounting
    assert rec["schedule"] == "zb" and rec["n_stages"] == 4
    assert rec["measured_bubble_fraction"] == pytest.approx(
        acct["bubble_fraction"], abs=1e-4
    )
    assert rec["analytic_bubble_fraction"] == acct["bubble_fraction"]
    assert [s["useful_slots"] for s in rec["per_stage"]] == [12, 12, 12, 12]

    # degrade path: a timeline with no pp_phase records reconstructs
    # to None (the report's "not a pipeline run" line)
    assert measured_bubble_fraction([{"kind": "span"}]) is None
    assert measured_bubble_fraction([]) is None
