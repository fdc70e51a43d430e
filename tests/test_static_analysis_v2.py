"""ddplint v2: sharding-flow pass (SF2xx), schedule-as-data lint
(SL3xx), and the compile-only mesh simulator — mutation tests (each
seeded violation must fire its distinct rule id) plus the CLI/store
wiring.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

import distributeddataparallel_tpu as ddp
from distributeddataparallel_tpu.analysis import (
    mesh_sim,
    schedule_lint,
    shard_flow,
)
from distributeddataparallel_tpu.analysis.rules import RULES, Finding
from distributeddataparallel_tpu.analysis.schedule_lint import (
    grad_sync_schedule_ir,
    gpipe_schedule_ir,
    lint_schedule,
    one_f_one_b_schedule_ir,
    zb_schedule_ir,
)
from distributeddataparallel_tpu.observability import baseline as bl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))
import ddplint  # noqa: E402
import perf_gate  # noqa: E402

# ---------------------------------------------------------------------
# sharding-flow pass (SF201-SF204)
# ---------------------------------------------------------------------

MAN_DP = {"mode": "dp", "grad_reduce": {"data": {"psum": (1, None)}}}
MAN_ZERO = {
    "mode": "zero",
    "grad_reduce": {"data": {"reduce_scatter": (1, None),
                             "psum": (0, None)}},
}
MAN_GATHER = {
    "mode": "fsdp",
    "grad_reduce": {"data": {"all_gather": (1, None),
                             "reduce_scatter": (1, None),
                             "psum": (0, None)}},
}


@pytest.fixture(scope="module")
def mesh(devices):
    return ddp.make_mesh(("data",))


def _lowered_text(fn, mesh, *args, in_specs, out_specs=P()):
    sm = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(sm).lower(*args).as_text()


def test_sf201_replicated_gradient_anomaly(mesh):
    # a dp-style dense all_reduce linted under a ZeRO manifest: the
    # sharded-optimizer contract says gradient payloads reduce-scatter
    text = _lowered_text(
        lambda x: jax.lax.psum(x, "data"), mesh,
        jnp.ones((64,), jnp.float32), in_specs=(P("data"),),
    )
    rep = shard_flow.lint_flow(
        text, manifest=MAN_ZERO, grad_bytes_floor=16,
    )
    assert "SF201" in {f.rule for f in rep.findings}
    # the same program under its own dp manifest is clean
    assert shard_flow.lint_flow(text, manifest=MAN_DP).ok


def test_sf202_reshard_in_loop(mesh):
    # all_gather of a LOOP-INVARIANT value inside a fori_loop: the
    # gather hoists, paying wire bytes every iteration for nothing
    def body(w, x):
        def it(i, acc):
            full = jax.lax.all_gather(w, "data", tiled=True)
            return acc + jnp.sum(full) + x[0, 0]

        return jax.lax.fori_loop(0, 6, it, 0.0)

    text = _lowered_text(
        body, mesh,
        jnp.arange(64, dtype=jnp.float32), jnp.ones((8, 4), jnp.float32),
        in_specs=(P("data"), P("data")),
    )
    rep = shard_flow.lint_flow(text, manifest=MAN_DP)
    assert "SF202" in {f.rule for f in rep.findings}


def test_parse_module_recovers_loop_context(mesh):
    # XLA outlines fori_loop bodies into private functions called from
    # the while region — the parser must still see the gather as
    # in-loop with an invariant operand
    def body(w):
        def it(i, acc):
            return acc + jnp.sum(jax.lax.all_gather(w, "data", tiled=True))

        return jax.lax.fori_loop(0, 6, it, 0.0)

    text = _lowered_text(
        body, mesh, jnp.arange(64, dtype=jnp.float32),
        in_specs=(P("data"),),
    )
    _, colls = shard_flow.parse_module(text)
    gathers = [c for c in colls if c.op == "all_gather"]
    assert gathers, "lowering lost the all_gather"
    assert any(
        c.in_loop and any(c.loop_invariant_operands) for c in gathers
    )


def test_sf203_gather_exceeds_hbm_budget(mesh):
    text = _lowered_text(
        lambda x: jax.lax.all_gather(x, "data", tiled=True), mesh,
        jnp.ones((64,), jnp.float32), in_specs=(P("data"),),
        out_specs=P(),
    )
    # result is 64 x f32 = 256 bytes; a 100-byte "HBM" cannot hold it
    rep = shard_flow.lint_flow(
        text, manifest=MAN_GATHER, hbm_budget_bytes=100,
    )
    assert "SF203" in {f.rule for f in rep.findings}
    assert shard_flow.lint_flow(
        text, manifest=MAN_GATHER, hbm_budget_bytes=1 << 30,
    ).ok


def test_sf204_custom_vjp_hides_collective(mesh):
    @jax.custom_vjp
    def sneaky(x):
        return jax.lax.psum(x, "data")

    sneaky.defvjp(lambda x: (sneaky(x), None), lambda res, g: (g,))

    def prog(x):
        return jnp.sum(sneaky(x))

    sm = jax.shard_map(
        prog, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
        check_vma=False,
    )
    jaxpr = jax.make_jaxpr(sm)(jnp.ones((64,), jnp.float32))
    found = shard_flow.lint_custom_vjp(
        jaxpr, manifest=MAN_DP, where="flow:test"
    )
    assert {f.rule for f in found} == {"SF204"}
    # the manifest waiver acknowledges an intentional in-vjp collective
    waived = shard_flow.lint_custom_vjp(
        jaxpr,
        manifest={**MAN_DP, "custom_vjp_collectives_ok": True},
        where="flow:test",
    )
    assert waived == []


def test_flow_clean_on_live_factories(mesh):
    from distributeddataparallel_tpu.training.train_step import (
        make_train_step,
    )

    params = {"w": jnp.ones((8, 4)), "b": jnp.ones((4,))}
    batch = {"x": jnp.ones((8, 8)), "y": jnp.ones((8, 4))}

    def loss_fn(p, b, _rng):
        pred = b["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - b["y"]) ** 2), {}

    for kw in ({}, {"zero": True}):
        step = make_train_step(loss_fn, mesh=mesh, **kw)
        state = ddp.TrainState.create(
            apply_fn=None, params=params, tx=optax.sgd(0.1)
        )
        if kw.get("zero"):
            from distributeddataparallel_tpu.parallel.zero import (
                zero_state,
            )

            state = zero_state(
                apply_fn=None, params=params, tx=optax.sgd(0.1),
                mesh=mesh,
            )
        rep = shard_flow.analyze_step(
            step, state, batch, jax.random.PRNGKey(0)
        )
        assert rep.ok, [str(f) for f in rep.findings]
        assert rep.collectives


# ---------------------------------------------------------------------
# schedule-as-data lint (SL301-SL304)
# ---------------------------------------------------------------------


def test_1f1b_table_matches_factory_accounting():
    from distributeddataparallel_tpu.parallel.pipeline_parallel import (
        pp_bubble_fraction,
    )

    # independent derivations: table census vs the factory's closed form
    for n, m, v in [(2, 2, 1), (4, 8, 1), (4, 6, 1), (2, 4, 2),
                    (4, 8, 2), (3, 7, 1)]:
        ir = one_f_one_b_schedule_ir(n, m, v)
        acct = pp_bubble_fraction(n, m, v)
        assert abs(ir.bubble_fraction() - acct["bubble_fraction"]) < 5e-4, (
            (n, m, v)
        )
        assert lint_schedule(ir, bubble=acct) == []


def test_zb_table_matches_factory_accounting():
    from distributeddataparallel_tpu.parallel.pipeline_parallel import (
        pp_bubble_fraction,
    )

    # same cross-check for the zero-bubble table: the IR derives its
    # phase windows from its own unit extents, the factory from
    # _zb_segments — independent arithmetic that must agree exactly
    for n, m, v in [(2, 2, 1), (2, 4, 1), (4, 8, 1), (4, 16, 1),
                    (2, 4, 2), (4, 8, 2), (8, 32, 1), (3, 7, 1)]:
        ir = zb_schedule_ir(n, m, v)
        acct = pp_bubble_fraction(n, m, v, schedule="zb")
        assert abs(ir.bubble_fraction() - acct["bubble_fraction"]) < 5e-4, (
            (n, m, v)
        )
        assert lint_schedule(ir, bubble=acct) == [], (n, m, v)
        # zb keeps W work on the table: every (stage, chunk, microbatch)
        # triple contributes exactly one F, one B, and one W unit
        phases = [u.phase for u in ir.units]
        assert phases.count("F") == phases.count("B") == \
            phases.count("W") == n * m * v


def test_sl301_zb_w_before_b_fires():
    import dataclasses

    ir = zb_schedule_ir(4, 8)
    assert lint_schedule(ir) == []
    units = list(ir.units)
    # drag one W unit to before its B: weight grads need the incoming
    # cotangent, so a W ahead of its B is an impossible schedule
    for i, u in enumerate(units):
        if u.phase == "W" and u.tick > 0:
            units[i] = dataclasses.replace(u, tick=0)
            break
    broken = dataclasses.replace(ir, units=tuple(units))
    assert "SL301" in {f.rule for f in lint_schedule(broken)}


def test_sl302_zb_dropped_and_extra_hop_fire():
    ir = zb_schedule_ir(4, 8)
    manifest = {"grad_reduce": {ir.hop_axis: {"ppermute": (1, None)}}}
    assert ir.hops_total is not None
    ok = lint_schedule(ir, manifest=manifest, traced_hops=ir.hops_total)
    assert ok == [], [str(f) for f in ok]
    # dropped boundary hop (a ppermute optimized away / miscounted)
    assert "SL302" in {
        f.rule for f in lint_schedule(
            ir, manifest=manifest, traced_hops=ir.hops_total - 1
        )
    }
    # extra hop (double-send)
    assert "SL302" in {
        f.rule for f in lint_schedule(
            ir, manifest=manifest, traced_hops=ir.hops_total + 1
        )
    }


def test_sl304_zb_bubble_drift_fires():
    ir = zb_schedule_ir(4, 16)
    assert lint_schedule(ir, bubble=ir.bubble_fraction()) == []
    # seeded mutant: factory accounting that disagrees with the table
    assert "SL304" in {
        f.rule
        for f in lint_schedule(ir, bubble=ir.bubble_fraction() + 0.05)
    }


def test_sl301_missing_unit_fires():
    import dataclasses

    ir = gpipe_schedule_ir(4, 4)
    broken = dataclasses.replace(ir, units=ir.units[:-1])
    assert "SL301" in {f.rule for f in lint_schedule(broken)}


def test_sl301_backward_before_forward_fires():
    import dataclasses

    ir = one_f_one_b_schedule_ir(2, 2)
    units = list(ir.units)
    # find a B unit whose F is later in the warm-up and swap its tick
    # to before the matching forward
    for i, u in enumerate(units):
        if u.phase == "B" and u.tick > 0:
            units[i] = dataclasses.replace(u, tick=0)
            break
    broken = dataclasses.replace(ir, units=tuple(units))
    assert "SL301" in {f.rule for f in lint_schedule(broken)}


def test_sl302_undeclared_hop_and_count_mismatch():
    ir = grad_sync_schedule_ir(3)
    ok_manifest = {"grad_reduce": {"data": {"psum": (1, None)}}}
    assert lint_schedule(ir, manifest=ok_manifest, traced_hops=3) == []
    # hop primitive absent from the manifest's axis entry
    assert "SL302" in {
        f.rule for f in lint_schedule(ir, manifest={"grad_reduce": {}})
    }
    # exact-hop schedule traced with one extra collective (double sync)
    assert "SL302" in {
        f.rule
        for f in lint_schedule(ir, manifest=ok_manifest, traced_hops=4)
    }


def test_sl303_ring_too_small_fires():
    import dataclasses

    ir = one_f_one_b_schedule_ir(4, 8, virtual=2)
    assert lint_schedule(ir) == []
    broken = dataclasses.replace(
        ir, ring={"n_slots": 3, "modulus": ir.ring["modulus"]}
    )
    assert "SL303" in {f.rule for f in lint_schedule(broken)}


def test_sl304_bubble_drift_fires():
    ir = one_f_one_b_schedule_ir(4, 8)
    assert lint_schedule(ir, bubble=ir.bubble_fraction()) == []
    assert "SL304" in {
        f.rule
        for f in lint_schedule(ir, bubble=ir.bubble_fraction() + 0.05)
    }


def test_pp_factory_attaches_schedule_ir(devices):
    from distributeddataparallel_tpu.models import tiny_lm
    from distributeddataparallel_tpu.parallel import make_pp_train_step

    mesh2 = ddp.make_mesh(("data", "pipe"), shape=(2, 4))
    cfg = tiny_lm(
        num_layers=4, num_heads=2, d_model=32, d_ff=64,
        max_seq_len=32, scan_layers=True,
    )
    for schedule in ("gpipe", "1f1b", "zb"):
        step = make_pp_train_step(
            cfg, mesh=mesh2, microbatches=4, schedule=schedule,
        )
        ir = step.schedule_ir
        assert ir.kind == schedule
        assert ir.n_stages == 4 and ir.n_microbatches == 4
        findings = lint_schedule(
            ir,
            manifest=step.collective_manifest,
            bubble=step.bubble_accounting,
        )
        assert findings == [], [str(f) for f in findings]


def test_bucketed_step_attaches_comm_schedule(mesh):
    from distributeddataparallel_tpu.training.train_step import (
        make_train_step,
    )

    params = {"w": jnp.ones((8, 4)), "b": jnp.ones((4,))}

    def loss_fn(p, b, _rng):
        return jnp.mean((b["x"] @ p["w"] + p["b"]) ** 2), {}

    step = make_train_step(loss_fn, mesh=mesh, bucket_bytes=1 << 20)
    ir = step.comm_schedule(params)
    assert ir.kind == "grad-sync"
    assert ir.hop_prim == "psum" and ir.hop_axis == "data"
    assert lint_schedule(ir, manifest=step.collective_manifest) == []
    # unbucketed plain-dp steps carry no schedule IR
    plain = make_train_step(loss_fn, mesh=mesh)
    assert getattr(plain, "comm_schedule", None) is None


# ---------------------------------------------------------------------
# mesh simulation + baseline-store round trip
# ---------------------------------------------------------------------


def test_mesh_sim_record_roundtrips_store(devices, tmp_path):
    record = mesh_sim.simulate("cnn", "dp", batch_per_chip=2)
    assert record["record"] == "mesh_sim"
    assert record["devices"] == len(jax.devices())
    assert record["findings"] == []
    assert record["fit"]["fits"] is True
    assert record["headline"]["sim_required_bytes"] == \
        record["fit"]["required_bytes"]

    store = str(tmp_path / "runs")
    name = mesh_sim.fingerprint(record)
    bl.append_run(store, record, name=name, source="meshsim")
    runs = bl.read_runs(store)
    assert len(runs) == 1
    assert runs[0]["name"] == name
    assert runs[0]["headline"] == record["headline"]


def test_mesh_sim_record_gates_as_bench(devices, tmp_path):
    record = mesh_sim.simulate("cnn", "dp")
    path = tmp_path / "sim.json"
    path.write_text(json.dumps(record))
    flat, source = perf_gate.load_run(str(path))
    assert source == "bench"
    assert flat["sim_required_bytes"] == record["fit"]["required_bytes"]
    # every sim headline metric is bytes-suffixed -> lower-is-better
    metrics = perf_gate.gate_metrics_for(flat, source, 0.05)
    assert all(d == "lower" for d, _tol in metrics.values())


def test_mesh_sim_budget_miss_reported(devices):
    record = mesh_sim.simulate("cnn", "dp", hbm_budget_bytes=1024)
    assert record["fit"]["fits"] is False


@pytest.mark.slow
def test_meshsim_cli_worker_roundtrip(tmp_path):
    # one orchestrated case end to end in a fresh interpreter
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "ddp_meshsim.py"),
         "--model", "cnn", "--mode", "dp", "--devices", "8", "--json",
         "--store", str(tmp_path / "runs")],
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    record = json.loads(out.stdout.strip().splitlines()[-1])
    assert record["model"] == "cnn" and record["devices"] == 8
    assert bl.read_runs(str(tmp_path / "runs"))


# ---------------------------------------------------------------------
# ddplint CLI: --changed-only, --events-dir, rule-id registry gate
# ---------------------------------------------------------------------

_VIOLATION = "events.emit('sa2_ghost_kind', step=1)\n"


def _git(cwd, *argv):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *argv],
        cwd=cwd, check=True, capture_output=True,
    )


@pytest.fixture()
def lint_repo(tmp_path):
    """A tiny git repo shaped like the tree ddplint targets: dpp.py at
    the root plus a scripts/ dir, one committed violation in each."""
    (tmp_path / "scripts").mkdir()
    (tmp_path / "dpp.py").write_text("x = 1\n")
    (tmp_path / "scripts" / "util.py").write_text(_VIOLATION)
    (tmp_path / "README.md").write_text("hi\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    return tmp_path


def test_changed_only_dirty_tree_narrows_targets(lint_repo):
    # dirty file gains a violation; the committed violation in
    # scripts/util.py is untouched and must NOT be linted
    (lint_repo / "dpp.py").write_text(_VIOLATION)
    findings = ddplint.run_ast(True, root=lint_repo)
    assert findings and all(f.where.startswith("dpp.py") for f in findings)
    # the full run still sees both
    full = ddplint.run_ast(False, root=lint_repo)
    assert {f.where.split(":")[0] for f in full} == {
        "dpp.py", "scripts/util.py"
    }


def test_changed_only_renamed_file_lints_new_path(lint_repo):
    _git(lint_repo, "mv", "scripts/util.py", "scripts/renamed.py")
    (lint_repo / "scripts" / "renamed.py").write_text(_VIOLATION)
    findings = ddplint.run_ast(True, root=lint_repo)
    assert findings
    assert all(
        f.where.startswith("scripts/renamed.py") for f in findings
    )


def test_changed_only_no_python_changes(lint_repo, monkeypatch, capsys):
    (lint_repo / "README.md").write_text("only docs changed\n")
    assert ddplint.run_ast(True, root=lint_repo) == []
    # the graph layer is skipped outright: no step-defining paths moved
    monkeypatch.setattr(ddplint, "ROOT", lint_repo)
    assert ddplint.main(["--graph", "--changed-only"]) == 0
    out = capsys.readouterr().out
    assert "skipped (no step-defining changes)" in out


def test_events_dir_emits_schema_valid_lint_report(tmp_path, capsys):
    from distributeddataparallel_tpu.observability.schema import (
        validate_file,
    )

    assert ddplint.main(
        ["--ast", "--events-dir", str(tmp_path)]
    ) == 0
    path = tmp_path / "events-lint.jsonl"
    assert path.exists()
    assert validate_file(path) == []
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["kind"] for r in recs] == ["lint_report"]
    assert recs[0]["layer"] == "ast" and recs[0]["n_findings"] == 0


def test_unregistered_rule_id_is_operational_error(monkeypatch, capsys):
    monkeypatch.setattr(
        ddplint, "run_ast",
        lambda *a, **k: [Finding("ZZ999", "x.py:1", "made-up rule")],
    )
    assert ddplint.main(["--ast"]) == 2
    assert "ZZ999" in capsys.readouterr().err


def test_new_rules_registered():
    for rid in ("SF201", "SF202", "SF203", "SF204",
                "SL301", "SL302", "SL303", "SL304"):
        assert rid in RULES, rid
