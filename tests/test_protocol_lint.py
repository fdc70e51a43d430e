"""ddplint v3: protocol-as-data model checker (PL4xx), timeline
conformance (PL405), the sync_lint concurrency AST rules (AL105-AL108),
and the consolidated perf_gate direction table.

The load-bearing contracts:

- every healthy shipped spec explores EXHAUSTIVELY (complete=True) and
  clean at CI scope (>=2 actors, >=1 fault) in seconds, so the protocol
  gate can run on every commit;
- every seeded mutant — one per rule id — is caught by exactly the
  intended rule, with a minimal counterexample trace on PL401;
- the conformance replay accepts the timeline an actual in-process
  fleet run (including an engine kill and drain-requeue) records, and
  rejects each hand-corrupted variant;
- the live modules and the checked specs share their constants
  (handoff.MAX_ATTEMPTS, the verdict ladder, the re-host election), so
  the plan the checker explores is the plan the runtime executes;
- perf_gate's ordered direction table classifies every metric name the
  bench headline actually emits the way the bench scripts document.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

sys.path.insert(0, "/root/repo")
sys.path.insert(0, os.path.join("/root/repo", "scripts"))

from distributeddataparallel_tpu.analysis import (  # noqa: E402
    ast_rules,
    conformance,
    protocol,
    sync_lint,
)
from distributeddataparallel_tpu.analysis.protocol import (  # noqa: E402
    HANDOFF_MAX_ATTEMPTS,
    Transition,
    allocator_spec,
    elect_rehost_owner,
    handoff_spec,
    rendezvous_spec,
    router_spec,
    verdict_rung,
)
from distributeddataparallel_tpu.analysis.rules import (  # noqa: E402
    RULES,
    rule_table,
)

import check_events  # noqa: E402
import perf_gate  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NEW_RULES = (
    "AL105", "AL106", "AL107", "AL108",
    "PL401", "PL402", "PL403", "PL404", "PL405", "PL406",
)


# ------------------------------------------------------- registration


def test_new_rules_registered():
    for rid in NEW_RULES:
        assert rid in RULES, rid
    table = rule_table()
    for rid in NEW_RULES:
        assert rid in table, rid


def test_live_modules_share_spec_constants():
    from distributeddataparallel_tpu.runtime.rendezvous import elect_rehost
    from distributeddataparallel_tpu.serving.handoff import MAX_ATTEMPTS

    assert MAX_ATTEMPTS == HANDOFF_MAX_ATTEMPTS
    assert elect_rehost(["h2", "h0", "h1"]) == "h0"
    assert elect_rehost_owner(["h2", "h0", "h1"]) == "h0"
    with pytest.raises(ValueError):
        elect_rehost_owner([])
    assert verdict_rung(True) == "drain"
    assert verdict_rung(False) == "fail"


# --------------------------------------------- healthy specs explore


def test_healthy_specs_exhaustive_and_clean():
    t0 = time.monotonic()
    reports = protocol.explore_all()
    elapsed = time.monotonic() - t0
    assert len(reports) == 4
    for rep in reports:
        assert rep.ok, (rep.spec.name, [str(f) for f in rep.findings])
        assert rep.complete, rep.spec.name
        assert rep.n_states > 0
    # CI budget: the acceptance bound is 30s; the suite is ~100x under
    assert elapsed < 30.0, f"exploration took {elapsed:.1f}s"


def test_spec_scope_has_actors_and_faults():
    # >=2 actors and >=1 fault action per distributed spec — the
    # small-scope hypothesis needs both to mean anything
    rdzv = rendezvous_spec()
    rout = router_spec()
    hand = handoff_spec()
    names = lambda s: {t.name for t in s.transitions}  # noqa: E731
    assert len({m for m, _st in rdzv.init()[0]}) >= 2
    assert "tombstone" in names(rdzv)
    assert "engine_die" in names(rout)
    assert "corrupt" in names(hand)


# ------------------------------------------------------ seeded mutants


def _rules_of(spec):
    rep = protocol.explore(spec)
    return {f.rule for f in rep.findings}, rep


@pytest.mark.parametrize("spec_fn,rule,needle", [
    (lambda: rendezvous_spec(fence=False), "PL401", "epoch-unique"),
    (lambda: rendezvous_spec(elect=lambda s: sorted(s)[-1]),
     "PL401", "rehost-owner"),
    (lambda: rendezvous_spec(barrier_guard=False),
     "PL401", "tombstone-barrier"),
    (lambda: router_spec(affinity_uses_prefill=True),
     "PL401", "affinity-tier"),
    (lambda: router_spec(complete_purges=False),
     "PL401", "drop-vs-complete"),
    (lambda: handoff_spec(dedup=False), "PL401", "at-most-once"),
    (lambda: allocator_spec(cow=False), "PL401", "cow-before-write"),
    (lambda: allocator_spec(conserve=False),
     "PL401", "refcount-conservation"),
])
def test_mutant_trips_invariant(spec_fn, rule, needle):
    rules, rep = _rules_of(spec_fn())
    assert rule in rules, (rep.spec.name, rules)
    msgs = [f.message for f in rep.findings if f.rule == rule]
    assert any(needle in m for m in msgs), msgs
    # PL401 counterexamples carry the minimal trace from the initial
    # state (BFS order): always present, bounded, starts at init
    for m in msgs:
        assert "init" in m, m


def test_mutant_escalate_missing_deadlocks():
    rules, rep = _rules_of(handoff_spec(escalate=False))
    assert "PL402" in rules, rules


def test_mutant_unreachable_state_pl403():
    spec = handoff_spec()
    spec = dataclasses.replace(spec, states=spec.states + ("limbo",))
    rules, rep = _rules_of(spec)
    assert "PL403" in rules, rules
    assert any("limbo" in f.message for f in rep.findings)


def test_mutant_dead_transition_pl404():
    spec = handoff_spec()
    spec = dataclasses.replace(
        spec,
        transitions=spec.transitions
        + (Transition("never_fires", "unsent", "failed"),),
    )
    rules, rep = _rules_of(spec)
    assert "PL404" in rules, rules
    assert any("never_fires" in f.message for f in rep.findings)


def test_mutant_malformed_spec_pl406():
    spec = dataclasses.replace(handoff_spec(), initial="bogus")
    rules, _rep = _rules_of(spec)
    assert "PL406" in rules, rules


# ------------------------------------------------------ sync_lint (AL)


def _lint(src, rel="distributeddataparallel_tpu/runtime/x.py"):
    return sync_lint.lint_source(src, rel)


def test_al105_blocking_socket():
    src = (
        "import socket\n"
        "def dial(h, p):\n"
        "    return socket.create_connection((h, p))\n"
    )
    assert [f.rule for f in _lint(src)] == ["AL105"]


def test_al105_waived_by_pragma():
    src = (
        "import socket\n"
        "def dial(h, p):\n"
        "    # ddplint: allow[blocking-socket] — caller retries\n"
        "    return socket.create_connection((h, p))\n"
    )
    assert _lint(src) == []


def test_al105_retry_call_covers_even_later_in_file():
    # the retry_call wrapper may appear AFTER the dial helper in file
    # order; the pre-pass must still credit it
    src = (
        "import socket\n"
        "def _dial(h, p):\n"
        "    return retry_call(lambda: socket.create_connection((h, p)))\n"
    )
    assert _lint(src) == []


def test_al106_wallclock_only_in_virtual_modules():
    src = (
        "import time\n"
        "def pump(self):\n"
        "    return time.monotonic()\n"
    )
    rel = "distributeddataparallel_tpu/serving/router.py"
    assert [f.rule for f in _lint(src, rel)] == ["AL106"]
    # same source outside the VirtualClock-replayable set: clean
    assert _lint(src, "distributeddataparallel_tpu/training/x.py") == []


def test_al107_host_sync_in_serve_loop():
    src = (
        "import numpy as np\n"
        "def step(self, x):\n"
        "    return np.asarray(x)\n"
        "def build(self, x):\n"
        "    return np.asarray(x)\n"
    )
    rel = "distributeddataparallel_tpu/serving/engine.py"
    found = _lint(src, rel)
    # only the serve-loop-shaped function (step) is flagged, not build
    assert [f.rule for f in found] == ["AL107"]
    assert "step()" in found[0].message


def test_al108_lock_discipline():
    src = (
        "import threading\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._items = []\n"
        "    def put(self, x):\n"
        "        with self._lock:\n"
        "            self._items.append(x)\n"
        "    def drop(self):\n"
        "        self._items.pop()\n"
    )
    found = _lint(src)
    assert [f.rule for f in found] == ["AL108"]
    assert "drop()" in found[0].message


def test_tree_is_sync_lint_clean():
    # the shipped tree carries justified pragmas at every intentional
    # site; anything new must justify itself the same way
    targets = ast_rules.default_targets(REPO)
    assert sync_lint.lint_paths(targets, REPO) == []


# -------------------------------------------------- conformance (PL405)


def _clean_timeline():
    return [
        {"kind": "membership_epoch", "epoch": 1,
         "roster": ["h0", "h1", "h2"], "proc": 0},
        {"kind": "rdzv_rehost", "owner": "h0", "generation": 1},
        {"kind": "gang_verdict", "rung": "resize", "fault": "host-kill"},
        {"kind": "route_admit", "req": 0, "engine": "d0",
         "prefill": "p0", "affinity": False},
        {"kind": "kv_handoff", "req": 0, "attempts": 2},
        {"kind": "engine_verdict", "engine": "d0", "rung": "drain"},
        {"kind": "route_admit", "req": 0, "engine": "d1",
         "prefill": None, "affinity": False},
        {"kind": "route_admit", "req": 1, "engine": "d1",
         "prefill": None, "affinity": True},
    ]


def test_conformance_clean_timeline_passes():
    assert conformance.check_timeline(_clean_timeline()) == []


@pytest.mark.parametrize("corrupt,needle", [
    # affinity hit that still owns a prefill engine
    (lambda t: t.__setitem__(7, {
        "kind": "route_admit", "req": 1, "engine": "d1",
        "prefill": "p0", "affinity": True}), "affinity"),
    # same epoch committed with a different roster
    (lambda t: t.insert(1, {
        "kind": "membership_epoch", "epoch": 1,
        "roster": ["h0", "h1"], "proc": 1}), "forked membership"),
    # per-writer epoch going backwards
    (lambda t: t.insert(1, {
        "kind": "membership_epoch", "epoch": 0,
        "roster": ["h0", "h1", "h2"], "proc": 0}), "backwards"),
    # re-host onto a host outside the committed roster
    (lambda t: t.__setitem__(1, {
        "kind": "rdzv_rehost", "owner": "zz", "generation": 1}),
     "rehost-owner"),
    # store generation not fencing its predecessor
    (lambda t: t.insert(2, {
        "kind": "rdzv_rehost", "owner": "h1", "generation": 1}),
     "fence"),
    # rung off the declared gang ladder
    (lambda t: t.__setitem__(2, {
        "kind": "gang_verdict", "rung": "shrug"}), "ladder"),
    # handoff attempts past the NAK budget
    (lambda t: t.__setitem__(4, {
        "kind": "kv_handoff", "req": 0,
        "attempts": HANDOFF_MAX_ATTEMPTS + 1}), "NAK budget"),
    # handoff for a request never admitted through prefill
    (lambda t: t.append({
        "kind": "kv_handoff", "req": 99, "attempts": 1}), "nowhere"),
    # routing onto a tombstoned engine
    (lambda t: t.append({
        "kind": "route_admit", "req": 2, "engine": "d0",
        "prefill": None, "affinity": False}), "tombstone"),
    # re-admission with no engine_verdict in between (double-own)
    (lambda t: t.insert(5, {
        "kind": "route_admit", "req": 0, "engine": "d1",
        "prefill": None, "affinity": False}), "double-own"),
    # an engine dying twice
    (lambda t: t.append({
        "kind": "engine_verdict", "engine": "d0", "rung": "drain"}),
     "at most once"),
    # rung off the declared engine ladder
    (lambda t: t.append({
        "kind": "engine_verdict", "engine": "d1", "rung": "explode"}),
     "declared"),
])
def test_conformance_catches_corruption(corrupt, needle):
    timeline = _clean_timeline()
    corrupt(timeline)
    found = conformance.check_timeline(timeline)
    assert found, needle
    assert any(f.rule == "PL405" for f in found)
    assert any(needle in f.message for f in found), (
        needle, [str(f) for f in found],
    )


def test_conformance_ignores_foreign_kinds():
    # kinds outside the protocol vocabulary never trip the replay —
    # one checker serves training chaos AND serving fleet timelines
    records = [{"kind": "step", "step": 1}, {"kind": "mfu", "mfu": 0.1}]
    assert conformance.check_timeline(records) == []


# -------------------------------- conformance on a real fleet timeline


@pytest.fixture(scope="module")
def fleet_events_dir(tmp_path_factory):
    """One in-process fleet run — engine kill included — recorded to an
    events dir, shared by the conformance/CLI tests below."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddataparallel_tpu.models import TransformerLM, tiny_lm
    from distributeddataparallel_tpu.observability.events import (
        EventLog,
        events_path,
    )
    from distributeddataparallel_tpu.serving import (
        EngineConfig,
        FleetConfig,
        ServingFleet,
        VirtualClock,
    )

    out = tmp_path_factory.mktemp("fleet_events")
    cfg = tiny_lm(
        vocab_size=97, num_layers=2, num_heads=2, d_model=32, d_ff=64,
        max_seq_len=64, positional="learned", norm="layernorm",
        activation="gelu", tie_embeddings=True,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    events = EventLog(events_path(str(out), 0), 0)
    clock = VirtualClock()
    fleet = ServingFleet(
        model, params,
        EngineConfig(num_slots=4, num_blocks=48, block_size=8,
                     prefill_chunk=8),
        FleetConfig(prefill=1, decode=2),
        events=events, time_fn=clock, check_invariants=True,
    )
    rng = np.random.default_rng(7)
    fids = [
        fleet.submit(rng.integers(1, cfg.vocab_size, 16 + i).tolist(), 6)
        for i in range(5)
    ]
    for _ in range(3):
        fleet.step()
        clock.tick()
    fleet.kill_engine("decode-0")
    steps = 0
    while fleet.has_work():
        fleet.step()
        clock.tick()
        steps += 1
        assert steps < 800, "fleet failed to drain"
    assert sorted(fleet.completed) == sorted(fids)
    return str(out)


def test_fleet_recorded_timeline_is_conformant(fleet_events_dir):
    from distributeddataparallel_tpu.observability.events import (
        load_timeline,
    )

    records = load_timeline(fleet_events_dir)
    assert records, "fleet run recorded no events"
    kinds = {r["kind"] for r in records}
    # the run exercised the protocol vocabulary, not just run_start
    assert {"route_admit", "kv_handoff", "engine_verdict"} <= kinds
    assert conformance.check_timeline(records) == []


def test_check_events_cli_conformance(fleet_events_dir, tmp_path):
    # events DIR: merged on the fly, conformant
    assert check_events.main(["--conformance", fleet_events_dir]) == 0
    # hand-corrupt the merged timeline: duplicate the engine_verdict
    # (schema-valid record, protocol-invalid history) -> exit 1
    src = os.path.join(fleet_events_dir, "timeline.jsonl")
    lines = open(src).read().splitlines()
    verdict = next(
        ln for ln in lines if json.loads(ln)["kind"] == "engine_verdict"
    )
    bad = tmp_path / "timeline.jsonl"
    bad.write_text("\n".join(lines + [verdict]) + "\n")
    assert check_events.main(["--conformance", str(bad)]) == 1


# ------------------------------------------------- ddplint CLI (PL4xx)


def test_ddplint_protocol_cli_clean():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "ddplint.py"),
         "--protocol"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for spec in ("rendezvous", "router", "handoff", "allocator"):
        assert f"proto [{spec}] ok" in proc.stdout, proc.stdout


def test_ddplint_list_rules_covers_new_layers():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "ddplint.py"),
         "--list-rules"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for rid in ("PL401", "PL405", "AL105", "AL108"):
        assert rid in proc.stdout, rid


# --------------------------------------- perf_gate direction table


#: every numeric metric of the ``parsed.headline`` format perf_gate's
#: "bench" source reads (no script in the repo writes it any more —
#: ROADMAP D19), with its documented gate direction — the whole
#: contract the ordered _DIRECTION_TABLE must reproduce
BENCH_HEADLINE_DIRECTIONS = {
    "resnet50_img_s_chip": "higher",
    "resnet50_mfu": "higher",
    "gpt2_tok_s_chip": "higher",
    "gpt2_mfu": "higher",
    "llama_tok_s_chip": "higher",
    "llama_mfu": "higher",
    "decode_tok_s_chip_b256": "higher",
    "decode_hbm_util_b8": "higher",
    "decode_int8_llama_step_speedup": "higher",
    "decode_int8_gpt2_b8_step_speedup": "higher",
    "moe_e16_over_e4": "higher",
    "moe_roofline": "higher",
    "moe_ep_shard_frac_measured": "higher",
    "flash_vs_xla_block_speedup": "higher",
    "pp_interleaved_bubble_v4_over_v1": "lower",
    "zb_bubble_frac": "lower",
    "zb_step_s": "lower",
    "input_host_gather_img_s": "higher",
    "input_host_over_device": "higher",
    "token_gather_tok_s": "higher",
    "token_host_over_device": "higher",
    "resize_downtime_s": "lower",
    "restart_reclaimed_s": "higher",
    "integrity_overhead_frac": "lower",
    "z2_hwm_bytes": "lower",
    "z3_hwm_bytes": "lower",
    "z2_step_s": "lower",
    "z2_hwm_drop": "higher",
    "serve_tok_s": "higher",
    "serve_p99_ttft_s": "lower",
    "serve_cb_speedup": "higher",
    "spec_tok_s_speedup": "higher",
    "prefix_hit_frac": "higher",
    "prefill_flops_avoided_frac": "higher",
    "fastpath_p99_ttft_s": "lower",
    "fleet_tok_s_speedup": "higher",
    "fleet_p99_ttft_s": "lower",
    "handoff_s": "lower",
    "dropped_req_total": "hard-zero",
    "tuned_step_s": "lower",
    "tune_gain_frac": "higher",
    "ttft_queue_share_frac": "lower",
    "ttft_handoff_share_frac": "lower",
    "ttft_decomp_err_frac": "lower",
}


def test_bench_headline_directions_exhaustive():
    for name, want in BENCH_HEADLINE_DIRECTIONS.items():
        assert perf_gate._bench_direction(name) == want, name


def test_direction_table_order_carries_semantics():
    # row 1 (win suffixes) must beat row 4's broad cost patterns:
    # "step_speedup" CONTAINS "step_s", "_hit_frac" ends in "_frac",
    # "reclaimed_s" ends in "_s" and sits next to "restart"
    assert perf_gate._bench_direction("step_speedup") == "higher"
    assert perf_gate._bench_direction("restart_reclaimed_s") == "higher"
    # row 2 pins the TTFT-decomposition shares lower-better explicitly
    # — even a future "..._share_frac"-shaped win suffix in row 1 must
    # not flip them (and decomp error is never a win)
    assert perf_gate._bench_direction("ttft_queue_share_frac") == "lower"
    assert perf_gate._bench_direction("ttft_decomp_err_frac") == "lower"
    # row 3 (hard-zero) must beat row 4's plain "dropped"
    assert perf_gate._bench_direction("dropped_req_total") == "hard-zero"
    assert perf_gate._bench_direction("dropped_frames") == "lower"
    # unmatched names default higher
    assert perf_gate._bench_direction("goodput") == "higher"


def test_gate_metrics_for_maps_hard_zero_to_pairwise_lower():
    metrics = perf_gate.gate_metrics_for(
        {"dropped_req_total": 1.0, "serve_tok_s": 5.0, "handoff_s": 0.2},
        "bench", 0.05,
    )
    assert metrics["dropped_req_total"] == ("lower", 0.05)
    assert metrics["serve_tok_s"] == ("higher", 0.05)
    assert metrics["handoff_s"] == ("lower", 0.05)
