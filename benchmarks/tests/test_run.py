"""A whole run on the CPU at a tiny size: discovery by name, the result
line, the control, and `correct` coming out false under each fault."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness
from benchmarks.tests.conftest import ROOT

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]


def run(root, cell, trace=False, seed=2 ** 31 + 77):
    return harness.run_cell(cell, seed, 0.5, trace, root=root,
                            require_chip=False)[0]


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.train", {"train_tokens_s_chip", "setup_s"}),
    ("tiny.train4", {"train_tokens_s_chip", "setup_s"}),
    ("tiny.serve", {"serve_ttft_p90_ms", "serve_tpot_p90_ms",
                    "serve_tokens_s", "setup_s"}),
])
def test_new_files_are_found_by_name_and_the_line_has_the_contract_keys(
    tiny_root, cell, metrics
):
    """The tiny configuration, mixes and cells exist only as files dropped
    into a copy plus entries in its BENCHMARK.json."""
    result = run(tiny_root, cell)
    assert list(result) == RESULT_KEYS
    assert result["correct"] is True
    assert set(result["metrics"]) == metrics
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["attempted"] > 0 and result["failed"] == 0
    json.dumps(result)


def test_a_new_layer_metric_is_a_new_file(tiny_root):
    path = os.path.join(tiny_root, "benchmarks", "layer_metrics",
                        "steps_counted.py")
    with open(path, "w") as fh:
        fh.write("def read(ctx):\n    return ctx['measured']['steps']\n")
    module = harness.load_module("layer_metrics", "steps_counted", tiny_root)
    assert module.read({"measured": {"steps": 7}}) == 7
    # a reader that finds nothing to read returns nothing
    idle = harness.load_module("layer_metrics", "device_idle_frac.train")
    assert idle.read({"trace": None}) is None


def test_unknown_names_are_errors(tiny_root):
    with pytest.raises(harness.BenchmarkError):
        harness.load_cell("no.such.cell", tiny_root)
    with pytest.raises(harness.BenchmarkError):
        harness.load_peaks("TPU v99", tiny_root)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "gpt2-medium.train-b8x1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not 'tpu'" in proc.stderr


def test_same_seed_same_inputs_and_every_seed_the_same_sizes():
    from benchmarks import traffic

    mix = {"rate_rps": 20.0, "prompt_len": [4, 20], "output_len": [2, 10],
           "mix_seed": 1}
    a = traffic.make_trace(mix, 2 ** 31 + 5, 2.0, 128)
    b = traffic.make_trace(mix, 2 ** 31 + 5, 2.0, 128)
    c = traffic.make_trace(mix, 6, 2.0, 128)
    assert [r["arrival_s"] for r in a] == [r["arrival_s"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    sizes = lambda t: sorted(  # noqa: E731
        (len(r["prompt"]), r["max_new_tokens"]) for r in t
    )
    assert sizes(a) == sizes(c) and len(a) == 40
    assert [r["arrival_s"] for r in a] != [r["arrival_s"] for r in c]
    assert all(0 < r["arrival_s"] < 2.0 for r in a)


# -- the control, and the faults ------------------------------------------

def test_the_control_in_lower_precision_is_not_correct(tiny_root):
    """The reference put in the program's place and computed in fp8 fails
    the tiny cell's limits (on the chip the cells' own limits, PERF.md)."""
    from benchmarks import readings
    from benchmarks.reference import gpt2

    cell = harness.load_cell("tiny.train", tiny_root)
    train = harness.load_module("kinds", "train", tiny_root)
    import jax

    env = {"cell": cell, "config": cell["config"], "traffic": cell["traffic"],
           "devices": jax.devices()[:1], "seed": 5, "root": tiny_root,
           "spans": harness.Spans(), "window_s": 0.2}
    session = train.setup(env)
    session.release()
    control = readings.as_program(session.reference(quant=gpt2.fake_fp8))
    compared = train.compare(control, session.reference(),
                             cell["traffic"]["limits"])
    assert any(limit is not None and value > limit
               for _, value, limit in compared)
    assert all(value <= limit for _, value, limit in session.check())


def unchanged_state(real):
    def make(*args, **kwargs):
        step = real(*args, **kwargs)

        def broken(state, batch, rng):
            new, metrics = step(jax_copy(state), batch, rng)
            return state, metrics
        return broken
    return make


def jax_copy(state):
    import jax

    return jax.tree.map(lambda x: x.copy(), state)


def half_batch(real):
    def make(*args, **kwargs):
        step = real(*args, **kwargs)

        def broken(state, batch, rng):
            import numpy as np

            rows = np.asarray(batch["tokens"])
            rows = np.concatenate([rows[: len(rows) // 2]] * 2)
            return step(state, {"tokens": rows}, rng)
        return broken
    return make


def no_exchange(real):
    def make(*args, **kwargs):
        return real(*args, grad_sync=False, **kwargs)
    return make


@pytest.mark.parametrize("cell,fault", [
    ("tiny.train", unchanged_state), ("tiny.train", half_batch),
    ("tiny.train4", no_exchange),
])
def test_a_broken_train_step_is_not_correct(tiny_root, monkeypatch, cell, fault):
    import distributeddataparallel_tpu as ddp

    monkeypatch.setattr(ddp, "make_train_step", fault(ddp.make_train_step))
    assert run(tiny_root, cell)["correct"] is False


def test_an_altered_token_is_not_correct(tiny_root, monkeypatch):
    from distributeddataparallel_tpu.serving import engine as engine_mod

    real_init = engine_mod.InferenceEngine.__init__

    def broken_init(self, *args, **kwargs):
        real_init(self, *args, **kwargs)
        decode = self._decode_prog
        vocab = self._dm.cfg.vocab_size

        def altered(*a):
            pool, nxt = decode(*a)
            return pool, (nxt + 1) % vocab
        self._decode_prog = altered

    monkeypatch.setattr(engine_mod.InferenceEngine, "__init__", broken_init)
    assert run(tiny_root, "tiny.serve")["correct"] is False
