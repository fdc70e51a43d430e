"""flops.py against the program's own arithmetic and the published
parameter counts."""

import json
import os
import types

import pytest

from benchmarks import flops
from benchmarks.tests.conftest import ROOT


def config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name,params", [
    ("gpt2-medium-355m", 354_823_168), ("gpt2-large-774m", 774_030_080),
])
def test_param_count(name, params):
    assert flops.param_count(config(name)) == params


@pytest.mark.parametrize("name", ["gpt2-medium-355m", "gpt2-large-774m"])
def test_forward_flops_match_the_programs_cost_model(name):
    from distributeddataparallel_tpu.observability.cost_model import (
        transformer_fwd_flops,
    )

    cfg = config(name)
    want = transformer_fwd_flops(
        types.SimpleNamespace(head_dim=None, **cfg["overrides"]),
        batch=8, seq_len=1024,
    )
    assert flops.forward_flops(cfg, 8, 1024, causal=False) == want
    # causal attention is (S + 1) / 2S of the full score and value work
    full = flops.forward_flops(cfg, 8, 1024, causal=False)
    causal = flops.forward_flops(cfg, 8, 1024, causal=True)
    attn = 8 * flops.attention_flops(cfg, 1024, 1024)
    assert causal == pytest.approx(full - attn * (1 - 1025 / 2048))


def test_param_count_matches_the_programs_tree():
    """The count from shapes equals the leaves the program builds."""
    import jax

    from benchmarks import harness

    train = harness.load_module("kinds", "train")
    cfg = config("gpt2-medium-355m")
    env = {"config": cfg, "traffic": {"model_overrides": {"attn_impl": "xla"}}}
    from distributeddataparallel_tpu.models.transformer import TransformerLM

    shapes = train.param_shapes(TransformerLM(train.model_config(env)))
    n = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n == flops.param_count(cfg)


def test_serve_flops_counts_real_contexts():
    cfg = config("gpt2-large-774m")
    mm = 2 * flops.matmul_param_count(cfg)
    one = flops.serve_flops(cfg, [], [99])
    assert one == mm + flops.attention_flops(cfg, 1, 100)
    chunk = flops.serve_flops(cfg, [(128, 128)], [])
    assert chunk == 128 * mm + flops.attention_flops(cfg, 128, 128 + 64.5)
