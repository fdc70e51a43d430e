"""The benchmark's own tests: CPU only, four virtual devices, tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 4)

TINY_END_TO_END = [
    {"name": "train_tokens_s_chip", "unit": "tokens/s/chip", "better": "higher",
     "bound": 0.1, "source": "host_clock",
     "workloads": ["tiny.train", "tiny.train4"]},
    {"name": "serve_ttft_p90_ms", "unit": "ms", "better": "lower",
     "bound": 0.1, "source": "host_clock", "workloads": ["tiny.serve"]},
    {"name": "serve_tpot_p90_ms", "unit": "ms", "better": "lower",
     "bound": 0.1, "source": "host_clock", "workloads": ["tiny.serve"]},
    {"name": "serve_tokens_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.1, "source": "host_clock", "workloads": ["tiny.serve"]},
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
     "source": "host_clock"},
]


def make_tiny_root(dst: str) -> str:
    """A checkout-shaped directory: a copy of ``benchmarks/`` with the
    tiny configuration and mixes dropped in as NEW files, and a
    BENCHMARK.json that names them.  No file that is there is edited."""
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), os.path.join(dst, "benchmarks"),
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    data = os.path.join(ROOT, "benchmarks", "tests", "data")
    shutil.copy(os.path.join(data, "tiny.json"),
                os.path.join(dst, "benchmarks", "configs", "tiny.json"))
    for name in ("tiny-train.json", "tiny-serve.json"):
        shutil.copy(os.path.join(data, name),
                    os.path.join(dst, "benchmarks", "traffic", name))
    bench = {
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "benchmarks/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [
            {"name": "tiny.train", "config": "tiny", "traffic": "tiny-train",
             "chips": 1, "why": "test"},
            {"name": "tiny.train4", "config": "tiny", "traffic": "tiny-train",
             "chips": 4, "why": "test"},
            {"name": "tiny.serve", "config": "tiny", "traffic": "tiny-serve",
             "chips": 1, "why": "test"},
        ],
        "end_to_end": TINY_END_TO_END,
        "per_layer": [],
    }
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return dst


@pytest.fixture()
def tiny_root(tmp_path):
    return make_tiny_root(str(tmp_path / "checkout"))
