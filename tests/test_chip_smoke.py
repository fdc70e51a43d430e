"""The chip path may not hide the device: on a machine without a chip
the smoke fails at once, a named ``--device`` is required rather than
preferred, the compile cache is placed from outside or at one fixed
path, and ``attention(impl="auto")`` never swaps a failing kernel for
the reference."""

import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_chip_smoke_fails_fast_without_a_chip():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", "a failed smoke prints no result"
    # It says what it asked for and what the environment offered.
    assert "--device tpu" in proc.stderr
    assert "JAX_PLATFORMS='cpu'" in proc.stderr


def test_chip_smoke_last_line_is_the_verdict_alone(
    tmp_path, monkeypatch, capsys
):
    """What the driver reads: stdout's last line is one JSON object with
    exactly ``ok`` and ``device`` {platform, kind, count}; the per-phase
    record goes on the line before it and into result.json."""
    import json

    monkeypatch.syspath_prepend(str(REPO))
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "OUT", str(tmp_path))
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    chip_smoke.report(device, {"kernel": {"device": device}}, 1.5)
    record, verdict = map(json.loads, capsys.readouterr().out.splitlines())
    assert verdict == {"ok": True, "device": device}
    assert isinstance(verdict["device"]["count"], int)
    assert set(record) == {"phases", "wall_s", "note"}
    assert json.loads((tmp_path / "result.json").read_text()) == {
        **verdict, **record,
    }


def test_device_tpu_is_required_not_preferred(monkeypatch):
    """``--device tpu`` under ``JAX_PLATFORMS=cpu`` stops the run: the
    variable is not consulted, and the platform JAX delivered (the
    suite's CPU) is checked against the flag and named."""
    import dpp
    from distributeddataparallel_tpu.runtime.distributed import device_summary

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    jax.devices()  # the suite's CPU backend is up: selection cannot move it
    before = jax.config.jax_platforms
    try:
        dpp.select_device(dpp.parse_args(["--device", "tpu"]))
        assert jax.config.jax_platforms == "tpu"
        with pytest.raises(SystemExit, match="platform 'cpu'"):
            device_summary("tpu")
    finally:
        jax.config.update("jax_platforms", before)
    assert device_summary("auto") == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind, "count": 8,
    }
    with pytest.raises(SystemExit, match="--fake-devices requires"):
        dpp.select_device(
            dpp.parse_args(["--device", "tpu", "--fake-devices", "2"])
        )


def test_serve_refuses_fleet_on_tpu():
    sys.path.insert(0, str(REPO / "scripts"))
    try:
        import ddp_serve
    finally:
        sys.path.pop(0)
    with pytest.raises(SystemExit, match="fleet workers are CPU"):
        ddp_serve.main(["--device", "tpu", "--fleet", "1:2"])


def test_compile_cache_resolver(tmp_path, monkeypatch):
    from distributeddataparallel_tpu.training import warm_start

    # Placed from outside: returned untouched, nothing set in code.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    updates = []
    monkeypatch.setattr(
        warm_start.jax.config, "update", lambda *a: updates.append(a)
    )
    assert warm_start.resolve_compile_cache() == "/some/dir"
    assert updates == [] and not os.path.exists("/some/dir")

    # Not placed: the same <checkout>/.jax_cache from any working
    # directory, exported for children.
    seen = []
    for cwd in (tmp_path, pathlib.Path("/")):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.chdir(cwd)
        seen.append(warm_start.resolve_compile_cache())
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == seen[-1]
    assert seen == [str(REPO / ".jax_cache")] * 2
    assert [value for _, value in updates] == seen  # told to jax, too


def test_auto_attention_does_not_fall_back(monkeypatch):
    """With supported() shapes, 'auto' IS the kernel: a kernel that
    fails must fail the caller, not become the O(S^2) reference."""
    from distributeddataparallel_tpu.ops import pallas_attention
    from distributeddataparallel_tpu.ops.attention import attention

    def broken_kernel(*a, **k):
        raise RuntimeError("Mosaic failed to compile")

    monkeypatch.setattr(pallas_attention, "supported", lambda q, k, v: True)
    monkeypatch.setattr(pallas_attention, "flash_attention", broken_kernel)
    x = jnp.ones((1, 128, 2, 16), jnp.float32)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        attention(x, x, x, impl="auto")
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        jax.jit(lambda x: attention(x, x, x, impl="auto"))(x)
    # "xla" stays the explicit way to ask for the reference.
    assert attention(x, x, x, impl="xla").shape == x.shape
