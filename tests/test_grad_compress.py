"""bf16 gradient-compression comm hook (torch DDP ``bf16_compress_hook``
analog, the ``register_comm_hook`` surface behind ref dpp.py:52):

- numerics: a compressed DP step tracks the exact step to bf16 tolerance
  and the compression REALLY happens (wire dtype is bf16 in the compiled
  HLO; results differ bitwise from the exact step);
- composition: buckets, accumulation, grad-clip, the in-scan-body sync
  (scanned stacks), and the CLI flag;
- rejections: layouts that own their reductions (--zero/--fsdp/--pp).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import distributeddataparallel_tpu as ddp
from distributeddataparallel_tpu.data.loader import shard_batch
from distributeddataparallel_tpu.models import TransformerLM, tiny_lm
from distributeddataparallel_tpu.ops import lm_cross_entropy
from distributeddataparallel_tpu.parallel.data_parallel import (
    broadcast_params,
)
from distributeddataparallel_tpu.runtime.distributed import make_mesh
from distributeddataparallel_tpu.training.state import TrainState
from distributeddataparallel_tpu.training.train_step import make_train_step

from distributeddataparallel_tpu.models.simple_cnn import TinyMLP
from distributeddataparallel_tpu.ops.losses import cross_entropy_loss


def _setup(lr=0.1, seed=0):
    model = TinyMLP(features=(32,), num_classes=10)
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8, 8, 1))
    )["params"]

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["image"])
        return cross_entropy_loss(logits, batch["label"]), {}

    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.sgd(lr)
    )
    return model, state, loss_fn


def _fake_batches(num_steps, global_batch, seed=0):
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(10, 8, 8, 1)).astype(np.float32)
    out = []
    for _ in range(num_steps):
        labels = rng.integers(0, 10, size=(global_batch,))
        imgs = protos[labels] + 0.1 * rng.normal(
            size=(global_batch, 8, 8, 1)
        ).astype(np.float32)
        out.append(
            {"image": imgs.astype(np.float32),
             "label": labels.astype(np.int32)}
        )
    return out


def _run_steps(state, loss_fn, mesh, batches, **kw):
    step = make_train_step(loss_fn, mesh=mesh, donate=False, **kw)
    state = broadcast_params(state, mesh)
    for b in batches:
        state, metrics = step(state, shard_batch(b, mesh), jax.random.PRNGKey(1))
    return state, metrics


def test_compress_tracks_exact_step(devices):
    """bf16-compressed DP == exact DP to bf16 tolerance over several
    steps — and not bitwise (the hook is live, not a no-op)."""
    mesh = make_mesh(("data",))
    batches = _fake_batches(4, 8 * len(jax.devices()))
    _, state, loss_fn = _setup()
    exact, _ = _run_steps(state, loss_fn, mesh, batches)
    comp, m = _run_steps(state, loss_fn, mesh, batches, grad_compress="bf16")
    exact_l, comp_l = jax.tree.leaves(exact.params), jax.tree.leaves(comp.params)
    for a, b in zip(exact_l, comp_l):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=5e-3
        )
    assert float(m["loss"]) == float(m["loss"])
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(exact_l, comp_l)
    ), "compression produced bitwise-identical params - hook not applied?"


def test_compress_wire_dtype_is_bf16(devices):
    """Every gradient psum in the traced step carries a bf16 payload
    (only the f32 loss-metric pmean stays f32).  Checked at jaxpr level:
    the CPU backend's float-normalization pass re-promotes bf16
    all-reduces to f32 in its compiled HLO, so the backend-independent
    trace is where the hook's wire dtype is visible on CPU; the TPU
    compiled wire dtype is pinned by the TPU-gated test below."""
    mesh = make_mesh(("data",))
    _, state, loss_fn = _setup()
    state = broadcast_params(state, mesh)
    batch = shard_batch(_fake_batches(1, 8 * len(jax.devices()))[0], mesh)
    step = make_train_step(
        loss_fn, mesh=mesh, donate=False, grad_compress="bf16"
    )
    jx = str(jax.make_jaxpr(step)(state, batch, jax.random.PRNGKey(0)))
    psums = [
        l.strip() for l in jx.splitlines()
        if "= psum" in l and "f32[]" not in l
    ]
    assert psums, "no gradient psums found in the traced step"
    assert all(
        ":bf16[" in l.split("=")[0] for l in psums
    ), f"non-bf16 gradient psum: {psums}"


def test_tpu_compress_wire_dtype(devices):
    """On the REAL TPU compiler the compressed all-reduce stays bf16 on
    the wire (no silent re-promotion), AOT-compiled for the 8-chip v5e
    topology."""
    pytest.importorskip("jax.experimental.topologies")
    from distributeddataparallel_tpu.runtime.distributed import (
        tpu_topology_mesh,
    )

    try:
        mesh = tpu_topology_mesh()
        _, state, loss_fn = _setup()
        state_sds = jax.eval_shape(lambda: state)
        batch = _fake_batches(1, 8 * mesh.devices.size)[0]
        batch_sds = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in batch.items()
        }
        step = make_train_step(
            loss_fn, mesh=mesh, donate=False, grad_compress="bf16"
        )
        txt = (
            step.lower(state_sds, batch_sds, jax.random.PRNGKey(0))
            .compile()
            .as_text()
        )
    except Exception as exc:  # no TPU compiler in this process
        pytest.skip(f"TPU topology compile unavailable: {exc!r}")
    assert any(
        "bf16[" in l.split("(")[0]
        for l in txt.splitlines()
        if "all-reduce" in l
    ), "no bf16 all-reduce in TPU HLO - wire compression lost"


def test_compress_composes_buckets_accum_clip(devices):
    """compress x {bucket_bytes, accum_steps, grad_clip} stays within
    bf16 tolerance of the exact composed step."""
    mesh = make_mesh(("data",))
    batches = _fake_batches(2, 8 * len(jax.devices()))
    _, state, loss_fn = _setup()
    kw = dict(bucket_bytes=1 << 10, accum_steps=2, grad_clip=1.0)
    exact, _ = _run_steps(state, loss_fn, mesh, batches, **kw)
    comp, _ = _run_steps(
        state, loss_fn, mesh, batches, grad_compress="bf16", **kw
    )
    for a, b in zip(
        jax.tree.leaves(exact.params), jax.tree.leaves(comp.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=5e-3
        )


def test_compress_scan_body_sync(devices):
    """Scanned stack with grad_sync_axis + grad_sync_compress: the
    in-body bf16 reduction tracks the exact in-body reduction (presynced
    leaves excluded from the step's own sync in both runs)."""
    mesh = make_mesh(("data",))
    cfg = tiny_lm(
        num_layers=2, scan_layers=True, remat=True, grad_sync_axis="data"
    )
    cfg_c = dataclasses.replace(cfg, grad_sync_compress="bf16")
    rngs = np.random.default_rng(0)
    toks = rngs.integers(
        0, cfg.vocab_size, size=(2 * len(jax.devices()), 17)
    ).astype(np.int32)

    def make(cfg):
        model = TransformerLM(cfg)
        params = TransformerLM(
            dataclasses.replace(cfg, grad_sync_axis=None)
        ).init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]

        def loss_fn(p, b, rng):
            logits = model.apply({"params": p}, b["tokens"][:, :-1])
            return lm_cross_entropy(logits, b["tokens"][:, 1:]), {}

        st = TrainState.create(
            apply_fn=None, params=params, tx=optax.sgd(0.05)
        )
        return st, loss_fn

    presync = lambda p: p[0] == "layers"  # noqa: E731
    st, lf = make(cfg)
    exact, _ = _run_steps(
        st, lf, mesh, [{"tokens": toks}], presynced=presync
    )
    st_c, lf_c = make(cfg_c)
    comp, _ = _run_steps(
        st_c, lf_c, mesh, [{"tokens": toks}],
        presynced=presync, grad_compress="bf16",
    )
    for a, b in zip(
        jax.tree.leaves(exact.params), jax.tree.leaves(comp.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=5e-3
        )


def test_compress_rejections(devices):
    """Layouts that own their reductions reject the hook loudly."""
    mesh = make_mesh(("data",))
    _, state, loss_fn = _setup()
    with pytest.raises(ValueError, match="grad_compress"):
        make_train_step(
            loss_fn, mesh=mesh, zero=True, grad_compress="bf16"
        )
    with pytest.raises(ValueError, match="grad_compress"):
        make_train_step(
            loss_fn, mesh=mesh, grad_sync=False, grad_compress="bf16"
        )
    with pytest.raises(ValueError, match="compress"):
        ddp.all_reduce_gradients({}, compress="fp8")


def test_cli_grad_compress(devices):
    """dpp.py --grad-compress bf16 end-to-end; --zero rejects it."""
    import sys

    sys.path.insert(0, "/root/repo")
    import dpp

    args = dpp.parse_args(
        [
            "--device", "cpu", "--model", "mlp", "--epochs", "1",
            "--num-examples", "64", "--batch-size", "4",
            "--grad-compress", "bf16", "--log-every", "1000",
        ]
    )
    loss = dpp.train(args)
    assert loss == loss
    with pytest.raises(SystemExit, match="grad-compress"):
        dpp.validate_args(
            dpp.parse_args(
                ["--device", "cpu", "--model", "mlp", "--grad-compress",
                 "bf16", "--zero"]
            )
        )
