"""Topology-independent checkpoint restore (docs/design/elasticity.md):
manifest v2 records the saving mesh, restore detects a topology
mismatch and reshard-on-loads — including the e2e chaos leg the ISSUE
acceptance names: train on mesh A → SIGTERM emergency save → resume on
mesh B (different ``dp_replicate``, ZeRO on) with losses tracking the
uninterrupted run; plus the memory-bounded chunked redistribution and
the unverified-restore operator signal."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

pytestmark = pytest.mark.chaos

from tests.resilience.conftest import MicroLoaderProvider, MicroProvider

from d9d_tpu.core.mesh import MeshParameters
from d9d_tpu.loop import AdamWProvider, CausalLMTask, Trainer, TrainerConfig
from d9d_tpu.loop.components.checkpointer import StateCheckpointer
from d9d_tpu.resilience import (
    ManifestVersionError,
    TrainingPreempted,
    job_mesh_spec,
    manifest_mesh,
    redistribute_tree,
    topology_mismatch,
    tree_mesh_summary,
)
from d9d_tpu.resilience.chaos import sigterm_at_step
from d9d_tpu.resilience.manifest import (
    MANIFEST_NAME,
    read_manifest,
    validate_checkpoint_dir,
    write_manifest,
)
from d9d_tpu.telemetry import get_telemetry


def _trainer(tmp_path, *, dp, zero, total_steps=6, **overrides):
    ctx = MeshParameters(dp_replicate=dp).build(jax.devices()[:dp])
    defaults = dict(
        global_batch_size=8,
        microbatch_size=8,
        seq_len=8,
        total_steps=total_steps,
        log_every=1,
        prefetch_batches=0,
        telemetry_console=False,
        gc_every_steps=None,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_every_steps=100,  # only emergency/final saves fire
        checkpoint_async=False,
        zero_sharding=zero,
    )
    defaults.update(overrides)
    return Trainer(
        ctx=ctx,
        config=TrainerConfig(**defaults),
        model_provider=MicroProvider(),
        dataset_provider=MicroLoaderProvider(),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(),
    )


# ---------------------------------------------------------------------------
# manifest v2 units


def test_manifest_v2_records_saving_mesh(tmp_path):
    step_dir = tmp_path / "save_3"
    step_dir.mkdir()
    (step_dir / "payload.bin").write_bytes(b"x" * 64)
    ctx = MeshParameters(dp_replicate=2).build(jax.devices()[:2])
    arrays = {"w": jax.device_put(
        jnp.zeros((4, 4)), NamedSharding(ctx.mesh, P())
    )}
    spec = job_mesh_spec(ctx=ctx, zero_sharding=True, arrays=arrays)
    write_manifest(step_dir, step=3, mesh=spec)
    manifest = read_manifest(step_dir)
    assert manifest["version"] == 2
    mesh = manifest["mesh"]
    assert mesh["zero_sharding"] is True
    assert mesh["device_count"] == 2
    assert mesh["mesh_parameters"]["dp_replicate"] == 2
    assert mesh["axes"]["dp_r"] == 2
    # per-leaf shardings recorded (diagnostic block)
    assert any("w" in k for k in mesh["leaf_shardings"])
    assert validate_checkpoint_dir(step_dir) is True
    assert manifest_mesh(step_dir) == mesh


def test_manifest_v1_files_stay_readable(tmp_path):
    """≤-current rule (mirrors the telemetry schema): a v1 manifest —
    no version-gated fields beyond the inventory — validates fine."""
    step_dir = tmp_path / "save_1"
    step_dir.mkdir()
    (step_dir / "payload.bin").write_bytes(b"y" * 32)
    write_manifest(step_dir, step=1)  # no mesh block
    manifest = json.loads((step_dir / MANIFEST_NAME).read_text())
    manifest["version"] = 1
    manifest.pop("mesh", None)
    (step_dir / MANIFEST_NAME).write_text(json.dumps(manifest))
    assert validate_checkpoint_dir(step_dir) is True
    assert manifest_mesh(step_dir) is None  # pre-v2: no topology info


def test_future_manifest_version_skips_without_pruning(tmp_path):
    """A manifest from a NEWER writer raises ManifestVersionError — not
    an integrity failure: the walk-back must skip the step, never prune
    an intact checkpoint it merely cannot read."""
    step_dir = tmp_path / "save_2"
    step_dir.mkdir()
    (step_dir / "payload.bin").write_bytes(b"z" * 16)
    write_manifest(step_dir, step=2)
    manifest = json.loads((step_dir / MANIFEST_NAME).read_text())
    manifest["version"] = 99
    (step_dir / MANIFEST_NAME).write_text(json.dumps(manifest))
    from d9d_tpu.resilience import CheckpointIntegrityError

    with pytest.raises(ManifestVersionError) as exc:
        validate_checkpoint_dir(step_dir)
    assert not isinstance(exc.value, CheckpointIntegrityError)
    assert manifest_mesh(step_dir) is None  # best-effort accessor


def test_topology_mismatch_detection():
    ctx2 = MeshParameters(dp_replicate=2).build(jax.devices()[:2])
    arrays2 = {"w": jax.device_put(
        jnp.zeros((8,)), NamedSharding(ctx2.mesh, P())
    )}
    spec2 = job_mesh_spec(ctx=ctx2, arrays=arrays2)
    assert not topology_mismatch(spec2, tree_mesh_summary(arrays2))
    ctx4 = MeshParameters(dp_replicate=4).build(jax.devices()[:4])
    arrays4 = {"w": jax.device_put(
        jnp.zeros((8,)), NamedSharding(ctx4.mesh, P())
    )}
    assert topology_mismatch(spec2, tree_mesh_summary(arrays4))
    # unknown on either side is conservative: no mismatch
    assert not topology_mismatch(None, tree_mesh_summary(arrays4))
    assert not topology_mismatch(spec2, None)


# ---------------------------------------------------------------------------
# memory-bounded redistribution


@pytest.mark.parametrize("ambient", ["target", "source", "other"])
def test_redistribute_tree_chunks_under_budget(ambient):
    """The chunked path slices under the source's devices and writes
    under the target's, whichever mesh the caller left ambient."""
    src_mesh = MeshParameters(dp_replicate=2).build(jax.devices()[:2]).mesh
    dst_mesh = MeshParameters(dp_replicate=4).build(jax.devices()[:4]).mesh
    if ambient == "source":
        jax.set_mesh(src_mesh)
    elif ambient == "other":
        MeshParameters(dp_replicate=2).build(jax.devices()[4:6])
    data = np.arange(64 * 16, dtype=np.float32).reshape(64, 16)
    leaf = jax.device_put(jnp.asarray(data), NamedSharding(src_mesh, P()))
    target = NamedSharding(dst_mesh, P())
    nbytes = data.nbytes  # 4 KiB
    budget = nbytes // 8  # forces 8 chunks of 8 rows
    tele = get_telemetry()
    chunks_before = tele.counter("resilience/reshard_chunks").value
    out = redistribute_tree(
        {"w": leaf}, {"w": target}, hbm_budget_bytes=budget
    )
    np.testing.assert_array_equal(np.asarray(out["w"]), data)
    assert out["w"].sharding.is_equivalent_to(target, 2)
    assert tele.counter("resilience/reshard_chunks").value \
        - chunks_before == 8
    # already-placed leaves skip entirely (no extra chunks)
    before = tele.counter("resilience/reshard_chunks").value
    out2 = redistribute_tree(out, {"w": target}, hbm_budget_bytes=budget)
    assert out2["w"] is out["w"]
    assert tele.counter("resilience/reshard_chunks").value == before


def test_cross_mesh_checkpoint_restore_with_budget(tmp_path):
    """Save on mesh A (2 devices), restore onto mesh B (4 devices) with
    a tight HBM budget: the manifest's mesh block flags the mismatch,
    the oversized replicated leaf restores through the device-sharded
    staging layout, and the chunked re-place bounds every transfer."""
    ctx_a = MeshParameters(dp_replicate=2).build(jax.devices()[:2])
    data = np.arange(64 * 128, dtype=np.float32).reshape(64, 128)
    arrays = {
        "w": jax.device_put(
            jnp.asarray(data), NamedSharding(ctx_a.mesh, P())
        ),
        "count": jax.device_put(
            jnp.int32(7), NamedSharding(ctx_a.mesh, P())
        ),
    }
    ckpt = StateCheckpointer(tmp_path / "ckpt", async_save=False)
    ckpt.save(
        1, arrays, {"step": 1},
        mesh_spec=job_mesh_spec(ctx=ctx_a, arrays=arrays),
    )
    ckpt.close()
    saved_mesh = manifest_mesh(tmp_path / "ckpt" / "save_1")
    assert saved_mesh["device_count"] == 2

    ctx_b = MeshParameters(dp_replicate=4).build(jax.devices()[:4])
    target = {
        "w": jax.device_put(
            jnp.zeros_like(data), NamedSharding(ctx_b.mesh, P())
        ),
        "count": jax.device_put(
            jnp.int32(0), NamedSharding(ctx_b.mesh, P())
        ),
    }
    tele = get_telemetry()
    restores_before = tele.counter("resilience/reshard_restores").value
    chunks_before = tele.counter("resilience/reshard_chunks").value
    ckpt2 = StateCheckpointer(tmp_path / "ckpt", async_save=False)
    step, restored, meta = ckpt2.restore(
        target, reshard_hbm_budget_bytes=4096
    )
    ckpt2.close()
    assert step == 1 and meta["step"] == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), data)
    assert int(restored["count"]) == 7
    # final placement is the live target's, on the NEW mesh
    assert restored["w"].sharding.is_equivalent_to(
        NamedSharding(ctx_b.mesh, P()), 2
    )
    assert tele.counter("resilience/reshard_restores").value \
        - restores_before == 1
    # 32 KiB leaf over a 4 KiB budget → the chunked path actually ran
    assert tele.counter("resilience/reshard_chunks").value \
        - chunks_before >= 8
    assert tele.gauge("resilience/reshard_bytes").value >= data.nbytes


def test_unverified_restore_counts_and_restores(tmp_path):
    ctx = MeshParameters(dp_replicate=2).build(jax.devices()[:2])
    arrays = {"w": jax.device_put(
        jnp.arange(8.0), NamedSharding(ctx.mesh, P())
    )}
    ckpt = StateCheckpointer(tmp_path / "ckpt", async_save=False)
    ckpt.save(2, arrays, {"step": 2}, mesh_spec=job_mesh_spec(ctx=ctx))
    ckpt.close()
    (tmp_path / "ckpt" / "save_2" / MANIFEST_NAME).unlink()
    tele = get_telemetry()
    before = tele.counter("resilience/unverified_restore").value
    ckpt2 = StateCheckpointer(tmp_path / "ckpt", async_save=False)
    # explicit-step restore (previously completely silent when
    # unverified) now counts the attempt — and still restores
    step, restored, _meta = ckpt2.restore(arrays, step=2)
    assert step == 2
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.arange(8.0)
    )
    assert tele.counter("resilience/unverified_restore").value \
        - before == 1
    # resume-latest counts it too
    ckpt2.restore(arrays)
    ckpt2.close()
    assert tele.counter("resilience/unverified_restore").value \
        - before == 2


# ---------------------------------------------------------------------------
# the e2e chaos leg (ISSUE acceptance): mesh A → SIGTERM → mesh B


def _losses(history):
    return {h["step"]: h["loss"] for h in history}


def _run_cross_topology(tmp_path, *, dp_save, dp_restore, zero):
    baseline = _trainer(
        tmp_path / "base", dp=dp_save, zero=zero, checkpoint_dir=None
    )
    base_losses = _losses(baseline.train())
    baseline.close()

    interrupted = _trainer(tmp_path, dp=dp_save, zero=zero)
    sigterm_at_step(interrupted.events, 3)
    with pytest.raises(TrainingPreempted) as exc:
        interrupted.train()
    interrupted.close()
    preempt_step = exc.value.step
    assert 0 < preempt_step < 6
    # the emergency save carries the manifest v2 mesh block
    saved_mesh = manifest_mesh(
        tmp_path / "ckpt" / f"save_{preempt_step}"
    )
    assert saved_mesh is not None
    assert saved_mesh["device_count"] == dp_save
    assert saved_mesh["zero_sharding"] is zero

    tele = get_telemetry()
    reshards_before = tele.counter("resilience/reshard_restores").value
    resumed = _trainer(tmp_path, dp=dp_restore, zero=zero)
    resumed_losses = _losses(resumed.train())
    resumed.close()
    # the cross-topology restore went through the reshard path
    assert tele.counter("resilience/reshard_restores").value \
        > reshards_before
    # stateful-loader rewind + resharded params/moments: the resumed
    # run's losses track the uninterrupted run at ulp tolerance (the
    # residual is dp_r collective summation order)
    resumed_steps = sorted(resumed_losses)
    assert resumed_steps[0] == preempt_step + 1
    assert resumed_steps[-1] == 6
    for step in resumed_steps:
        np.testing.assert_allclose(
            resumed_losses[step], base_losses[step], rtol=2e-5,
            err_msg=f"step {step}",
        )


def test_sigterm_save_dp2_zero_resumes_on_dp1(tmp_path):
    """The acceptance leg: N-chip ZeRO-sharded emergency save resumes
    on fewer chips (sharding tables rebuilt for the new dp_replicate),
    losses tracking the uninterrupted run."""
    _run_cross_topology(tmp_path, dp_save=2, dp_restore=1, zero=True)


@pytest.mark.slow  # a third full micro-train; the dp1 leg covers tier-1
def test_sigterm_save_dp2_zero_resumes_on_dp4(tmp_path):
    """The grow direction: resume on MORE chips than saved."""
    _run_cross_topology(tmp_path, dp_save=2, dp_restore=4, zero=True)


@pytest.mark.slow
def test_sigterm_save_dp4_zero_resumes_on_dp2(tmp_path):
    """The inverse of the inverse: a wider ZeRO save shrinking."""
    _run_cross_topology(tmp_path, dp_save=4, dp_restore=2, zero=True)
