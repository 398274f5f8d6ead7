"""Fast unit coverage of the resilience surfaces: manifest validation
modes, guard/config validation, serve degraded-mode knobs, data-retry
contracts, exit-code plumbing, and the curves rename compat aliases.
All host-only (no model compiles) — sub-second each."""

import json
import signal
import threading
from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.chaos

from d9d_tpu.loop import TrainerConfig
from d9d_tpu.loop.components.data_loader import (
    DataFetchError,
    StatefulDataLoader,
)
from d9d_tpu.loop.components.timeout_manager import TimeoutManager
from d9d_tpu.resilience import (
    EXIT_PREEMPTED,
    EXIT_WATCHDOG,
    HostAnomalyGuard,
    PreemptionGuard,
    TrainingPreempted,
)
from d9d_tpu.resilience.chaos import FlakyDataset
from d9d_tpu.resilience.manifest import (
    MANIFEST_NAME,
    CheckpointIntegrityError,
    read_manifest,
    validate_checkpoint_dir,
    write_manifest,
)
from d9d_tpu.telemetry import Telemetry


# -- manifest -------------------------------------------------------------

def _fake_step_dir(tmp_path: Path) -> Path:
    d = tmp_path / "save_7"
    (d / "arrays").mkdir(parents=True)
    (d / "meta").mkdir()
    (d / "arrays" / "data0").write_bytes(b"\x01" * 1024)
    (d / "meta" / "metadata").write_text(json.dumps({"step": 7}))
    return d


def test_manifest_roundtrip_validates(tmp_path):
    d = _fake_step_dir(tmp_path)
    write_manifest(d, step=7)
    m = read_manifest(d)
    assert m["step"] == 7
    paths = {f["path"] for f in m["files"]}
    assert paths == {"arrays/data0", "meta/metadata"}
    # small files carry content checksums
    assert all("sha256" in f for f in m["files"])
    assert validate_checkpoint_dir(d) is True


def test_manifest_detects_truncation(tmp_path):
    d = _fake_step_dir(tmp_path)
    write_manifest(d, step=7)
    (d / "arrays" / "data0").write_bytes(b"\x01" * 100)
    with pytest.raises(CheckpointIntegrityError, match="size mismatch"):
        validate_checkpoint_dir(d)


def test_manifest_detects_missing_file(tmp_path):
    d = _fake_step_dir(tmp_path)
    write_manifest(d, step=7)
    (d / "arrays" / "data0").unlink()
    with pytest.raises(CheckpointIntegrityError, match="missing file"):
        validate_checkpoint_dir(d)


def test_manifest_detects_content_corruption(tmp_path):
    d = _fake_step_dir(tmp_path)
    write_manifest(d, step=7)
    # same size, different bytes: only the checksum can catch this
    (d / "meta" / "metadata").write_text(
        json.dumps({"step": 9})[: len(json.dumps({"step": 7}))].ljust(
            len(json.dumps({"step": 7})), " "
        )
    )
    with pytest.raises(CheckpointIntegrityError, match="checksum mismatch"):
        validate_checkpoint_dir(d)


def test_manifest_absent_is_unverified_not_invalid(tmp_path):
    d = _fake_step_dir(tmp_path)
    assert validate_checkpoint_dir(d) is False  # unverified, no raise


def test_manifest_missing_dir_raises(tmp_path):
    with pytest.raises(CheckpointIntegrityError, match="missing"):
        validate_checkpoint_dir(tmp_path / "save_404")


def test_manifest_excludes_itself_and_is_atomic(tmp_path):
    d = _fake_step_dir(tmp_path)
    write_manifest(d, step=7)
    write_manifest(d, step=7)  # rewrite over existing: atomic replace
    m = read_manifest(d)
    assert MANIFEST_NAME not in {f["path"] for f in m["files"]}
    assert not (d / (MANIFEST_NAME + ".tmp")).exists()


# -- host anomaly guard ---------------------------------------------------

def test_guard_rejects_unknown_policy():
    with pytest.raises(ValueError, match="policy"):
        HostAnomalyGuard(policy="explode")


def test_guard_rejects_bad_rollback_after():
    with pytest.raises(ValueError, match="rollback_after"):
        HostAnomalyGuard(policy="warn", rollback_after=0)


def test_guard_reset_clears_streaks():
    tele = Telemetry()
    g = HostAnomalyGuard(
        policy="rollback", rollback_after=1, spike_factor=2.0,
        spike_window=4, telemetry=tele,
    )
    for s in range(5):
        g.observe(s, {"loss": 1.0})
    assert g.observe(5, {"loss": 100.0}) == "rollback"
    g.reset()
    # post-reset: the window is empty, the old spike streak is gone
    assert g.observe(6, {"loss": 100.0}) == "ok"


def test_guard_spike_disabled_with_none_factor():
    g = HostAnomalyGuard(policy="warn", spike_factor=None,
                         telemetry=Telemetry())
    for s in range(8):
        assert g.observe(s, {"loss": 1.0}) == "ok"
    assert g.observe(9, {"loss": 1e9}) == "ok"


def test_device_streak_triggers_rollback_via_metrics():
    g = HostAnomalyGuard(policy="rollback", rollback_after=3,
                         telemetry=Telemetry())
    m = lambda streak: {  # noqa: E731
        "loss": float("nan"), "resilience/anomaly": 1.0,
        "resilience/anomaly_streak": float(streak),
        "resilience/anomaly_total": float(streak),
    }
    assert g.observe(1, m(1)) == "warn"
    assert g.observe(2, m(2)) == "warn"
    assert g.observe(3, m(3)) == "rollback"


# -- trainer config knobs -------------------------------------------------

def _cfg(**kw):
    base = dict(global_batch_size=8, microbatch_size=8, seq_len=8,
                total_steps=1)
    base.update(kw)
    return TrainerConfig(**base)


def test_config_accepts_policies_and_exit_codes():
    cfg = _cfg(anomaly_policy="rollback", preemption_exit_code=90,
               watchdog_exit_code=91)
    assert cfg.anomaly_policy == "rollback"
    assert cfg.preemption_exit_code == 90
    assert cfg.watchdog_exit_code == 91
    assert _cfg().anomaly_policy is None  # guard off by default


def test_config_rejects_unknown_policy():
    with pytest.raises(Exception):
        _cfg(anomaly_policy="nope")


def test_config_rejects_degenerate_spike_factor():
    with pytest.raises(Exception):
        _cfg(anomaly_spike_factor=1.0)


def test_build_train_step_rejects_unknown_policy():
    from d9d_tpu.loop.train_step import build_train_step

    with pytest.raises(ValueError, match="anomaly_policy"):
        build_train_step(module=None, task=None, optimizer=None,
                         num_microbatches=1, anomaly_policy="bogus")


# -- preemption / exit codes ----------------------------------------------

def test_exit_code_constants_documented():
    assert EXIT_PREEMPTED == 83
    assert EXIT_WATCHDOG == 42
    assert TimeoutManager().exit_code == EXIT_WATCHDOG
    assert TimeoutManager(exit_code=7).exit_code == 7


def test_training_preempted_is_system_exit_with_code():
    e = TrainingPreempted(83, step=12)
    assert isinstance(e, SystemExit)
    assert e.code == 83 and e.step == 12
    assert "83" in str(e) and "12" in str(e)


def test_preemption_guard_disabled_is_inert():
    g = PreemptionGuard(enabled=False, telemetry=Telemetry())
    with g:
        assert not g.triggered
    g.trip(signal.SIGTERM)
    assert g.triggered  # flag still works programmatically


def test_preemption_guard_degrades_off_main_thread():
    """Signal handlers need the main thread; elsewhere the guard must
    turn itself off with a warning instead of crashing the trainer."""
    g = PreemptionGuard(telemetry=Telemetry())
    seen = {}

    def enter():
        with g:
            seen["triggered"] = g.triggered

    t = threading.Thread(target=enter)
    t.start()
    t.join(5.0)
    assert seen == {"triggered": False}  # no crash, guard inert


# -- data retry -----------------------------------------------------------

def _loader(ds, **kw):
    kw.setdefault("shuffle", False)
    kw.setdefault("batch_size", 2)
    return StatefulDataLoader(ds, **kw)


def test_retry_survives_transient_failures():
    ds = FlakyDataset([{"x": np.ones(2)} for _ in range(8)],
                      fail_calls={1})
    loader = _loader(ds, retry_attempts=2, retry_backoff_s=0.0)
    batches = list(iter(loader))
    assert len(batches) == 4
    assert ds.failures == 1


def test_retry_exhaustion_names_position():
    ds = FlakyDataset([{"x": np.ones(2)} for _ in range(8)], dead_from=4)
    loader = _loader(ds, retry_attempts=1, retry_backoff_s=0.0)
    it = iter(loader)
    next(it)
    next(it)
    with pytest.raises(DataFetchError) as exc:
        next(it)
    assert exc.value.epoch == 0 and exc.value.batch_index == 2
    assert "epoch 0 batch 2" in str(exc.value)
    assert "2 attempt" in str(exc.value)  # initial try + 1 retry


def test_retry_default_off_wraps_immediately():
    ds = FlakyDataset([{"x": np.ones(2)} for _ in range(4)],
                      fail_calls={0})
    with pytest.raises(DataFetchError):
        next(iter(_loader(ds)))
    assert ds.calls == 1  # no retry by default


def test_loader_rejects_negative_retries():
    with pytest.raises(ValueError, match="retry_attempts"):
        _loader([1, 2], retry_attempts=-1)


def test_backoff_is_capped(monkeypatch):
    sleeps = []
    ds = FlakyDataset([{"x": np.ones(2)} for _ in range(4)],
                      fail_calls={0, 1, 2})
    loader = _loader(ds, retry_attempts=3, retry_backoff_s=0.1,
                     retry_max_backoff_s=0.15)
    import d9d_tpu.loop.components.data_loader as dl

    monkeypatch.setattr(dl.time, "sleep", lambda s: sleeps.append(s))
    next(iter(loader))
    assert sleeps == [0.1, 0.15, 0.15]  # exponential, capped at max


# -- serve knob validation ------------------------------------------------

def test_serve_stats_reset_covers_degraded_counters():
    from d9d_tpu.loop.serve_accounting import ServeStats

    s = ServeStats()
    s.rejected = 3
    s.expired = 2
    s.reset()
    assert s.rejected == 0 and s.expired == 0


# -- curves rename (VERDICT Weak #6): aliases are the same classes --------

def test_curve_aliases_preserve_api():
    from d9d_tpu.lr_scheduler.curves import (
        CosineAnneal,
        CurveBase,
        CurveCosine,
        CurveExponential,
        CurveLinear,
        CurvePoly,
        LinearInterp,
        LogSpaceInterp,
        PowerInterp,
        ScheduleCurve,
    )

    assert CurveBase is ScheduleCurve
    assert CurveLinear is LinearInterp
    assert CurveCosine is CosineAnneal
    assert CurvePoly is PowerInterp
    assert CurveExponential is LogSpaceInterp
    # positional construction kept (CurvePoly(2.0) spelling)
    assert CurvePoly(3.0).power == 3.0
    # legacy compute() spelling still answers
    assert float(CurveLinear().compute(0.0, 2.0, 0.5)) == 1.0
    assert float(LinearInterp().blend(0.0, 2.0, 0.25)) == 0.5

    # a pre-rename subclass implementing only compute() still works,
    # through BOTH spellings
    class LegacyCurve(CurveBase):
        def compute(self, start, end, step_p):
            return end

    assert LegacyCurve().compute(0.0, 5.0, 0.1) == 5.0
    assert LegacyCurve().blend(0.0, 5.0, 0.1) == 5.0
    # and a curve implementing neither fails loudly at call time
    class EmptyCurve(ScheduleCurve):
        pass

    with pytest.raises(NotImplementedError):
        EmptyCurve().blend(0.0, 1.0, 0.5)
