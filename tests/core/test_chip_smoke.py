"""``chip_smoke.py`` off the chip: its phases as functions at tiny widths
on the CPU mesh, its refusal to run as a script without a TPU, and the
compile-cache helper every script entry calls first.

The CPU rig shows that the phases' control flow and checks hold; the
kernels and collectives they look for in the HLO exist only on the chip
(``expect_kernels=False`` here), where ``python chip_smoke.py`` is the test.
"""

import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from d9d_tpu.core import compile_cache
from tests.conftest import load_repo_module

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def smoke():
    return load_repo_module("chip_smoke", "chip_smoke.py")


@pytest.fixture(scope="module")
def sizes(smoke):
    return smoke.Sizes.tiny_cpu()


@pytest.fixture(scope="module")
def trained(smoke, sizes, devices):
    """The train phase, run once: (its JSON line, the trained params)."""
    return smoke.phase_train(sizes, 0, expect_kernels=False)


def test_phase_distributed_is_a_noop_on_one_host(smoke, devices):
    line = smoke.phase_distributed()
    assert line["initialized"] is False and line["process_count"] == 1


def test_phase_sync(smoke, sizes, devices):
    line = smoke.phase_sync(sizes)
    assert line["waits"] and line["total_s"] >= line["enqueue_s"]
    assert line["peak_flops"] is None  # no peak, so no bound, on the CPU


def test_phase_train(sizes, trained):
    line, params = trained
    assert len(line["losses"]) == sizes.train_steps
    assert line["losses"][-1] < line["losses"][0]
    assert abs(line["losses"][0] - line["eager_forward_loss"]) <= line["loss_tol"]
    assert line["params"] == sum(x.size for x in jax.tree.leaves(params))


def test_phase_serve_matches_generate(smoke, sizes, trained):
    line = smoke.phase_serve(sizes, 0, trained[1], expect_kernels=False)
    assert line["streams_equal_generate"] == len(sizes.prompt_lens)
    assert line["compiles_after_warmup"] == 0
    assert line["tokens"] == sum(sizes.new_tokens)


def test_phase_four_devices_matches_one(smoke, sizes, devices):
    line = smoke.phase_four_chip(sizes, 0, devices[:4], expect_kernels=False)
    assert len(line["sharded"]["losses"]) == len(line["one_chip"]["losses"])
    assert line["loss_gaps"][0] <= line["tol_step0"]
    assert max(line["loss_gaps"]) <= line["tol_later"]
    placement = line["placement"]
    assert placement["devices"] == [d.id for d in devices[:4]]
    assert placement["expert"]["shard"][0] * 4 == placement["expert"]["shape"][0]
    # FSDP gathers and scatters are XLA's on any backend; the ragged
    # all-to-all is emulated on the CPU (ops/ep_dispatch.py)
    assert line["collectives_in_hlo"]["all-gather"] > 0
    assert line["collectives_in_hlo"]["ragged-all-to-all"] == 0


def test_a_failed_check_raises(smoke):
    with pytest.raises(smoke.SmokeFailure, match="did not fall"):
        smoke.require(False, "loss did not fall")


def test_script_refuses_to_measure_without_a_tpu():
    """No CPU path when run as a script: non-zero exit, and the last
    line says ``"ok": false``. (``benchmarks/run.py``'s refusal is
    ``tests/benchmarks/test_run_tiny.py::test_no_tpu_no_result``.)"""
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0, out.stdout[-500:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"


# -- the compile-cache helper -------------------------------------------------


@pytest.fixture
def cache_config():
    """Whatever the helper sets is put back."""
    before = jax.config.jax_compilation_cache_dir
    metadata = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update(
        "jax_compilation_cache_include_metadata_in_key", metadata
    )


def test_cache_helper_honours_the_environment(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # jax took the directory from the environment at import; the helper
    # sets no other
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_defaults_inside_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    chosen = pathlib.Path(compile_cache.enable_compile_cache())
    assert chosen == ROOT / ".jax_compile_cache"
    assert jax.config.jax_compilation_cache_dir == str(chosen)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert chosen.name + "/" in ignored


def test_a_programs_scopes_are_part_of_its_cache_key(tmp_path, cache_config):
    """Two programs that differ only in a ``jax.named_scope`` are two
    cache entries once the helper has run: a trace is read by scope, so a
    hit must never hand back another program's names."""
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    def scoped(name):
        def f(x):
            with jax.named_scope(name):
                return jnp.sin(x) * 2.0
        return f

    was_on = jax.config.jax_enable_compilation_cache
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    compile_cache.enable_compile_cache()
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        compilation_cache.reset_cache()
        x = jnp.arange(8.0)
        entries = []
        # one call site: the caller's line is metadata too
        for name in ("stage/a", "stage/a", "stage/b"):
            jax.jit(scoped(name))(x).block_until_ready()
            entries.append({p.name for p in tmp_path.iterdir()})
        first, again, other = entries
        assert first and again == first  # the same program: a hit
        assert len(other) > len(first)  # another scope: another key
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
        compilation_cache.reset_cache()


def test_cache_helper_agrees_across_processes(tmp_path):
    """The path is part of the cache key: two processes of one checkout,
    started from different directories, must name the same one; and with
    the variable set, jax holds exactly that directory."""
    code = (
        "import jax\n"
        "from d9d_tpu.core.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )

    def run(cwd, **env):
        base = {k: v for k, v in os.environ.items()
                if k != compile_cache.ENV_VAR}
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=cwd, check=True,
            env={**base, "PYTHONPATH": str(ROOT), "JAX_PLATFORMS": "cpu", **env},
        )
        return out.stdout.split()

    first, second = run(ROOT), run(tmp_path)
    assert first == second == [str(ROOT / ".jax_compile_cache")] * 2
    placed = run(tmp_path, **{compile_cache.ENV_VAR: str(tmp_path / "c")})
    assert placed == [str(tmp_path / "c")] * 2


# -- the peak table the sync check and the MFU gauge divide by ---------------


@pytest.mark.parametrize(
    "platform,kind,expect",
    [
        ("tpu", "TPU v5 lite", 197e12),
        ("cpu", "cpu", None),
        ("tpu", "TPU v9x", ValueError),
    ],
    ids=["v5e", "cpu-has-no-peak", "unknown-tpu-raises"],
)
def test_device_peak_flops(monkeypatch, platform, kind, expect):
    from d9d_tpu.telemetry.flops import device_peak_flops

    class FakeDevice:
        def __init__(self):
            self.platform, self.device_kind = platform, kind

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeDevice()])
    if expect is ValueError:
        with pytest.raises(ValueError, match="PEAK_FLOPS"):
            device_peak_flops()
    else:
        assert device_peak_flops() == expect
