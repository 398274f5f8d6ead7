"""Global test configuration.

Tests run on a virtual 8-device CPU mesh (the TPU analogue of the
reference's 8-process `torchrun` rig — reference Makefile:9-12). The env
vars must be set before jax initializes its backends.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# The tier-1 command sets JAX_PLATFORMS=cpu; pin it here too so a bare
# `pytest` on a machine with an accelerator still runs the CPU rig.
jax.config.update("jax_platforms", "cpu")

# The tests keep the persistent compilation cache off, whatever
# JAX_COMPILATION_CACHE_DIR says: a test must compile what it tests, and
# the compile-only TPU tests (tests/core/test_chip_compile.py) would write
# entries no CPU process can read back. Script entry points place the
# cache through d9d_tpu.core.compile_cache instead.
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True, scope="module")
def _ambient_mesh_stays_in_its_module():
    """``MeshParameters.build`` makes its mesh ambient (``jax.set_mesh``)
    and nothing unsets it, so a module that built a one- or four-device
    mesh broke whichever module the worker ran next ("incompatible
    devices ... jit's context mesh"): which tests failed depended on how
    the files fell to the workers. Each module ends with the ambient
    mesh it started with."""
    before = jax.sharding.get_mesh()
    yield
    jax.set_mesh(before)


@pytest.fixture(autouse=True)
def _fixed_seed():
    import random

    import numpy as np

    random.seed(0)
    np.random.seed(0)
    yield


def pytest_terminal_summary(terminalreporter):
    """The ten slowest test files of the run with their summed seconds
    (set-up, call and teardown, over the workers), from the reports pytest
    already holds: every builder's log shows what a PR added to the tier-1
    clock without a second run."""
    seconds = {}
    for reports in terminalreporter.stats.values():
        for report in reports:
            if hasattr(report, "duration") and hasattr(report, "nodeid"):
                path = report.nodeid.split("::")[0]
                seconds[path] = seconds.get(path, 0.0) + report.duration
    if not seconds:
        return
    terminalreporter.section("slowest test files")
    for path in sorted(seconds, key=seconds.get, reverse=True)[:10]:
        terminalreporter.write_line(f"{seconds[path]:8.1f}s {path}")
    terminalreporter.write_line(f"{sum(seconds.values()):8.1f}s all files")


def load_repo_module(name, relpath):
    """Load a repo script (chip_smoke.py, tools/*.py) by path — shared by
    the harness tests so the spec/exec boilerplate lives once."""
    import importlib.util
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(name, root / relpath)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod
