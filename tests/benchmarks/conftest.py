"""One tiny run a (cell, trace level, devices) in a whole test session.

Under xdist the workers of a session share the last lines of the tiny
runs (``test_run_tiny.py tiny_line``) as files in a directory named by
the session's id. The workers remove it themselves, the last to end its
session: the controller of a run over ``tests/`` never loads this file
(it collects nothing), so a hook of its own would not run there. Every
worker signs in when its collection ends, which is before any worker
runs a test, and signs out when its session ends."""

import os
import shutil
import tempfile
from pathlib import Path

UID = os.environ.get("PYTEST_XDIST_TESTRUNUID")
WORKER = os.environ.get("PYTEST_XDIST_WORKER")


def tiny_lines_dir(testrunuid: str) -> Path:
    """Where the workers of the xdist session ``testrunuid`` keep the
    tiny runs' last lines."""
    return Path(tempfile.gettempdir()) / f"d9d_bench_tiny_lines_{testrunuid}"


def _signed_in(worker: str) -> Path:
    return tiny_lines_dir(UID) / f"worker.{worker}"


def pytest_collection_modifyitems(session, config, items):
    if UID and WORKER:
        tiny_lines_dir(UID).mkdir(exist_ok=True)
        _signed_in(WORKER).touch()


def pytest_sessionfinish(session):
    if not (UID and WORKER):
        return
    _signed_in(WORKER).unlink(missing_ok=True)
    if not list(tiny_lines_dir(UID).glob("worker.*")):
        shutil.rmtree(tiny_lines_dir(UID), ignore_errors=True)
