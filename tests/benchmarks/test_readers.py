"""The generic readers on hand-made observations, and the seeded init the
training cells use. No device, no trace."""

import types

import pytest

from benchmarks.harness import readers

PHASES = ["data_wait", "host_dispatch", "metric_flush"]


def span(name, step, dur_s):
    return types.SimpleNamespace(name=name, step=step, dur_s=dur_s)


def observed(flush_s: float):
    """20 steps of a 1 s step after 3 of warm-up: the host spends 0.01 s
    dispatching and fetches metrics every tenth step; it runs free in
    the two steps after a fetch and waits 0.9 s for the device in
    ``data_wait`` in every other."""
    spans = [span("train/phase/host_dispatch", 0, 5.0)]  # warm-up: left out
    for step in range(3, 23):
        tenth = step % 10 == 0
        spans += [
            span("train/phase/host_dispatch", step, 0.01),
            span("train/phase/data_wait", step,
                 0.0 if step % 10 in (0, 1, 2) else 0.9),
            span("train/phase/device_block", step, 0.05),  # not asked for
        ]
        if tenth:
            spans.append(span("train/phase/metric_flush", step, flush_s))
    return types.SimpleNamespace(
        spans=spans, first_step=3, steps_in_window=20, window_s=20.0
    )


def run_of(o):
    return readers.Run(cell=None, observed=o, setup_s=0.0, inventory=(),
                       device_kind="cpu")


def test_plain_phase_share_is_the_sum_over_the_window():
    share = readers.phase_share(run_of(observed(flush_s=0.5)), PHASES)
    want = 20 * 0.01 + 14 * 0.9 + 2 * 0.5
    assert share == pytest.approx(100.0 * want / 20.0)


def test_plain_share_sees_a_periodic_stall_and_the_decile_does_not():
    quick, slow = run_of(observed(0.1)), run_of(observed(0.9))
    assert readers.phase_share(slow, PHASES) > readers.phase_share(quick, PHASES) + 3
    free = readers.phase_share(quick, PHASES, decile=1)
    assert free == readers.phase_share(slow, PHASES, decile=1)
    assert free == pytest.approx(1.0)  # the dispatch alone, of a 1 s step


def test_phase_share_without_spans_reports_nothing():
    empty = types.SimpleNamespace(
        spans=[], first_step=0, steps_in_window=0, window_s=1.0)
    assert readers.phase_share(run_of(empty), PHASES) is None
    assert readers.phase_share(run_of(empty), PHASES, decile=1) is None


def test_seeded_params_follow_the_seed_and_not_the_program():
    """One init program for every seed: the key is an argument, so two
    seeds lower to the same text, and the weights follow the seed."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import build
    from d9d_tpu.core import MeshParameters
    from d9d_tpu.parallel import replicate_plan

    ctx = MeshParameters().build(jax.devices()[:1])
    module, sample = nn.Dense(4), (jnp.zeros((2, 3)),)
    make = lambda seed: build.seeded_params(  # noqa: E731
        module, sample, seed, ctx.mesh, replicate_plan(ctx))
    a, b, big = make(7), make(7), make(2**31 + 11)
    kernel = lambda p: np.asarray(p["params"]["kernel"])  # noqa: E731
    assert np.array_equal(kernel(a), kernel(b))
    assert not np.array_equal(kernel(a), kernel(big))
    assert not np.array_equal(kernel(a), kernel(make(8)))
