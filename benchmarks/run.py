"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <config>.<traffic> --seed N \\
        --seconds S --trace 0|1|2

A new process each time. It builds the cell's system from its files
(``benchmarks/configs``, ``benchmarks/traffic``), warms up, compares the
program with the family's plain reference outside the window, measures
for ``--seconds`` seconds and prints one JSON object as its last line:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, in a
traced run, ``breakdown``. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.

``--trace 2`` is a ``--trace 0`` run followed by a short traced window in
the same process. Until the measured window has closed and its numbers
are taken it does what ``--trace 0`` does and nothing of the profiler is
started; then it starts and stops the profiler once for nothing (the
first start's cost falls into no number), traces a few seconds of the
same traffic and prints one line with both kinds of metric: end to end,
``program_span`` and ``program_counter`` metrics from the measured
window, ``device_trace`` metrics from the traced seconds.

It needs a TPU with as many chips as the cell asks for and exits 4
without a result when there is none: there is no fallback. ``--tiny``
(the benchmark's own tests) runs the same code at toy widths on
whatever backend there is and reports counts only, never a time.
"""

import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NO_TPU = 4


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths, any backend, counts only (tests)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from benchmarks.harness import manifest

    cell = manifest.cell(args.workload)

    marks = {}
    import jax

    marks["jax_imported"] = time.perf_counter()
    if not args.tiny:
        from d9d_tpu.core.compile_cache import enable_compile_cache

        enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if not args.tiny and (platform != "tpu" or len(devices) < cell.chips):
        print(
            f"benchmarks/run.py needs {cell.chips} TPU chip(s); jax found "
            f"{len(devices)} {platform} device(s)", file=sys.stderr,
        )
        return NO_TPU
    if len(devices) < cell.chips:
        print(f"--tiny still needs {cell.chips} devices", file=sys.stderr)
        return NO_TPU
    devices = devices[:cell.chips]
    marks["devices_found"] = time.perf_counter()

    import importlib

    from benchmarks.harness import layers, readers
    from benchmarks.harness import trace as tr
    from d9d_tpu.telemetry import introspect

    # a traffic kind is a module of its own, found by name
    kind = importlib.import_module(
        f"benchmarks.kinds.{cell.traffic['kind']}"
    )
    trace_dir = (
        tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    )
    try:
        observed = kind.run(
            cell, args.seed, args.seconds, trace_dir, args.tiny, devices,
            trace_after=args.trace == 2,
        )
        run = readers.Run(
            cell=cell, observed=observed,
            setup_s=observed.opened_at - START,
            inventory=introspect.inventory(),
            device_kind=devices[0].device_kind,
        )
        if trace_dir is not None and platform == "tpu":
            run.trace = tr.load_xplane(tr.newest_xplane(trace_dir))
            run.programs = tuple(
                map(layers.compiled_program, observed.hlo_texts)
            )
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    verdict = kind.verdict(observed)
    entries = {
        0: cell.end_to_end, 1: cell.per_layer,
        2: cell.end_to_end + cell.per_layer,
    }[args.trace]
    metrics = {}
    for entry in entries:
        # a CPU run gives counts only: nothing timed is reported there
        if platform != "tpu" and entry["source"] != "program_counter":
            continue
        value = readers.read(run, entry["name"])
        if value is not None:
            metrics[entry["name"]] = {
                "value": float(value), "unit": entry["unit"],
            }

    device = {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak_bytes(devices, run.inventory),
    }
    line = {
        "correct": not verdict["failures"],
        **kind.attempts(observed),
        "metrics": metrics, "device": device,
    }
    if run.trace is not None:
        busy, window = tr.busy_and_window(run.trace)
        device.update(busy_s=busy, window_s=window)
        spans = tr.program_spans(run.trace)
        line["breakdown"] = {
            "device_ops": tr.top_ops(
                run.ops, layers.own_scope(run.ran), n=10),
            "idle_gaps": tr.idle_gaps(run.trace, spans, n=10),
        }
        if args.trace == 2:
            # the same idle time by the program's own spans of the traced
            # seconds (the always-on phase clocks, host/gc), placed on
            # the trace's clock by the program's clock anchor
            line["breakdown"]["idle_gaps_by_program_span"] = tr.idle_gaps(
                run.trace,
                layers.phase_spans_on_trace(run.trace, *observed.traced),
                n=10,
            )
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
        "samples": kind.samples(observed),
        "setup_marks_s": {
            k: v - START for k, v in {**marks, **observed.marks}.items()},
        "checks": verdict, "notes": run.notes,
    }, default=str), flush=True)
    print(json.dumps(line), flush=True)
    return 0


def memory_peak_bytes(devices, inventory) -> int:
    """Peak on the fullest chip. The allocator's peak leaves out a running
    program's temporaries on the v5e (PERF.md, PR 21), so the largest
    compiled program's own claim (arguments + outputs + temporaries, per
    device) stands beside it and the larger of the two is reported."""
    allocator = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices
    )
    claims = [r.hbm_peak_bytes for r in inventory if r.hbm_peak_bytes]
    return max([allocator, *map(int, claims)])


if __name__ == "__main__":
    sys.exit(main())
