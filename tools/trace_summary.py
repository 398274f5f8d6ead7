"""Summarize a jax.profiler trace OR telemetry JSONL event logs.

Usage:  python tools/trace_summary.py <logdir> [--top 25]
        python tools/trace_summary.py <telemetry dir or *.jsonl...> \\
            [--perfetto out.json]

**Profiler mode** — <logdir> is whatever was passed to
``jax.profiler.trace`` (the tool walks into the newest
``plugins/profile/<run>/`` underneath it and reads every
``*.trace.json.gz``). Prints one table of device-lane time grouped into
categories (matmul / custom-call / sort / scatter-gather / copy-layout /
collective / fusion / other) and the top individual ops — the quickest way
to see where an MoE or pipeline step actually spends its time without
opening xprof. Host-side lanes (Python, runtime threads) are excluded;
on CPU traces, where XLA compute runs on host threads, pass --all-lanes.

**Telemetry mode** — when the inputs are telemetry JSONL event logs
(``JsonlSink`` files, detected by the schema ``meta`` first line), the
tool instead prints the span-timeline aggregate, the per-executable
compile/FLOPs/HBM inventory (``executable`` events from
``telemetry/introspect.py``), and the final flush's counters. With
``--perfetto out.json`` it additionally merges ALL input files —
clock-aligned across processes via each file's monotonic epoch — into
one Chrome-trace/Perfetto JSON (``d9d_tpu/telemetry/trace_export.py``):
PP stage busy/bubble and serve admission become one inspectable
timeline at https://ui.perfetto.dev.

Two attribution tables ride the repo's own instrumentation
(core/tracing.py, the ``record_function`` analogue):

- **host regions**: TraceAnnotation events named ``pp.*`` (one per pipeline
  action, by kind/stage/microbatch), ``pp_opt.*`` (optimizer phases),
  ``loop.*`` (batch staging) and ``serve.*`` (continuous-batching dispatch /
  readback / admission, loop/serve.py), collapsed over stage/microbatch —
  shows where the single-controller dispatch loop spends host time;
- **device scopes**: device ops whose HLO metadata carries a
  ``jax.named_scope`` path (``pp_s0/fwd``, ``ep/dispatch_a2a``,
  ``train/optimizer``, …), grouped by the leading path components.
"""

import argparse
import collections
import glob
import gzip
import json
import os
import pathlib
import re
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

# order matters: collectives first, or all-gather/reduce-scatter would be
# swallowed by the scatter-gather pattern
CATEGORIES = [
    ("collective", re.compile(
        r"all-reduce|all-gather|all-to-all|reduce-scatter|collective|permute",
        re.I)),
    ("matmul", re.compile(r"dot|matmul|conv|einsum|ragged-dot", re.I)),
    ("custom-call", re.compile(r"custom-call|tpu_custom_call|pallas", re.I)),
    ("sort", re.compile(r"\bsort|top-k|topk", re.I)),
    ("scatter-gather", re.compile(r"scatter|gather|dynamic-slice|dynamic-update", re.I)),
    ("copy-layout", re.compile(r"copy|transpose|bitcast|reshape|pad\b", re.I)),
    ("fusion", re.compile(r"fusion|fused", re.I)),
]


def categorize(name: str) -> str:
    for cat, rx in CATEGORIES:
        if rx.search(name):
            return cat
    return "other"


def newest_profile_dir(logdir: str) -> str:
    runs = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*")))
    if not runs:
        # maybe logdir IS a profile run dir already
        if glob.glob(os.path.join(logdir, "*.trace.json.gz")):
            return logdir
        raise SystemExit(f"no plugins/profile/* runs under {logdir}")
    return runs[-1]


def load_events(run_dir: str):
    events, processes, threads = [], {}, {}
    for path in glob.glob(os.path.join(run_dir, "*.trace.json.gz")):
        data = json.loads(gzip.open(path).read())
        for e in data.get("traceEvents", []):
            ph = e.get("ph")
            if ph == "M":
                if e.get("name") == "process_name":
                    processes[e["pid"]] = e["args"]["name"]
                elif e.get("name") == "thread_name":
                    threads[(e["pid"], e.get("tid"))] = e["args"]["name"]
            elif ph == "X":
                events.append(e)
    return events, processes, threads


REGION_PREFIXES = ("pp.", "pp_opt.", "loop.", "serve.")
_MB_SUFFIX = re.compile(r"\.s\d+\.mb\d+$|\.mb\d+$")
# named-scope paths as stamped by this repo's instrumentation; matched
# anywhere in the op metadata because JAX prepends jit(<fn>)/ components
_SCOPE = re.compile(
    r"(?:^|/)((?:pp_s\d+|pp_opt|ep|train|loop|moe|decoder)/[\w.-]+)"
)


def summarize_host_regions(events):
    """Aggregate the repo's TraceAnnotation regions (any lane), collapsed
    over stage/microbatch → {label: (total_us, count)}."""
    agg = {}
    for e in events:
        name = e.get("name", "")
        if not name.startswith(REGION_PREFIXES):
            continue
        dur = e.get("dur", 0)
        if dur <= 0:
            continue
        label = _MB_SUFFIX.sub("", name)
        tot, cnt = agg.get(label, (0, 0))
        agg[label] = (tot + dur, cnt + 1)
    return agg


def scope_of(e) -> str | None:
    """This repo's named-scope path (2 components) from the op name or its
    HLO metadata, e.g. 'pp_s0/fwd' or 'ep/dispatch_a2a' — tolerant of the
    'jit(<fn>)/' prefix JAX stamps in front."""
    for cand in (e.get("name", ""),
                 str(e.get("args", {}).get("long_name", "")),
                 str(e.get("args", {}).get("tf_op", ""))):
        m = _SCOPE.search(cand)
        if m:
            return m.group(1)
    return None


# -- telemetry-JSONL mode ----------------------------------------------


def _is_telemetry_jsonl(path) -> bool:
    """True when the file opens with the telemetry schema meta header."""
    try:
        with open(path) as fh:
            first = json.loads(fh.readline())
        return first.get("kind") == "meta" and "schema" in first
    except (OSError, ValueError):
        return False


def collect_telemetry_files(paths) -> list:
    """Telemetry JSONL files among ``paths`` (files or directories);
    empty when the inputs are not telemetry logs (profiler mode)."""
    from d9d_tpu.telemetry.trace_export import discover_jsonl

    files = []
    for p in paths:
        files.extend(f for f in discover_jsonl(p) if _is_telemetry_jsonl(f))
    return files


def _fmt_bytes(v) -> str:
    if v is None:
        return "-"
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(v) < 1024 or unit == "GiB":
            return f"{v:.1f}{unit}" if unit != "B" else f"{int(v)}B"
        v /= 1024
    return f"{v:.1f}GiB"  # pragma: no cover — loop always returns


def _numerics_sort_key(item):
    """Worst offenders first: non-finite rows, then by grad/act absmax
    descending (NaN absmax sorts last among the finite rows)."""
    name, row = item
    absmax = row.get("absmax")
    bad = not row.get("finite", True)
    mag = absmax if isinstance(absmax, (int, float)) and absmax == absmax else -1.0
    return (0 if bad else 1, -mag, name)


def print_numerics(numerics_events, *, top: int) -> None:
    """The --numerics table: per-layer stats of the LAST window in the
    logs (by step, then file order), worst offenders first."""
    if not numerics_events:
        print("\nno numerics events in the logs (enable "
              "TrainerConfig.numerics_every_steps)")
        return
    path, ev = max(
        enumerate(numerics_events),
        key=lambda ie: (ie[1][1].get("step", -1), ie[0]),
    )[1]
    rows = ev.get("rows", {})
    print(f"\nnumerics window at step {ev.get('step')} "
          f"[{path.name}] ({len(rows)} row(s), worst first):")
    print(f"{'grad/act_rms':>13}  {'absmax':>11}  {'param_rms':>10}  "
          f"{'upd:param':>10}  {'m2_max':>10}  {'fin':>3}  {'kind':>5}  name")

    def fmt(v, w):
        return f"{v:>{w}.4g}" if isinstance(v, (int, float)) else f"{'-':>{w}}"

    for name, row in sorted(rows.items(), key=_numerics_sort_key)[:top]:
        print(
            f"{fmt(row.get('rms'), 13)}  {fmt(row.get('absmax'), 11)}  "
            f"{fmt(row.get('param_rms'), 10)}  "
            f"{fmt(row.get('update_ratio'), 10)}  "
            f"{fmt(row.get('moment2_max'), 10)}  "
            f"{'ok' if row.get('finite', True) else 'NaN':>3}  "
            f"{row.get('kind', '?'):>5}  {name}"
        )
    fn = ev.get("first_nonfinite")
    if fn:
        print(f"first non-finite: {fn.get('site')}:{fn.get('name')}")


_PP_STAGE_RE = re.compile(r"^pp/s(\d+)/(busy_s|bubble_s|bubble_frac)$")
_PP_RUN_RE = re.compile(r"^pp/run/r(\d+)/k(\d+)/wall_s$")


def print_pp_timeline(last_flush) -> None:
    """The --pp-timeline tables: per-stage busy/bubble attribution and
    per-run wall from each log's FINAL flush gauges — the fused
    runtime's ``pp_timeline_every_steps`` cadence (or the legacy
    interpreter, which attributes every step)."""
    printed = False
    for path, ev in last_flush.items():
        gauges = {
            k: v for k, v in ev.get("gauges", {}).items() if v is not None
        }
        stages = collections.defaultdict(dict)  # stage → {metric: v}
        runs = {}  # (rank, run) → wall_s
        for k, v in gauges.items():
            m = _PP_STAGE_RE.match(k)
            if m:
                stages[int(m.group(1))][m.group(2)] = v
                continue
            m = _PP_RUN_RE.match(k)
            if m:
                runs[(int(m.group(1)), int(m.group(2)))] = v
        if not stages and not runs:
            continue
        printed = True
        if stages:
            print(f"\npp timeline — per-stage attribution [{path.name}]:")
            print(f"{'stage':>5}  {'busy_s':>10}  {'bubble_s':>10}  "
                  f"{'bubble_frac':>11}")
            for s in sorted(stages):
                row = stages[s]
                print(
                    f"{s:>5}  {row.get('busy_s', float('nan')):>10.4f}  "
                    f"{row.get('bubble_s', float('nan')):>10.4f}  "
                    f"{row.get('bubble_frac', float('nan')):>11.3f}"
                )
            rollup = gauges.get("pp/bubble_frac")
            if rollup is not None:
                print(f"rollup pp/bubble_frac = {rollup:.3f}")
        if runs:
            print(f"\npp timeline — per-run wall [{path.name}]:")
            print(f"{'rank':>4}  {'run':>4}  {'wall_s':>10}")
            for (rank, run), wall in sorted(runs.items()):
                print(f"{rank:>4}  {run:>4}  {wall:>10.4f}")
    if not printed:
        print(
            "\nno pp timeline gauges in the logs (enable "
            "TrainerConfig.pp_timeline_every_steps on a "
            "runtime=\"fused\" pipeline run, or use the legacy "
            "interpreter, and make sure a flush follows the cadence "
            "step)"
        )


def print_audit(executables, *, top: int) -> None:
    """The --audit table: per-executable compiled-artifact facts
    (telemetry/audit_capture.py ``audit`` blocks on executable events)
    — collective counts, donated vs aliased buffers, largest baked
    constant, dtype census — next to the inventory table."""
    audited = [
        (path, ev) for path, ev in executables if ev.get("audit")
    ]
    if not audited:
        print(
            "\nno audit facts in the logs (the producing run must "
            "export D9D_AUDIT_CAPTURE=1 so compile-time artifact "
            "capture is on)"
        )
        return
    print(
        f"\ncompiled-artifact audit facts ({len(audited)} captured "
        "executable(s)):"
    )
    print(
        f"{'collectives':>24}  {'donated':>8}  {'aliased':>8}  "
        f"{'max_const':>10}  {'f64':>3}  {'f32mm':>5}  {'cb':>2}  "
        "dtypes  ctx:name"
    )
    shown = audited[: top * 2]
    for _path, ev in shown:
        a = ev["audit"]
        coll = a.get("collectives", {})
        coll_s = (
            ",".join(f"{k.replace('collective-', 'c-')}:{v}"
                     for k, v in sorted(coll.items()))
            if coll else "-"
        )
        consts = a.get("consts", [])
        max_const = _fmt_bytes(consts[0]["bytes"]) if consts else "-"
        dtypes = ",".join(
            f"{k.replace('float', 'f').replace('bfloat', 'bf')}:{v}"
            for k, v in sorted(a.get("dtype_ops", {}).items())
        )
        print(
            f"{coll_s:>24}  {a.get('donated_declared', 0):>8}  "
            f"{a.get('aliased_pairs', 0):>8}  {max_const:>10}  "
            f"{len(a.get('f64_ops', [])):>3}  "
            f"{a.get('f32_matmuls', 0):>5}  "
            f"{len(a.get('callbacks', [])):>2}  "
            f"{dtypes}  {a.get('context', '?')}:{ev['name']}"
        )
    if len(audited) > len(shown):
        print(f"(+{len(audited) - len(shown)} more — raise --top)")
    print(
        "audit these facts against AUDIT_BASELINE.json with "
        "`d9d-audit --facts <jsonl...>`"
    )


def summarize_telemetry(
    files, *, top: int, perfetto=None, trace_id=None, numerics=False,
    audit=False, pp_timeline=False,
) -> None:
    """Telemetry-mode report: span aggregate, per-executable inventory,
    per-request trace summary (schema v3 ``request_trace``), final flush
    counters; optional merged Perfetto export. ``trace_id`` filters the
    request-trace section to one request's full milestone sequence;
    ``numerics`` prints the per-layer table of the last numerics window
    (schema v4); ``audit`` prints the compiled-artifact facts table
    (audit blocks on executable events); ``pp_timeline`` prints the
    per-stage busy/bubble + per-run wall tables from the final flush's
    pipeline-timeline gauges. Reads leniently — a crashed process's
    truncated log must still report."""
    from d9d_tpu.telemetry.trace_export import _read_events_lenient

    spans = collections.defaultdict(lambda: [0.0, 0])  # name → [Σs, n]
    executables = []
    last_flush = {}
    requests = collections.defaultdict(list)  # trace_id → [events]
    numerics_events = []  # (path, event)
    for path in files:
        for ev in _read_events_lenient(path):
            if ev["kind"] == "span":
                agg = spans[ev["name"]]
                agg[0] += ev["dur_s"]
                agg[1] += 1
            elif ev["kind"] == "executable":
                executables.append((path, ev))
            elif ev["kind"] == "flush":
                last_flush[path] = ev
            elif ev["kind"] == "request_trace":
                requests[ev["trace_id"]].append(ev)
            elif ev["kind"] == "numerics":
                numerics_events.append((path, ev))

    print(f"telemetry logs: {[str(f) for f in files]}")
    if numerics:
        print_numerics(numerics_events, top=top)
    if pp_timeline:
        print_pp_timeline(last_flush)
    if trace_id is not None:
        evs = sorted(requests.get(trace_id, []), key=lambda e: e["t"])
        if not evs:
            print(f"\nno request_trace events for trace id {trace_id!r} "
                  f"({len(requests)} trace id(s) in the logs)")
        else:
            t0 = evs[0]["t"]
            print(f"\nrequest {trace_id} ({len(evs)} milestone(s)):")
            print(f"{'+ms':>10}  {'replica':>8}  {'rid':>5}  event")
            for ev in evs:
                meta = ev.get("meta")
                print(
                    f"{(ev['t'] - t0) * 1e3:>10.3f}  "
                    f"{str(ev.get('replica', '-')):>8}  "
                    f"{str(ev.get('rid', '-')):>5}  {ev['event']}"
                    + (f"  {meta}" if meta else "")
                )
    elif requests:
        migrations = sum(
            1 for evs in requests.values() for e in evs
            if e["event"] in ("migrate", "continuation")
        )
        by_replica = collections.Counter(
            e.get("replica", "-") for evs in requests.values() for e in evs
            if e["event"] == "submit"
        )
        print(
            f"\nrequest traces: {len(requests)} request(s), "
            f"{migrations} migration/continuation event(s); "
            f"submits by replica: {dict(sorted(by_replica.items()))} "
            "(--trace-id ID for one request's milestones)"
        )
    if spans:
        print(f"\nspans (Σ over {len(files)} process log(s)):")
        print(f"{'s':>10}  {'calls':>6}  {'ms/call':>9}  name")
        ordered = sorted(spans.items(), key=lambda kv: -kv[1][0])[:top]
        for name, (tot, cnt) in ordered:
            print(f"{tot:>10.3f}  {cnt:>6}  {tot/cnt*1e3:>9.3f}  {name}")

    if executables:
        print("\nper-executable inventory (compile cost / HLO analyses):")
        print(
            f"{'compile_s':>10}  {'GFLOPs':>9}  {'hbm_peak':>10}  "
            f"{'args':>10}  {'temps':>10}  {'re':>2}  name"
        )
        for _path, ev in executables:
            hbm = ev.get("hbm", {})
            flops = ev.get("flops")
            print(
                f"{ev['lower_s'] + ev['compile_s']:>10.3f}  "
                f"{(flops / 1e9 if flops is not None else float('nan')):>9.3f}  "
                f"{_fmt_bytes(hbm.get('peak')):>10}  "
                f"{_fmt_bytes(hbm.get('args')):>10}  "
                f"{_fmt_bytes(hbm.get('temps')):>10}  "
                f"{'R' if ev.get('recompile') else '':>2}  {ev['name']}"
            )
        recompiles = sum(1 for _p, e in executables if e.get("recompile"))
        print(
            f"{len(executables)} executables, {recompiles} recompile(s) "
            "(R rows)"
        )
    if audit:
        print_audit(executables, top=top)

    # per-replica serve rollup (the serve/{label}/* namespacing — the
    # fleet assigns r{i}, embedders may use any path-free label):
    # final-flush counters side by side, one row per replica
    for path, ev in last_flush.items():
        per_replica = collections.defaultdict(dict)
        for k, v in ev.get("counters", {}).items():
            m = re.match(r"^serve/([^/]+)/(.+)$", k)
            if m:
                per_replica[m.group(1)][m.group(2)] = v
        if per_replica:
            keys = sorted({k for d in per_replica.values() for k in d})
            print(f"\nper-replica serve counters [{path.name}]:")
            print(f"{'replica':>8}  " + "  ".join(f"{k:>20}" for k in keys))
            for r in sorted(per_replica):
                print(f"{r:>8}  " + "  ".join(
                    f"{per_replica[r].get(k, 0):>20.6g}" for k in keys
                ))

    for path, ev in last_flush.items():
        interesting = {
            k: v for k, v in ev.get("counters", {}).items()
        }
        interesting.update({
            k: v for k, v in ev.get("gauges", {}).items() if v is not None
        })
        if interesting:
            print(f"\nfinal flush counters/gauges [{path.name}]:")
            for k in sorted(interesting):
                print(f"  {k} = {interesting[k]:.6g}")

    if perfetto:
        from d9d_tpu.telemetry.trace_export import export_perfetto

        trace = export_perfetto(files, perfetto)
        print(
            f"\nperfetto: wrote {len(trace['traceEvents'])} events from "
            f"{trace['metadata']['processes']} process log(s) to {perfetto}"
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "logdir", nargs="+",
        help="jax.profiler trace dir, OR telemetry JSONL files/dirs",
    )
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument(
        "--all-lanes", action="store_true",
        help="include host lanes (needed for CPU traces, where XLA compute "
        "runs on host threads)",
    )
    ap.add_argument(
        "--perfetto", metavar="OUT.json", default=None,
        help="telemetry mode: merge all input JSONL logs into one "
        "clock-aligned Chrome-trace/Perfetto file",
    )
    ap.add_argument(
        "--trace-id", default=None,
        help="telemetry mode: print the full request_trace milestone "
        "sequence for one per-request trace id (schema v3)",
    )
    ap.add_argument(
        "--numerics", action="store_true",
        help="telemetry mode: print the per-layer numerics table of the "
        "last window (schema v4, worst offenders first)",
    )
    ap.add_argument(
        "--audit", action="store_true",
        help="telemetry mode: print the compiled-artifact audit facts "
        "table (collective counts, donation coverage, baked constants, "
        "dtype census) from executable events captured under "
        "D9D_AUDIT_CAPTURE=1",
    )
    ap.add_argument(
        "--pp-timeline", action="store_true",
        help="telemetry mode: print the per-stage busy/bubble table and "
        "the per-run wall table from the final flush's pipeline-timeline "
        "gauges (TrainerConfig.pp_timeline_every_steps on the fused "
        "runtime, or the legacy interpreter)",
    )
    args = ap.parse_args()

    telemetry_files = collect_telemetry_files(args.logdir)
    if telemetry_files:
        summarize_telemetry(
            telemetry_files, top=args.top, perfetto=args.perfetto,
            trace_id=args.trace_id, numerics=args.numerics,
            audit=args.audit, pp_timeline=args.pp_timeline,
        )
        return
    if args.perfetto:
        raise SystemExit(
            "--perfetto needs telemetry JSONL inputs (JsonlSink event "
            "logs); none found among the given paths"
        )
    if args.numerics:
        raise SystemExit(
            "--numerics needs telemetry JSONL inputs (schema-v4 "
            "numerics events from a TrainerConfig.numerics_every_steps "
            "run); none found among the given paths"
        )
    if args.audit:
        raise SystemExit(
            "--audit needs telemetry JSONL inputs (executable events "
            "with audit blocks from a D9D_AUDIT_CAPTURE=1 run); none "
            "found among the given paths"
        )
    if args.pp_timeline:
        raise SystemExit(
            "--pp-timeline needs telemetry JSONL inputs (flush events "
            "carrying pp/s{S}/* gauges from a "
            "TrainerConfig.pp_timeline_every_steps run); none found "
            "among the given paths"
        )
    if len(args.logdir) != 1:
        raise SystemExit("profiler mode takes exactly one logdir")

    run_dir = newest_profile_dir(args.logdir[0])
    events, processes, threads = load_events(run_dir)

    def is_device_lane(pid) -> bool:
        return "/device:" in processes.get(pid, "")

    # Device processes carry several thread lanes ("XLA Ops" plus
    # module/step span lanes, where one module event ~= the sum of its op
    # events) — keep only the op lane when it exists or totals double.
    device_pids = {p for p in processes if is_device_lane(p)}
    op_tids = {
        (pid, tid)
        for (pid, tid), name in threads.items()
        if pid in device_pids and "XLA Ops" in name
    }
    pids_with_op_lane = {pid for pid, _ in op_tids}

    degraded = device_pids - pids_with_op_lane
    if degraded and not args.all_lanes:
        print(
            f"warning: device process(es) {sorted(degraded)} have no "
            "'XLA Ops' lane — module/step span lanes are being counted, "
            "totals may be ~2x actual op time",
            file=sys.stderr,
        )

    def keep(e) -> bool:
        pid, tid = e.get("pid"), e.get("tid")
        if args.all_lanes:
            return True
        if pid not in device_pids:
            return False
        if pid in pids_with_op_lane:
            return (pid, tid) in op_tids
        return True

    by_name = collections.Counter()
    lanes = collections.Counter()
    for e in events:
        if not keep(e):
            continue
        dur = e.get("dur", 0)  # microseconds
        if dur <= 0:
            continue
        by_name[e["name"]] += dur
        lanes[processes.get(e.get("pid"), "?")] += dur

    if not by_name:
        hint = "" if args.all_lanes else " (try --all-lanes for CPU traces)"
        raise SystemExit(f"no timed events found in {run_dir}{hint}")

    total = sum(by_name.values())
    by_cat = collections.Counter()
    for name, dur in by_name.items():
        by_cat[categorize(name)] += dur

    print(f"run: {run_dir}")
    print(f"lanes: {dict(lanes)}")
    print(f"\ntotal timed op time: {total/1e3:.3f} ms\n")
    print(f"{'category':<16}{'ms':>12}{'share':>9}")
    for cat, dur in by_cat.most_common():
        print(f"{cat:<16}{dur/1e3:>12.3f}{dur/total:>8.1%}")
    print(f"\ntop {args.top} ops:")
    print(f"{'ms':>10}  {'share':>6}  name")
    for name, dur in by_name.most_common(args.top):
        print(f"{dur/1e3:>10.3f}  {dur/total:>6.1%}  {name[:100]}")

    # device time grouped by named-scope path (pp_s*/{fwd,bwd}, ep/*, ...)
    by_scope = collections.Counter()
    for e in events:
        if not keep(e):
            continue
        dur = e.get("dur", 0)
        if dur <= 0:
            continue
        scope = scope_of(e)
        if scope:
            by_scope[scope] += dur
    if by_scope:
        print("\ndevice time by named scope:")
        print(f"{'ms':>10}  {'share':>6}  scope")
        for scope, dur in by_scope.most_common(args.top):
            print(f"{dur/1e3:>10.3f}  {dur/total:>6.1%}  {scope}")

    # host dispatch regions from the repo's TraceAnnotations (all lanes)
    regions = summarize_host_regions(events)
    if regions:
        print("\nhost trace-annotation regions (Σ over stages/microbatches):")
        print(f"{'ms':>10}  {'calls':>6}  {'ms/call':>9}  region")
        for label, (tot, cnt) in sorted(
            regions.items(), key=lambda kv: -kv[1][0]
        ):
            print(f"{tot/1e3:>10.3f}  {cnt:>6}  {tot/cnt/1e3:>9.4f}  {label}")
    else:
        print("\n(no pp./pp_opt./loop./serve. trace-annotation regions in "
              "this trace — capture with set_trace_annotations(True) or via "
              "JobProfiler)")


if __name__ == "__main__":
    main()
