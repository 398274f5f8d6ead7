"""The generic readers: how a metric file's ``reader`` becomes a number.

A metric is ``benchmarks/metrics/<name>.json``. Its ``reader`` is either
``{"use": "<a function below>", "args": {...}}`` or ``{"file": true}``,
which loads ``benchmarks/metrics/<name>.py`` and calls its
``read(run)``. A reader that finds nothing to read returns ``None`` and
the harness leaves the metric out of the line. Readers never touch the
program: they see what the cell observed (``run.observed``), the
normalised trace of a traced run (``run.trace``) with its device ops
grouped by instruction (``run.ops``) and the compiled programs that
ran them (``run.ran``), and the cell's files.
"""

import dataclasses
import importlib.util
import statistics

import numpy as np

from . import costs, layers, manifest
from . import trace as tr


@dataclasses.dataclass
class Run:
    cell: object
    observed: object
    setup_s: float
    inventory: tuple
    device_kind: str
    trace: dict | None = None
    programs: tuple = ()  # layers.compiled_program of each program traced
    notes: dict = dataclasses.field(default_factory=dict)
    reading: str = "scope"  # the metric being read: what its notes go under
    _grouped: tuple | None = dataclasses.field(default=None, repr=False)

    def note(self, key: str, value) -> None:
        """Beside the metric being read: ``<metric>.<key>``."""
        self.notes[f"{self.reading}.{key}"] = value

    def _once(self) -> tuple:
        """The traced device ops grouped by instruction (``trace.grouped``)
        and the compiled programs each module of the trace may be
        (``layers.programs_that_ran``), worked out once a trace and not
        once a metric: the Jamba cell's 4 s hold 540,000 events and the
        Laguna step's text is 18 MB. Device time no scope's pattern can
        take (``layers.unread_seconds``) is noted as ``scope.unplaced_ms``
        and ``scope.unscoped_ms`` where it is not 0, so that a share that
        leaves work out says so."""
        held = self._grouped
        if (held is None or held[0] is not self.trace
                or held[1] is not self.programs):
            ops = tr.grouped(self.trace)
            ran = layers.programs_that_ran(ops, self.programs)
            held = self._grouped = (self.trace, self.programs, ops, ran)
            for key, seconds in layers.unread_seconds(ops, ran).items():
                if seconds:
                    self.notes[f"scope.{key}_ms"] = 1e3 * seconds
        return held

    @property
    def ops(self) -> tr.Ops:
        return self._once()[2]

    @property
    def ran(self) -> dict:
        return self._once()[3]

    @property
    def hf(self) -> dict:
        return self.cell.config

    @property
    def peak(self):
        from . import peaks

        return peaks.peak_for(self.device_kind)


def read(run: Run, name: str):
    own = manifest.metric_file(name)
    reader = own["reader"]
    run.reading = name
    if reader.get("file"):
        path = manifest.BENCH_DIR / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace(".", "_").replace("-", "_"), path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read(run)
    return globals()[reader["use"]](run, **reader.get("args", {}))


# -- end to end ---------------------------------------------------------------


def setup_seconds(run: Run):
    return run.setup_s


def train_rate(run: Run):
    o = run.observed
    return o.steps_in_window * o.tokens_per_step / o.window_s / o.chips


def serve_rate(run: Run):
    """Tokens emitted after the first chunk boundary inside the window up
    to the last one, over the time between the two."""
    b = run.observed.boundaries
    if len(b) < 2:
        return None
    return sum(tokens for _, tokens in b[1:]) / (b[-1][0] - b[0][0])


def request_percentile(run: Run, field: str, q: float, scale: float = 1.0):
    values = [getattr(r, field) for r in run.observed.requests]
    return float(np.percentile(values, q)) * scale if values else None


# -- entry points -------------------------------------------------------------


def compile_seconds(run: Run):
    return sum(r.lower_s + r.compile_s for r in run.inventory)


def compiles_in_window(run: Run):
    return float(run.observed.compiles_in_window)


# -- training loop ------------------------------------------------------------


def train_mfu(run: Run):
    """Model FLOPs per token x tokens per second per chip over the peak;
    recomputed operations are not counted."""
    per_token = costs.train_flops_per_token(run.hf, run.observed.seq_len)
    return 100.0 * per_token * train_rate(run) / run.peak.bf16_flops


def unthrottled_phase_share(run: Run, phases, decile: int):
    """What the loop costs the host in ``phases`` when nothing holds it
    back: that decile, over the window's steps, of a step's time in
    them, over the window's seconds per step. The Trainer dispatches
    without waiting, so in most steps the host is throttled by the
    device somewhere inside these phases (their plain sum over the
    window is pinned at 100 %); right after each metric fetch it runs
    free for a step or two, and the lower decile is such a step."""
    o = run.observed
    names = {f"train/phase/{p}" for p in phases}
    per_step: dict[int, float] = {}
    for s in o.spans:
        if s.name in names and s.step is not None and s.step >= o.first_step:
            per_step[s.step] = per_step.get(s.step, 0.0) + s.dur_s
    if len(per_step) < 10:
        return None
    own = statistics.quantiles(per_step.values(), n=10)[decile - 1]
    return 100.0 * own / (o.window_s / o.steps_in_window)


def hbm_claim_gb(run: Run):
    claim = run.observed.step_hbm_bytes
    return claim / 1e9 if claim else None


# -- serving loop -------------------------------------------------------------


def stats_ratio(run: Run, numerator: str, denominator: str,
                scale: float = 1.0):
    stats = run.observed.stats_window
    if not stats[denominator]:
        return None
    return scale * stats[numerator] / stats[denominator]


def prompt_step_share(run: Run):
    o = run.observed
    busy = o.stats_window["slot_steps_busy"]
    return 100.0 * o.prompt_steps_window / busy if busy else None


# -- from the trace -----------------------------------------------------------


def _traced(run: Run) -> bool:
    return run.trace is not None and bool(run.trace["devices"])


def module_median_ms(run: Run, pattern: str):
    if not _traced(run):
        return None
    runs = tr.module_seconds(run.trace, pattern)
    return 1e3 * statistics.median(runs) if runs else None


def device_idle(run: Run):
    return 100.0 * tr.idle_share(run.trace) if _traced(run) else None


def collective_exposed(run: Run):
    if not _traced(run):
        return None
    return 100.0 * tr.collective_exposed_share(run.trace)


def host_gap_per_chunk(run: Run, spans, module_pattern: str):
    """Device-idle milliseconds inside the named host spans, per chunk."""
    if not _traced(run):
        return None
    chunks = len(tr.module_seconds(run.trace, module_pattern))
    if not chunks:
        return None
    program = tr.program_spans(run.trace)
    idle = tr.idle_seconds_in(run.trace, program, set(spans))
    return 1e3 * idle / chunks / max(len(run.trace["devices"]), 1)


def kernel_roofline(run: Run, cost: str, module_pattern: str, call=None,
                    scope=None, product_scope=None):
    """A kernel's share of its roofline: the least time the chip could
    take for the work the cell's shapes define (per execution of the
    step program, per device) over the kernel's device time. The
    metric's file says which events of the step program are the
    kernel's, each taken by its own instruction
    (``layers.own_instruction``): named like ``call``, under ``scope``,
    or a matrix product under ``product_scope`` (``layers.own_seconds``:
    nothing where two programs the trace cannot tell apart disagree)."""
    measured = layers.own_seconds(
        run, module_pattern, call, scope, product_scope)
    if measured is None:
        return None
    executions = len(tr.module_seconds(run.trace, module_pattern))
    executions /= max(len(run.trace["devices"]), 1)
    if not measured["events"] or not executions:
        return None
    work = KERNEL_COSTS[cost](run)
    least, bound = costs.roofline_seconds(work, run.peak)
    share = tr.roofline_share(least * executions, measured["seconds"])
    run.note("bound", bound)
    run.note("device_s", measured["seconds"])
    return 100.0 * share


def _scaled(work: dict, factor: float) -> dict:
    return {k: v * factor for k, v in work.items()}


def _expert_mm_train(run: Run) -> dict:
    o = run.observed
    one = costs.expert_mm_train(run.hf, o.tokens_per_step)
    return _scaled(one, costs.n_trained_sparse_layers(run.hf) / o.chips)


def _flash_train(run: Run) -> dict:
    o = run.observed
    one = costs.flash_train(
        run.hf, o.tokens_per_step // o.seq_len, o.seq_len
    )
    return _scaled(one, costs.n_trained_attention_layers(run.hf) / o.chips)


def _expert_mm_decode(run: Run) -> dict:
    """Per execution of the fused chunk: ``chunk_k`` decode steps through
    the stack, and through no multi-token-prediction module."""
    o = run.observed
    touched = costs.expected_experts_touched(run.hf, o.slots)
    one = costs.expert_mm_decode(run.hf, o.slots, touched)
    return _scaled(one, costs.n_sparse_layers(run.hf) * o.chunk_k)


KERNEL_COSTS = {
    "expert_mm_train": _expert_mm_train,
    "flash_train": _flash_train,
    "expert_mm_decode": _expert_mm_decode,
}
