"""Device-side introspection: compile/recompile accounting and
per-executable HLO cost + HBM attribution.

PR 4's telemetry sees the host (phase timelines, serve latencies, MFU
gauges) but is blind to the device: nothing records compile time,
detects a silent steady-state recompile, or attributes HBM per
executable. This module closes that gap with one wrapper:

``tracked_jit(fn, name=...)`` behaves like ``jax.jit(fn, ...)`` but
routes every distinct abstract input signature through the explicit AOT
path (``lower()`` → ``compile()`` → call), which makes three things
observable for free:

- **compile spans** — lowering + backend-compile wall time land as a
  ``compile/{name}`` span (feeding the same-named histogram) with the
  lower/compile split in its meta;
- **recompile guard** — a compile on a wrapper that already holds a
  compiled signature is a *recompile*; after the configured warmup
  (``RecompileGuard``) each one bumps the ``compile/recompile`` counter
  and emits a rate-limited warning — the silent-recompile tripwire for
  the steady-state training loop;
- **executable inventory** — ``compiled.cost_analysis()`` /
  ``memory_analysis()`` (normalized in ``core/compat.py``; backends may
  return None) are harvested into a process-wide inventory: FLOPs,
  bytes-accessed, and the args/outputs/temps/generated-code HBM
  breakdown per executable, streamed to sinks as schema-v2
  ``executable`` events and read back through :func:`inventory` by the
  benchmark's ``entry.compile_s`` / ``entry.lower_s``
  (``benchmarks/harness/readers.py``).

The happy path costs **zero** extra device dispatches or readbacks: the
AOT call is the very dispatch ``jax.jit`` would have made. The
signature key is a Python walk over every argument leaf; it runs when a
signature is first seen and when the C++ dispatch of the last call's
executable does not recognise a call (``TrackedJit._steady_call``), not
on every call: a steady loop pays three clock reads and a tuple, which
each call also reports (``TrackedJit.last_call``, totals on the
``ExecutableRecord``). If the AOT machinery raises during lower/compile
(exotic argument types, plugin quirks), the wrapper permanently
degrades to the plain jitted function for that site, logs once, and
keeps the program running — introspection must never take down
training.

No jax import at module load: the telemetry package core stays
jax-free; ``tracked_jit`` defers the import to first use.
"""

import dataclasses
import hashlib
import logging
import threading
import time
import weakref
from typing import Any, Callable, NamedTuple

from d9d_tpu.telemetry import audit_capture  # stdlib-only at import

__all__ = [
    "CallCost",
    "ExecutableRecord",
    "RecompileGuard",
    "TrackedJit",
    "compiled_hlo",
    "executable_flops",
    "inventory",
    "recompile_guard",
    "reset_inventory",
    "tracked_jit",
]

logger = logging.getLogger("d9d_tpu.telemetry.introspect")


class CallCost(NamedTuple):
    """What one call through a :class:`TrackedJit` cost the host: the
    seconds before the compiled call (flatten, signature, lookup; a
    compile's own seconds are left out, its span has them), the seconds
    inside ``compiled(*args)`` (the enqueue), and the argument leaves."""

    key_s: float
    enqueue_s: float
    arg_leaves: int


@dataclasses.dataclass
class ExecutableRecord:
    """One compiled executable: identity, compile cost, HLO analyses."""

    name: str
    signature: str  # digest of the abstract input signature
    lower_s: float
    compile_s: float
    recompile: bool  # this wrapper already held a compiled signature
    step: int | None
    flops: float | None = None
    bytes_accessed: float | None = None
    # memory_analysis breakdown (bytes); None where the backend declines
    argument_bytes: int | None = None
    output_bytes: int | None = None
    temp_bytes: int | None = None
    generated_code_bytes: int | None = None
    alias_bytes: int | None = None
    calls: int = 0
    # what the wrapper itself cost over those calls (host clock, no
    # sync): finding the executable, and inside ``compiled(*args)``
    key_s: float = 0.0
    enqueue_s: float = 0.0
    arg_leaves: int = 0  # argument leaves of this signature
    # compile-time artifact facts (telemetry/audit_capture.py): only
    # populated when audit capture is opted in — collective census,
    # donation coverage, baked consts, dtype census, host callbacks
    audit: dict[str, Any] | None = None
    # schedule manifest (optional, set by the producing component before
    # first call — the fused PP executor attaches its per-run op list:
    # rank, run index, ordered ops with stage/kind/microbatch and
    # declared read/write value keys). Rides the same executable event
    # as ``audit`` — optional fields need no schema bump.
    manifest: dict[str, Any] | None = None

    @property
    def hbm_peak_bytes(self) -> int | None:
        """Args + outputs + temps + generated code minus aliased (donated
        inputs overlap outputs) — the executable's device-memory claim
        the HBM budget gauge compares against chip capacity."""
        parts = [
            self.argument_bytes,
            self.output_bytes,
            self.temp_bytes,
            self.generated_code_bytes,
        ]
        if all(p is None for p in parts):
            return None
        total = sum(p for p in parts if p is not None)
        if self.alias_bytes is not None:
            total -= self.alias_bytes
        return max(total, 0)

    def event(self) -> dict[str, Any]:
        """The schema-v2 ``executable`` event payload sinks receive."""
        ev: dict[str, Any] = {
            "name": self.name,
            "signature": self.signature,
            "lower_s": self.lower_s,
            "compile_s": self.compile_s,
            "recompile": self.recompile,
        }
        if self.step is not None:
            ev["step"] = self.step
        if self.flops is not None:
            ev["flops"] = self.flops
        if self.bytes_accessed is not None:
            ev["bytes_accessed"] = self.bytes_accessed
        hbm = {
            k: v
            for k, v in (
                ("args", self.argument_bytes),
                ("outputs", self.output_bytes),
                ("temps", self.temp_bytes),
                ("generated_code", self.generated_code_bytes),
                ("alias", self.alias_bytes),
                ("peak", self.hbm_peak_bytes),
            )
            if v is not None
        }
        if hbm:
            ev["hbm"] = hbm
        if self.audit is not None:
            ev["audit"] = self.audit
        if self.manifest is not None:
            ev["manifest"] = self.manifest
        return ev


# -- process-wide executable inventory ----------------------------------

_INVENTORY: list[ExecutableRecord] = []
_INVENTORY_LOCK = threading.Lock()


def inventory() -> tuple[ExecutableRecord, ...]:
    """Every executable compiled through ``tracked_jit`` in this
    process, in compile order."""
    with _INVENTORY_LOCK:
        return tuple(_INVENTORY)


def reset_inventory() -> None:
    """Drop the inventory (tests / bench measurement windows). Wrappers
    keep their compiled executables — only the records are cleared."""
    with _INVENTORY_LOCK:
        _INVENTORY.clear()


def executable_flops(name: str) -> float | None:
    """XLA-reported FLOPs of the newest inventory record for ``name``
    (the cross-check input for ``flops/model_vs_xla_divergence``)."""
    with _INVENTORY_LOCK:
        for rec in reversed(_INVENTORY):
            if rec.name == name and rec.flops is not None:
                return rec.flops
    return None


# live wrappers, so a caller can read back the programs that really ran
_WRAPPERS: "weakref.WeakSet[TrackedJit]" = weakref.WeakSet()


def compiled_hlo(name: str) -> list[str]:
    """Optimized HLO text of every executable that live ``tracked_jit``
    wrappers named ``name`` hold — the programs that were dispatched,
    not a re-lowering of them. ``chip_smoke.py`` reads it to show which
    kernels and collectives a chip run took."""
    with _INVENTORY_LOCK:
        wrappers = [w for w in _WRAPPERS if w.name == name]
    return [
        compiled.as_text()
        for w in wrappers
        for compiled in list(w._compiled.values())
    ]


# -- recompile guard ----------------------------------------------------


class RecompileGuard:
    """Arms the silent-recompile tripwire once warmup is over.

    Warmup is expressed in *loop steps of the current train() session*:
    the trainer calls :meth:`note_step` after each completed step and
    the guard flips steady once ``warmup_steps`` have run — by then
    every legitimate signature variant (ragged last microbatch, guarded
    vs unguarded step, both fused-serve variants in a warmed batcher)
    has compiled. Recompiles during warmup only count toward
    ``compile/recompiles_total``; recompiles in steady state
    additionally bump ``compile/recompile`` and emit a rate-limited
    warning. Harnesses without a step loop (bench sweeps compiling many
    configs on purpose) simply never arm the guard.
    """

    def __init__(self, *, warmup_steps: int = 1, warn_every_s: float = 30.0):
        self.warmup_steps = warmup_steps
        self.warn_every_s = warn_every_s
        self._steady = False
        self._last_warn = -float("inf")
        self._lock = threading.Lock()

    @property
    def steady(self) -> bool:
        return self._steady

    def configure(self, warmup_steps: int) -> None:
        """Re-arm for a fresh session: steady resets, warmup restarts."""
        self.warmup_steps = warmup_steps
        self._steady = False

    def note_step(self, session_steps: int) -> None:
        """Called by the loop after each completed step with the number
        of steps run *this session* (a resumed process re-warms: its
        wrappers start empty regardless of the global step counter)."""
        if not self._steady and session_steps >= self.warmup_steps:
            self._steady = True

    def mark_steady(self) -> None:
        self._steady = True

    def reset(self) -> None:
        self._steady = False
        self._last_warn = -float("inf")

    def on_recompile(self, name: str, signature: str, telemetry) -> None:
        """Account one recompile; warn (rate-limited) iff steady."""
        telemetry.counter("compile/recompiles_total").add(1)
        if not self._steady:
            return
        telemetry.counter("compile/recompile").add(1)
        with self._lock:
            now = time.monotonic()
            warn = now - self._last_warn >= self.warn_every_s
            if warn:
                self._last_warn = now
        if warn:
            logger.warning(
                "steady-state recompile of %r (signature %s): an input "
                "shape/dtype/sharding changed after warmup — every such "
                "step pays a full XLA compile",
                name, signature,
            )


_GUARD = RecompileGuard()


def recompile_guard() -> RecompileGuard:
    """The process-wide guard every ``tracked_jit`` wrapper consults."""
    return _GUARD


# -- signature fingerprinting -------------------------------------------


# sharding → canonical placement token, memoized by the (hashable)
# sharding value. The token must identify PLACEMENT, not the Python
# wrapper type: a jitted step returns GSPMD shardings for arrays that
# went in as NamedShardings, with identical device layout — keying on
# the objects themselves would flag every step-2 call as a recompile
# that jax.jit's own cache never performs.
_SHARDING_TOKENS: dict[Any, Any] = {}


def _sharding_token(sharding, ndim: int) -> Any:
    try:
        key = (sharding, ndim)
        token = _SHARDING_TOKENS.get(key)
        if token is None:
            token = _SHARDING_TOKENS[key] = (
                str(sharding._to_xla_hlo_sharding(ndim)),
                tuple(sorted(d.id for d in sharding.device_set)),
                getattr(sharding, "memory_kind", None),
            )
        return token
    except Exception:  # noqa: BLE001 — exotic sharding: degrade to repr
        return str(sharding)


def _leaf_sig(x) -> Any:
    """Hashable abstract signature of one argument leaf, matching what
    ``jax.jit``'s cache key distinguishes: shape/dtype/placement for
    arrays, weak type-identity for host scalars (different Python int
    *values* share one trace, so the value must not enter the key)."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        sharding = getattr(x, "sharding", None)
        if sharding is not None:
            sharding = _sharding_token(sharding, len(x.shape))
        return (tuple(x.shape), str(x.dtype), sharding)
    if x is None or isinstance(x, (bool, int, float, complex)):
        return type(x).__name__
    return repr(x)


class _OtherSignature(Exception):
    """A steady call met arguments of another signature: ``key``."""

    def __init__(self, key):
        super().__init__()
        self.key = key


class TrackedJit:
    """``jax.jit`` with compile/recompile/cost/HBM accounting.

    Call-compatible with the jitted function (positional and keyword
    arguments; donation and other jit kwargs pass through). Each
    distinct abstract input signature is lowered and compiled once via
    the AOT path and the resulting executable is cached here — exactly
    the cache ``jax.jit`` keeps internally, made observable.
    """

    def __init__(self, fn: Callable, *, name: str, **jit_kwargs: Any):
        import jax  # deferred: telemetry package core stays jax-free

        self.name = name
        self._fn = fn
        self._jit = jax.jit(fn, **jit_kwargs)
        # kept for the audit-capture donation check (declared donated
        # buffers are counted against the concrete call arguments)
        self._jit_kwargs = dict(jit_kwargs)
        # schedule manifest (ExecutableRecord.manifest): producers that
        # know the program's internal structure (the fused PP executor's
        # per-run op list) set this BEFORE the first call; every record
        # this wrapper files then carries it into the JSONL sidecar and
        # the introspection inventory
        self.manifest: dict[str, Any] | None = None
        self._compiled: dict[Any, Any] = {}
        self._records: dict[Any, ExecutableRecord] = {}
        self._fallback = False
        # the newest call's own cost on the host (None before any): the
        # caller that owns the call's span puts it there; the running
        # totals are on the signature's ExecutableRecord
        self.last_call: CallCost | None = None
        # the newest signature's C++-dispatched call (_steady_call), and
        # every one made so far
        self._steady: tuple | None = None
        self._walked_s = 0.0  # of a walk inside the newest steady call
        self._steady_calls: dict[Any, tuple] = {}
        self._lock = threading.Lock()
        with _INVENTORY_LOCK:
            _WRAPPERS.add(self)

    # the plain jitted function, for callers that need jit attributes
    @property
    def jitted(self):
        return self._jit

    def _signature_key(self, args, kwargs):
        import jax

        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        return (tuple(_leaf_sig(x) for x in leaves), treedef)

    def _compile(self, key, args, kwargs):
        """Lower + compile ``key``'s signature, harvest analyses, file
        the record. Returns the compiled executable, or None after
        degrading to the plain jit path (machinery failure only —
        errors from *running* the computation always propagate)."""
        from d9d_tpu.core import compat
        from d9d_tpu.telemetry import get_telemetry

        tele = get_telemetry()
        recompile = bool(self._compiled)
        # artifact capture (audit_capture.py) is compile-time-only and
        # opt-in: with it off this path is byte-identical to before; with
        # it on, trace()+lower() replace the single lower() call (the
        # same trace jax runs inside lower(), split so the jaxpr is
        # inspectable) — the dispatch path below never changes
        capture = audit_capture.capture_enabled()
        traced = None
        t0 = time.perf_counter()
        try:
            if capture and hasattr(self._jit, "trace"):
                try:
                    traced = self._jit.trace(*args, **kwargs)
                    lowered = traced.lower()
                except Exception:  # noqa: BLE001 — capture must never
                    # degrade the TRACKED path: a quirk specific to the
                    # trace() split falls back to the plain lower()
                    # (facts omitted, accounting kept); a genuinely
                    # untraceable fn re-raises identically from lower()
                    # and lands in the outer fallback as before
                    traced = None
                    logger.warning(
                        "audit capture: trace() failed for %r; "
                        "retrying the plain lower() path (facts "
                        "omitted, compile accounting kept)",
                        self.name, exc_info=True,
                    )
                    lowered = self._jit.lower(*args, **kwargs)
            else:
                lowered = self._jit.lower(*args, **kwargs)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        except Exception:  # noqa: BLE001 — degrade, never break the loop
            self._fallback = True
            logger.warning(
                "tracked_jit(%r): AOT lower/compile failed; falling back "
                "to plain jax.jit for this site (compile/HBM accounting "
                "disabled for it)", self.name, exc_info=True,
            )
            return None

        sig = hashlib.sha1(repr(key).encode()).hexdigest()[:10]
        record = ExecutableRecord(
            name=self.name,
            signature=sig,
            lower_s=t1 - t0,
            compile_s=t2 - t1,
            recompile=recompile,
            step=tele.registry.current_step,
        )
        ca = compat.compiled_cost_analysis(compiled)
        if ca:
            record.flops = ca.get("flops")
            record.bytes_accessed = ca.get("bytes accessed")
        ma = compat.compiled_memory_analysis(compiled)
        if ma:
            record.argument_bytes = ma.get("argument_size_in_bytes")
            record.output_bytes = ma.get("output_size_in_bytes")
            record.temp_bytes = ma.get("temp_size_in_bytes")
            record.generated_code_bytes = ma.get(
                "generated_code_size_in_bytes"
            )
            record.alias_bytes = ma.get("alias_size_in_bytes")
        if self.manifest is not None:
            record.manifest = self.manifest

        if capture:
            try:
                record.audit = audit_capture.extract_facts(
                    self.name,
                    closed_jaxpr=getattr(traced, "jaxpr", None),
                    compiled_text=compiled.as_text(),
                    args=args,
                    kwargs=kwargs,
                    jit_kwargs=self._jit_kwargs,
                ).to_dict()
            except Exception:  # noqa: BLE001 — facts are observability,
                # never a reason to fail a compile; the audit gate reads
                # a missing block as "not captured" and fails THERE
                logger.warning(
                    "audit capture failed for %r (facts omitted)",
                    self.name, exc_info=True,
                )

        with _INVENTORY_LOCK:
            _INVENTORY.append(record)
        self._records[key] = record

        # compile/{name} span (feeds the same-named histogram) with the
        # lower/compile split; counters for cheap cross-run aggregation
        tele.registry.record_span(
            f"compile/{self.name}", t0, t2 - t0,
            meta={
                "lower_s": record.lower_s,
                "compile_s": record.compile_s,
                "signature": sig,
                "recompile": recompile,
            },
        )
        tele.counter("compile/count").add(1)
        tele.counter("compile/wall_s").add(t2 - t0)
        if recompile:
            _GUARD.on_recompile(self.name, sig, tele)

        # HBM budget gauges: per-executable claim, plus the fraction of
        # chip capacity where the backend reports one (TPU; CPU rigs
        # have no bytes_limit and skip the fraction)
        peak = record.hbm_peak_bytes
        if peak is not None:
            tele.gauge(f"hbm/{self.name}/peak_bytes").set(peak)
            cap = compat.device_hbm_capacity()
            if cap:
                tele.gauge("hbm/device_capacity_bytes").set(cap)
                tele.gauge(f"hbm/{self.name}/budget_frac").set(peak / cap)

        tele.record_executable(record.event())
        return compiled

    def __call__(self, *args, **kwargs):
        t0 = time.perf_counter()
        if self._fallback:
            return self._call_plain(t0, args, kwargs)
        key = None
        steady = self._steady
        if steady is not None:
            # the signature of the call before: the C++ dispatch of its
            # executable recognises it with no Python walk over the leaves
            call, record, leaves = steady
            t1 = time.perf_counter()
            try:
                out = call(*args, **kwargs)
            except _OtherSignature as other:
                key = other.key
            else:
                self._account(record, t0, t1, 0.0, leaves)
                return out
        if key is None:
            key = self._signature_key(args, kwargs)
        compiled = self._compiled.get(key)
        compile_s = 0.0
        if compiled is None:
            tc = time.perf_counter()
            with self._lock:
                compiled = self._compiled.get(key)
                if compiled is None and not self._fallback:
                    compiled = self._compile(key, args, kwargs)
                    if compiled is not None:
                        self._compiled[key] = compiled
            if compiled is None:  # degraded inside _compile
                return self._call_plain(t0, args, kwargs)
            compile_s = time.perf_counter() - tc
        record = self._records.get(key)
        t1 = time.perf_counter()
        out = compiled(*args, **kwargs)
        self._account(record, t0, t1, compile_s, len(key[0]))
        self._steady = self._steady_call(key, compiled, record)
        return out

    def _account(self, record, t0, t1, compile_s, leaves):
        """File what the call that just returned cost the host: as
        ``last_call`` for the caller, and onto the signature's record."""
        # a walk the C++ dispatch asked for ran inside the call: it is
        # the key's, not the enqueue's
        walked, self._walked_s = self._walked_s, 0.0
        cost = self.last_call = CallCost(
            t1 - t0 - compile_s + walked,
            time.perf_counter() - t1 - walked, leaves,
        )
        if record is not None:
            record.calls += 1
            record.key_s += cost.key_s
            record.enqueue_s += cost.enqueue_s
            record.arg_leaves = leaves

    def _steady_call(self, key, compiled, record):
        """``(call, record, leaves)`` for the steady state of ``key``:
        ``call`` dispatches ``compiled`` from C++ for every argument
        signature it has seen, as ``jax.jit`` and ``Compiled.__call__``
        do, and asks Python only about one it has not. There the walk
        over the leaves decides: this executable's signature goes on to
        jax's own AOT call (which C++ then remembers), any other raises
        :class:`_OtherSignature` with the key the walk made. ``None``
        where this jax does not offer the pieces (the walk then runs on
        every call, as before), or the record is gone."""
        if record is None:
            return None
        made = self._steady_calls.get(key)
        if made is None:
            aot_miss = getattr(
                getattr(compiled, "_call", None), "_cache_miss", None
            )
            if aot_miss is None:  # no C++ call for this executable
                return None

            me = weakref.ref(self)  # the call hangs off self: no cycle

            def cache_miss(*args, **kwargs):
                wrapper = me()
                t = time.perf_counter()
                seen = wrapper._signature_key(args, kwargs)
                if seen != key:
                    raise _OtherSignature(seen)
                wrapper._walked_s = time.perf_counter() - t
                return aot_miss(*args, **kwargs)

            try:
                from jax._src import tree_util
                from jax._src.interpreters import pxla
                from jax._src.lib import xla_client

                call = xla_client._xla.pjit(
                    self.name, None, cache_miss, [], [],
                    pxla.JitGlobalCppCacheKeys(),
                    tree_util.dispatch_registry, pxla.cc_shard_arg,
                )
            except Exception:  # noqa: BLE001 — another jax: keep walking
                return None
            made = self._steady_calls[key] = (call, record, len(key[0]))
        return made

    def _call_plain(self, t0, args, kwargs):
        """The degraded site: plain ``jax.jit``, whose own cache finds
        the executable inside the call."""
        out = self._jit(*args, **kwargs)
        self.last_call = CallCost(0.0, time.perf_counter() - t0, 0)
        return out


def tracked_jit(fn: Callable, *, name: str, **jit_kwargs: Any) -> TrackedJit:
    """Drop-in ``jax.jit`` replacement with device-side introspection
    (see module docstring). ``name`` keys every signal this wrapper
    emits: the ``compile/{name}`` span, ``hbm/{name}/*`` gauges, and
    the executable-inventory rows."""
    return TrackedJit(fn, name=name, **jit_kwargs)
