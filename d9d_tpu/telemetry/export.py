"""Live metrics endpoint: Prometheus text rendering of the registry +
a stdlib ``http.server`` background thread serving ``/metrics``,
``/healthz`` and ``/readyz`` (docs/design/observability.md).

Until now every telemetry signal was process-local and post-hoc (JSONL
files, tracker runs, a rate-limited console line) — an operator could
not *scrape* a live replica. This module is the pull side of the
monitoring plane:

- :func:`render_prometheus` renders one registry snapshot in the
  Prometheus text exposition format (``text/plain; version=0.0.4``):
  counters and gauges become samples, fixed-bin histograms become
  cumulative ``_bucket{le=...}`` series plus ``_sum``/``_count``.
  Replica-namespaced serving metrics (``serve/r{i}/...`` — the
  fleet's per-replica instruments) are folded into one metric family
  with a ``replica`` label, so fleet dashboards aggregate with ordinary
  PromQL instead of regexes.
- :class:`MetricsServer` serves it from a daemon thread. The scrape
  path is pure host work — a registry snapshot, gauge-fn evaluation and
  string formatting; it never touches jax, so the serving loop's
  zero-added-readbacks contract is structurally safe (and additionally
  gated by ``tools/bench_compare.py``'s exporter leg). ``/metrics``
  first evaluates the hub's attached SLO monitor (``telemetry/slo.py``)
  so scraped burn rates are current even if nothing has flushed.

Readiness contract (``/readyz``): the endpoint answers 503 until the
owning component reports ready — a ``ContinuousBatcher`` past its first
readback, a ``Trainer`` past its introspection warmup steps, a
``ServingFleet`` with at least one ready live replica (per-replica
detail rides ``/healthz``). Load balancers and schedulers gate traffic
on this, so "compiling" never reads as "serving".

Health contract (``/healthz``): liveness plus owner detail — a fleet
reports per-replica ``{live, retired, dead, ready, active}`` and, with
an ``FleetAutopilot`` bound, an ``autopilot`` block (burning policies,
burn/idle ages, pending canary, last decision) — one scrape explains
both what the fleet looks like and what the control loop is about to
do about it (docs/design/elasticity.md "SLO autopilot").

Lifecycle: opt-in via ``TrainerConfig.metrics_port``,
``ContinuousBatcher(metrics_port=...)`` or
``ServingFleet(metrics_port=...)``; ``port=0`` binds an ephemeral port
(tests; read it back from :attr:`MetricsServer.port`). Owners close the
server in their ``finally``/``close()`` paths.
"""

import json
import logging
import math
import re
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable

__all__ = [
    "MetricsServer",
    "render_prometheus",
]

logger = logging.getLogger("d9d_tpu.telemetry")

_NAME_RE = re.compile(r"[^a-zA-Z0-9_]")
# any path-free replica label (loop/serve_accounting.py _validate_label's
# contract), not just the fleet's r{i} — a custom "east1" label must
# fold into the same metric family as everyone else, or fleet PromQL
# aggregations silently exclude that replica
_REPLICA_RE = re.compile(r"^serve/([^/]+)/(.+)$")


def _sanitize(name: str) -> str:
    out = _NAME_RE.sub("_", name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _family(name: str) -> tuple[str, dict[str, str]]:
    """Metric family + labels for a registry instrument name: the
    per-replica namespace ``serve/{label}/x`` folds into family
    ``serve/x`` with a ``replica`` label (the fleet's ``r{i}`` labels
    shorten to the index); everything else is label-free."""
    m = _REPLICA_RE.match(name)
    if m:
        label = m.group(1)
        if re.fullmatch(r"r\d+", label):
            label = label[1:]
        return f"serve/{m.group(2)}", {"replica": label}
    return name, {}


def _fmt_value(v: float) -> str:
    if math.isnan(v):
        return "NaN"
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    return f"{v:.10g}"


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{str(v)}"' for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


def render_prometheus(
    snapshot: dict[str, Any], *, prefix: str = "d9d"
) -> str:
    """Render one ``MetricRegistry.snapshot()`` as Prometheus text
    exposition format. Deterministic ordering (sorted families) so two
    renders of the same snapshot are byte-identical."""
    # family → (type, [(sanitized sample suffix, labels, value)])
    families: dict[str, tuple[str, list]] = {}

    def fam(name: str, kind: str):
        base, labels = _family(name)
        key = f"{prefix}_{_sanitize(base)}" if prefix else _sanitize(base)
        entry = families.get(key)
        if entry is None:
            entry = families[key] = (kind, [])
        return key, labels, entry[1]

    for name, value in snapshot.get("counters", {}).items():
        key, labels, samples = fam(name, "counter")
        samples.append((key, labels, float(value)))
    for name, value in snapshot.get("gauges", {}).items():
        key, labels, samples = fam(name, "gauge")
        samples.append((key, labels, float(value)))
    for name, h in snapshot.get("histograms", {}).items():
        key, labels, samples = fam(name, "histogram")
        cum = 0
        # the registry's FINAL bin absorbs samples >= its upper edge
        # (nothing is dropped), so that edge cannot be claimed as a
        # `le` bound — a 10s latency in a 2s-top histogram must not
        # render as `le="2"`. The last finite bucket emitted is the
        # second-to-last edge; the final bin's contents are only
        # representable under +Inf.
        for edge, count in zip(h["edges"][1:-1], h["counts"][:-1]):
            cum += count
            samples.append((
                f"{key}_bucket",
                {**labels, "le": _fmt_value(float(edge))},
                float(cum),
            ))
        samples.append((f"{key}_bucket", {**labels, "le": "+Inf"},
                        float(h["count"])))
        samples.append((f"{key}_sum", labels, float(h["sum"])))
        samples.append((f"{key}_count", labels, float(h["count"])))

    lines: list[str] = []
    for key in sorted(families):
        kind, samples = families[key]
        lines.append(f"# TYPE {key} {kind}")
        for sample_name, labels, value in samples:
            lines.append(
                f"{sample_name}{_fmt_labels(labels)} {_fmt_value(value)}"
            )
    return "\n".join(lines) + "\n"


class MetricsServer:
    """Background HTTP exporter over one telemetry hub.

    ``readiness`` is a callable returning ``bool`` or ``(bool, detail
    dict)``; ``health`` a callable returning a JSON-serializable detail
    dict (per-replica status for a fleet). Both run inside scrape
    handling — keep them host-only and cheap. Exceptions in either
    degrade to unhealthy/unready responses, never to a dead endpoint.

    ``profile`` is the optional on-demand capture backend behind
    ``GET /debug/profile?duration_s=``: a callable taking the duration
    and returning the capture path, or ``None`` while a capture is
    already live (``JobProfiler.capture``'s exact contract). Without a
    backend the endpoint answers 404; requests are rate-limited to one
    per ``profile_min_interval_s`` (429), errors degrade to 500 — the
    endpoint never raises and never touches the step path.
    """

    def __init__(
        self,
        telemetry=None,
        *,
        port: int = 0,
        host: str = "127.0.0.1",
        readiness: Callable[[], Any] | None = None,
        health: Callable[[], dict] | None = None,
        profile: Callable[[float], Any] | None = None,
        profile_min_interval_s: float = 30.0,
        prefix: str = "d9d",
    ):
        if telemetry is None:
            from d9d_tpu.telemetry import get_telemetry

            telemetry = get_telemetry()
        self._tele = telemetry
        self._host = host
        self._want_port = int(port)
        self._readiness = readiness
        self._health = health
        self.profile = profile
        self.profile_min_interval_s = profile_min_interval_s
        self._profile_last_t = -math.inf
        self._prefix = prefix
        self._server: ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None

    # -- endpoint bodies (shared with tests via direct calls) ----------

    def metrics_text(self) -> str:
        """The /metrics body: evaluate the attached SLO monitor (scraped
        burn rates stay current without a flush), then render."""
        monitor = getattr(self._tele, "slo_monitor", None)
        if monitor is not None:
            try:
                monitor.evaluate()
            except Exception:  # noqa: BLE001 — a bad policy must not 500
                logger.exception("SLO evaluation failed during scrape")
        return render_prometheus(
            self._tele.registry.snapshot(), prefix=self._prefix
        )

    def health_body(self) -> tuple[int, dict]:
        try:
            detail = self._health() if self._health is not None else {}
            return 200, {"status": "ok", **detail}
        except Exception as e:  # noqa: BLE001 — report, don't die
            return 500, {"status": "error", "error": repr(e)}

    def ready_body(self) -> tuple[int, dict]:
        try:
            out = self._readiness() if self._readiness is not None else True
        except Exception as e:  # noqa: BLE001 — not ready, with a reason
            return 503, {"ready": False, "error": repr(e)}
        ready, detail = (
            out if isinstance(out, tuple) else (out, {})
        )
        return (200 if ready else 503), {"ready": bool(ready), **detail}

    def profile_body(self, query: str) -> tuple[int, dict]:
        """The /debug/profile body. Status codes are the operator
        contract: 404 no backend wired, 400 bad duration, 429 rate
        limited, 503 a capture is already live, 500 backend error, 200
        with the capture path on success."""
        if self.profile is None:
            return 404, {"error": "no profiling backend wired"}
        try:
            params = urllib.parse.parse_qs(query)
            duration = float(params.get("duration_s", ["2.0"])[0])
        except (ValueError, TypeError):
            return 400, {"error": "duration_s must be a number"}
        if not (0.0 < duration <= 60.0):
            return 400, {
                "error": "duration_s must be in (0, 60]",
                "duration_s": duration,
            }
        now = time.monotonic()
        if now - self._profile_last_t < self.profile_min_interval_s:
            return 429, {
                "error": "rate limited",
                "retry_after_s": round(
                    self.profile_min_interval_s
                    - (now - self._profile_last_t), 1
                ),
            }
        try:
            out = self.profile(duration)
        except Exception as e:  # noqa: BLE001 — report, don't die
            logger.exception("on-demand profile capture failed")
            return 500, {"error": repr(e)}
        if out is None:
            return 503, {"busy": True, "error": "a capture is live"}
        self._profile_last_t = now
        return 200, {"capture": str(out), "duration_s": duration}

    # -- lifecycle ------------------------------------------------------

    def start(self) -> "MetricsServer":
        if self._server is not None:
            return self
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet: scrapes are periodic
                pass

            def _send(self, code: int, body: bytes, ctype: str) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 — http.server API
                path, _, query = self.path.partition("?")
                try:
                    if path == "/metrics":
                        self._send(
                            200, outer.metrics_text().encode(),
                            "text/plain; version=0.0.4; charset=utf-8",
                        )
                    elif path == "/healthz":
                        code, body = outer.health_body()
                        self._send(
                            code, json.dumps(body).encode(),
                            "application/json",
                        )
                    elif path == "/readyz":
                        code, body = outer.ready_body()
                        self._send(
                            code, json.dumps(body).encode(),
                            "application/json",
                        )
                    elif path == "/debug/profile":
                        code, body = outer.profile_body(query)
                        self._send(
                            code, json.dumps(body).encode(),
                            "application/json",
                        )
                    else:
                        self._send(404, b"not found", "text/plain")
                except BrokenPipeError:  # scraper went away mid-response
                    pass

        self._server = ThreadingHTTPServer(
            (self._host, self._want_port), Handler
        )
        self._server.daemon_threads = True
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name="d9d-metrics-server",
            daemon=True,
        )
        self._thread.start()
        logger.info(
            "metrics endpoint up at http://%s:%d/metrics",
            self._host, self.port,
        )
        return self

    @property
    def port(self) -> int:
        """The bound port (resolves ``port=0`` ephemeral binds)."""
        if self._server is None:
            return self._want_port
        return self._server.server_address[1]

    def url(self, path: str = "/metrics") -> str:
        return f"http://{self._host}:{self.port}{path}"

    def close(self) -> None:
        server, thread = self._server, self._thread
        self._server = self._thread = None
        if server is not None:
            server.shutdown()
            server.server_close()
        if thread is not None:
            thread.join(timeout=5.0)
