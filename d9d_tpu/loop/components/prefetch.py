"""Background batch prefetch: overlap host input work with device steps.

Reference: d9d/loop/component/data_loader_factory.py:102 — torchdata's
worker-backed ``StatefulDataLoader`` keeps batch N+1's host work off the
step path. TPU equivalent: a producer thread runs the
host input pipeline — raw fetch from the loader and task
``prepare_batch`` (numpy), plus device staging whenever that is
collective-free — ``depth`` batches ahead of the consuming train loop,
so step N's compute overlaps step N+1's input processing. Single-process
runs stage in the producer too (async ``device_put``); multi-process
runs MUST stage on the consumer thread via ``finish_fn`` — ``device_put``
onto a multi-process sharding performs a cross-process consistency
collective, and producer-thread collectives interleave differently per
process against the main thread's step collectives (observed deadlock on
the 2-process rig).

Exact resume stays exact: the producer snapshots the loader's *position*
right after each fetch (the loader advances before yielding, so the
snapshot IS the resume point after consuming that batch), and the
consumer records the snapshot of every batch it hands out. Checkpoints
then serialize the loader state *as of the consumed batch* via
``StatefulDataLoader.state_dict_at`` — never the producer's run-ahead
position.
"""

import queue
import threading
from collections.abc import Callable, Iterator
from typing import Any

from d9d_tpu.core.tracing import annotate
from d9d_tpu.core.types import PyTree
from d9d_tpu.telemetry import get_telemetry

__all__ = ["BatchPrefetcher"]

_DONE = object()


class BatchPrefetcher:
    """Iterator of staged batches produced ``depth`` ahead on a thread.

    ``stage_fn`` runs in the producer thread (prepare + device staging);
    ``position_fn`` (optional) snapshots the underlying loader position
    after each raw fetch — :attr:`consumed_position` then tracks the
    resume point of the last batch handed to the consumer.
    """

    def __init__(
        self,
        data_iter: Iterator[PyTree],
        stage_fn: Callable[[PyTree], PyTree],
        *,
        depth: int = 2,
        position_fn: Callable[[], Any] | None = None,
        finish_fn: Callable[[PyTree], PyTree] | None = None,
    ):
        """``stage_fn`` runs in the producer thread; ``finish_fn`` (if
        given) runs on the CONSUMER thread at ``__next__``. Multi-process
        trainers must keep ``device_put`` onto multi-process shardings in
        ``finish_fn``: jax turns it into a cross-process consistency
        collective (``multihost_utils.assert_equal``), and collectives
        issued from a producer thread interleave differently per process
        against the main thread's step collectives — a deadlock observed
        on the 2-process e2e rig. Host-only work (tokenize/pack/reshape)
        stays safely in the producer."""
        if depth < 1:
            raise ValueError("prefetch depth must be >= 1")
        self._iter = data_iter
        self._stage_fn = stage_fn
        self._finish_fn = finish_fn
        self._position_fn = position_fn
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self.consumed_position: Any | None = None
        self._thread = threading.Thread(
            target=self._produce, name="d9d-batch-prefetch", daemon=True
        )
        self._thread.start()

    # -- producer ------------------------------------------------------

    def _put(self, item) -> bool:
        """Bounded put that aborts promptly when the consumer closed."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    raw = next(self._iter)
                except StopIteration:
                    self._put(_DONE)
                    return
                pos = self._position_fn() if self._position_fn else None
                with annotate("loop.prefetch_stage"):
                    staged = self._stage_fn(raw)
                if not self._put(("batch", staged, pos)):
                    return
        except BaseException as e:  # noqa: BLE001 — reraised in consumer
            get_telemetry().counter("io/prefetch_errors").add(1)
            self._put(("error", e, None))

    # -- consumer ------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self) -> PyTree:
        # bounded waits + liveness checks: a producer thread that dies
        # without delivering its sentinel (injected crash, interpreter
        # teardown racing a worker) must surface as an immediate,
        # explanatory error — not an unbounded q.get() hang
        while True:
            try:
                item = self._q.get(timeout=1.0)
                break
            except queue.Empty:
                if not self._thread.is_alive():
                    # the producer may have enqueued its final item (a
                    # real error, end-of-data) and exited between our
                    # timeout and this liveness check — drain once more
                    # before declaring a silent death, or the generic
                    # message would shadow the real diagnostic
                    try:
                        item = self._q.get_nowait()
                        break
                    except queue.Empty:
                        pass
                    raise RuntimeError(
                        "prefetch producer thread died without delivering "
                        "a batch, an error, or end-of-data (last consumed "
                        f"position: {self.consumed_position})"
                    ) from None
        if item is _DONE:
            raise StopIteration
        kind, payload, pos = item
        if kind == "error":
            # the producer's exception travels intact (DataFetchError
            # carries the failing epoch/batch position in its message)
            raise payload
        if self._finish_fn is not None:
            payload = self._finish_fn(payload)
        # only after the batch is fully materialized for the consumer —
        # a finish_fn failure must not mark the batch consumed (exact
        # resume would skip it)
        self.consumed_position = pos
        return payload

    def close(self) -> None:
        """Stop the producer and release its queue slot."""
        self._stop.set()
        try:  # unblock a producer waiting on a full queue
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
