"""The serving loop's cache: its layout on the device and who resides in it.

``CacheManager`` is what ``loop/serve.py``'s scheduler asks about the
decode cache: may this request have row *i*, and from which position;
release row *i*; the table to pack into a chunk; the bytes an admission
writes. It owns the cache pytree's LAYOUT (which leaves are pools,
tables, rings or per-row state, and what each weighs) and its RESIDENCY
(the host allocator of ``loop/kv_paging.py``, the prefix cache, the
peaks of a measurement window, the shipment format between replicas).
The scheduler holds the pytree itself only to pass it through the fused
call; what the program does to it at an admission and around a step is
the three pure functions at the end of this module, which the program
calls. It records nothing: what happened comes back as values and the
scheduler tells the accounting (``loop/serve_accounting.py``).

Per-row cache state rides the decode modules unchanged: the cache is
seeded with a PER-ROW ``[B]`` ``cache_index`` (modules accept either
rank — ``nn/attention.py``; the flash-decode kernel takes per-row
``start`` offsets natively), row admission resets just that row's cache
slice, and idle and dead rows are pinned inside the jitted step
(:func:`pin_idle_rows`).

Three kinds of cache live behind this one manager: the sequence
caches, contiguous or paged (:class:`CacheManager` says how), and two
that paging leaves alone.

Recurrent layers (GDN ``delta_state``, Mamba ``ssm_state``, the short
convolutions' ``conv_tail``) keep state that is per-row already and is
never paged: the rule is ``nn/decode_flags.recurrent_leaves`` (any
per-row leaf that is neither pageable sequence content nor a write
index), never a model's name. Admission zeroes a row's leaves in the
same dispatch that starts it, so whatever an idle or dead row wrote
there (it keeps stepping on token 0 under static shapes) cannot reach
the next request; a model with such leaves serves with the prefix cache
off (its state summarizes the whole prefix and cannot be rebuilt from
shared KV pages) and ``prefix_cache=True`` raises. The zeroing
(``nn/decode_flags.zero_rows``, the ops under ``serve/reset_rows``)
writes a zero row into each per-row leaf at each admitted index, in
place: the device pays for the admitted rows' bytes and a launch a leaf
a row, never for the state (``recurrent_state_bytes``; a row's share of
every per-row leaf is ``row_reset_bytes``).

An attention layer that reads a window of positions keeps, under
paging, a ring of pages a row (``nn/attention.py _ring_page_table``;
the leaves ``decode_flags.RING_CACHE_LEAVES``), the window and one page
of positions however long the context, beside the full layers' pools:
no allocator, no table leaf, no garbage page, no zeroing at admission
(what a ring still holds is behind the position masks).
``window_cache_bytes`` is what they hold. A ring has dropped what a
shared prefix page would stand for, so such a model serves with the
prefix cache off by the rule that covers recurrent state
(``decode_flags.window_leaves``), ``prefix_cache=True`` raises, and so
does ``kv_quant``.
"""

import collections
import contextlib
import dataclasses
import math
import zlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict, unflatten_dict

from d9d_tpu.core.types import Array
from d9d_tpu.loop.kv_paging import PagedKVAllocator
from d9d_tpu.nn.decode_flags import (
    PAGE_TABLE_LEAF,
    PAGED_CACHE_LEAVES,
    PAGED_SCALE_SUFFIX,
    map_cache_index,
    map_page_table,
    per_row_leaves,
    recurrent_leaves,
    ring_caches,
    window_leaves,
    zero_rows,
)

# default per-transfer staging bound for KV page shipments: the same
# order as elastic-restore's redistribute budget — big enough that a
# whole tiny-model prefix ships in one chunk, small enough that a long
# production prefix never stages the full run on the host at once
TRANSFER_BUDGET_BYTES = 64 << 20


@dataclasses.dataclass
class KVPageShipment:
    """One cross-replica KV prefix shipment (host-side, self-checking).

    ``payload`` maps each paged pool leaf path (values AND int8 scale
    siblings) to a ``[n_pages, ...]`` host array stacked in block
    order; ``checksums[i]`` is a crc32 over page ``i``'s bytes across
    every leaf in sorted-path order, verified by the importer BEFORE
    any allocator or pool mutation — a flipped byte or truncated
    payload is detected, and the request falls back to re-prefill.
    ``weights_version`` pins the generation the pages were computed
    under: cached KV is weights-dependent, so an importer on any other
    generation must reject (same invariant as ``install_weights``
    prefix invalidation)."""

    page_size: int
    tokens: list            # the full-block token prefix the pages cover
    n_pages: int
    weights_version: int
    kv_quant: Optional[str]
    payload: dict
    checksums: list
    chunks: int = 0         # transfer chunks the export staged through

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.payload.values())


def _page_checksums(payload: dict) -> list:
    """Per-page crc32 across every payload leaf in sorted-path order."""
    if not payload:
        return []
    n = next(iter(payload.values())).shape[0]
    out = []
    for i in range(n):
        c = 0
        for name in sorted(payload):
            c = zlib.crc32(
                np.ascontiguousarray(payload[name][i]).tobytes(), c
            )
        out.append(c)
    return out


def _nbytes(leaves) -> int:
    return sum(
        math.prod(s.shape) * jnp.dtype(s.dtype).itemsize for s in leaves
    )


class CacheManager:
    """Layout and residency of one batcher's decode cache.

    Paged KV cache + prefix cache (docs/design/generation.md): with
    ``page_size`` set, the sequence caches become device-resident page
    POOLS (``[num_pages, ..., page_size, ...]``) indexed through a
    static-shape per-row ``[B, max_pages]`` page table — HBM per request
    is proportional to its ACTUAL length instead of
    ``decode_max_length``, admission is bounded by free pages rather
    than batch rows (head-of-line waits, never rejects, when pages run
    short), and a content-hashed prefix cache maps a shared prompt's
    pages copy-on-write into later requests so it prefills once per
    replica. All policy (free lists, refcounts, hashing, LRU eviction —
    ``loop/kv_paging.py``) runs on the host at the SAME chunk boundaries
    admission already owns; the page table is a traced cache leaf like
    ``cache_index``, so the host-interaction contract and the
    ``tracked_jit`` fingerprints are untouched (``tools/bench_compare.py``
    gates the paged leg's dispatch/readback/compile counts against the
    contiguous leg's). The flash-decode kernel gathers a row's live
    pages through the page table
    (``ops/attention/pallas_decode.py paged_decode_geometry``); the
    eager path gathers a contiguous per-row view and remains the bitwise
    exactness reference — greedy paged serving is token-identical to the
    contiguous layout, prefix hit or cold. ``num_pages`` sizes the pool
    (default: enough for every slot at full ``decode_max_length`` + the
    reserved garbage page — no savings until you shrink it).
    ``prefix_cache`` — None (default) auto-enables when every sequence
    cache is pageable and disables for models with unpageable per-row
    state; True forces (raising if unsound), False disables.

    ``kv_quant="int8"`` (paged mode only — the page is the
    quantization granule, docs/design/generation.md "Low-precision
    serving") stores the KV pools as int8 with f32
    per-(page, slot[, head]) scale pools riding next to them as sibling
    cache leaves. Writes quantize at the per-row scatter, reads
    dequantize in the decode-attention gather/kernel; the prefix cache
    and continuation handoff are unchanged (scale pages share the value
    pages' page table). Decoding is no longer bit-identical to
    bf16/f32 — it is drift-bounded, gated by the parity tests and the
    autopilot canary.
    """

    def __init__(
        self,
        model,
        *,
        batch_size: int,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        prefix_cache: Optional[bool] = None,
        kv_quant: Optional[str] = None,
    ):
        self.decode_max_length = int(getattr(model, "decode_max_length", 0))
        if self.decode_max_length <= 0:
            raise ValueError("model must be built with decode_max_length > 0")
        self._b = batch_size
        # paged KV mode: fixed-size page pools + per-row page tables
        # instead of contiguous per-row cache leaves
        self.paged = page_size is not None
        self.page_size = self.num_pages = None
        self.pages_per_row = 0  # columns of the table a chunk carries
        if self.paged:
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            self.page_size = int(page_size)
            self.pages_per_row = -(-self.decode_max_length // self.page_size)
            self.num_pages = (
                int(num_pages) if num_pages is not None
                # default: every slot can hold a full-length request
                # (+ the reserved garbage page) — paging then changes
                # accounting but strands nothing; shrink it to actually
                # overcommit HBM
                else batch_size * self.pages_per_row + 1
            )
        elif num_pages is not None or prefix_cache is not None:
            raise ValueError(
                "num_pages/prefix_cache need paged mode (set page_size)"
            )
        if kv_quant is not None and not self.paged:
            raise ValueError("kv_quant needs paged mode (set page_size)")
        if kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant must be None or 'int8', got {kv_quant!r}"
            )
        self.kv_quant = kv_quant
        self._leaves = self._lay_out(model)
        # KV residency accounting (the serve/kv_* gauges and
        # hbm_bytes_per_request): peaks over the measurement window
        self.peak_running = 0
        self.allocator = None
        if self.paged:
            if prefix_cache and self.unpageable_leaves:
                raise ValueError(
                    "prefix_cache=True is unsound for this model: cache "
                    f"leaves {self.unpageable_leaves} hold per-row "
                    "recurrent state that summarizes the whole prefix, "
                    "or a window layer's ring that has dropped it, and "
                    "cannot be restored from KV pages"
                )
            self.allocator = PagedKVAllocator(
                num_pages=self.num_pages,
                page_size=self.page_size,
                rows=batch_size,
                max_pages_per_row=self.pages_per_row,
                enable_prefix_cache=(
                    prefix_cache if prefix_cache is not None
                    else not self.unpageable_leaves
                ),
            )

    # ------------------------------------------------------------------
    # layout

    def _lay_out(self, model) -> dict:
        """``{path: shape}`` of the cache the batcher steps, and what its
        parts weigh, from the model's shapes alone."""
        z = jnp.zeros((self._b, 1), jnp.int32)
        # eval_shape: cache SHAPES only — model.init would materialize
        # (and immediately discard) a full second copy of the parameters.
        # Paged, a window layer declares a ring of pages a row in place
        # of a context's worth of cache (nn/attention.py)
        with (
            ring_caches(self.page_size) if self.paged
            else contextlib.nullcontext(())
        ) as windows:
            shapes = jax.eval_shape(
                model.init, jax.random.PRNGKey(0), z, z, z
            )
        # window -> how many layers keep a ring of it
        self.ring_windows = collections.Counter(windows)
        flat = flatten_dict(shapes["cache"])
        # layers that hold a range of their router's experts count the
        # routed pairs that land here (nn/moe.py): the fused chunk
        # carries the counts out with its tokens
        self.counts_held_rows = any(
            p[-1] == "rows_held"
            for p in flatten_dict(shapes.get("moe_stats", {}))
        )
        # dense-layout byte total of the sequence caches: the paged
        # mode's savings denominator, and the contiguous mode's (static)
        # KV residency for the hbm-bytes-per-request accounting
        self.kv_bytes_static = _nbytes(
            s for p, s in flat.items() if p[-1] in PAGED_CACHE_LEAVES
        )
        # per-row cache leaves that are NOT pageable (GDN and Mamba
        # recurrent state, conv tails, toy memories): paging leaves them
        # per-row; their presence auto-disables the prefix cache (their
        # state can't be rebuilt from shared KV pages). An admission
        # zeroes the admitted rows of them (decode_flags.zero_rows).
        recurrent = recurrent_leaves(shapes["cache"])
        rings = window_leaves(shapes["cache"])
        if rings and self.kv_quant is not None:
            raise ValueError(
                "kv_quant does not cover a window layer's ring of pages "
                f"({sorted({p[-1] for p in rings})})"
            )
        self.unpageable_leaves = sorted(
            {p[-1] for p in recurrent} | {p[-1] for p in rings}
        )
        self.recurrent_state_bytes = _nbytes(recurrent.values())
        self.window_cache_bytes = _nbytes(rings.values())
        self.page_bytes = 0
        out = {}
        for p, s in flat.items():
            if p[-1] == "cache_index":
                # per-row write indices: seed [B] zeros in place of the
                # scalar — the decode modules accept either rank
                out[p] = jax.ShapeDtypeStruct((self._b,), jnp.int32)
            elif self.paged and p[-1] in PAGED_CACHE_LEAVES:
                axis = PAGED_CACHE_LEAVES[p[-1]]
                if s.shape[axis] != self.decode_max_length:
                    raise ValueError(
                        f"cache leaf {'/'.join(p)} slot axis {axis} is "
                        f"{s.shape[axis]}, expected decode_max_length="
                        f"{self.decode_max_length}"
                    )
                pool_shape = (
                    (self.num_pages,) + s.shape[1:axis]
                    + (self.page_size,) + s.shape[axis + 1:]
                )
                if self.kv_quant is not None:
                    # int8 pool + f32 per-(page, slot[, head]) scale
                    # pool: the scale leaf drops only the trailing
                    # feature dim, so one scale covers one slot's
                    # feature vector (the finest granule the one-token
                    # scatter can maintain) and the scale pool indexes
                    # through the SAME page table as its value pool
                    pool = jax.ShapeDtypeStruct(pool_shape, jnp.int8)
                    scale = jax.ShapeDtypeStruct(
                        pool_shape[:-1], jnp.float32
                    )
                    out[p[:-1] + (p[-1] + PAGED_SCALE_SUFFIX,)] = scale
                    self.page_bytes += _nbytes([scale]) // self.num_pages
                else:
                    pool = jax.ShapeDtypeStruct(pool_shape, s.dtype)
                out[p] = pool
                # one table per module scope (identical contents; a few
                # ints per layer) so the module reads its own sibling
                out[p[:-1] + (PAGE_TABLE_LEAF,)] = jax.ShapeDtypeStruct(
                    (self._b, self.pages_per_row), jnp.int32
                )
                self.page_bytes += _nbytes([pool]) // self.num_pages
            else:
                out[p] = jax.ShapeDtypeStruct(s.shape, s.dtype)
        # what an admission writes: a zero row into each per-row leaf
        self.row_reset_bytes = (
            _nbytes(per_row_leaves(unflatten_dict(out)).values()) // self._b
        )
        return out

    def zeros(self):
        """The cache pytree, every leaf zero: what the batcher steps."""
        return unflatten_dict({
            p: jnp.zeros(s.shape, s.dtype) for p, s in self._leaves.items()
        })

    # ------------------------------------------------------------------
    # residency (loop/kv_paging.py): all host work, all at the existing
    # chunk boundaries — the dispatch/readback contract and the
    # tracked_jit fingerprints are untouched. Unpaged, a row is all a
    # request needs and every question below has the trivial answer.

    @property
    def prefix_cache_enabled(self) -> bool:
        return self.paged and self.allocator.prefix_cache_enabled

    @property
    def table(self) -> np.ndarray:
        """The allocator's ``[B, pages_per_row]`` page table (paged)."""
        return self.allocator.table

    @property
    def pages_in_use(self) -> int:
        return self.allocator.pages_in_use

    @property
    def pages_free(self) -> int:
        return self.allocator.pages_free

    def check_fits(self, total_tokens: int) -> None:
        """Refuse a request no boundary could ever admit."""
        if self.paged and not self.allocator.fits_ever(total_tokens):
            raise ValueError(
                f"request needs {self.allocator.pages_needed(total_tokens)} "
                f"pages but the pool holds {self.num_pages - 1} allocatable "
                f"(num_pages={self.num_pages}, page_size="
                f"{self.page_size}); it could never be admitted"
            )

    def fits_after_flush(self, total_tokens: int) -> bool:
        """Could a request of this footprint map onto pages by the next
        admit boundary, which flushes deferred frees first?
        (Conservative beyond that: prefix hits and LRU eviction could
        only help.)"""
        return not self.paged or (
            self.allocator.pages_needed(total_tokens)
            <= self.allocator.pages_free_after_flush()
        )

    def admit(
        self, row: int, rid: int, prompt: Sequence[int], total_tokens: int
    ) -> Optional[int]:
        """Give ``row`` to the request: the first position it still has
        to be fed from (past its prefix-cache hit), or None while the
        pool cannot map it (prefix-cache walk + free-list allocation):
        admission is bounded by free pages, not rows."""
        if not self.paged:
            return 0
        alloc = self.allocator.admit(row, rid, prompt, total_tokens)
        return None if alloc is None else alloc.start_pos

    def mark_filled(self, rid: int) -> None:
        """The request's whole prompt is DISPATCHED: its prefix-cache
        entries become hit-eligible — later admits dispatch after, so
        their reads see the writes (idempotent)."""
        if self.paged:
            self.allocator.mark_filled(rid)

    def drop_request(self, rid: int) -> None:
        """The request failed or left the queue: its admission memo
        goes, and the prefix entries it had not finished filling — a
        half-written page must never be hit."""
        if self.paged:
            self.allocator.abort_filling(rid)

    def release_row(self, row: int, *, defer: bool) -> None:
        """Drop a retired row's page references. A row that finished
        in-device (its writes are already pinned to the garbage page),
        or any row at a clean boundary, frees immediately; a host-side
        kill with chunks in flight DEFERS — the device twin may still be
        live and writing into these pages, so they stay held until
        :meth:`flush_deferred` at a clean boundary, whose chunk carries
        the zeroed table row."""
        if not self.paged:
            return
        if defer:
            self.allocator.defer_release(row)
        else:
            self.allocator.release(row)

    def flush_deferred(self) -> None:
        if self.paged:
            self.allocator.flush_deferred()

    def invalidate_prefix_cache(self) -> Optional[int]:
        """Drop every prefix entry (cached KV is weights-dependent);
        how many went, None where there is no prefix cache."""
        if not self.prefix_cache_enabled:
            return None
        return self.allocator.invalidate_prefix_cache()

    def note_running(self, running: int) -> None:
        """Peak concurrency of the window, which both modes share."""
        self.peak_running = max(self.peak_running, running)

    def reset_window(self) -> None:
        """A measurement window opens; the prefix cache itself stays
        warm deliberately (like compile warmth)."""
        self.peak_running = 0
        if self.paged:
            kv = self.allocator
            kv.peak_pages_in_use = kv.pages_in_use
            kv.prefix_hits = kv.prefix_misses = kv.prefix_hit_tokens = 0

    def hbm_bytes_per_request(self) -> float:
        """Peak resident KV bytes over peak concurrent running requests
        (:meth:`note_running`) for the current measurement window — deterministic given the schedule, so the
        bench gate can pin it exactly. Contiguous mode charges the full
        static allocation (every row's decode_max_length is resident
        whether used or not); paged mode charges pages actually
        mapped."""
        if self.paged:
            resident = (
                self.allocator.peak_pages_in_use * self.page_bytes
                + self.window_cache_bytes
            )
        else:
            resident = self.kv_bytes_static
        return resident / max(1, self.peak_running)

    def prefix_hit_rate(self) -> float:
        """Admissions served (partly) from the prefix cache over all
        admissions in the window; 0.0 when disabled or idle."""
        if not self.paged:
            return 0.0
        kv = self.allocator
        total = kv.prefix_hits + kv.prefix_misses
        return kv.prefix_hits / total if total else 0.0

    # ------------------------------------------------------------------
    # cross-replica KV page shipment (docs/design/elasticity.md
    # "Disaggregated serving"): a prefill replica exports the READY
    # prefix pages covering a prompt; a decode replica imports them as
    # ready prefix entries and copies the payloads into its own pool.
    # Pure transfers at clean chunk boundaries — page pulls/pushes are
    # untracked device array ops, never tracked_jit dispatches, so the
    # steady-state executable census and the dispatch counts the bench
    # gates are untouched. EVERY failure (version skew, checksum
    # mismatch, allocation shortfall) returns None and the caller falls
    # back to plain continuation re-prefill — fallback, not failure, is
    # the contract.

    def pool_leaves(self, cache) -> dict:
        """Paged pool leaves (values + int8 scale siblings) by path."""
        return {
            "/".join(p): leaf
            for p, leaf in flatten_dict(cache).items()
            if p[-1] in PAGED_CACHE_LEAVES
            or p[-1].endswith(PAGED_SCALE_SUFFIX)
        }

    def _transfer_pages(self, transfer_budget_bytes: int) -> int:
        return max(1, int(transfer_budget_bytes) // max(1, self.page_bytes))

    def export_pages(
        self,
        cache,
        tokens: Sequence[int],
        *,
        weights_version: int,
        transfer_budget_bytes: int = TRANSFER_BUDGET_BYTES,
    ) -> Optional[KVPageShipment]:
        """Pull the READY prefix pages covering ``tokens``' leading
        full blocks off the device pool, chunk-by-chunk under
        ``transfer_budget_bytes`` (the ``_chunked_place`` discipline
        from ``resilience/elastic.py`` — bounded host staging however
        large the run). ``cache`` must be a clean boundary's (only that
        is an exact pool view). None when not paged or nothing is
        cached — the caller re-prefills instead."""
        if not self.paged:
            return None
        tokens = [int(x) for x in tokens]
        pages = self.allocator.export_prefix(tokens)
        if not pages:
            return None
        leaves = self.pool_leaves(cache)
        chunk_len = self._transfer_pages(transfer_budget_bytes)
        parts: dict[str, list] = {name: [] for name in leaves}
        chunks = 0
        for a in range(0, len(pages), chunk_len):
            idx = jnp.asarray(np.asarray(pages[a:a + chunk_len], np.int32))
            for name, pool in leaves.items():
                # d9d-lint: disable=D9D003 — bounded page-payload pull at
                # a clean boundary (a transfer, not a decode readback)
                parts[name].append(np.asarray(pool[idx]))
            chunks += 1
        payload = {
            name: np.concatenate(arrs, axis=0)
            for name, arrs in parts.items()
        }
        return KVPageShipment(
            page_size=self.page_size,
            tokens=tokens[: len(pages) * self.page_size],
            n_pages=len(pages),
            weights_version=weights_version,
            kv_quant=self.kv_quant,
            payload=payload,
            checksums=_page_checksums(payload),
            chunks=chunks,
        )

    def import_pages(
        self,
        cache,
        ship: KVPageShipment,
        *,
        weights_version: Optional[int],
        transfer_budget_bytes: int = TRANSFER_BUDGET_BYTES,
    ) -> tuple:
        """Install a shipment's pages as READY prefix entries and copy
        the payloads into ``cache``'s pools (chunked under the same
        transfer budget): ``(cache with the pages written, pages that
        took, None)``, or ``(None, 0, refusal)``. Checksums are verified
        BEFORE any allocator or pool mutation — a corrupt/truncated
        shipment is detected and rejected whole, never half-imported
        (refusal ``"checksum"``). A shipment of another weights
        generation than ``weights_version`` (None: a publish is staged,
        no generation matches) rejects too (``"version_mismatch"``):
        cached KV is weights-dependent, the same invariant as
        ``install_weights`` prefix invalidation. A format the pool does
        not hold, or no pages to place it, has no name."""
        if (
            not self.prefix_cache_enabled
            or ship.page_size != self.page_size
            or ship.kv_quant != self.kv_quant
        ):
            return None, 0, None
        if ship.weights_version != weights_version:
            return None, 0, "version_mismatch"
        leaves = self.pool_leaves(cache)
        if (
            set(ship.payload) != set(leaves)
            or any(
                ship.payload[n].shape[0] != ship.n_pages
                for n in ship.payload
            )
            or _page_checksums(ship.payload) != list(ship.checksums)
        ):
            return None, 0, "checksum"
        placed = self.allocator.import_pages(ship.tokens, ship.n_pages)
        if placed is None:
            return None, 0, None
        chunk_len = self._transfer_pages(transfer_budget_bytes)
        flat = flatten_dict(cache)
        for a in range(0, len(placed), chunk_len):
            part = placed[a:a + chunk_len]
            src = np.asarray([b for b, _ in part], np.int32)
            dest = jnp.asarray(np.asarray([p for _, p in part], np.int32))
            for name in leaves:
                path = tuple(name.split("/"))
                flat[path] = flat[path].at[dest].set(
                    jnp.asarray(ship.payload[name][src])
                )
        return unflatten_dict(flat), len(placed), None


# ----------------------------------------------------------------------
# what the fused chunk's program does to the cache pytree (trace-safe,
# pure): ``loop/serve.py _build_fused`` calls these, in this order


def _pin_cache_index(cache, live: Array):
    """Pin dead/idle rows' per-row write indices to 0: the jitted step
    advances every row's ``cache_index``, so without the pin a long-idle
    slot would climb past capacity (spurious checkify overflow under
    contract validation) and defeat the flash-decode whole-block skip
    (a huge start makes every block visible)."""
    return map_cache_index(cache, lambda idx: jnp.where(live, idx, 0))


def _pin_page_table(cache, live: Array):
    """Paged companion of :func:`_pin_cache_index`: pin dead/idle rows'
    page-table rows to the reserved garbage page (0). A row that dies
    mid-chunk keeps executing static-shape steps — with its write index
    pinned to 0 its writes land at logical slot 0, and WITHOUT this pin
    that is ``page_table[b, 0]``, which may be a freed page or (worse) a
    SHARED prefix page. With it, dead rows scribble harmlessly into the
    garbage page until the host reuses the slot. No table leaf, nothing
    traced: an unpaged cache comes back as it is."""
    return map_page_table(
        cache, lambda pt: jnp.where(live[:, None], pt, 0)
    )


def admit_rows(cache, admit_mask: Array, admit_pos: Optional[Array] = None):
    """An admission, fused into the chunk that starts the rows: zero the
    admitted rows' PER-ROW leaves (``decode_flags.per_row_leaves``: pools
    are shared, stale page bytes sit behind the slot mask) and, paged,
    jump their write index to ``admit_pos`` — the first position past
    their prefix-cache hit."""
    cache = zero_rows(cache, admit_mask)
    if admit_pos is None:
        return cache
    return map_cache_index(
        cache, lambda idx: jnp.where(admit_mask, admit_pos, idx)
    )


def write_table(cache, table: Array, live: Array):
    """The host's table into every ``page_table`` leaf, THEN the pin by
    the device's own ``live`` (after the admission has set it), before
    the chunk's first step: the host's mirror still holds the pages of a
    row whose death it has not read yet (a follow-up chunk dispatched
    with that death unread), so that row goes on writing into the
    garbage page, and a row the host zeroed (released, or a zombie whose
    pages wait for a clean boundary) is rerouted there."""
    cache = map_page_table(cache, lambda _pt: table)
    return _pin_page_table(cache, live)


def pin_idle_rows(cache, live: Array):
    """After every step: dead and idle rows' write indices to 0 and
    their page tables to the garbage page."""
    return _pin_page_table(_pin_cache_index(cache, live), live)
