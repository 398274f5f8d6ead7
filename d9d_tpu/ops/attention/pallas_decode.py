"""Pallas TPU flash-decode attention over a KV slot cache.

Parity: the reference serves decode through its flash varlen path
(d9d/kernel/flash_attn/function.py:384, flash_attn_varlen_func with
cache seqlens); this is the TPU-native equivalent for the KV-cache
decode step that previously routed to the eager fallback
(pallas_flash.py routes cross-length attention to eager — fine for the
training bench geometry, wrong for serving batches where the [B,H,T,S]
eager logits round-trip HBM every step).

Decode attention is KV-cache-bandwidth-bound: the optimal kernel
streams each (batch, kv-head) cache slice from HBM EXACTLY ONCE and
never materializes logits. Two layout decisions follow:

- The GQA group is the matmul M dimension. ``q [B,T,Hq,D]`` is reshaped
  to ``[B, Hkv, g·T, D]`` (g = Hq/Hkv) so one grid step attends every
  query head of the group against the shared kv block. The training
  kernel's (b, h, q-block, kv-block) grid would re-stream the whole
  cache g times per group — a g× HBM tax that training amortizes over
  large q blocks but decode (T ~ 1) cannot. The cache arrives
  HEADS-MAJOR ``[B, Hkv, S, D]`` — the layout the GQA decode cache
  maintains on write — so the kernel streams it directly; a read-side
  relayout would copy every slot every step and erase the win.
- The kv-block grid dim is innermost and sequential; per-(b, kv-head)
  online-softmax state (m, l, acc over g·T rows) persists in VMEM
  scratch across kv steps, exactly like the training forward.

Paged mode (the serving loop's page pools, ``_paged_decode_call``) is
the same arithmetic with the cache gathered through a page table: one
grid step a group of batch rows, each row's live pages copied a block
of pages at a time into a double-buffered VMEM block (all kv heads of a
page in one copy), one online-softmax update for the group's blocks,
the (row, kv head) pairs as the batch of its two products; pages past a
row's last query are neither copied nor attended. The absorbed latent
decode (``latent_decode_attention``, ``_DecodeConfig.latent``) is a
configuration of the same kernel: its two pools are the latent rows and
the shared rotary key rows seen as one kv head, a score is the sum of a
query row's two products with them, and the values are the latent rows
already in the block.

Slot semantics ride positions: the cache write index ``start`` enters
as a traced SMEM scalar, queries sit at global positions
``start + [0,T)``, keys at their slot index — so causal/window masking
over slots needs no mask tensor, and kv blocks wholly in the causal
future of the last query are skipped (a decode step on a mostly-empty
cache touches only ceil((start+T)/block_kv) blocks). Per-key validity
(ragged left-padded prompts: loop/generate.py's [B,1,1,S] mask) streams
as an int row-vector alongside k/v. Sinks join outside the kernel as
the standard (o, lse) denominator correction (pallas_flash.py:21).

Forward-only by design: decode never differentiates. ``jax.jit``-safe
(static T/S/g; ``start`` traced).
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from d9d_tpu.core.types import Array

NEG_BIG = -1e30
LANES = 128

# practical bound on the resident q block (g·T rows): the kernel keeps
# one un-tiled [rows, D] q block + fp32 accumulators per (b, kv-head);
# beyond this, a big prefill chunk is better served by the training
# flash kernel's tiled grid (callers fall back to the eager slot path
# or cap their chunk size — loop/generate.py documents the bound)
MAX_DECODE_ROWS = 1024


def decode_attention_backend() -> str:
    """'pallas' or 'eager' — env-selected like the SDPA backend family.

    ``D9D_TPU_DECODE_ATTN``: ``auto`` (default; pallas on TPU, eager
    elsewhere — interpret-mode pallas is a test vehicle, not a CPU
    serving path), ``pallas``, or ``eager``.
    """
    mode = os.environ.get("D9D_TPU_DECODE_ATTN", "auto")
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "eager"
    return mode


@dataclasses.dataclass(frozen=True)
class _DecodeConfig:
    scale: float
    window: int | None
    t: int           # new tokens this step (queries)
    rows: int        # g·T real query rows per (b, kv-head)
    rows_pad: int    # rows padded to the sublane multiple
    s_len: int       # real cache capacity (pre-padding)
    block_kv: int
    has_valid: bool
    interpret: bool
    # int8 KV pools with per-slot scale pools riding behind k/v (paged
    # mode only; never combines with has_valid — the serving loop's
    # paged rows are never left-padded)
    quant: bool = False
    # paged mode: pages a copied block holds, and batch rows a grid step
    # attends (paged_decode_geometry)
    pages_per_step: int = 1
    rows_per_step: int = 1
    # paged mode, the absorbed latent decode: the pools are the latent
    # rows ``[P, 1, page, r]`` and the shared rotary key rows
    # ``[P, 1, page, d_rope]``, a query row is ``[q_abs | q_rope]``, a
    # score the sum of its two products, and THE VALUES ARE THE FIRST
    # POOL'S ROWS: the block that was copied for the scores
    latent: bool = False


def _decode_kernel(*refs, cfg: _DecodeConfig):
    valid_ref = None
    if cfg.has_valid:
        offs_ref, q_ref, k_ref, v_ref, valid_ref = refs[:5]
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[5:]
    else:
        offs_ref, q_ref, k_ref, v_ref = refs[:4]
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[4:]
    # per-batch-row write index (continuous batching: rows fill at
    # independent rates; a shared index is just the broadcast case)
    start = offs_ref[pl.program_id(0)]
    ik = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # whole-block skip: every key slot past the LAST query's position is
    # invisible (and with a window, every slot at/before the FIRST
    # query's window floor) — traced predicates, pl.when skips the MXU
    # work. This is what makes a step on a warm-but-not-full cache cost
    # O(start + T), not O(s_max).
    k_lo = ik * cfg.block_kv
    k_hi = k_lo + cfg.block_kv - 1
    skip = k_lo > start + (cfg.t - 1)
    if cfg.window is not None:
        skip |= k_hi <= start - cfg.window

    @pl.when(jnp.logical_not(skip))
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * cfg.scale  # [rows_pad, bkv]

        rp, bkv = s.shape
        row = jax.lax.broadcasted_iota(jnp.int32, (rp, bkv), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (rp, bkv), 1)
        # row r = (head-in-group, token i) flattened as ig·T + i, so the
        # query's global slot position is start + r % T
        q_pos = start + jax.lax.rem(row, cfg.t)
        mask = (k_pos < cfg.s_len) & (k_pos <= q_pos) & (row < cfg.rows)
        if cfg.window is not None:
            mask &= k_pos > q_pos - cfg.window
        if valid_ref is not None:
            mask &= valid_ref[0, :, :] != 0  # [1, bkv] key validity
        s = jnp.where(mask, s, NEG_BIG)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # gate by the mask, not just the sentinel: while every key a row
        # has seen is masked, m_new stays NEG_BIG and exp(s - m_new)
        # would be 1 for masked entries — silently emitting mean-of-V.
        # Zeroing masked probabilities keeps l at 0 for such rows, so
        # the finalize epilogue yields exact zeros instead.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_ref[:, :1] + p.sum(axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        o_ref[0, 0, :, :] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype
        )
        lse_ref[0, 0, :, :] = m + jnp.log(jnp.maximum(l, 1e-30))


def _pad_to(n: int, m: int) -> int:
    return (-n) % m


# Paged mode. A grid step is a group of batch rows; inside it each row's
# LIVE pages are copied from the pools (left in HBM) into a
# double-buffered VMEM block of ``pages_per_step`` pages a row, and the
# group's blocks meet the online softmax at once. On the chip (PERF.md,
# PR 33) a page operand of the BlockSpec pipeline costs 0.05 us whether
# its page is live or not: a step of one 16 KB page was bound by that,
# not by its bytes. The contiguous path streams 512 positions a step too.
PAGED_STEP_POSITIONS = 512
# A grid step and its one online-softmax update (a chain of dependent
# operations: product, row maximum, exponential, row sum, product,
# rescale) cost 0.54 us together however many rows they serve, and a row
# 0.30 us of its own with one live page (two transfers started and waited
# for, its scalars): 256 rows of 2 kv heads of 128 + 128 with one live
# page each take 0.84 / 0.55 / 0.42 / 0.36 us a row at 1 / 2 / 4 / 8 rows
# a step, and at a serving table's contexts (mean 351 positions) 1.22 /
# 0.96 / 0.83 / 0.80 us where the copied bytes are 0.48 (PERF.md, PR 57:
# the calls timed alone). A group is the largest power of two up to
# ``PAGED_STEP_ROWS`` that meets two bounds, both from that timing.
# Bytes: both buffers of the group's K and V blocks within
# ``PAGED_VMEM_BUDGET``; every shape timed past it was slower than at
# half the group (8 kv heads of 128 + 128: 637 us a call at 2 rows and
# 8 MiB, 662 at 4 and 16 MiB; 4 of 256 + 128: 510 at 6 MiB, 520 at 12),
# and the call stays inside the 16 MiB of VMEM a kernel has unasked (it
# claims the buffers and a twentieth more: the float32 casts stay in
# registers). Width: ``PAGED_STEP_WIDTH`` score rows an update (group x
# kv heads x padded query rows); the gain is all there at 128 (two kv
# heads of 16 rows: 224 us at 4 rows a step and at 8; one of 64: 266 /
# 264 / 267 at 128 / 256 / 512), nothing timed lost by 512, and at 256
# every shape timed has its best group (one kv head of 24 rows takes 8).
PAGED_VMEM_BUDGET = 8 * 1024 * 1024
PAGED_STEP_ROWS = 8
PAGED_STEP_WIDTH = 256


def window_pages(window: int, page_size: int) -> int:
    """Pages a window of ``window`` positions can span: a query reads
    ``window - 1`` positions behind its own, which end at most that many
    pages back (rounded up), and its own page. What a window layer's ring
    of pages holds a row (``nn/attention.py``) and the most a row has
    live in the paged kernel."""
    return -(-(window - 1) // page_size) + 1


@dataclasses.dataclass(frozen=True)
class PagedDecodeGeometry:
    """The tiling :func:`_paged_decode_call` runs for given shapes."""

    pages_per_step: int      # pages a copied block of one row holds
    rows_per_step: int       # batch rows a grid step attends
    grid: tuple[int, ...]    # one step a group of rows
    vmem_bytes: int          # both buffers of the K and V blocks


def paged_decode_geometry(
    *, batch: int, kv_heads: int, n_pages: int, page_size: int,
    head_dim: int, kv_itemsize: int, v_head_dim: int | None = None,
    window: int | None = None, query_rows: int = 1,
) -> PagedDecodeGeometry:
    """``pages_per_step``, ``rows_per_step`` and the grid, from the
    shapes alone.

    A copied page holds ALL kv heads (the pool is ``[P, Hkv, page, D]``:
    a page's heads are one contiguous slab), so the grid has no kv head
    dimension, and a row's blocks are a loop inside its grid step, over
    the live ones only. A block takes enough pages to cover
    ``PAGED_STEP_POSITIONS`` key positions (8 pages of 64), at most the
    row's pages, and fewer until both buffers of the K and V blocks of
    one row fit ``PAGED_VMEM_BUDGET`` (one page of every kv head is the
    least). Under a ``window`` a row never has more live pages than the
    window and the query's own page span, and a block takes no more.

    A grid step attends the largest group of ``PAGED_STEP_ROWS`` rows, a
    power of two, whose buffers fit the same budget and whose update is
    no wider than ``PAGED_STEP_WIDTH`` score rows (``query_rows``, the
    ``g x T`` queries a kv head, padded to sublanes, times the kv heads,
    times the group); a batch it does not divide is padded with dead
    rows.
    """
    width = head_dim + (head_dim if v_head_dim is None else v_head_dim)
    page_bytes = kv_heads * page_size * width * kv_itemsize  # K and V
    pps = min(n_pages, max(1, PAGED_STEP_POSITIONS // page_size))
    if window is not None:
        pps = min(pps, window_pages(window, page_size))
    while pps > 1 and 2 * pps * page_bytes > PAGED_VMEM_BUDGET:
        pps -= 1
    row_bytes = 2 * pps * page_bytes
    row_width = kv_heads * (query_rows + _pad_to(query_rows, 8))
    group = PAGED_STEP_ROWS
    while group > 1 and (group * row_bytes > PAGED_VMEM_BUDGET
                         or group * row_width > PAGED_STEP_WIDTH):
        group //= 2
    return PagedDecodeGeometry(
        pages_per_step=pps, rows_per_step=group, grid=(-(-batch // group),),
        vmem_bytes=group * row_bytes,
    )


def _paged_decode_kernel(offs_ref, pt_ref, q_ref, *refs,
                         cfg: _DecodeConfig, n_pages: int):
    """Grid step ``gi`` attends rows ``gi * R .. gi * R + R - 1``
    (``R = cfg.rows_per_step``): all kv heads, each row's live pages only
    (from the window's floor to the last query's page), a block of
    ``cfg.pages_per_step`` pages a row at a time. Iteration ``ib`` waits
    for block ``ib`` of every row that has one and runs ONE online-softmax
    update over the ``R x Hkv`` (row, head) pairs as the batch dimension
    of the two products; while it runs, iteration ``ib + 1`` of the same
    rows (or block 0 of the next group's rows) is on its way into the
    other buffer. A row whose live pages ended before ``ib`` sits the
    update out by the position mask: its block's first key lies past its
    last query, so ``p = 0``, ``m`` stays and ``alpha = 1``, whatever
    (finite) rows its buffer still holds. Same arithmetic as
    :func:`_decode_kernel`: one update a block of keys, in position
    order; a block's pages that were not copied are past the last query
    and masked by position."""
    ks_ref = vs_ref = None
    if cfg.quant:
        (ks_ref, vs_ref), refs = refs[:2], refs[2:]
    pools, (o_ref, lse_ref), bufs = refs[:2], refs[2:4], refs[4:6]
    sems, slot_ref, m_ref, l_ref, acc_ref = refs[6:]
    page, pps, group = cfg.block_kv, cfg.pages_per_step, cfg.rows_per_step
    block = pps * page
    value_buf = bufs[0] if cfg.latent else bufs[1]
    gi = pl.program_id(0)
    n_groups = pl.num_programs(0)

    def live_pages(row):
        """(first live page, live pages) of a row: pages wholly past the
        last query, or at or below the first query's window floor, are
        never copied."""
        start = offs_ref[row]
        last = jnp.minimum((start + (cfg.t - 1)) // page, n_pages - 1)
        first = 0
        if cfg.window is not None:
            floor = jnp.maximum(start - cfg.window + 1, 0)
            # the floor's own page; with int8 pools its block's first
            # page, since the gathered scale rows are cut on block edges
            first = floor // block * pps if cfg.quant else floor // page
        return first, last - first + 1

    def page_copy(pool, buf, pid, j, slot, r, i):
        return pltpu.make_async_copy(
            pool.at[pid],
            buf.at[slot, r, :, pl.ds(pl.multiple_of(j * page, page), page), :],
            sems.at[slot, i],
        )

    def start_copies(row, r, first_page, count, slot):
        """``count`` pages of ``row`` from ``first_page`` on, into row
        ``r`` of buffer ``slot``."""
        def one_page(j, carry):
            pid = pt_ref[row * n_pages + first_page + j]
            for i, (pool, buf) in enumerate(zip(pools, bufs)):
                page_copy(pool, buf, pid, j, slot, r, i).start()
            return carry

        jax.lax.fori_loop(0, count, one_page, None)

    def wait_copies(count, slot):
        def one_page(j, carry):
            for i, (pool, buf) in enumerate(zip(pools, bufs)):
                # a wait counts a page's bytes, whichever page and row
                # they were: the group's copies share a buffer's semaphore
                page_copy(pool, buf, 0, 0, slot, 0, i).wait()
            return carry

        jax.lax.fori_loop(0, count, one_page, None)

    def block_pages(live, ib):
        """Pages of a row's block ``ib``: none past its live pages."""
        return jnp.clip(live - ib * pps, 0, pps)

    rows = [gi * group + r for r in range(group)]
    firsts, lives = zip(*(live_pages(row) for row in rows))
    n_iter = functools.reduce(
        jnp.maximum, [pl.cdiv(live, pps) for live in lives])
    # the next group's rows; after the last group none, with no pages
    nxt_rows = [jnp.minimum(gi + 1, n_groups - 1) * group + r
                for r in range(group)]
    nxt_firsts, nxt_lives = zip(*(live_pages(row) for row in nxt_rows))
    nxt_lives = [jnp.where(gi + 1 < n_groups, live, 0) for live in nxt_lives]

    @pl.when(gi == 0)
    def _first_group():
        slot_ref[0] = 0
        # a buffer's unwritten tail, and the whole buffer of a row that
        # sits an update out, is masked by position, but 0 x NaN in the
        # value dot is NaN: no buffer starts with arbitrary bits
        value_buf[...] = jnp.zeros_like(value_buf)
        for r in range(group):
            start_copies(rows[r], r, firsts[r], block_pages(lives[r], 0), 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_BIG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    starts = [offs_ref[row] for row in rows]
    hkv, rp = q_ref.shape[1], q_ref.shape[2]

    def heads_as_batch(x):  # [R, Hkv, a, b] -> [R x Hkv, a, b]
        return x.reshape(group * hkv, *x.shape[2:])

    def scale_rows(ref, ib):
        # a sat-out row's block index may lie past its gathered rows:
        # any row of finite scales does for scores that are masked
        last = ref.shape[2] - 1
        return jnp.stack([
            ref[r, :, pl.ds(jnp.minimum(firsts[r] // pps + ib, last), 1), :]
            for r in range(group)
        ])  # [R, Hkv, 1, block]

    def attend(slot, ib):
        def scores(q, k):  # [., rows_pad, D] x [., block, D]
            return jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )

        q = heads_as_batch(q_ref[...].astype(jnp.float32))   # [., rows_pad, D]
        k = heads_as_batch(bufs[0][slot].astype(jnp.float32))  # [., block, D]
        if cfg.latent:
            # q = [q_abs | q_rope]: the latent rows meet the first, the
            # rotary key rows the second, and the latent rows are the values
            r = k.shape[-1]
            k_rope = heads_as_batch(bufs[1][slot].astype(jnp.float32))
            v = k
            s = scores(q[..., :r], k) + scores(q[..., r:], k_rope)
        else:
            v = heads_as_batch(bufs[1][slot].astype(jnp.float32))
            s = scores(q, k)
        s = (s * cfg.scale).reshape(group, hkv, rp, block)
        if cfg.quant:
            # int8 keys: a slot's scale multiplies its column of
            # scores (row [Hkv, 1, block] of the row's gathered scales),
            # in float32 like the rest; the values' scales meet p below
            s = s * scale_rows(ks_ref, ib)

        row = jax.lax.broadcasted_iota(jnp.int32, (rp, block), 0)
        key = jax.lax.broadcasted_iota(jnp.int32, (rp, block), 1)
        # row r = (head-in-group, token i) flattened as ig·T + i
        token = jax.lax.rem(row, cfg.t)
        masks = []
        for r in range(group):
            k_pos = (firsts[r] + ib * pps) * page + key
            q_pos = starts[r] + token
            mask = (k_pos < cfg.s_len) & (k_pos <= q_pos) & (row < cfg.rows)
            if cfg.window is not None:
                mask &= k_pos > q_pos - cfg.window
            masks.append(mask)
        mask = jnp.stack(masks)[:, None]  # [R, 1, rows_pad, block]
        s = jnp.where(mask, s, NEG_BIG)

        m_prev = m_ref[..., :1]
        m_new = jnp.maximum(m_prev, s.max(axis=3, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # gated by the mask, not just the sentinel (see _decode_kernel):
        # fully masked rows keep l at 0 and finalize to exact zeros
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_ref[..., :1] + p.sum(axis=3, keepdims=True)
        if cfg.quant:
            p = p * scale_rows(vs_ref, ib)
        pv = jax.lax.dot_general(
            heads_as_batch(p), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv.reshape(acc_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    def attend_blocks(ib, carry):
        slot = slot_ref[0]
        # the iteration after this one: the group's next blocks, else
        # the next group's first
        in_group = ib + 1 < n_iter
        for r in range(group):
            start_copies(
                jnp.where(in_group, rows[r], nxt_rows[r]), r,
                jnp.where(in_group, firsts[r] + (ib + 1) * pps, nxt_firsts[r]),
                jnp.where(in_group, block_pages(lives[r], ib + 1),
                          block_pages(nxt_lives[r], 0)),
                1 - slot,
            )

        wait_copies(sum(block_pages(live, ib) for live in lives), slot)

        attend(slot, ib)
        slot_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, n_iter, attend_blocks, None)

    m = m_ref[..., :1]
    l = l_ref[..., :1]
    o_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(jnp.maximum(l, 1e-30))


# d9d-lint: disable=D9D001 — standalone-use decorator; serving traces this inside the tracked serve/step program (a TrackedJit cannot be called under a trace)
@functools.partial(jax.jit, static_argnames=("cfg",))
def _paged_decode_call(cfg: _DecodeConfig, q_rows, k_pool, v_pool,
                       offsets, page_table, k_scale=None, v_scale=None):
    """``q_rows [B, Hkv, rows_pad, D]`` vs page pools
    ``k/v [P, Hkv, page_size, D]`` gathered through
    ``page_table [B, n_pages]`` → same outputs as :func:`_decode_call`
    on the contiguous equivalent: a different INDEX, not a different
    algorithm. The pools stay in HBM; the kernel copies a row's live
    pages itself, ``cfg.pages_per_step`` a block and
    ``cfg.rows_per_step`` rows a grid step
    (:func:`paged_decode_geometry`), so a page costs a copy only while
    it is live, a grid step moves a block of pages of several rows, and
    one online-softmax update serves them all. ``cfg.block_kv`` is the
    page size.

    ``cfg.quant``: k/v pools are int8 and ``k/v_scale [P, Hkv, ps]``
    carry the per-slot dequantization scales. A row's scales are
    gathered here through the same table into lane-dense rows, one a
    block of keys, and meet the scores and the probabilities in the
    kernel's float32 math.

    ``cfg.latent``: ``k_pool [P, 1, page_size, r]`` holds the latent rows,
    which are the values too, ``v_pool [P, 1, page_size, d_rope]`` the
    shared rotary key rows, and ``q_rows`` is ``[B, 1, rows_pad,
    r + d_rope]``; the output is ``r`` wide."""
    b, hkv, rp, d = q_rows.shape
    dk = k_pool.shape[-1]  # d, but for the latent rows' r
    dv = dk if cfg.latent else v_pool.shape[-1]
    n_pages = page_table.shape[1]
    pps, group = cfg.pages_per_step, cfg.rows_per_step
    block = pps * cfg.block_kv
    dead = _pad_to(b, group)
    if dead:
        # dead rows fill the last group: position 0 of table row 0's
        # first page, the garbage page, as idle slots are
        q_rows = jnp.pad(q_rows, ((0, dead), (0, 0), (0, 0), (0, 0)))
        offsets = jnp.pad(offsets, (0, dead))
        page_table = jnp.pad(page_table, ((0, dead), (0, 0)))
    rows = b + dead

    scale_specs, scale_rows = [], ()
    if cfg.quant:
        # a row's scales, gathered here: [B, Hkv, blocks, block], one
        # lane-dense row a block of keys (3 % of the int8 bytes)
        n_blocks = pl.cdiv(n_pages, pps)
        table = jnp.pad(page_table, ((0, 0), (0, n_blocks * pps - n_pages)))

        def gathered(scale):  # [P, Hkv, page] -> [B, Hkv, blocks, block]
            g = scale[table].reshape(rows, n_blocks, pps, hkv, cfg.block_kv)
            return g.transpose(0, 3, 1, 2, 4).reshape(
                rows, hkv, n_blocks, block)

        scale_rows = (gathered(k_scale), gathered(v_scale))
        scale_specs = [pl.BlockSpec(
            (group, hkv, n_blocks, block), lambda gi, offs, pt: (gi, 0, 0, 0),
        )] * 2

    def group_spec(width):
        return pl.BlockSpec((group, hkv, rp, width),
                            lambda gi, offs, pt: (gi, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # offsets, the page table (flat: SMEM pads rows)
        grid=(rows // group,),
        in_specs=[group_spec(d), *scale_specs]
        + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=[group_spec(dv), group_spec(1)],
        scratch_shapes=[
            pltpu.VMEM((2, group, hkv, block, dk), k_pool.dtype),
            pltpu.VMEM((2, group, hkv, block, v_pool.shape[-1]), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),  # the buffer the next block waits on
            pltpu.VMEM((group, hkv, rp, LANES), jnp.float32),
            pltpu.VMEM((group, hkv, rp, LANES), jnp.float32),
            pltpu.VMEM((group, hkv, rp, dv), jnp.float32),
        ],
    )
    # the scope holds the rows a grid step attends and the name the pages
    # a block: a trace says which tiling ran, of which configuration
    kind = "latent_decode" if cfg.latent else "paged_decode"
    with jax.named_scope(f"{kind}_r{group}"):
        o, lse = pl.pallas_call(
            functools.partial(_paged_decode_kernel, cfg=cfg, n_pages=n_pages),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((rows, hkv, rp, dv), q_rows.dtype),
                jax.ShapeDtypeStruct((rows, hkv, rp, 1), jnp.float32),
            ],
            # groups in order: a group starts the next group's first copies
            compiler_params=(
                None if cfg.interpret else pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",)
                )
            ),
            interpret=cfg.interpret,
            name=f"{kind}_p{pps}",
        )(offsets, page_table.reshape(-1), q_rows, *scale_rows, k_pool, v_pool)
    return o[:b], lse[:b, ..., 0]


# Paged append. A decode step's new key and value rows, one a batch row
# and kv head, written into the heads-major pools from one call that
# holds both pools in HBM, aliased to its outputs. A row's write is the
# SUBLANE TILE it lies in: a 16-bit pool packs two positions to a sublane
# word, so a transfer cannot be cut at one position; the kernel copies
# ``pool[page, :, tile, :]`` (all kv heads in one strided transfer) into
# VMEM, replaces the row's position by a select, and copies the tile
# back. XLA's scatter of the same rows (``nn/attention.py
# _scatter_head_rows``) runs as a loop over rows x heads at 65 to 80 ns a
# row on the chip (ledger, PR 54).
APPEND_ROWS_IN_FLIGHT = 8


def append_tile(page_size: int, dtype) -> int:
    """Positions in a pool's sublane tile, from the dtype's packing: 8
    sublanes of 32-bit words, ``4 // itemsize`` positions a word (8 for
    float32, 16 for bfloat16). A page that is not whole tiles (toy pages
    on the CPU rig) is its own tile: a whole dimension is always a legal
    transfer."""
    tile = 8 * (4 // jnp.dtype(dtype).itemsize)
    return tile if page_size % tile == 0 else page_size


def _paged_append_kernel(page_ref, off_ref, k_rows_ref, v_rows_ref,
                         k_in, v_in, k_pool, v_pool, k_buf, v_buf, sems,
                         *, tile: int, depth: int):
    """Row ``r`` of the batch: fetch the tile of ``(page[r], off[r])``
    from both pools, put the new rows at ``off[r] % tile``, write the
    tiles back. ``depth`` rows' buffers: while a row's tiles are written
    out, the next rows' are on their way in. Rows are independent: a live
    row owns the page it writes; dead rows share the garbage page (or
    scribble in their own ring), where a race writes garbage over
    garbage."""
    del k_in, v_in  # the pools themselves: aliased to k_pool / v_pool
    n_rows = k_rows_ref.shape[0]
    pools, bufs = (k_pool, v_pool), (k_buf, v_buf)
    new_rows = (k_rows_ref, v_rows_ref)

    def tile_copy(row, i, out: bool):
        first = pl.multiple_of(off_ref[row] // tile * tile, tile)
        hbm = pools[i].at[page_ref[row], :, pl.ds(first, tile), :]
        slot = row % depth
        vmem = bufs[i].at[slot]
        src, dst = (vmem, hbm) if out else (hbm, vmem)
        return pltpu.make_async_copy(src, dst, sems.at[int(out), slot, i])

    def fetch(row, carry=None):
        for i in range(2):
            tile_copy(row, i, out=False).start()

    def wait_written(row):
        for i in range(2):
            tile_copy(row, i, out=True).wait()

    jax.lax.fori_loop(0, min(depth, n_rows), fetch, None)

    def one_row(row, carry):
        at = off_ref[row] % tile
        for i in range(2):
            tile_copy(row, i, out=False).wait()
            buf = bufs[i].at[row % depth]
            held = buf[...]  # [H, tile, D]
            position = jax.lax.broadcasted_iota(jnp.int32, held.shape, 1)
            buf[...] = jnp.where(
                position == at, new_rows[i][row][:, None, :], held)
            tile_copy(row, i, out=True).start()

        # the row before has had this row's work to land: its buffers
        # take the row ``depth`` after it
        @pl.when(row > 0)
        def _reuse():
            wait_written(row - 1)

            @pl.when(row - 1 + depth < n_rows)
            def _next():
                fetch(row - 1 + depth)

        return carry

    jax.lax.fori_loop(0, n_rows, one_row, None)
    wait_written(n_rows - 1)


def paged_append(k_pool, v_pool, page, off, k_rows, v_rows, *,
                 interpret: bool | None = None):
    """``k_pool [P, H, ps, Dk]`` and ``v_pool [P, H, ps, Dv]`` with
    ``k_rows [B, H, Dk]`` and ``v_rows [B, H, Dv]`` written at
    ``(page[b], h, off[b])``: the bits ``nn/attention.py
    _scatter_head_rows`` writes (the reference this is tested against),
    every other position untouched. One call serves both pools; they stay
    in HBM and are the call's outputs, so a caller that donates them (the
    serving chunk's carried cache) has them updated in place. Rows whose
    ``(page, tile)`` coincide (dead rows on the garbage page) leave that
    tile holding one of their writes or a mix of them."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, dk = k_rows.shape
    dv = v_rows.shape[-1]
    tile = append_tile(k_pool.shape[2], k_pool.dtype)
    depth = min(APPEND_ROWS_IN_FLIGHT, b)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    pool = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # a row's page and its position in it
        grid=(),
        in_specs=[whole, whole, pool, pool],
        out_specs=[pool, pool],
        scratch_shapes=[
            pltpu.VMEM((depth, h, tile, dk), k_pool.dtype),
            pltpu.VMEM((depth, h, tile, dv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, depth, 2)),  # in / out, buffer, pool
        ],
    )
    pools = pl.pallas_call(
        functools.partial(_paged_append_kernel, tile=tile, depth=depth),
        grid_spec=grid_spec,
        # HBM by name, which the aliased operands inherit: a pool that
        # fits the compiler's fast memory (Qwen3's 37.8 MB, S(1) in the
        # compiled text) is otherwise staged there whole around the call
        # and copied back out, every layer every step
        out_shape=[
            pltpu.HBM(k_pool.shape, k_pool.dtype),
            pltpu.HBM(v_pool.shape, v_pool.dtype),
        ],
        # operands count the scalar prefetch: page, off, rows, rows, pools
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        name="paged_append",
    )(page.astype(jnp.int32), off.astype(jnp.int32),
      k_rows.astype(k_pool.dtype), v_rows.astype(v_pool.dtype),
      k_pool, v_pool)
    # where the pools are a program's own donated arguments and results (a
    # step outside any loop) the compiler's verifier refuses the named
    # outputs as aliases of unnamed parameters; behind a barrier it takes
    # them (tests/core/test_chip_compile.py)
    return jax.lax.optimization_barrier(tuple(pools))


# d9d-lint: disable=D9D001 — standalone-use decorator; serving traces this inside the tracked serve/step program (a TrackedJit cannot be called under a trace)
@functools.partial(
    jax.jit,
    static_argnames=("cfg",),
)
def _decode_call(cfg: _DecodeConfig, q_rows, kp, vp, valid, offsets):
    """``q_rows [B, Hkv, rows_pad, D]`` vs cache ``k/v [B, Hkv, S_pad, D]``
    (heads-major — the caller's cache layout, streamed with no relayout)
    → ``(o [B, Hkv, rows_pad, D], lse [B, Hkv, rows_pad])``."""
    b, hkv, rp, d = q_rows.shape
    dv = vp.shape[-1]
    s_pad = kp.shape[2]
    n_kv = s_pad // cfg.block_kv

    valid_specs, valid_bufs = (), ()
    if cfg.has_valid:
        valid_specs = (
            pl.BlockSpec((1, 1, cfg.block_kv),
                         lambda bi, hi, ki: (bi, 0, ki)),
        )
        valid_bufs = (valid[:, None, :].astype(jnp.int32),)

    o, lse = pl.pallas_call(
        functools.partial(_decode_kernel, cfg=cfg),
        grid=(b, hkv, n_kv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, rp, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, cfg.block_kv, d),
                         lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, cfg.block_kv, dv),
                         lambda bi, hi, ki: (bi, hi, ki, 0)),
            *valid_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rp, dv), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, rp, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, rp, dv), q_rows.dtype),
            jax.ShapeDtypeStruct((b, hkv, rp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rp, LANES), jnp.float32),
            pltpu.VMEM((rp, LANES), jnp.float32),
            pltpu.VMEM((rp, dv), jnp.float32),
        ],
        compiler_params=(
            None if cfg.interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")
            )
        ),
        interpret=cfg.interpret,
    )(offsets, q_rows, kp, vp, *valid_bufs)
    return o, lse[..., 0]


def flash_decode_attention(
    q: Array,
    k_cache: Array,
    v_cache: Array,
    *,
    start: Array,
    softmax_scale: float | None = None,
    window_size: int | None = None,
    sinks: Array | None = None,
    kv_valid: Array | None = None,
    page_table: Array | None = None,
    k_scale: Array | None = None,
    v_scale: Array | None = None,
    block_kv: int = 512,
    interpret: bool | None = None,
) -> Array:
    """Decode-step attention: ``q [B,T,Hq,D]`` (new tokens at cache
    positions ``start + [0,T)``) against the full slot cache
    ``k [B,Hkv,S,D]``, ``v [B,Hkv,S,Dv]`` (HEADS-MAJOR — the layout
    ``_decode_cache_append_heads_major`` maintains, so the cache streams
    into the kernel with zero per-step relayout) → ``[B,T,Hq,Dv]``.

    Slot-causal + optional sliding window over global positions;
    ``start`` may be a scalar (one shared write index — the closed-batch
    generate loop) or per-row ``[B]`` (continuous batching: each row's
    cache fills at its own rate). ``kv_valid [B,S]`` masks dead slots
    (left-padded ragged prompts); ``sinks [Hq]`` join the softmax
    denominator via the standard outside-the-kernel correction.
    Forward-only (decode never backpropagates). Semantics match
    ``eager_sdpa(q, cacheᵀ, cacheᵀ, causal=False,
    mask=_decode_slot_mask(...))`` — the parity test drives both — with
    one deliberate divergence: a query row whose EVERY key is masked
    (e.g. ``kv_valid`` zeroing all slots at or before its position)
    produces exact ZEROS here, where the eager oracle's finite softmax
    sentinel yields a uniform mean-of-V. Module callers never hit this
    case (a row's just-written key is always valid), but public callers
    passing custom validity get the guarded-softmax behavior.

    PAGED mode (``page_table [B, n_pages]`` set): ``k/v`` are page
    POOLS ``[P, Hkv, page_size, D]`` and row ``b``'s logical page ``p``
    lives in pool page ``page_table[b, p]``. A grid step is a group of
    rows (:func:`paged_decode_geometry`: 8, 4, 2 or 1 by the shapes;
    ``block_kv`` does not apply): it copies each row's live pages, a
    block of pages at a time, all kv heads of a page in one copy, and
    runs one online-softmax update for the group's blocks; a row's
    result is what it is with one row a step.
    Everything else — per-row ``start``, pages past the row's last
    query never touched, windows, sinks, the online softmax — is
    unchanged. ``kv_valid`` does not compose with paging (the serving
    loop never passes it).

    QUANTIZED paged mode (``k_scale``/``v_scale [P, Hkv, page_size]``
    set): the pools are int8 and each slot's feature vector carries a
    f32 scale; a row's scales are gathered through the same page table
    and the kernel applies them in its float32 math (a key's scale to
    its column of scores, a value's to its probabilities) — HBM
    traffic per slot drops to D int8 bytes + one f32 scale. Note int8
    TPU tiles are (32, 128): on-chip (non-interpret) runs need
    ``page_size >= 32``; the CPU interpret tier has no such floor.
    """
    b, t, hq, d = q.shape
    _, hkv, s, _ = k_cache.shape
    dv = v_cache.shape[-1]  # value heads may be narrower than the keys'
    if hq % hkv != 0:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    g = hq // hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    rows = g * t
    rp = rows + _pad_to(rows, 8)

    # [B,T,Hq,D] → [B,Hkv,g·T,D], row r = ig·T + i (shared by both the
    # contiguous and paged calls, as are the epilogue slices, the sink
    # fold and the output reshape below — the two paths differ ONLY in
    # how kv blocks are indexed)
    q_rows = (
        q.transpose(0, 2, 1, 3)
        .reshape(b, hkv, g * t, d)
    )
    if rp != rows:
        q_rows = jnp.pad(q_rows, ((0, 0), (0, 0), (0, rp - rows), (0, 0)))
    offsets = jnp.broadcast_to(
        jnp.asarray(start, jnp.int32).reshape(-1), (b,)
    )

    if page_table is not None:
        if kv_valid is not None:
            raise NotImplementedError(
                "paged decode does not take kv_valid (the serving loop's "
                "paged rows are never left-padded)"
            )
        if (k_scale is None) != (v_scale is None):
            raise ValueError("k_scale and v_scale must be set together")
        page_size = k_cache.shape[2]
        n_pages = page_table.shape[1]
        geo = paged_decode_geometry(
            batch=b, kv_heads=hkv, n_pages=n_pages, page_size=page_size,
            head_dim=d, kv_itemsize=k_cache.dtype.itemsize,
            v_head_dim=dv, window=window_size, query_rows=rows,
        )
        cfg = _DecodeConfig(
            scale=softmax_scale if softmax_scale is not None else d**-0.5,
            window=window_size,
            t=t,
            rows=rows,
            rows_pad=rp,
            s_len=n_pages * page_size,  # every gathered slot addressable
            block_kv=page_size,
            has_valid=False,
            interpret=interpret,
            quant=k_scale is not None,
            pages_per_step=geo.pages_per_step,
            rows_per_step=geo.rows_per_step,
        )
        o, lse = _paged_decode_call(
            cfg, q_rows, k_cache, v_cache, offsets,
            page_table.astype(jnp.int32),
            k_scale=k_scale, v_scale=v_scale,
        )
    else:
        if k_scale is not None or v_scale is not None:
            raise NotImplementedError(
                "k_scale/v_scale are paged-mode arguments (quantized "
                "pools need a page_table)"
            )
        bkv = min(block_kv, s + _pad_to(s, LANES))
        s_pad = s + _pad_to(s, bkv)

        cfg = _DecodeConfig(
            scale=softmax_scale if softmax_scale is not None else d**-0.5,
            window=window_size,
            t=t,
            rows=rows,
            rows_pad=rp,
            s_len=s,
            block_kv=bkv,
            has_valid=kv_valid is not None,
            interpret=interpret,
        )

        pad_s = s_pad - s
        kp = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad_s), (0, 0))) if pad_s else k_cache
        vp = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad_s), (0, 0))) if pad_s else v_cache
        validp = None
        if kv_valid is not None:
            validp = jnp.pad(kv_valid, ((0, 0), (0, pad_s))) if pad_s else kv_valid

        o, lse = _decode_call(cfg, q_rows, kp, vp, validp, offsets)

    o = o[:, :, :rows]
    lse = lse[:, :, :rows]
    if sinks is not None:
        # sink joins only the denominator: o' = o / (1 + exp(sink - lse))
        sink_rows = jnp.repeat(
            sinks.astype(jnp.float32).reshape(hkv, g), t, axis=1
        ).reshape(1, hkv, rows, 1)
        z = jnp.clip(sink_rows - lse[..., None], max=60.0)
        o = (o.astype(jnp.float32) / (1.0 + jnp.exp(z))).astype(o.dtype)

    # [B,Hkv,g·T,Dv] → [B,T,Hq,Dv]
    return (
        o.reshape(b, hkv, g, t, dv)
        .transpose(0, 3, 1, 2, 4)
        .reshape(b, t, hq, dv)
    )


def latent_decode_attention(
    q_abs: Array,
    q_rope: Array,
    latent_pool: Array,
    rope_pool: Array,
    *,
    start: Array,
    page_table: Array,
    softmax_scale: float,
    interpret: bool | None = None,
) -> Array:
    """One token's absorbed latent attention through the page table:
    ``q_abs [B, 1, H, r]`` (the query with the key up-projection folded
    in) and ``q_rope [B, 1, H, d_rope]`` against the pools
    ``latent_pool [P, page_size, r]`` and ``rope_pool [P, page_size,
    d_rope or more]`` (on the chip a whole lane tile, its rows filled
    with zeros: Mosaic cuts no page out of a pool of narrower rows), row
    ``b``'s logical page ``p`` in pool page ``page_table[b, p]`` and its
    query at position ``start[b]`` → ``[B, 1, H, r]``, the softmax's
    weighted sum of the latent rows, which the caller folds through the
    value up-projection.

    The paged kernel of :func:`flash_decode_attention` with the pools
    seen as one kv head (a free view) and ``_DecodeConfig.latent`` set: a
    score is ``(q_abs . c + q_rope . k_rope) * softmax_scale``, the values
    are the copied latent rows, and only a row's live pages leave HBM,
    where ``nn/attention.py latent_attend`` multiplies the gathered view
    of every page of every row under a mask. The same arithmetic in
    float32 (pool rows are cast before each product; the queries come and
    the result leaves in the queries' dtype, float32 from the module) with
    the softmax taken a block of keys at a time in position order; a row
    with no visible position gives exact zeros."""
    b, t, h, r = q_abs.shape
    if t != 1:
        raise NotImplementedError(
            f"the paged latent decode attends one token a row; got t={t}"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    page_size, rope_width = rope_pool.shape[1:]
    n_pages = page_table.shape[1]
    geo = paged_decode_geometry(
        batch=b, kv_heads=1, n_pages=n_pages, page_size=page_size,
        head_dim=r, v_head_dim=rope_width,
        kv_itemsize=latent_pool.dtype.itemsize, query_rows=h,
    )
    rp = h + _pad_to(h, 8)
    cfg = _DecodeConfig(
        scale=softmax_scale,
        window=None,
        t=1,
        rows=h,
        rows_pad=rp,
        s_len=n_pages * page_size,
        block_kv=page_size,
        has_valid=False,
        interpret=interpret,
        pages_per_step=geo.pages_per_step,
        rows_per_step=geo.rows_per_step,
        latent=True,
    )
    # [B, 1, H, r + d_rope] is [B, one kv head, H query rows, .] as it is;
    # zeros meet the zeros that fill a rotary key row to a lane tile
    q_rows = jnp.concatenate([q_abs, q_rope.astype(q_abs.dtype)], axis=-1)
    q_rows = jnp.pad(q_rows, (
        (0, 0), (0, 0), (0, rp - h), (0, rope_width - q_rope.shape[-1])))
    o, _ = _paged_decode_call(
        cfg, q_rows, latent_pool[:, None], rope_pool[:, None],
        jnp.asarray(start, jnp.int32), page_table.astype(jnp.int32),
    )
    return o[:, :, :h]
