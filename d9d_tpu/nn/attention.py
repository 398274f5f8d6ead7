"""Grouped-query attention block.

Reference: d9d/module/block/attention/grouped_query.py:10 — QKV projections
→ optional per-head QK RMSNorm → (optionally partial) RoPE → pluggable SDPA
backend → optional sigmoid output gate → output projection. Feature surface
covers Qwen3 (qk-norm), GPT-OSS-style sinks, and sliding-window models.
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from d9d_tpu.core.types import Array
from d9d_tpu.nn import logical_axes as la
from d9d_tpu.nn.norm import RMSNorm
from d9d_tpu.nn.sdpa.protocol import SdpaBackend
from d9d_tpu.ops import RopeStyle, apply_rope


def _decode_contract_checks(start, t: int, s_max: int):
    """Functionalized assertions for the two traced decode contracts:
    the multi-token prefill FAST PATH is only valid on an
    empty cache (continuation chunks — ``in_continuation_chunk()`` —
    take the slot-cache path instead and are valid at any index), and
    the cache must never overflow (past capacity,
    ``dynamic_update_slice`` clamps and attention silently degrades).
    ``checkify.debug_check`` is a no-op in plain jit but fails loudly
    when the caller wraps with ``checkify.checkify`` — which the decode
    contract tests do; ``loop/generate.py`` additionally enforces both
    bounds statically before tracing.
    """
    from jax.experimental import checkify

    from d9d_tpu.nn.decode_flags import (
        bounds_held_by_caller,
        in_continuation_chunk,
    )

    if bounds_held_by_caller():
        return
    # jnp.all: start may be per-row [B] (continuous batching)
    checkify.debug_check(
        jnp.all(start + t <= s_max),
        f"decode cache overflow: cache index + {t} new tokens exceed "
        f"decode_max_length={s_max}",
    )
    if t > 1 and not in_continuation_chunk():
        checkify.debug_check(
            jnp.all(start == 0),
            f"decode prefill (t={t} > 1) requires an empty cache "
            f"(the fast path attends only the new tokens); wrap "
            f"continuation chunks in "
            f"d9d_tpu.nn.decode_flags.continuation_chunk()",
        )


def _decode_cache_index(module: nn.Module):
    """The module's decode write-index variable (declare once per trace —
    flax forbids re-declaring a name within one __call__).

    Initialized SCALAR (one shared index — the closed-batch generate
    loop). A serving loop may seed the cache collection with a per-row
    ``[B]`` index instead (flax returns the provided value untouched);
    every consumer below handles both ranks — this is how continuous
    batching (loop/serve.py) lets each row's cache fill at its own rate
    without any module plumbing.
    """
    return module.variable(
        "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
    )


def _decode_page_table(module: nn.Module):
    """The module's page-table cache variable if the serving loop seeded
    one (paged KV mode, loop/serve.py), else None. Presence of the leaf
    IS the mode flag: the serving loop converts this module's sequence
    caches into page pools in the same pass that seeds the table, so
    the two can't disagree."""
    from d9d_tpu.nn.decode_flags import PAGE_TABLE_LEAF

    if not module.has_variable("cache", PAGE_TABLE_LEAF):
        return None
    return module.variable("cache", PAGE_TABLE_LEAF, lambda: None).value


def _paged_write_checks(start, t: int, mask) -> None:
    """The paged cache is a serving-loop construct: the loop feeds one
    token per row per step (prompts are teacher-forced), never passes a
    slot mask, and seeds per-row write indices. Anything else reaching a
    paged module is a caller bug — fail loudly, not approximately."""
    if t != 1:
        raise NotImplementedError(
            "paged decode caches serve single-token steps only (the "
            "serving loop teacher-forces prompts token-by-token); got "
            f"t={t}"
        )
    if mask is not None:
        raise NotImplementedError(
            "paged decode does not take a slot mask (paged rows are "
            "never left-padded)"
        )
    if jnp.ndim(start) == 0:
        raise ValueError(
            "paged decode needs per-row [B] write indices (the serving "
            "loop seeds them); got a scalar cache_index"
        )


def _paged_slot(page_table, start, page_size: int):
    """Row-wise (page, offset) for logical slot ``start [B]``: the page
    id gathered from the table, the offset within it. Dead/idle rows
    (serve.py pins their ``start`` to 0 and their table row to 0) land
    on the reserved garbage page."""
    page = jnp.take_along_axis(
        page_table, (start // page_size)[:, None], axis=1
    )[:, 0]
    return page, start % page_size


def _paged_scale_var(module: nn.Module, name: str):
    """The sibling scale-pool variable of pool leaf ``name`` if the
    serving loop seeded one (``kv_quant`` mode, loop/serve.py), else
    None. Like the page table, presence of the leaf IS the mode flag:
    the loop creates int8 pools and their scale pools in the same
    pass, so the two cannot disagree."""
    from d9d_tpu.nn.decode_flags import PAGED_SCALE_SUFFIX

    scale_name = name + PAGED_SCALE_SUFFIX
    if not module.has_variable("cache", scale_name):
        return None
    return module.variable("cache", scale_name, lambda: None)


def _quantize_rows(v):
    """Symmetric int8 quantization of feature vectors: ``v [..., D]`` →
    ``(int8 [..., D], f32 scale [...])`` with scale = absmax/127 per
    leading index. An all-zero vector gets scale 0 and quantizes to
    exact zeros (dequant reproduces them — garbage-page writes stay
    harmless)."""
    vf = v.astype(jnp.float32)
    scale = jnp.max(jnp.abs(vf), axis=-1) / 127.0
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(
        jnp.round(vf / safe[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _decode_cache_append(module: nn.Module, value, name: str, s_max: int,
                         start, page_table=None):
    """Append ``value [B, T, ...]`` at cache slot ``start`` (scalar, or
    per-row ``[B]`` for continuous batching).

    One definition for every decode cache (GQA k/v, MLA latent/rope key).
    Capacity contract: callers must never feed more than ``s_max`` total
    tokens — the write index is traced, so this cannot be checked here;
    past the end ``dynamic_update_slice`` clamps and outputs silently
    degrade (loop/generate.py enforces the bound statically up front).
    Returns the full cache buffer.

    With ``page_table [B, n_pages]`` the buffer is a page POOL
    ``[P, page_size, ...]`` (seeded by the serving loop); the one new
    token scatters to ``(page_table[b, start // ps], start % ps)`` and
    the CONTIGUOUS PER-ROW VIEW ``[B, n_pages·ps, ...]`` is returned:
    every page of every row, live or not, written out and read back each
    step (85 MB a layer at 64 slots x 1,152 positions of 576 numbers:
    2.7 times the device time of the attend it fed; ledger, PR 57). Who
    still takes that gather: MLA's single-token steps under the ``eager``
    decode backend (every CPU run that is not an interpret-mode kernel
    test), over int8 pools, and with ``decode_absorbed=False`` (the
    oracle). Under the ``pallas`` backend the absorbed step appends
    through :func:`_paged_append_latent` and reads the pools through the
    page table itself, live pages only.
    """
    from jax import lax

    b = value.shape[0]
    if page_table is not None:
        ref = module.variable("cache", name, lambda: None)
        pool = ref.value  # [P, ps, ...]
        ps = pool.shape[1]
        page, off = _paged_slot(page_table, start, ps)
        sref = _paged_scale_var(module, name)
        if sref is not None:
            # int8 pool (kv_quant): quantize the one new row at the
            # scatter, dequantize the whole gathered view at the read —
            # consumers see the value dtype either way
            qv, sc = _quantize_rows(value[:, 0])
            ref.value = pool.at[page, off].set(qv)
            sref.value = sref.value.at[page, off].set(sc)
            g = ref.value[page_table]       # [B, n, ps, ...] int8
            gs = sref.value[page_table]     # [B, n, ps] f32
            g = (g.astype(jnp.float32) * gs[..., None]).astype(value.dtype)
        else:
            ref.value = pool.at[page, off].set(value[:, 0])
            g = ref.value[page_table]  # [B, n_pages, ps, ...]
        return g.reshape((b, -1) + g.shape[3:])
    ref = module.variable(
        "cache", name,
        lambda: jnp.zeros((b, s_max) + value.shape[2:], value.dtype),
    )
    if jnp.ndim(start) == 0:
        ref.value = lax.dynamic_update_slice(
            ref.value, value, (0, start) + (0,) * (value.ndim - 2)
        )
    else:
        ref.value = jax.vmap(
            lambda c, v, s: lax.dynamic_update_slice(
                c, v, (s,) + (0,) * (v.ndim - 1)
            )
        )(ref.value, value, start)
    return ref.value


def _scatter_head_rows(pool, page, off, rows):
    """``pool [P, H, ps, D]`` with ``rows [B, H, D]`` written at
    ``(page[b], h, off[b])``: a scatter of whole ``D``-rows into the pool
    seen as ``[P·H·ps, D]`` (a free view: ``ps`` is whole sublane tiles).
    Written as ``pool.at[page, :, off, :].set(rows)`` the TPU compiler
    gives the scatter a layout of its own (heads inside positions) and
    copies the WHOLE pool into it and back to the decode kernel's every
    step: 3.9 ms a layer a step against 0.56 at 256 slots x 18 pages x 4
    heads x (256 + 128) numbers (my chip run, PR 41; PERF.md §6).

    Where it still runs (PR 55): under the ``eager`` decode backend
    (every CPU run that is not an interpret-mode kernel test), for int8
    pools and their scale pools under both backends, and for a module
    that appends to one pool alone (:func:`_decode_cache_append_heads_major`
    with a ``page_table``). Under the ``pallas`` backend a GQA layer's
    two pools take ``pallas_decode.paged_append``
    (:func:`_paged_append_kv`), which is tested against this: the chip
    runs the scatter as a loop over rows, 65 to 80 ns each (ledger,
    PR 54)."""
    p, h, ps, d = pool.shape
    flat = (
        (page[:, None] * h + jnp.arange(h, dtype=page.dtype)[None, :]) * ps
        + off[:, None]
    ).reshape(-1)
    return (
        pool.reshape(p * h * ps, d).at[flat].set(rows.reshape(-1, d))
        .reshape(pool.shape)
    )


def _paged_scatter_append(module: nn.Module, ref, name: str, page, off,
                          value):
    """One heads-major pool ``ref`` (leaf ``name``) with ``value
    [B, 1, H, D]`` scattered to ``(page[b], h, off[b])``; returns the
    pool."""
    sref = _paged_scale_var(module, name)
    if sref is not None:
        # int8 pool (kv_quant): per-(row, head) scales land in the
        # [P, H, ps] scale pool at the same (page, offset); readers
        # (the flash kernel's scale BlockSpec / the quantized eager
        # gather) dequantize — the raw int8 pool is returned
        qv, sc = _quantize_rows(value[:, 0])  # [B,H,D] i8, [B,H] f32
        ref.value = _scatter_head_rows(ref.value, page, off, qv)
        sref.value = sref.value.at[page, :, off].set(sc)
        return ref.value
    ref.value = _scatter_head_rows(ref.value, page, off, value[:, 0])
    return ref.value


def _paged_append_kv(module: nn.Module, k, v, names, start, page_table):
    """A decode step's new ``k [B, 1, H, Dk]`` and ``v [B, 1, H, Dv]``
    into the heads-major page pools ``names`` (shared pools or a window
    layer's ring of pages: both are ``[P, H, ps, D]`` behind a table);
    returns the two pools.

    The append follows the switch the attend follows: under the
    ``pallas`` decode backend one ``pallas_decode.paged_append`` call
    writes both pools, held in place; under ``eager`` each pool takes
    :func:`_scatter_head_rows`, the reference the kernel is tested
    against. int8 pools (a scale pool beside them) keep the scatter for
    rows and scales alike: a scale pool has no tile to cut."""
    from d9d_tpu.nn.decode_flags import PAGED_SCALE_SUFFIX
    from d9d_tpu.ops.attention.pallas_decode import (
        decode_attention_backend,
        paged_append,
    )

    refs = [module.variable("cache", name, lambda: None) for name in names]
    page, off = _paged_slot(page_table, start, refs[0].value.shape[2])
    with jax.named_scope("cache_append"):
        if (
            decode_attention_backend() == "pallas"
            and not module.has_variable("cache", names[0] + PAGED_SCALE_SUFFIX)
        ):
            pools = paged_append(
                refs[0].value, refs[1].value, page, off, k[:, 0], v[:, 0]
            )
            for ref, pool in zip(refs, pools):
                ref.value = pool
            return pools
        return [
            _paged_scatter_append(module, ref, name, page, off, value)
            for ref, name, value in zip(refs, names, (k, v))
        ]


def _paged_append_latent(module: nn.Module, c, k_rope, start, page_table):
    """A decode step's new latent row ``c [B, 1, r]`` and rotary key row
    ``k_rope [B, 1, d]`` into the pools ``cached_latent`` and
    ``cached_rope_key`` (``[P, ps, .]``); returns the two pools, no
    gathered view. One ``pallas_decode.paged_append`` call that holds
    both in HBM, the pools seen as one kv head (with one token a row the
    token axis of the new rows stands for it): behind XLA's scatter the
    compiler stages a 75.6 MB latent pool in its fast memory and copies
    it back out for the kernel that reads it, a layer a step
    (tests/core/test_chip_compile.py; PERF.md, PR 60)."""
    from d9d_tpu.ops.attention.pallas_decode import paged_append

    refs = [module.variable("cache", name, lambda: None)
            for name in ("cached_latent", "cached_rope_key")]
    page, off = _paged_slot(page_table, start, refs[0].value.shape[1])
    pools = paged_append(
        refs[0].value[:, None], refs[1].value[:, None], page, off, c, k_rope
    )
    for ref, pool in zip(refs, pools):
        ref.value = pool[:, 0]
    return [ref.value for ref in refs]


def _decode_cache_append_heads_major(module: nn.Module, value, name: str,
                                     s_max: int, start, page_table=None):
    """Append ``value [B, T, H, D]`` at cache slot ``start`` of a
    HEADS-MAJOR cache buffer ``[B, H, s_max, D]``.

    GQA decode caches store the flash-decode kernel's streaming layout
    (ops/attention/pallas_decode.py) so the per-step attention never
    relayouts the cache — the write-side transpose touches only the T
    new tokens (T = 1 on decode steps), while a read-side transpose
    would copy all ``s_max`` slots every step. Same capacity contract
    as :func:`_decode_cache_append`.

    With ``page_table [B, n_pages]`` the buffer is a heads-major page
    POOL ``[P, H, page_size, D]``; the one new token scatters to its
    row's (page, offset) and the POOL is returned — the flash-decode
    kernel streams it directly through the gathering block index map
    (no per-step relayout, exactly like the dense layout), and the
    eager fallback gathers a contiguous view via
    :func:`_gather_pages_heads_major`.
    """
    from jax import lax

    b, _, h, d = value.shape
    if page_table is not None:
        ref = module.variable("cache", name, lambda: None)
        page, off = _paged_slot(page_table, start, ref.value.shape[2])
        return _paged_scatter_append(module, ref, name, page, off, value)
    ref = module.variable(
        "cache", name,
        lambda: jnp.zeros((b, h, s_max, d), value.dtype),
    )
    vt = jnp.transpose(value, (0, 2, 1, 3))
    if jnp.ndim(start) == 0:
        ref.value = lax.dynamic_update_slice(
            ref.value, vt, (0, 0, start, 0)
        )
    else:  # per-row [B] write indices (continuous batching)
        ref.value = jax.vmap(
            lambda c, v, s: lax.dynamic_update_slice(c, v, (0, s, 0))
        )(ref.value, vt, start)
    return ref.value


def _cache_row_pad(width: int) -> int:
    """Numbers to add to a cached row of ``width``, so that a row wider
    than the TPU's 128-lane tile ends on a tile's edge: the chip stores
    such a row in whole tiles whatever its logical width (192 numbers
    take 256), and the paged decode kernel, which copies pages out of
    the pools itself, can only cut a pool on those edges. A row of one
    tile or less is stored, and left, as it is."""
    from d9d_tpu.ops.attention.pallas_decode import LANES

    return (-width) % LANES if width > LANES else 0


def _ring_page_table(module: nn.Module, b: int, s_max: int,
                     window_size, kv_shapes, dtype):
    """The page table of a window layer's RING OF PAGES, or None where
    the layer keeps a whole context (no window; ``generate``'s contiguous
    cache; an unpaged serving loop).

    A query at position ``i`` of a window layer reads positions
    ``(i - window, i]`` and nothing older, so the serving loop's paged
    mode (``decode_flags.ring_caches`` around its ``init``) has such a
    layer declare, in place of ``cached_key`` / ``cached_value``, the two
    ``RING_CACHE_LEAVES``: ``[B * ring_pages, H, page_size, D]``, laid
    out as a page pool so the paged decode kernel and the eager gather
    read them as they read one, with row ``b`` the sole owner of pages
    ``b * ring_pages ..``. Logical page ``p`` of a row lives in its ring
    page ``p % ring_pages``: the window and the page being written never
    span more (``pallas_decode.window_pages``), so a page is overwritten
    only once every position in it has left the window. The table is
    that rule written out, ``[B, ceil(s_max / page_size)]``: a constant
    of the program, no leaf, nothing for the host to push. Whatever a
    ring page still holds of an older pass or an earlier request sits at
    logical positions after the row's write index or before its window,
    which the position masks hide, so admission zeroes nothing here, and
    a dead row (write index pinned to 0) scribbles into its own ring:
    no garbage page. Presence of the leaves is the mode flag, as with
    ``page_table``."""
    from d9d_tpu.nn.decode_flags import (
        RING_CACHE_LEAVES,
        note_ring,
        ring_page_size,
    )
    from d9d_tpu.ops.attention.pallas_decode import window_pages

    if window_size is None:
        return None
    page_size = ring_page_size()
    if page_size is not None and module.is_initializing():
        note_ring(window_size)
        pages = b * window_pages(window_size, page_size)
        for name, (heads, width) in zip(RING_CACHE_LEAVES, kv_shapes):
            # put, not declared: the append below declares each leaf
            # once, as it does a pool the serving loop seeded
            module.put_variable(
                "cache", name,
                jnp.zeros((pages, heads, page_size, width), dtype),
            )
    if not module.has_variable("cache", RING_CACHE_LEAVES[0]):
        return None
    ring = module.get_variable("cache", RING_CACHE_LEAVES[0])
    per_row, page_size = ring.shape[0] // b, ring.shape[2]
    logical = jnp.arange(-(-s_max // page_size), dtype=jnp.int32)
    return (
        jnp.arange(b, dtype=jnp.int32)[:, None] * per_row
        + logical[None, :] % per_row
    )


def _gather_pages_heads_major(pool, page_table):
    """Contiguous per-row view of a heads-major page pool:
    ``[P, H, ps, D]`` gathered through ``[B, n]`` →
    ``[B, H, n·ps, D]`` — the eager fallback's (and the parity
    oracle's) bridge back to the dense layout. Slot order is preserved,
    so outputs are bitwise what the dense cache would produce."""
    g = pool[page_table]  # [B, n, H, ps, D]
    b, n, h, ps, d = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, h, n * ps, d)


def _gather_pages_heads_major_quant(pool, scale_pool, page_table, dtype):
    """Quantized sibling of :func:`_gather_pages_heads_major`: gather
    the int8 pool AND its ``[P, H, ps]`` scale pool through the same
    table, widen ``int8 * scale`` per slot, return the dense view in
    the module compute dtype. This is the CPU-tier parity anchor: the
    flash kernel's in-VMEM rescale must match this eager math."""
    g = pool[page_table]            # [B, n, H, ps, D] int8
    gs = scale_pool[page_table]     # [B, n, H, ps] f32
    b, n, h, ps, d = g.shape
    wide = (g.astype(jnp.float32) * gs[..., None]).astype(dtype)
    return wide.transpose(0, 2, 1, 3, 4).reshape(b, h, n * ps, d)


def _check_slot_mask(mask, s_max: int):
    """Shared decode mask contract: 4D broadcastable to
    ``[B, Hq, T, s_max]`` with the key axis indexing CACHE SLOTS
    (loop/generate.py passes ``[B, 1, 1, S_max]`` key-validity for
    left-padded ragged prompts; slot order equals time order per row, so
    causality stays slot-based). 2D/3D token-position masks are rejected
    — their shape can coincide with the slot layout and silently mean the
    wrong thing."""
    if mask is not None and (mask.ndim != 4 or mask.shape[-1] != s_max):
        raise NotImplementedError(
            "decode mode accepts only a 4D [B, Hq, T, decode_max_length] "
            f"cache-slot mask (loop/generate.py's form); got {mask.shape}"
        )


def _decode_slot_mask(start, t: int, s_max: int, window_size, mask):
    """Slot-based causal (+window, +caller) mask for decode attention
    (mask contract: :func:`_check_slot_mask`). ``start`` scalar →
    ``[1, 1, t, s_max]``; per-row ``[B]`` → ``[B, 1, t, s_max]``."""
    _check_slot_mask(mask, s_max)
    if jnp.ndim(start) == 0:
        q_abs = start + jnp.arange(t, dtype=jnp.int32)[:, None]
        k_pos = jnp.arange(s_max, dtype=jnp.int32)[None, :]
        dec_mask = (k_pos <= q_abs)[None, None]  # [1, 1, t, s_max]
        if window_size is not None:
            dec_mask &= (k_pos > q_abs - window_size)[None, None]
    else:
        q_abs = (
            start[:, None, None]
            + jnp.arange(t, dtype=jnp.int32)[None, :, None]
        )  # [B, t, 1]
        k_pos = jnp.arange(s_max, dtype=jnp.int32)[None, None, :]
        dec_mask = (k_pos <= q_abs)[:, None]  # [B, 1, t, s_max]
        if window_size is not None:
            dec_mask &= (k_pos > q_abs - window_size)[:, None]
    if mask is not None:
        dec_mask = dec_mask & mask
    return dec_mask


def _prefill_segments(mask, t: int, s_max: int) -> dict:
    """Segment-id kwargs expressing a slot-validity mask during a prefill
    that attends only the new tokens (left pads get id 0, real tokens 1 —
    real queries then never see pad keys; pad rows' outputs are don't-care
    positions discarded downstream). Only the key-validity FORM
    ``[B, 1, 1, s_max]`` is expressible as segments, so head/query-varying
    masks are rejected rather than silently collapsed."""
    if mask is None:
        return {}
    _check_slot_mask(mask, s_max)
    if mask.shape[1] != 1 or mask.shape[2] != 1:
        raise NotImplementedError(
            "the decode prefill fast path supports only key-validity "
            f"masks [B, 1, 1, s_max]; got {mask.shape}"
        )
    seg = mask[:, 0, 0, :t].astype(jnp.int32)
    return {"q_segments": seg, "kv_segments": seg}


def rotate_leading(u, cos, sin, rot: int, style: RopeStyle):
    """``u [..., D]`` with its first ``rot`` numbers rotated by the first
    ``rot // 2`` frequencies of ``cos`` / ``sin``, the rest passed
    through."""
    cos_r, sin_r = cos[..., : rot // 2], sin[..., : rot // 2]
    if rot == u.shape[-1]:
        return apply_rope(u, cos_r, sin_r, style)
    return jnp.concatenate(
        [apply_rope(u[..., :rot], cos_r, sin_r, style), u[..., rot:]],
        axis=-1,
    )


class _ProjKernel(nn.Module):
    """Declare a Dense-compatible kernel (``<name>/kernel``, shape
    ``[in, features]``, lecun-normal, logical axes) and return it raw —
    lets the fused-QKV path own the matmul while the parameter pytree
    stays identical to three ``nn.Dense`` modules."""

    features: int
    axes: tuple
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, in_features: int) -> Array:
        return self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), self.axes
            ),
            (in_features, self.features),
            self.param_dtype,
        )


class GroupedQueryAttention(nn.Module):
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    sdpa: SdpaBackend
    qk_norm: bool = False
    qk_norm_eps: float = 1e-6
    # zero-centered qk-norm weights (Qwen3-Next style: scale = 1 + w)
    qk_norm_zero_centered: bool = False
    rope_style: RopeStyle = RopeStyle.HALF
    rope_fraction: float = 1.0
    use_sinks: bool = False
    use_output_gate: bool = False
    # the output gate is one logit a query head (``gate_proj`` of width
    # ``h``), broadcast over the head's numbers, not one logit a number
    gate_per_head: bool = False
    window_size: int | None = None
    softmax_scale: float | None = None
    # value heads of their own width (0 = ``head_dim``; never wider): the
    # caches, the decode kernels and ``o_proj`` take it as it is, the
    # training SDPA is given V zero-padded to ``head_dim``
    # (softmax(QKᵀ)·[V|0] = [out|0], as the latent module does)
    v_head_dim: int = 0
    # a constant on the value projection's output
    value_scale: float = 1.0
    # One matmul for q/k/v over a runtime kernel concat (the activation
    # rows stream from HBM once instead of three times; same math, same
    # parameter pytree — q_proj/k_proj/v_proj kernels stay separate for
    # checkpoints/HF/PEFT/plans). Off by default: under tensor parallelism
    # the concat crosses the tp-sharded head dim and XLA must reshard the
    # kernels.
    fused_qkv: bool = False
    # Autoregressive decode mode (loop/generate.py), on when > 0:
    # maintains KV-cache variables in the "cache" collection
    # (cached_key/cached_value of this static length + a write index) and
    # attends new tokens against the cache. 0 keeps the training path
    # byte-identical.
    decode_max_length: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: Array,
        cos: Optional[Array],
        sin: Optional[Array],
        mask: Optional[Array] = None,
    ) -> Array:
        b, t, _ = x.shape
        h, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        dv = self.v_head_dim or d
        if h % hkv != 0:
            raise ValueError(f"num_heads {h} not divisible by num_kv_heads {hkv}")
        if dv > d:
            raise ValueError(f"v_head_dim ({dv}) must not exceed head_dim ({d})")

        def proj(features, name, axes):
            return nn.Dense(
                features,
                use_bias=False,
                name=name,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), axes
                ),
            )

        if self.fused_qkv:
            # enforce the documented TP constraint: the runtime kernel
            # concat crosses the tp-sharded head dim, so XLA would reshard
            # the kernels every step — fail loudly instead of silently
            # regressing (tp in the ambient mesh is how the plans shard
            # HEADS/KV_HEADS)
            from d9d_tpu.core.compat import get_abstract_mesh

            mesh = get_abstract_mesh()
            if mesh is not None and dict(mesh.shape).get("tp", 1) > 1:
                raise ValueError(
                    "fused_qkv=True under a tp>1 mesh would reshard the "
                    "q/k/v kernels every step; use fused_qkv=False with "
                    "tensor parallelism"
                )
            in_f = x.shape[-1]

            def kernel(features, name, axes):
                # identical param path ("<name>/kernel"), shape and init
                # stream as nn.Dense, so checkpoints and plans are
                # indistinguishable from the unfused layout
                return _ProjKernel(
                    features=features, axes=axes,
                    param_dtype=self.param_dtype, name=name,
                )(in_f)

            w = jnp.concatenate(
                [
                    kernel(h * d, "q_proj", (la.EMBED, la.HEADS)),
                    kernel(hkv * d, "k_proj", (la.EMBED, la.KV_HEADS)),
                    kernel(hkv * dv, "v_proj", (la.EMBED, la.KV_HEADS)),
                ],
                axis=-1,
            ).astype(self.dtype)
            qkv = x.astype(self.dtype) @ w
            q = qkv[..., : h * d].reshape(b, t, h, d)
            k = qkv[..., h * d : (h + hkv) * d].reshape(b, t, hkv, d)
            v = qkv[..., (h + hkv) * d :].reshape(b, t, hkv, dv)
        else:
            q = proj(h * d, "q_proj", (la.EMBED, la.HEADS))(x).reshape(b, t, h, d)
            k = proj(hkv * d, "k_proj", (la.EMBED, la.KV_HEADS))(x).reshape(b, t, hkv, d)
            v = proj(hkv * dv, "v_proj", (la.EMBED, la.KV_HEADS))(x).reshape(b, t, hkv, dv)
        if self.value_scale != 1.0:
            v = v * jnp.asarray(self.value_scale, v.dtype)

        if self.qk_norm:
            q = RMSNorm(d, eps=self.qk_norm_eps, name="q_norm",
                        zero_centered=self.qk_norm_zero_centered,
                        param_dtype=self.param_dtype)(q)
            k = RMSNorm(d, eps=self.qk_norm_eps, name="k_norm",
                        zero_centered=self.qk_norm_zero_centered,
                        param_dtype=self.param_dtype)(k)

        # Partial RoPE: rotate the first `rot` dims, pass the rest through.
        # cos/sin must cover >= rot//2 frequencies; for NeoX-style partial
        # rotary semantics the *model* computes frequencies over the rotary
        # dim (not head_dim) and passes them here — this block only slices.
        rot = int(d * self.rope_fraction)
        if rot % 2 != 0:
            raise ValueError(
                f"rotary dim must be even: head_dim={d} * "
                f"rope_fraction={self.rope_fraction} gives {rot}"
            )
        if rot:
            with jax.named_scope("rope"):
                q, k = (
                    rotate_leading(u, cos, sin, rot, self.rope_style)
                    for u in (q, k)
                )

        sinks = None
        if self.use_sinks:
            sinks = self.param(
                "sinks",
                nn.with_logical_partitioning(nn.initializers.zeros, (la.HEADS,)),
                (h,),
                self.param_dtype,
            )

        if self.decode_max_length > 0:
            attn = self._decode_attend(q, k, v, sinks, mask, b, t)
        else:
            attn = self._sdpa_padded(
                q, k, v,
                causal=True,
                softmax_scale=self.softmax_scale,
                window_size=self.window_size,
                sinks=sinks,
                mask=mask,
            )
        # no checkpoint name here: what a rematerialised layer keeps of
        # this call, the flash kernels name themselves on their own
        # residuals (pallas_flash.py _name_kept). A second name on the
        # result out here kept a second copy of it, a pass over it a
        # layer, and never kept the kernel from running again

        out = attn.reshape(b, t, h * dv)
        if self.use_output_gate and self.gate_per_head:
            with jax.named_scope("head_gate"):
                gate = proj(h, "gate_proj", (la.EMBED, la.HEADS))(x)
                out = (attn * nn.sigmoid(gate)[..., None]).reshape(out.shape)
        elif self.use_output_gate:
            gate = proj(h * dv, "gate_proj", (la.EMBED, la.HEADS))(x)
            out = out * nn.sigmoid(gate)
        return proj(self.hidden_size, "o_proj", (la.HEADS, la.EMBED))(out)

    # methods, so that the ops keep ``self_attn._sdpa_padded`` and
    # ``self_attn._decode_attend`` in their scopes (the benchmark's
    # readers find the flash and paged-decode calls by them); the bodies
    # are functions of the module, shared with ``nn/cca.py``
    def _sdpa_padded(self, q, k, v, **kwargs):
        return sdpa_padded(self, q, k, v, **kwargs)

    def _decode_attend(self, q, k, v, sinks, mask, b, t):
        return decode_attend(self, q, k, v, sinks, mask, b, t)


def sdpa_padded(module, q, k, v, **kwargs):
    """The SDPA backend on value heads zero-padded to the query/key
    width, the padding cut off its output: every backend (flash and
    ring included) takes one head width."""
    pad = q.shape[-1] - v.shape[-1]
    if not pad:
        return module.sdpa(q, k, v, **kwargs)
    v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    return module.sdpa(q, k, v, **kwargs)[..., : v.shape[-1] - pad]


def decode_attend(module, q, k, v, sinks, mask, b, t):
    """KV-cache attention: write the new k/v at the cache index, then
    attend against the full static-length cache.

    Per-step attention is cache-bandwidth-bound; on TPU it runs the
    Pallas flash-decode kernel (ops/attention/pallas_decode.py):
    streams each (batch, kv-head) cache slice from HBM exactly once
    with the GQA group as the matmul M dim, skips slots past the
    write index, and never materializes [B,H,T,S] logits — the
    eager oracle remains the fallback (non-TPU, or masks beyond the
    key-validity form) and the parity reference. Cache mechanics +
    capacity/mask contracts: the module-level ``_decode_cache_append``
    / ``_decode_slot_mask`` helpers.
    """
    from d9d_tpu.nn.decode_flags import in_continuation_chunk
    from d9d_tpu.ops.attention.eager import eager_sdpa
    from d9d_tpu.ops.attention.pallas_decode import (
        MAX_DECODE_ROWS,
        decode_attention_backend,
        flash_decode_attention,
    )

    s_max = module.decode_max_length
    idx = _decode_cache_index(module)
    start = idx.value
    _decode_contract_checks(start, t, s_max)
    # a cached key row ends on a lane tile's edge (_cache_row_pad):
    # new keys are zero-padded on their way into a cache and so are
    # the queries that meet cached keys (zeros add nothing to a
    # score; the scale stays the head's own). The prefill fast path
    # below attends the new tokens as they are.
    scale = (
        module.softmax_scale if module.softmax_scale is not None
        else q.shape[-1] ** -0.5
    )
    k_new, q_new = k, q
    pad = _cache_row_pad(q.shape[-1])
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pad)))
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad)))
    page_table = _decode_page_table(module)
    key_leaf, value_leaf = "cached_key", "cached_value"
    ring_table = _ring_page_table(
        module, b, s_max, module.window_size,
        (k.shape[2:], v.shape[2:]), module.dtype,
    )
    if ring_table is not None:
        # a window layer under the paged serving loop: the paged
        # path below on the row's own ring of pages
        from d9d_tpu.nn.decode_flags import RING_CACHE_LEAVES

        page_table, (key_leaf, value_leaf) = ring_table, RING_CACHE_LEAVES
    if page_table is not None:
        # paged serving mode (loop/serve.py): one token per row per
        # step through page pools; the flash path streams the pool
        # through the gathering block index map, the eager oracle
        # gathers a contiguous per-row view
        index = start
        if ring_table is not None and module.is_initializing():
            # the serving loop's shape-only init: the index is
            # still the scalar it seeds per row
            start = jnp.broadcast_to(start, (b,))
        _paged_write_checks(start, t, mask)
        k_pool, v_pool = _paged_append_kv(
            module, k.astype(module.dtype), v.astype(module.dtype),
            (key_leaf, value_leaf), start, page_table,
        )
        idx.value = index + t
        # kv_quant mode (loop/serve.py): the appends above wrote
        # int8 + per-slot scales; both read paths dequantize
        k_scale = v_scale = None
        if module.has_variable("cache", "cached_key_scale"):
            k_scale = module.get_variable("cache", "cached_key_scale")
            v_scale = module.get_variable("cache", "cached_value_scale")
        rows = (module.num_heads // module.num_kv_heads) * t
        if (
            decode_attention_backend() == "pallas"
            and rows <= MAX_DECODE_ROWS
        ):
            return flash_decode_attention(
                q, k_pool, v_pool,
                start=start,
                softmax_scale=scale,
                window_size=module.window_size,
                sinks=sinks,
                page_table=page_table,
                k_scale=k_scale,
                v_scale=v_scale,
            )
        if k_scale is not None:
            keys = _gather_pages_heads_major_quant(
                k_pool, k_scale, page_table, module.dtype
            )
            values = _gather_pages_heads_major_quant(
                v_pool, v_scale, page_table, module.dtype
            )
        else:
            keys = _gather_pages_heads_major(k_pool, page_table)
            values = _gather_pages_heads_major(v_pool, page_table)
        s_virt = keys.shape[2]
        return eager_sdpa(
            q,
            jnp.transpose(keys, (0, 2, 1, 3)),
            jnp.transpose(values, (0, 2, 1, 3)),
            causal=False,
            softmax_scale=scale,
            sinks=sinks,
            mask=_decode_slot_mask(
                start, t, s_virt, module.window_size, None
            ),
        )
    # heads-major [B, Hkv, s_max, D]: the flash-decode kernel's
    # streaming layout, written in place (no per-step cache relayout)
    keys = _decode_cache_append_heads_major(
        module, k.astype(module.dtype), "cached_key", s_max, start
    )
    values = _decode_cache_append_heads_major(
        module, v.astype(module.dtype), "cached_value", s_max, start
    )
    idx.value = start + t
    if t > 1 and not in_continuation_chunk():
        # PREFILL fast path: attend the new tokens against themselves
        # through the training SDPA (flash on TPU) — the eager slot
        # path would materialize [t, s_max] logits, which explodes
        # for long prompts. Valid only when the cache was empty
        # (start == 0), which is exactly how loop/generate.py issues
        # its first (or only) multi-token call; start is traced, so
        # the contract is asserted via checkify
        # (_decode_contract_checks) and enforced statically by
        # generate(). Continuation prefill chunks (chunked prefill,
        # loop/generate.py prefill_chunk_size) fall through to the
        # slot-cache path below, which is valid at any cache index.
        return module._sdpa_padded(
            q_new, k_new, v,
            causal=True,
            softmax_scale=module.softmax_scale,
            window_size=module.window_size,
            sinks=sinks,
            **_prefill_segments(mask, t, s_max),
        )
    key_validity_mask = mask is None or (
        mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1
    )
    rows = (module.num_heads // module.num_kv_heads) * t
    if (
        decode_attention_backend() == "pallas"
        and key_validity_mask
        and rows <= MAX_DECODE_ROWS
    ):
        _check_slot_mask(mask, s_max)
        return flash_decode_attention(
            q, keys, values,
            start=start,
            softmax_scale=scale,
            window_size=module.window_size,
            sinks=sinks,
            kv_valid=None if mask is None else mask[:, 0, 0, :],
        )
    return eager_sdpa(
        q,
        jnp.transpose(keys, (0, 2, 1, 3)),
        jnp.transpose(values, (0, 2, 1, 3)),
        causal=False,
        softmax_scale=scale,
        sinks=sinks,
        mask=_decode_slot_mask(start, t, s_max, module.window_size, mask),
    )


def _decompress_kv(c, k_rope, w, num_heads: int, d_nope: int, dtype):
    """Expand MLA latents through kv_up: ``c [B,S,r]`` + shared rotated
    rope key ``k_rope [B,S,d_rope]`` → ``(k [B,S,H,d_nope+d_rope],
    v [B,S,H,d_v])`` with the single-head rope key broadcast to every
    head (MQA-style). One definition for the prefill/training body and
    the decompressed-decode oracle so their layouts cannot drift."""
    b, s = c.shape[:2]
    kv = (c.astype(dtype) @ w.astype(dtype)).reshape(b, s, num_heads, -1)
    k_nope, v = kv[..., :d_nope], kv[..., d_nope:]
    d_rope = k_rope.shape[-1]
    k = jnp.concatenate(
        [
            k_nope,
            jnp.broadcast_to(
                k_rope[:, :, None, :], (b, s, num_heads, d_rope)
            ).astype(k_nope.dtype),
        ],
        axis=-1,
    )
    return k, v


class LowRankProjection(nn.Module):
    """down-proj → RMSNorm → up-proj (reference
    d9d/module/block/attention/multi_head_latent.py:11)."""

    bottleneck: int
    features: int
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        def proj(features, name, axes):
            return nn.Dense(
                features,
                use_bias=False,
                name=name,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), axes
                ),
            )

        # HLO metadata only: which half of MLA's query bottleneck (its one
        # user) a traced op belongs to
        with jax.named_scope("mla/q_down"):
            x = proj(self.bottleneck, "down_proj", (la.EMBED, None))(x)
            x = RMSNorm(self.bottleneck, eps=self.norm_eps, name="norm",
                        param_dtype=self.param_dtype)(x)
        with jax.named_scope("mla/q_up"):
            return proj(self.features, "up_proj", (None, la.HEADS))(x)


class MultiHeadLatentAttention(nn.Module):
    """DeepSeek-V2 MLA (reference multi_head_latent.py:46).

    Q through an optional low-rank bottleneck; K/V through a shared latent
    compression whose up-projection yields per-head content (no-RoPE) keys
    and values; a decoupled single-head RoPE sub-vector is broadcast to all
    heads (MQA-style). V is zero-padded to the qk head dim so any SDPA
    backend (flash/ring included) can run it, then un-padded.
    """

    hidden_size: int
    num_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    sdpa: SdpaBackend
    q_lora_rank: int | None = None
    norm_eps: float = 1e-6
    rope_style: RopeStyle = RopeStyle.HALF
    # None -> d_qk**-0.5; DeepSeek yarn checkpoints fold an mscale
    # temperature into the attention scale (models/deepseek presets)
    softmax_scale: float | None = None
    # Latent-cache decode mode when > 0 (MLA's inference advantage: the
    # cache holds kv_lora_rank + qk_rope_head_dim floats per token — the
    # compressed latent plus the shared rotated rope key — instead of
    # num_heads*(d_nope+d_v)). Single-token steps run the ABSORBED form
    # (kv_up folded into the query/output sides, attention in rank space
    # — no per-step decompression); prefill (t > 1) decompresses once.
    decode_max_length: int = 0
    # False: single-token steps instead decompress EVERY cache slot
    # through kv_up and attend over the slot cache — the cost the
    # absorbed trick avoids. Kept as the absorbed form's correctness
    # oracle and the honest half of an A/B (timing a t=2 prefill on a
    # warm cache measures neither).
    decode_absorbed: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: Array,
        cos: Array,
        sin: Array,
        mask: Optional[Array] = None,
    ) -> Array:
        b, t, _ = x.shape
        h = self.num_heads
        d_nope, d_rope = self.qk_nope_head_dim, self.qk_rope_head_dim
        d_qk = d_nope + d_rope
        d_v = self.v_head_dim
        scale = (
            self.softmax_scale if self.softmax_scale is not None
            else d_qk**-0.5
        )
        if d_v > d_qk:
            raise ValueError(
                f"v_head_dim ({d_v}) must not exceed qk head dim ({d_qk})"
            )

        def proj(features, name, axes):
            return nn.Dense(
                features,
                use_bias=False,
                name=name,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), axes
                ),
            )

        # --- Q (direct or low-rank) ---
        if self.q_lora_rank is not None:
            q = LowRankProjection(
                bottleneck=self.q_lora_rank,
                features=h * d_qk,
                norm_eps=self.norm_eps,
                name="q_proj",
                dtype=self.dtype,
                param_dtype=self.param_dtype,
            )(x)
        else:
            with jax.named_scope("mla/q_up"):
                q = proj(h * d_qk, "q_proj", (la.EMBED, la.HEADS))(x)
        q = q.reshape(b, t, h, d_qk)
        q_nope, q_rope = q[..., :d_nope], q[..., d_nope:]
        q_rope = apply_rope(q_rope, cos[..., : d_rope // 2],
                            sin[..., : d_rope // 2], self.rope_style)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)

        # --- KV latent + decoupled shared rope key ---
        with jax.named_scope("mla/kv_down"):
            kv = proj(
                self.kv_lora_rank + d_rope, "kv_down_proj", (la.EMBED, None)
            )(x)
            c_kv = kv[..., : self.kv_lora_rank]
            k_rope = kv[..., self.kv_lora_rank:]
            c_kv = RMSNorm(
                self.kv_lora_rank, eps=self.norm_eps, name="kv_down_norm",
                param_dtype=self.param_dtype,
            )(c_kv)
        # rotate the shared rope key at ITS OWN positions before any
        # caching (write-time rope, like the KV cache's rotated keys)
        k_rope = apply_rope(
            k_rope[:, :, None, :], cos[..., : d_rope // 2],
            sin[..., : d_rope // 2], self.rope_style,
        )[:, :, 0, :]

        # kernel declared raw (same "kv_up_proj/kernel" param path and init
        # as the nn.Dense it replaces — checkpoints/mappers/plans are
        # unchanged) so the absorbed decode path below can fold it into
        # the query/output sides instead of decompressing the cache
        kv_up_w = _ProjKernel(
            features=h * (d_nope + d_v), axes=(None, la.HEADS),
            param_dtype=self.param_dtype, name="kv_up_proj",
        )(self.kv_lora_rank)

        decode = self.decode_max_length > 0
        prefill_segs = {}
        if decode:
            s_max = self.decode_max_length
            idx = _decode_cache_index(self)
            start = idx.value
            _decode_contract_checks(start, t, s_max)
            page_table = _decode_page_table(self)
            through_table = False
            if page_table is not None:
                # paged serving mode: the latent/rope-key pools scatter
                # the one new token. Under the ``pallas`` decode backend
                # the absorbed attend reads the pools through the page
                # table, live pages only; every other case is handed the
                # gathered per-row view, on which both decode paths
                # below run unchanged (masks are built over the gathered
                # length)
                _paged_write_checks(start, t, mask)
                through_table = _latent_reads_through_table(self)
                _note_latent_decode(self, through_table)
            # zeros that fill a cached rotary key row to a lane tile, for
            # the pool the paged kernel cuts pages from; they never meet a
            # query elsewhere
            rope_pad = _rope_key_pad(self, d_rope)

            def new_rope_row():
                row = k_rope.astype(self.dtype)
                if rope_pad:
                    row = jnp.pad(row, ((0, 0), (0, 0), (0, rope_pad)))
                return row

            # the write of the new token and, where the view is
            # gathered, the gather: where a step reads its cache rows
            # from HBM
            with jax.named_scope("mla/cache_append"):
                new_c = c_kv.astype(self.dtype)
                if through_table:
                    cached_c, cached_r = _paged_append_latent(
                        self, new_c, new_rope_row(), start, page_table
                    )
                else:
                    cached_c = _decode_cache_append(
                        self, new_c, "cached_latent", s_max, start,
                        page_table=page_table,
                    )
                    cached_r = _decode_cache_append(
                        self, new_rope_row(), "cached_rope_key", s_max,
                        start, page_table=page_table,
                    )
                    if rope_pad:
                        cached_r = cached_r[..., :d_rope]
            idx.value = start + t
            from d9d_tpu.nn.decode_flags import in_continuation_chunk

            if t == 1 or in_continuation_chunk():
                # (through the table a row sees the positions up to its
                # own: no mask over a view that was never gathered)
                dec_mask = None if through_table else _decode_slot_mask(
                    start, t, cached_c.shape[1], None, mask
                )
                if t == 1 and self.decode_absorbed:
                    # ABSORBED form (DeepSeek-V2 decode trick): fold
                    # W_up^K into the query and W_up^V into the output —
                    # q_nope^T (W_k c) == (W_k^T q_nope)^T c — so
                    # attention runs in rank space against the latent
                    # cache directly, with no per-step decompression of
                    # s_max slots
                    out = self._absorbed_attend(
                        q_nope, q_rope, cached_c, cached_r, kv_up_w,
                        dec_mask, d_qk, d_nope, d_v,
                        paged=(page_table, start) if through_table else None,
                    )
                else:
                    # decompressed slot attention: the single-step
                    # oracle (decode_absorbed=False) and the
                    # continuation-prefill-chunk path — a chunk
                    # amortizes the one full-cache decompression over
                    # its t tokens (the vLLM-style MLA chunk recipe)
                    out = self._decompressed_attend(
                        q, cached_c, cached_r, kv_up_w, dec_mask,
                        d_qk, d_nope,
                    )
                return proj(self.hidden_size, "o_proj",
                            (la.HEADS, la.EMBED))(out.reshape(b, t, h * d_v))
            # prefill (t > 1): decompress only the NEW tokens and attend
            # them causally through the training SDPA — valid for the
            # first call (start == 0), which is how loop/generate.py
            # issues its first (or only) multi-token call (contract at
            # GroupedQueryAttention._decode_attend; continuation chunks
            # took the slot path above)
            prefill_segs = _prefill_segments(mask, t, s_max)
        with jax.named_scope("mla/decompress"):
            k, v = _decompress_kv(
                c_kv, k_rope, kv_up_w, h, d_nope, self.dtype
            )

        # pad V: softmax(QKᵀ)·[V|0] = [out|0] (reference :199-207)
        pad = d_qk - d_v
        if pad > 0:
            v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))

        if decode:  # t > 1 prefill over just the new tokens
            out = self.sdpa(
                q, k, v, causal=True, softmax_scale=scale,
                **prefill_segs,
            )
        else:
            out = self.sdpa(
                q, k, v, causal=True, softmax_scale=scale, mask=mask
            )
        if pad > 0:
            out = out[..., :d_v]
        out = out.reshape(b, t, h * d_v)
        return proj(self.hidden_size, "o_proj", (la.HEADS, la.EMBED))(out)

    def _decompressed_attend(self, q, c, k_rope, w, dec_mask,
                             d_qk, d_nope):
        """Non-absorbed decode: decompress every cache slot through kv_up
        each step (O(s_max·r·h·(d_nope+d_v)) per token — the traffic the
        absorbed form avoids) and attend over the slot cache. Serves as
        the absorbed path's correctness oracle.
        """
        from d9d_tpu.ops.attention.eager import eager_sdpa

        with jax.named_scope("mla/decompress"):
            k, v = _decompress_kv(
                c, k_rope, w, self.num_heads, d_nope, self.dtype
            )
        scale = (
            self.softmax_scale if self.softmax_scale is not None
            else d_qk**-0.5
        )
        return eager_sdpa(
            q, k, v, causal=False, softmax_scale=scale, mask=dec_mask
        )

    def _absorbed_attend(self, q_nope, q_rope, c, k_rope, w, dec_mask,
                         d_qk, d_nope, d_v, paged=None):
        """Rank-space attention against the latent cache (fp32).

        scores = (W_k^T q_nope)^T c + q_rope^T k_rope; the value side
        stays latent until one final fold through W_v. Per step this
        costs O(t·h·r·(d_nope+d_v)) absorption + O(t·h·s·r) attention
        instead of decompressing all s_max slots through kv_up.

        ``c`` and ``k_rope`` are each row's cache ``[B, S, .]`` under
        ``dec_mask``, or with ``paged = (page_table, start)`` the page
        pools ``[P, page_size, .]``, attended through the table by
        ``pallas_decode.latent_decode_attention`` (one Pallas call under
        the same scope; no mask: a row sees positions up to its own).
        """
        h = self.num_heads
        r = self.kv_lora_rank
        scale = (
            self.softmax_scale if self.softmax_scale is not None
            else d_qk**-0.5
        )
        # the three scopes are what a trace tells the stages apart by
        # (benchmarks/metrics/kernel.mla_decode_roofline)
        with jax.named_scope("mla/absorb_q"):
            wk = w.astype(jnp.float32).reshape(r, h, d_nope + d_v)
            wv = wk[..., d_nope:]
            wk = wk[..., :d_nope]
            qn = q_nope.astype(jnp.float32)
            qr = q_rope.astype(jnp.float32)
            q_abs = jnp.einsum("bthd,rhd->bthr", qn, wk)
        with jax.named_scope("mla/latent_attend"):
            if paged is not None:
                from d9d_tpu.ops.attention.pallas_decode import (
                    latent_decode_attention,
                )

                page_table, start = paged
                out_lat = latent_decode_attention(
                    q_abs, qr, c, k_rope, start=start,
                    page_table=page_table, softmax_scale=scale,
                )
            else:
                out_lat = latent_attend(q_abs, qr, c, k_rope, dec_mask, scale)
        with jax.named_scope("mla/fold_v"):
            out = jnp.einsum("bthr,rhd->bthd", out_lat, wv)
            return out.astype(self.dtype)


def latent_attend(q_abs, q_rope, c, k_rope, dec_mask, scale):
    """The absorbed attend on each row's whole cache: float32 queries
    ``q_abs [B, T, H, r]`` and ``q_rope [B, T, H, d_rope]`` against
    ``c [B, S, r]`` and ``k_rope [B, S, d_rope]`` under ``dec_mask`` →
    the weighted sum of the latent rows ``[B, T, H, r]``. The reference
    the paged kernel's latent configuration is tested against."""
    cf = c.astype(jnp.float32)
    rf = k_rope.astype(jnp.float32)
    scores = (
        jnp.einsum("bthr,bsr->bhts", q_abs, cf)
        + jnp.einsum("bthd,bsd->bhts", q_rope, rf)
    ) * scale
    neg_big = jnp.asarray(-1e30, scores.dtype)
    scores = jnp.where(dec_mask, scores, neg_big)
    # finite mask sentinel (not -inf): a fully-masked row must
    # produce zeros like eager_sdpa's guarded softmax, not NaN
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(
        jnp.any(dec_mask, axis=-1, keepdims=True), p, 0.0
    )
    return jnp.einsum("bhts,bsr->bthr", p, cf)


# A paged latent layer's last traced decode step, by the module's path:
# whether it read its pools through the page table
_LATENT_DECODE_PATHS: dict[tuple, bool] = {}


def _latent_kernel_wanted(module: "MultiHeadLatentAttention") -> bool:
    """The absorbed form under the ``pallas`` decode backend: the switch
    the GQA layers' paged append and attend follow."""
    from d9d_tpu.ops.attention.pallas_decode import decode_attention_backend

    return module.decode_absorbed and decode_attention_backend() == "pallas"


def _rope_key_pad(module: "MultiHeadLatentAttention", d_rope: int) -> int:
    """Numbers added to a cached rotary key row. Mosaic cannot cut a page
    out of a pool whose rows are narrower than a 128-lane tile (the chip
    stores 64 numbers in a tile of 128 anyway; the compiler for a
    described v5e refuses the slice, PERF.md, PR 60), so where the paged
    kernel will read the pool, the serving loop's paged shape-only init
    (``decode_flags.ring_caches``) under :func:`_latent_kernel_wanted`,
    the leaf is declared a whole tile wide and every later step follows
    the leaf it is handed. ``generate``'s contiguous cache, the ``eager``
    backend and the oracle keep the rows as they are."""
    from d9d_tpu.nn.decode_flags import ring_page_size
    from d9d_tpu.ops.attention.pallas_decode import LANES

    if module.has_variable("cache", "cached_rope_key"):
        leaf = module.get_variable("cache", "cached_rope_key")
        return leaf.shape[-1] - d_rope
    if ring_page_size() is not None and _latent_kernel_wanted(module):
        return (-d_rope) % LANES
    return 0


def _latent_reads_through_table(module: "MultiHeadLatentAttention") -> bool:
    """Whether a paged single-token step of ``module`` attends its pools
    through the page table (``pallas_decode.latent_decode_attention``) or
    is handed the gathered view: the first under
    :func:`_latent_kernel_wanted`, over pools in the module's dtype (an
    int8 pool's scales have no reader there) whose rotary key rows are
    whole lane tiles (:func:`_rope_key_pad`: a pool seeded under another
    backend is not). From what the step sees."""
    from d9d_tpu.nn.decode_flags import PAGED_SCALE_SUFFIX
    from d9d_tpu.ops.attention.pallas_decode import LANES

    return (
        _latent_kernel_wanted(module)
        and not module.has_variable(
            "cache", "cached_latent" + PAGED_SCALE_SUFFIX)
        and module.get_variable(
            "cache", "cached_rope_key").shape[-1] % LANES == 0
    )


def _note_latent_decode(module: nn.Module, through_table: bool) -> None:
    """Record which way a paged latent layer's step was traced, as gauges
    of the process's telemetry registry: ``decode/latent/paged_layers``
    (layers whose last traced step reads the pools through the page
    table) and ``decode/latent/gathered_layers`` (layers handed the
    gathered view). The path is chosen where the step is traced, from
    static facts, so it is a count of layers and not of requests, as
    ``pallas_flash.py _note_grid`` notes its grids."""
    from d9d_tpu.telemetry import get_telemetry

    _LATENT_DECODE_PATHS[module.path] = through_table
    paged = sum(_LATENT_DECODE_PATHS.values())
    tele = get_telemetry()
    tele.gauge("decode/latent/paged_layers").set(paged)
    tele.gauge("decode/latent/gathered_layers").set(
        len(_LATENT_DECODE_PATHS) - paged
    )
