"""Trace-time decode-phase flags and the cache write-index traversal.

Chunked prefill (loop/generate.py ``prefill_chunk_size``) feeds a long
prompt through the decode cache in bounded pieces. Whether a multi-token
call is the FIRST chunk (empty cache — the flash prefill fast path
applies) or a CONTINUATION (the new tokens must attend the slot cache)
is static knowledge the caller has and the attention module needs, but
the cache write index is traced — so the fact travels as a trace-time
context flag, not data. ``generate()`` wraps continuation-chunk calls in
:func:`continuation_chunk`; attention modules read
:func:`in_continuation_chunk` while tracing (chunk calls are unrolled,
each traced under its own flag value).
"""

import contextlib
import contextvars

_continuation = contextvars.ContextVar(
    "d9d_tpu_decode_continuation", default=False
)


@contextlib.contextmanager
def continuation_chunk():
    """Mark model calls in this block as continuation prefill chunks:
    multi-token decode-mode calls attend the slot cache (valid at any
    cache index) instead of taking the empty-cache prefill fast path."""
    token = _continuation.set(True)
    try:
        yield
    finally:
        _continuation.reset(token)


def in_continuation_chunk() -> bool:
    return _continuation.get()


_bounds_held = contextvars.ContextVar(
    "d9d_tpu_decode_bounds_held", default=False
)


@contextlib.contextmanager
def caller_holds_bounds():
    """Mark model calls in this block as made by a caller that enforces
    the decode contracts itself, on the host, before it dispatches (the
    serving loop: ``submit()`` refuses a request that would pass
    ``decode_max_length`` and a step takes one token): the attention
    modules then trace no ``checkify.debug_check``. The check is a
    no-op in plain jit, but its ``ErrorEffect`` stays on the jaxpr, and
    a compiled program with an unordered effect gets no C++ dispatch
    from jax: every call flattens, shards and wraps its arguments in
    Python (12 to 14 us a leaf on the chip's host; PERF.md, PR 37)."""
    token = _bounds_held.set(True)
    try:
        yield
    finally:
        _bounds_held.reset(token)


def bounds_held_by_caller() -> bool:
    return _bounds_held.get()


def map_cache_index(cache, fn):
    """Apply ``fn`` to every decode write-index leaf of a cache pytree.

    The ONE place that encodes how those leaves are identified
    (``path[-1] == "cache_index"`` — the name ``_decode_cache_index``
    declares in every attention module), so the serving loop's per-row
    seeding/pinning and speculative decoding's rewind can't drift from
    each other or from a future leaf rename. Trace-safe: pure pytree
    surgery, callable inside jit.
    """
    from flax.traverse_util import flatten_dict, unflatten_dict

    flat = flatten_dict(cache)
    for path in list(flat):
        if path[-1] == "cache_index":
            flat[path] = fn(flat[path])
    return unflatten_dict(flat)


# -- paged KV cache plumbing (docs/design/generation.md) ----------------

# cache leaves that hold per-token sequence content and can be paged:
# leaf name → axis that indexes cache slots in the DENSE layout. The
# serving loop's paged mode converts exactly these into page pools
# ([num_pages, ..., page_size, ...] with the slot axis shrunk to
# page_size and a leading page axis) and seeds a sibling ``page_table``
# leaf; attention modules detect that sibling and indirect through it.
PAGED_CACHE_LEAVES = {
    "cached_key": 2,       # GQA heads-major [B, Hkv, S, D]
    "cached_value": 2,
    "cached_latent": 1,    # MLA [B, S, r]
    "cached_rope_key": 1,  # MLA [B, S, d_rope]
}

PAGE_TABLE_LEAF = "page_table"

# quantized-KV sibling leaves (docs/design/generation.md "Low-precision
# serving"): when a pool leaf is stored int8, a second pool named
# ``<leaf>_scale`` rides next to it holding the per-(page, slot[, head])
# dequantization scales — just more paged cache leaves sharing the SAME
# page table, so the allocator, prefix cache and continuation handoff
# treat value pages and scale pages identically. Attention modules
# detect quantization by the presence of the sibling scale leaf.
PAGED_SCALE_SUFFIX = "_scale"
PAGED_SCALE_LEAVES = {
    name + PAGED_SCALE_SUFFIX: axis
    for name, axis in PAGED_CACHE_LEAVES.items()
}

# -- window layers' rings of pages (nn/attention.py _ring_page_table) ---

# the second cache kind of the paged serving loop: the key and value
# leaves of an attention layer that reads a window of positions only,
# ``[B * ring_pages, Hkv, page_size, D]``. Each row owns its ring, the
# page table is a constant of the program, and what a layer holds does
# not grow with the context.
RING_CACHE_LEAVES = ("ring_key", "ring_value")

_rings = contextvars.ContextVar("d9d_tpu_decode_rings", default=None)


@contextlib.contextmanager
def ring_caches(page_size: int):
    """Have a model's ``init`` in this block declare its window layers'
    caches as rings of ``page_size``-position pages: the paged serving
    loop's shape-only init. The leaves that result are the mode flag for
    every later ``apply``, which needs no context. Yields the list the
    layers that did so append their windows to, in call order: the
    serving loop counts the positions such layers read from it."""
    windows: list[int] = []
    token = _rings.set((int(page_size), windows))
    try:
        yield windows
    finally:
        _rings.reset(token)


def ring_page_size():
    rings = _rings.get()
    return rings[0] if rings else None


def note_ring(window_size: int) -> None:
    """A window layer declared its ring (inside :func:`ring_caches`)."""
    _rings.get()[1].append(int(window_size))


def window_leaves(cache) -> dict:
    """``{path: leaf}`` of the window layers' ring leaves. Beside
    :func:`recurrent_leaves`, the other half of the rule that keeps the
    prefix cache off: a ring has dropped the positions a shared prefix
    page would stand for, so a hit could not give them back."""
    from flax.traverse_util import flatten_dict

    return {
        path: leaf for path, leaf in flatten_dict(cache).items()
        if path[-1] in RING_CACHE_LEAVES
    }


# leaves of a paged cache that do not lead with the batch dimension:
# pools shared by all rows, the tables the host allocator writes, and
# the rows' rings of pages
_SHARED_LEAVES = (
    set(PAGED_CACHE_LEAVES) | set(PAGED_SCALE_LEAVES) | {PAGE_TABLE_LEAF}
    | set(RING_CACHE_LEAVES)
)


def recurrent_leaves(cache) -> dict:
    """``{path: leaf}`` of the per-row cache leaves that hold neither
    pageable sequence content nor a write index: recurrent state that
    summarizes a row's whole prefix (GDN ``delta_state``, Mamba
    ``ssm_state``, the short convolutions' ``conv_tail``). The ONE
    statement of the rule: such a leaf cannot be rebuilt from shared KV
    pages, so ``ContinuousBatcher`` keeps its prefix cache off for a
    model that has one, and it cannot roll back past rejected
    proposals, so ``speculative_generate`` refuses the model. Works on
    arrays and on ``jax.eval_shape`` shapes alike."""
    from flax.traverse_util import flatten_dict

    return {
        path: leaf for path, leaf in flatten_dict(cache).items()
        if path[-1] not in _SHARED_LEAVES and path[-1] != "cache_index"
    }


def map_page_table(cache, fn):
    """Apply ``fn`` to every ``page_table`` leaf of a cache pytree (the
    paged counterpart of :func:`map_cache_index`; the serving loop uses
    it to write the host allocator's table mirror into every leaf inside
    the fused chunk, and to pin dead rows' tables to the garbage page
    in-device). No-op on unpaged caches."""
    from flax.traverse_util import flatten_dict, unflatten_dict

    flat = flatten_dict(cache)
    hit = False
    for path in list(flat):
        if path[-1] == PAGE_TABLE_LEAF:
            flat[path] = fn(flat[path])
            hit = True
    return unflatten_dict(flat) if hit else cache


def per_row_leaves(cache) -> dict:
    """``{path: leaf}`` of the cache leaves that lead with the batch
    dimension: everything but a paged cache's pools, tables and rings of
    pages. A pool shares its name with the dense leaf it replaces, and
    is told from it by the ``page_table`` the serving loop seeds beside
    it, as the attention modules tell them. Works on arrays and on
    ``jax.eval_shape`` shapes alike."""
    from flax.traverse_util import flatten_dict

    flat = flatten_dict(cache)

    def shared(path) -> bool:
        name = path[-1]
        if name == PAGE_TABLE_LEAF or name in RING_CACHE_LEAVES:
            return True
        pooled = name in PAGED_CACHE_LEAVES or name in PAGED_SCALE_LEAVES
        return pooled and path[:-1] + (PAGE_TABLE_LEAF,) in flat

    return {path: x for path, x in flat.items() if not shared(path)}


def zero_rows(cache, row_mask):
    """Zero the ``row_mask``-selected batch rows of every per-row cache
    leaf (:func:`per_row_leaves`: pools are shared across rows, admitted
    rows' table rows are written by the host allocator, and what a ring
    still holds is behind the position masks). The one reset of the
    serving loop, paged or not; trace-safe.

    The device pays for the rows it clears, not for the state: the
    mask's set indices and their count are taken on the device
    (``[B]``-sized work), and a loop of that many trips writes one zero
    row into each leaf in place, the leaves as its carry, so donated
    buffers pass through it without a copy. A ``[B]``-sized leaf (a
    ``cache_index``) is masked, which costs nothing. The ops stand
    under the scope ``serve/reset_rows``."""
    import jax
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict, unflatten_dict

    flat = flatten_dict(cache)
    per_row = per_row_leaves(cache)
    wide = [path for path, x in per_row.items() if x.ndim > 1]
    with jax.named_scope("serve/reset_rows"):
        for path, x in per_row.items():
            if x.ndim == 1:
                flat[path] = jnp.where(row_mask, jnp.zeros_like(x), x)
        if wide:
            (rows,) = jnp.nonzero(row_mask, size=row_mask.shape[0])

            def clear(i, leaves):
                return [
                    jax.lax.dynamic_update_slice_in_dim(
                        x, jnp.zeros((1,) + x.shape[1:], x.dtype),
                        rows[i], axis=0,
                    )
                    for x in leaves
                ]

            flat.update(zip(wide, jax.lax.fori_loop(
                0, row_mask.sum(dtype=jnp.int32), clear,
                [flat[path] for path in wide],
            )))
    return unflatten_dict(flat)
