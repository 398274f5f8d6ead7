"""Module-level paged-cache parity (nn/attention.py paged write/gather
against the dense slot caches): a paged cache whose gathered view
equals the dense cache must produce BITWISE-identical decode outputs —
for GQA (heads-major pools) and MLA (latent/rope-key pools, absorbed
and decompressed forms) — and the paged-mode contracts must fail
loudly. Quantized pools (int8 + sibling scale leaves, ``kv_quant``)
are parity-checked with a drift bound instead: int8 KV is lossy by
design, but the flash kernel's in-VMEM dequant and the eager gather's
dequant must agree with each other almost exactly. The serving-loop
integration is pinned in tests/loop/test_serve_paged.py; this file
isolates the module layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

from d9d_tpu.nn.attention import (
    GroupedQueryAttention,
    MultiHeadLatentAttention,
)
from d9d_tpu.nn.decode_flags import (
    PAGE_TABLE_LEAF,
    PAGED_CACHE_LEAVES,
    PAGED_SCALE_SUFFIX,
)
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.ops.rope import compute_rope_frequencies, make_rope_cos_sin

B, DML, PS = 2, 16, 4


def _rope(b, start, t, d_rope):
    inv, scale = compute_rope_frequencies(d_rope, 10000.0)
    pos = jnp.broadcast_to(jnp.arange(start, start + t), (b, t))
    return make_rope_cos_sin(pos, inv, scale)


def _paged_cache(dense_cache, quant=False):
    """Convert a (zeroed) dense cache dict into pools + page tables —
    identity page assignment, exactly what loop/serve.py seeds; with
    ``quant`` the pools are int8 and sibling f32 scale pools ride next
    to them (the ``kv_quant="int8"`` layout)."""
    n_pages = DML // PS
    pool_n = B * n_pages + 1
    pt = np.zeros((B, n_pages), np.int32)
    nxt = 1
    for bi in range(B):
        for pi in range(n_pages):
            pt[bi, pi] = nxt
            nxt += 1
    out = {}
    for p, leaf in flatten_dict(dense_cache).items():
        name = p[-1]
        if name == "cache_index":
            out[p] = jnp.zeros((B,), jnp.int32)
        elif name in PAGED_CACHE_LEAVES:
            axis = PAGED_CACHE_LEAVES[name]
            shape = (
                (pool_n,) + leaf.shape[1:axis] + (PS,)
                + leaf.shape[axis + 1:]
            )
            if quant:
                out[p] = jnp.zeros(shape, jnp.int8)
                out[p[:-1] + (name + PAGED_SCALE_SUFFIX,)] = jnp.zeros(
                    shape[:-1], jnp.float32
                )
            else:
                out[p] = jnp.zeros(shape, leaf.dtype)
            out[p[:-1] + (PAGE_TABLE_LEAF,)] = jnp.asarray(pt)
        else:
            out[p] = leaf
    return unflatten_dict(out)


def _per_row_cache(dense_cache):
    out = {}
    for p, leaf in flatten_dict(dense_cache).items():
        out[p] = (
            jnp.zeros((B,), jnp.int32) if p[-1] == "cache_index" else leaf
        )
    return unflatten_dict(out)


def _drive(blk, params, cache, d_rope, steps=6, dim=None):
    dim = dim if dim is not None else blk.hidden_size
    outs = []
    for i in range(steps):
        x = jax.random.normal(jax.random.PRNGKey(100 + i), (B, 1, dim))
        cos, sin = _rope(B, i, 1, d_rope)
        o, st = blk.apply(
            {"params": params, "cache": cache}, x, cos, sin,
            mutable=["cache"],
        )
        cache = st["cache"]
        outs.append(o)
    return jnp.concatenate(outs, axis=1)


def test_gqa_paged_bitwise_matches_dense():
    blk = GroupedQueryAttention(
        hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=8,
        sdpa=eager_sdpa, dtype=jnp.float32, decode_max_length=DML,
        use_sinks=True, window_size=6,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, 32))
    cos, sin = _rope(B, 0, 1, 8)
    variables = jax.jit(blk.init)(jax.random.PRNGKey(1), x, cos, sin)
    zero = jax.tree.map(jnp.zeros_like, variables["cache"])
    want = _drive(blk, variables["params"], _per_row_cache(zero), 8)
    got = _drive(blk, variables["params"], _paged_cache(zero), 8)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("absorbed", [True, False])
def test_mla_paged_bitwise_matches_dense(absorbed):
    blk = MultiHeadLatentAttention(
        hidden_size=64, num_heads=4, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, kv_lora_rank=32,
        sdpa=eager_sdpa, dtype=jnp.float32, decode_max_length=DML,
        decode_absorbed=absorbed,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, 64))
    cos, sin = _rope(B, 0, 1, 8)
    variables = jax.jit(blk.init)(jax.random.PRNGKey(1), x, cos, sin)
    zero = jax.tree.map(jnp.zeros_like, variables["cache"])
    want = _drive(blk, variables["params"], _per_row_cache(zero), 8)
    got = _drive(blk, variables["params"], _paged_cache(zero), 8)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_gqa_paged_quant_drift_bounded(monkeypatch):
    """Int8 paged KV vs the dense f32 cache: lossy but bounded — and
    the pallas kernel's in-VMEM dequant must agree with the eager
    gather's dequant almost exactly (same int8*scale widening, only
    accumulation order differs)."""
    blk = GroupedQueryAttention(
        hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=8,
        sdpa=eager_sdpa, dtype=jnp.float32, decode_max_length=DML,
        use_sinks=True, window_size=6,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, 32))
    cos, sin = _rope(B, 0, 1, 8)
    variables = jax.jit(blk.init)(jax.random.PRNGKey(1), x, cos, sin)
    zero = jax.tree.map(jnp.zeros_like, variables["cache"])
    want = _drive(blk, variables["params"], _per_row_cache(zero), 8)
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "eager")
    got_eager = _drive(
        blk, variables["params"], _paged_cache(zero, quant=True), 8
    )
    # int8 per-slot-per-head scales keep attention outputs close to the
    # full-precision reference; the bound is loose on purpose (lossy)
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(got_eager), atol=0.05, rtol=0.05
    )
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "pallas")
    got_flash = _drive(
        blk, variables["params"], _paged_cache(zero, quant=True), 8
    )
    # kernel dequant vs eager dequant: the SAME quantized bytes widen
    # through both paths — near-bitwise, not drift-bounded
    np.testing.assert_allclose(
        np.asarray(got_eager), np.asarray(got_flash), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("absorbed", [True, False])
def test_mla_paged_quant_drift_bounded(absorbed):
    blk = MultiHeadLatentAttention(
        hidden_size=64, num_heads=4, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, kv_lora_rank=32,
        sdpa=eager_sdpa, dtype=jnp.float32, decode_max_length=DML,
        decode_absorbed=absorbed,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, 64))
    cos, sin = _rope(B, 0, 1, 8)
    variables = jax.jit(blk.init)(jax.random.PRNGKey(1), x, cos, sin)
    zero = jax.tree.map(jnp.zeros_like, variables["cache"])
    want = _drive(blk, variables["params"], _per_row_cache(zero), 8)
    got = _drive(
        blk, variables["params"], _paged_cache(zero, quant=True), 8
    )
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(got), atol=0.05, rtol=0.05
    )


def test_paged_quant_pools_stay_int8():
    """The write path must keep quantized pools int8 (a silent f32
    resurrection would double the bytes and void the audit census) and
    actually land scales for written slots."""
    blk = GroupedQueryAttention(
        hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=8,
        sdpa=eager_sdpa, dtype=jnp.float32, decode_max_length=DML,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, 32))
    cos, sin = _rope(B, 0, 1, 8)
    variables = jax.jit(blk.init)(jax.random.PRNGKey(1), x, cos, sin)
    cache = _paged_cache(
        jax.tree.map(jnp.zeros_like, variables["cache"]), quant=True
    )
    _, st = blk.apply(
        {"params": variables["params"], "cache": cache}, x, cos, sin,
        mutable=["cache"],
    )
    flat = flatten_dict(st["cache"])
    for p, leaf in flat.items():
        if p[-1] in PAGED_CACHE_LEAVES:
            assert leaf.dtype == jnp.int8, p
        if p[-1].endswith(PAGED_SCALE_SUFFIX):
            assert leaf.dtype == jnp.float32, p
            assert np.abs(np.asarray(leaf)).max() > 0.0, p


def test_paged_contracts_fail_loudly():
    blk = GroupedQueryAttention(
        hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=8,
        sdpa=eager_sdpa, dtype=jnp.float32, decode_max_length=DML,
    )
    x1 = jax.random.normal(jax.random.PRNGKey(0), (B, 1, 32))
    cos, sin = _rope(B, 0, 1, 8)
    variables = jax.jit(blk.init)(jax.random.PRNGKey(1), x1, cos, sin)
    paged = _paged_cache(jax.tree.map(jnp.zeros_like, variables["cache"]))
    # multi-token calls never reach a paged cache (the serving loop
    # teacher-forces prompts token-by-token)
    x3 = jax.random.normal(jax.random.PRNGKey(2), (B, 3, 32))
    cos3, sin3 = _rope(B, 0, 3, 8)
    with pytest.raises(NotImplementedError, match="single-token"):
        blk.apply(
            {"params": variables["params"], "cache": paged},
            x3, cos3, sin3, mutable=["cache"],
        )
    # slot masks don't compose with paging
    with pytest.raises(NotImplementedError, match="slot mask"):
        blk.apply(
            {"params": variables["params"], "cache": paged},
            x1, cos, sin, mask=jnp.ones((B, 1, 1, DML), bool),
            mutable=["cache"],
        )