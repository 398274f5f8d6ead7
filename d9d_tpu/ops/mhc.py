"""The n-stream residual path's passes over the stream, one Pallas call each.

A call takes the stream as ``[B, n, T, C]``, the streams before the
tokens: a tile of tokens is then ``n`` slabs ``[tile, C]`` with nothing
padded, and the model's ``[B, T, n, C]`` becomes it by a transpose that
the compiler makes a change of layout and no copy (it keeps the stream
as ``{3,1,2,0}``; given ``[B T, n C]`` it kept the four rows a token as a
tile of their own and copied the stream twice at every layer's edge:
PERF.md section 6, PR 50). A call holds a tile of tokens' ``n C`` numbers
in VMEM and does everything its pass needs of them, so the stream
crosses HBM once a pass in its own dtype and no float32 array of its
width exists:

- :func:`read`: the float32 square sum, the coefficients' product (the
  operands in the maps' dtype, float32 accumulation), ``H_pre`` and the
  sublayer's input ``sum_j H_pre[j] x[j]`` (a row of ``C`` float32);
- :func:`write`: ``H_res x + H_post^T out``, ``n (n + 1)`` multiply-adds
  an element in float32, rounded once;
- their transposes, given (``jax.custom_vjp``): one call each, the
  residuals the calls' own inputs and two per-token float32 arrays.

What a token carries beside its rows (the ``n n + 2 n`` projections and
``1 / rms``; ``H_res`` and ``H_post``; their cotangents) crosses a call's
boundary as one float32 row of 128 lanes a token, tokens on the sublanes:
a kernel reads a coefficient as a column and broadcasts it along the
lanes of the rows it scales. ``T`` is padded to a tile inside. Off the
TPU the calls run interpreted.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from d9d_tpu.core.types import Array

LANES = 128
# A tile: this many tokens' rows at a time, worked on this many rows x at
# most this many columns at once (the float32 working set of the
# element-wise passes; the matrix products take the tile's rows whole).
# Swept on the chip at the Xing4.0 cell's shapes (PERF.md section 6, PR 50):
# 128 or 256 tokens read the same; chunks of 896 columns beat 512 and 256 in
# the read (0.53 / 0.61 / 0.90 ms) and equal them in the other three; 64
# rows beat 32 and 16 in the read (0.50 / 0.54 / 0.62) and in the update's
# transpose (1.28 / 1.35 / 1.34)
_TILE_TOKENS = 256
_BLOCK_ROWS = 64
_CHUNK_COLUMNS = 1024
_VMEM_LIMIT = 96 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def phi_rows(n: int) -> int:
    """Rows of the transposed maps a call takes: the ``n n + 2 n``
    projections, padded to whole sublane tiles of either dtype."""
    return _round_up(n * n + 2 * n, 16)


def _chunk(c: int) -> int:
    """Columns worked on at once: the widest divisor of ``C`` within
    ``_CHUNK_COLUMNS`` that is whole lane tiles, all of a ``C`` that has
    none."""
    return max(
        (w for w in range(LANES, min(c, _CHUNK_COLUMNS) + 1, LANES)
         if c % w == 0),
        default=c,
    )


def _cols(start, width: int):
    if width % LANES == 0 and not isinstance(start, int):
        start = pl.multiple_of(start, LANES)
    return pl.ds(start, width)


def _rows(r, rb: int):
    return pl.ds(pl.multiple_of(r * rb, rb), rb)


def _fold(p: Array) -> Array:
    """``[rows, w]`` → the sums of its lane tiles ``[rows, 128]`` (VPU
    adds), or its row sums ``[rows, 1]`` where ``w`` is not whole tiles."""
    w = p.shape[1]
    if w % LANES:
        return jnp.sum(p, axis=1, keepdims=True)
    return functools.reduce(
        jnp.add, (p[:, k:k + LANES] for k in range(0, w, LANES))
    )


def _fold_width(cw: int) -> int:
    return LANES if cw % LANES == 0 else 1


def _columns_to_lanes(columns, rows: int) -> Array:
    """``[rows, 1]`` columns → ``[rows, 128]`` with column ``k`` on lane
    ``k`` and zeros beyond."""
    lane = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    out = jnp.zeros((rows, LANES), jnp.float32)
    for k, column in enumerate(columns):
        out = jnp.where(lane == k, column, out)
    return out


def _precision(dtype):
    # float32 operands take every pass of the MXU: the plain form's
    # ``highest``
    return lax.Precision.HIGHEST if jnp.dtype(dtype).itemsize >= 4 else None


# -- read: norm, coefficients' product, H_pre, the input mix --------------------


def _read_kernel(ab_ref, x_ref, phi_ref, u_ref, small_ref, h_ref, *,
                 n, c, cw, rb, norm_eps):
    tt, k = x_ref.shape[1], n * n + 2 * n
    r_phi, md = phi_ref.shape[0], phi_ref.dtype
    chunks = c // cw

    def product(j, q, carry):
        raw, squares = carry
        xs = x_ref[j, :, _cols(q * cw, cw)]
        raw = raw + lax.dot_general(
            xs.astype(md), phi_ref[:, _cols(j * c + q * cw, cw)], _NT,
            preferred_element_type=jnp.float32, precision=_precision(md),
        )
        wide = xs.astype(jnp.float32)
        return raw, squares + _fold(wide * wide)

    carry = (jnp.zeros((tt, r_phi), jnp.float32),
             jnp.zeros((tt, _fold_width(cw)), jnp.float32))
    for j in range(n):
        carry = lax.fori_loop(
            0, chunks, functools.partial(product, j), carry)
    raw, squares = carry
    mean = jnp.sum(squares, axis=1, keepdims=True) / (n * c)
    inv_rms = lax.rsqrt(mean + norm_eps)  # [tt, 1]
    proj = jnp.concatenate(
        [raw * inv_rms, jnp.zeros((tt, LANES - r_phi), jnp.float32)], axis=1
    )
    lane = lax.broadcasted_iota(jnp.int32, (tt, LANES), 1)
    small_ref[...] = jnp.where(lane == k, inv_rms, proj)
    h_ref[...] = jax.nn.sigmoid(ab_ref[0:1, :] * proj + ab_ref[1:2, :])

    def mix(r, _):
        rows = _rows(r, rb)
        h = h_ref[rows, :]
        h_pre = [h[:, j:j + 1] for j in range(n)]

        def chunk(q, _):
            cols = _cols(q * cw, cw)
            acc = sum(
                h_pre[j] * x_ref[j, rows, cols].astype(jnp.float32)
                for j in range(n)
            )
            u_ref[rows, cols] = acc.astype(u_ref.dtype)
            return 0

        return lax.fori_loop(0, chunks, chunk, 0)

    lax.fori_loop(0, tt // rb, mix, 0)


def _read_bwd_kernel(ab_ref, x_ref, du_ref, small_ref, dsmall_ref, phi_ref,
                     dx_ref, dphi_ref, dpre_ref, h_ref, *, n, c, cw, rb):
    tt, k = x_ref.shape[1], n * n + 2 * n
    r_phi, md = phi_ref.shape[0], phi_ref.dtype
    chunks = c // cw

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)

    # d H_pre[j] = sum_c d_u[c] x[j, c], a row block at a time
    def pre(r, _):
        rows = _rows(r, rb)

        def chunk(q, parts):
            cols = _cols(q * cw, cw)
            d = du_ref[rows, cols].astype(jnp.float32)
            return tuple(
                part + _fold(d * x_ref[j, rows, cols].astype(jnp.float32))
                for j, part in enumerate(parts)
            )

        parts = lax.fori_loop(
            0, chunks, chunk,
            (jnp.zeros((rb, _fold_width(cw)), jnp.float32),) * n,
        )
        h_ref[rows, :] = _columns_to_lanes(
            [jnp.sum(p, axis=1, keepdims=True) for p in parts], rb)
        return 0

    lax.fori_loop(0, tt // rb, pre, 0)

    # the token's own small numbers, the whole tile at once
    lane = lax.broadcasted_iota(jnp.int32, (tt, LANES), 1)
    small = small_ref[...]
    inv_rms = small[:, k:k + 1]
    proj = jnp.where(lane < k, small, 0.0)
    a = ab_ref[0:1, :]
    h = jax.nn.sigmoid(a * proj + ab_ref[1:2, :])
    d_logit = jnp.where(lane < n, h_ref[...] * h * (1.0 - h), 0.0)
    dpre_ref[...] = d_logit
    d_proj = jnp.where(lane < k, dsmall_ref[...], 0.0) + a * d_logit
    d_raw = inv_rms * d_proj
    # through 1 / rms: d x += -(inv_rms^3 / (n C)) (sum_k d_proj_k raw_k) x,
    # and raw = proj / inv_rms
    through_norm = -(inv_rms * inv_rms) * jnp.sum(
        d_proj * proj, axis=1, keepdims=True) / (n * c)
    h_ref[...] = jnp.where(lane == n, through_norm, h)
    d_raw_rows = d_raw[:, :r_phi].astype(md)  # [tt, R]
    d_raw_columns = d_raw.T[:r_phi].astype(md)  # [R, tt]

    for j in range(n):
        h_pre = h_ref[:, j:j + 1]
        scale = h_ref[:, n:n + 1]

        def chunk(q, _, j=j, h_pre=h_pre, scale=scale):
            cols, of_phi = _cols(q * cw, cw), _cols(j * c + q * cw, cw)
            xs = x_ref[j, :, cols]
            dphi_ref[:, of_phi] += jnp.dot(
                d_raw_columns, xs.astype(md),
                preferred_element_type=jnp.float32, precision=_precision(md),
            )
            through_product = jnp.dot(
                d_raw_rows, phi_ref[:, of_phi],
                preferred_element_type=jnp.float32, precision=_precision(md),
            )
            dx_ref[j, :, cols] = (
                h_pre * du_ref[:, cols].astype(jnp.float32)
                + through_product + scale * xs.astype(jnp.float32)
            ).astype(dx_ref.dtype)
            return 0

        lax.fori_loop(0, chunks, chunk, 0)


# -- write: H_res x + H_post^T out ----------------------------------------------


def _write_kernel(x_ref, out_ref, h_ref, new_ref, *, n, c, cw, rb):
    tt = x_ref.shape[1]

    def block(r, _):
        rows = _rows(r, rb)
        h = h_ref[rows, :]
        coef = [h[:, m:m + 1] for m in range(n * n + n)]

        def chunk(q, _):
            cols = _cols(q * cw, cw)
            xs = [x_ref[j, rows, cols].astype(jnp.float32) for j in range(n)]
            wide = out_ref[rows, cols].astype(jnp.float32)
            for i in range(n):
                acc = sum(coef[i * n + j] * xs[j] for j in range(n))
                acc = acc + coef[n * n + i] * wide
                new_ref[i, rows, cols] = acc.astype(new_ref.dtype)
            return 0

        return lax.fori_loop(0, c // cw, chunk, 0)

    lax.fori_loop(0, tt // rb, block, 0)


def _write_bwd_kernel(g_ref, x_ref, out_ref, h_ref, dx_ref, dout_ref, dh_ref,
                      *, n, c, cw, rb):
    tt = x_ref.shape[1]

    def block(r, _):
        rows = _rows(r, rb)
        h = h_ref[rows, :]
        coef = [h[:, m:m + 1] for m in range(n * n + n)]

        def chunk(q, parts):
            cols = _cols(q * cw, cw)
            g = [g_ref[i, rows, cols].astype(jnp.float32) for i in range(n)]
            xs = [x_ref[j, rows, cols].astype(jnp.float32) for j in range(n)]
            wide = out_ref[rows, cols].astype(jnp.float32)
            for j in range(n):
                dx_ref[j, rows, cols] = sum(
                    coef[i * n + j] * g[i] for i in range(n)
                ).astype(dx_ref.dtype)
            dout_ref[rows, cols] = sum(
                coef[n * n + i] * g[i] for i in range(n)
            ).astype(dout_ref.dtype)
            products = [g[i] * xs[j] for i in range(n) for j in range(n)]
            products += [g[i] * wide for i in range(n)]
            return tuple(
                part + _fold(p) for part, p in zip(parts, products)
            )

        parts = lax.fori_loop(
            0, c // cw, chunk,
            (jnp.zeros((rb, _fold_width(cw)), jnp.float32),) * (n * n + n),
        )
        dh_ref[rows, :] = _columns_to_lanes(
            [jnp.sum(p, axis=1, keepdims=True) for p in parts], rb)
        return 0

    lax.fori_loop(0, tt // rb, block, 0)


# -- the calls -----------------------------------------------------------------


def _tile(tokens: int) -> int:
    return min(_TILE_TOKENS, _round_up(tokens, _BLOCK_ROWS))


def _pad_tokens(a: Array, tile: int, axis: int) -> Array:
    pad = -a.shape[axis] % tile
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _call(kernel, name, *, grid, in_specs, out_specs, out_shape, scratch=(),
          accumulates=False):
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "arbitrary" if accumulates else "parallel",) * 2,
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=jax.default_backend() != "tpu",
        name=name,
    )


def _streams(n: int, tile: int, c: int):
    """A tile of the stream ``[B, n, T, C]``: every stream's rows."""
    return pl.BlockSpec((None, n, tile, c), lambda b, t: (b, 0, t, 0))


def _rows_of(tile: int, width: int):
    """A tile of a per-token array ``[B, T, width]``."""
    return pl.BlockSpec((None, tile, width), lambda b, t: (b, t, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda b, t: (0, 0))


_sds = jax.ShapeDtypeStruct


def _scalars(a_pre: Array, b_pre: Array) -> Array:
    """``a_pre`` on every lane of row 0, ``b_pre`` on the first lanes of
    row 1: what a kernel adds and multiplies a token's lanes by."""
    rows = jnp.stack([
        jnp.full((LANES,), a_pre, jnp.float32),
        jnp.pad(b_pre.astype(jnp.float32), (0, LANES - b_pre.shape[0])),
    ])
    return jnp.pad(rows, ((0, 8 - rows.shape[0]), (0, 0)))  # a sublane tile


def _static(x: Array):
    """``(batch, n, tokens, C, tile, kernel parameters)`` of a stream."""
    batch, n, tokens, c = x.shape
    return batch, n, tokens, c, _tile(tokens), dict(
        n=n, c=c, cw=_chunk(c), rb=_BLOCK_ROWS)


# d9d-lint: disable=D9D001 — always traced inside a tracked step program (a TrackedJit cannot be called under a trace); the jit makes the twelve sublayers share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("norm_eps",))
def _read_call(x, phi_t, a_pre, b_pre, *, norm_eps):
    batch, n, tokens, c, tile, sizes = _static(x)
    x = _pad_tokens(x, tile, 2)
    padded = x.shape[2]
    u, small = _call(
        functools.partial(_read_kernel, norm_eps=norm_eps, **sizes),
        "mhc_read",
        grid=(batch, padded // tile),
        in_specs=[_whole((8, LANES)), _streams(n, tile, c),
                  _whole(phi_t.shape)],
        out_specs=[_rows_of(tile, c), _rows_of(tile, LANES)],
        out_shape=[_sds((batch, padded, c), jnp.float32),
                   _sds((batch, padded, LANES), jnp.float32)],
        scratch=[pltpu.VMEM((tile, LANES), jnp.float32)],
    )(_scalars(a_pre, b_pre), x, phi_t)
    return u[:, :tokens], small[:, :tokens]


# d9d-lint: disable=D9D001 — always traced inside a tracked step program (a TrackedJit cannot be called under a trace); the jit makes the twelve sublayers share one trace and one lowering
@jax.jit
def _read_bwd_call(x, phi_t, a_pre, b_pre, small, du, dsmall):
    batch, n, tokens, c, tile, sizes = _static(x)
    x = _pad_tokens(x, tile, 2)
    du, small, dsmall = (_pad_tokens(a, tile, 1) for a in (du, small, dsmall))
    padded = x.shape[2]
    dx, dphi, dpre = _call(
        functools.partial(_read_bwd_kernel, **sizes),
        "mhc_read_bwd",
        grid=(batch, padded // tile),
        in_specs=[_whole((8, LANES)), _streams(n, tile, c),
                  _rows_of(tile, c), _rows_of(tile, LANES),
                  _rows_of(tile, LANES), _whole(phi_t.shape)],
        out_specs=[_streams(n, tile, c), _whole(phi_t.shape),
                   _rows_of(tile, LANES)],
        out_shape=[_sds(x.shape, x.dtype), _sds(phi_t.shape, jnp.float32),
                   _sds((batch, padded, LANES), jnp.float32)],
        scratch=[pltpu.VMEM((tile, LANES), jnp.float32)],
        accumulates=True,
    )(_scalars(a_pre, b_pre), x, du, small, dsmall, phi_t)
    return dx[:, :, :tokens], dphi, dpre[:, :tokens]


# d9d-lint: disable=D9D001 — always traced inside a tracked step program (a TrackedJit cannot be called under a trace); the jit makes the twelve sublayers share one trace and one lowering
@jax.jit
def _write_call(x, out, h):
    batch, n, tokens, c, tile, sizes = _static(x)
    x = _pad_tokens(x, tile, 2)
    out, h = (_pad_tokens(a, tile, 1) for a in (out, h))
    new = _call(
        functools.partial(_write_kernel, **sizes),
        "mhc_write",
        grid=(batch, x.shape[2] // tile),
        in_specs=[_streams(n, tile, c), _rows_of(tile, c),
                  _rows_of(tile, LANES)],
        out_specs=_streams(n, tile, c),
        out_shape=_sds(x.shape, x.dtype),
    )(x, out, h)
    return new[:, :, :tokens]


# d9d-lint: disable=D9D001 — always traced inside a tracked step program (a TrackedJit cannot be called under a trace); the jit makes the twelve sublayers share one trace and one lowering
@jax.jit
def _write_bwd_call(g, x, out, h):
    batch, n, tokens, c, tile, sizes = _static(x)
    g, x = (_pad_tokens(a, tile, 2) for a in (g, x))
    out, h = (_pad_tokens(a, tile, 1) for a in (out, h))
    padded = x.shape[2]
    dx, dout, dh = _call(
        functools.partial(_write_bwd_kernel, **sizes),
        "mhc_write_bwd",
        grid=(batch, padded // tile),
        in_specs=[_streams(n, tile, c), _streams(n, tile, c),
                  _rows_of(tile, c), _rows_of(tile, LANES)],
        out_specs=[_streams(n, tile, c), _rows_of(tile, c),
                   _rows_of(tile, LANES)],
        out_shape=[_sds(x.shape, x.dtype), _sds(out.shape, out.dtype),
                   _sds((batch, padded, LANES), jnp.float32)],
    )(g, x, out, h)
    return dx[:, :, :tokens], dout[:, :tokens], dh[:, :tokens]


# -- the differentiable operations ---------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def read(x: Array, phi_t: Array, a_pre: Array, b_pre: Array,
         norm_eps: float) -> tuple[Array, Array]:
    """``x [B, n, T, C]``, the maps transposed ``[phi_rows(n), n C]`` (rows
    ``pre | post | res``, zeros below) → the sublayer's input ``[B, T, C]``
    and the projections ``[B, T, n n + 2 n]``, both float32: a token's
    ``(x phi) / rms(x)``, the square sum and the product's accumulation
    float32, the product's operands in ``phi_t``'s dtype. The input is
    left float32 for its reader to round: rounded here, in bf16, it cost
    the Xing4.0 cell 0.0006 of ``logits_rel_rms`` (0.01095 against the
    plain form's 0.01039, whose rounding the compiler had fused away;
    0.00993 with this; PERF.md section 6, PR 50)."""
    return _read_fwd(x, phi_t, a_pre, b_pre, norm_eps)[0]


def _read_fwd(x, phi_t, a_pre, b_pre, norm_eps):
    n = x.shape[1]
    u, small = _read_call(x, phi_t, a_pre, b_pre, norm_eps=norm_eps)
    return (u, small[..., :n * n + 2 * n]), (x, phi_t, a_pre, b_pre, small)


def _read_bwd(norm_eps, residuals, cotangents):
    x, phi_t, a_pre, b_pre, small = residuals
    du, dproj = cotangents
    n = x.shape[1]
    dx, dphi, dpre = _read_bwd_call(
        x, phi_t, a_pre, b_pre, small, du.astype(x.dtype), _to_lanes(dproj))
    d_logit = dpre[..., :n]  # d (a_pre proj_pre + b_pre), a token a row
    da = jnp.sum(d_logit * small[..., :n]).astype(a_pre.dtype)
    db = jnp.sum(d_logit, axis=(0, 1)).astype(b_pre.dtype)
    return dx, dphi.astype(phi_t.dtype), da, db


read.defvjp(_read_fwd, _read_bwd)


@jax.custom_vjp
def write(x: Array, out: Array, h: Array) -> Array:
    """``x [B, n, T, C]``, the sublayer's output ``[B, T, C]``, the token's
    coefficients ``[B, T, n n + n]`` float32 (``H_res`` row by row, then
    ``H_post``) → the next stream ``H_res x + H_post^T out`` in ``x``'s
    dtype, float32 sums rounded once."""
    return _write_call(x, out, _to_lanes(h))


def _to_lanes(h: Array) -> Array:
    return jnp.pad(
        h.astype(jnp.float32), ((0, 0), (0, 0), (0, LANES - h.shape[-1])))


def _write_fwd(x, out, h):
    return write(x, out, h), (x, out, h)


def _write_bwd(residuals, g):
    x, out, h = residuals
    dx, dout, dh = _write_bwd_call(g.astype(x.dtype), x, out, _to_lanes(h))
    return dx, dout, dh[..., :h.shape[-1]].astype(h.dtype)


write.defvjp(_write_fwd, _write_bwd)
