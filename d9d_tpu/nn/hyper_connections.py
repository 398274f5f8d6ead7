"""Manifold-constrained hyper-connections: an n-stream residual path.

mHC (arXiv:2512.24880) on hyper-connections (arXiv:2409.19606). The
residual stream is ``n`` rows of width ``C`` a token, ``x_l`` in
``R^{n x C}``. Around one sublayer ``F`` (attention, or the dense or
expert feed-forward):

    x'      = RMSNorm(vec(x_l))                 over all n C numbers, no gain
    H~pre   = a_pre  (x' phi_pre)  + b_pre      phi_pre,  phi_post: [n C, n]
    H~post  = a_post (x' phi_post) + b_post
    H~res   = a_res mat(x' phi_res) + b_res     phi_res: [n C, n n]
    H_pre   = sigmoid(H~pre)
    H_post  = 2 sigmoid(H~post)
    H_res   = Sinkhorn(exp(clamp(H~res)))       rows to sum 1, then columns,
                                                ``eps`` in each denominator
    x_{l+1} = H_res x_l + H_post^T F(norm(H_pre x_l))

``H_res`` is (nearly) doubly stochastic: mixing the streams neither grows
nor shrinks their sum. The stream starts as ``n`` copies of the embedding
and is read out by the sum over the streams (:func:`expand_streams`,
:func:`sum_streams`).

The stream's passes are Pallas calls (``ops/mhc.py``), one a pass, each
holding a tile of tokens' ``n C`` numbers in VMEM, so the stream crosses
HBM once a pass in the model's dtype and no float32 array of its width
exists. :meth:`HyperConnection.read` is one call: the norm's float32
square sum, the ``n n + 2 n`` projections (one product, its operands in
the module's dtype, float32 accumulation, the norm's ``rsqrt`` applied to
its result), ``H_pre`` and the sublayer's input. :meth:`HyperConnection
.write` is one call: ``n (n + 1)`` float32 multiply-adds an element,
rounded once. Their transposes are given, one call each: the update's
reads the next stream's cotangent, ``x`` and the sublayer's output and
gives ``H_res^T d``, the output's cotangent and the per-token sums that
are ``d H_res`` and ``d H_post``; the read's gives ``d x``, the maps'
gradient summed over the token tiles and ``d H_pre``'s logits. The two
cotangents of ``x`` are summed by autodiff, one more pass. Under a mesh
of several devices the calls run in a ``shard_map`` over the batch and
sequence axes (a Mosaic call cannot be partitioned by the compiler), the
maps whole, their gradients summed over those axes.

What stays float32 XLA: ``H_post``, ``H_res`` and the Sinkhorn rounds, on
the ``n n + 2 n`` numbers a token (0.8 MB at the Xing4.0 cell's sizes: not
the stream's bytes), with the tokens on the minor axis (``[n, n, B, T]``:
sixteen numbers a token would waste a vector register's tile each). The
ops carry ``mhc/{coef,sinkhorn,pre,post}`` scopes (the read's call, which
fuses the norm, the product and the mix, ``mhc/pre``; the update's
``mhc/post``) and the stream's two ends ``mhc/{expand,readout}``: a trace
tells the stream's passes from the sublayers'. The path holds no state, so
decode mode runs it as it stands (a one-token step is a padded tile).

Initialisers (the papers give none this repo could copy; the benchmark's
configuration file lists them as assumed): ``phi_*`` normal at
``(n C) ** -0.5``, so a projection of the unit-RMS ``x'`` is of order 1;
``a_* = 0.5``; ``b_pre = b_post = 0`` (``H_pre`` about a half,
``H_post`` about 1); ``b_res = 2 I`` (about 0.7 of a stream stays in
it). Every term is live at seeded init: the coefficients depend on the
token from the first step on.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from d9d_tpu.core.compat import get_abstract_mesh
from d9d_tpu.core.mesh import AXIS_CP_SHARD, AXIS_DP_REPLICATE, AXIS_DP_SHARD
from d9d_tpu.core.types import Array
from d9d_tpu.nn import logical_axes as la
from d9d_tpu.ops import mhc

A_INIT = 0.5
B_RES_DIAGONAL = 2.0


def expand_streams(x: Array, streams: int) -> Array:
    """``[B, T, C]`` → ``[B, T, n, C]``: the stream starts as n copies."""
    with jax.named_scope("mhc/expand"):
        return jnp.broadcast_to(
            x[:, :, None, :], (*x.shape[:2], streams, x.shape[-1])
        )


def sum_streams(x: Array) -> Array:
    """``[B, T, n, C]`` → ``[B, T, C]``: read out by the sum, float32 sums."""
    with jax.named_scope("mhc/readout"):
        return x.astype(jnp.float32).sum(axis=2).astype(x.dtype)


# Sinkhorn rounds a trip of the loop: unrolled whole, the 20 rounds of a
# step's 36 passes (12 sublayers forward, rematerialised and backward) made
# the step program 140 s to compile and 8.1 GB of temporaries; in a scan 89 s
# and 7.4 GB (described-v5e compiles, PR 35)
SINKHORN_UNROLL = 4


def sinkhorn(log_weights: Array, iters: int, eps: float) -> Array:
    """``[n, n, ...]`` → the same shape, rows then columns brought to sum
    1, ``iters`` times, from ``exp(log_weights)``."""

    def one_round(m, _):
        m = m / (m.sum(axis=1, keepdims=True) + eps)
        m = m / (m.sum(axis=0, keepdims=True) + eps)
        return m, None

    m, _ = jax.lax.scan(
        one_round, jnp.exp(log_weights), None, length=iters,
        unroll=min(SINKHORN_UNROLL, iters),
    )
    return m


def _axes_dividing(mesh, axes: tuple[str, ...], size: int) -> tuple[str, ...]:
    """Those of ``axes`` the mesh gives several devices, while their
    product still divides ``size``."""
    kept, product = [], 1
    for axis in axes:
        devices = mesh.shape.get(axis, 1)
        if devices > 1 and size % (product * devices) == 0:
            kept.append(axis)
            product *= devices
    return tuple(kept)


def _streams_first(x: Array) -> Array:
    """``[B, T, n, C]`` ↔ ``[B, n, T, C]``, what the calls take: the
    compiler keeps the stream in the layout that makes this no copy."""
    return jnp.swapaxes(x, 1, 2)


def _over_tokens(fn, per_token: tuple[Array, ...], whole: tuple[Array, ...]):
    """``fn(*per_token, *whole)`` on arrays that start ``[B, T]``, its
    results starting so too. A one-token step's batch goes as the tokens
    of one row (a tile is of tokens). Under a mesh of
    several devices each shard of the batch and sequence axes runs ``fn``
    on its own tokens (a Mosaic kernel cannot be partitioned by the
    compiler: ``ops/attention/pallas_flash.py _shard_over_mesh``), ``whole``
    whole on every device and its cotangents summed over those axes."""
    count = len(per_token)

    def shard(*args):
        b, t = args[0].shape[:2]
        if t == 1:
            args = [
                a.reshape(1, b, *a.shape[2:]) if i < count else a
                for i, a in enumerate(args)
            ]
        return tuple(o.reshape(b, t, *o.shape[2:]) for o in fn(*args))

    mesh = get_abstract_mesh()
    if mesh.size <= 1:
        return shard(*per_token, *whole)
    b, t = per_token[0].shape[:2]
    tokens = P(
        _axes_dividing(mesh, (AXIS_DP_REPLICATE, AXIS_DP_SHARD), b) or None,
        _axes_dividing(mesh, (AXIS_CP_SHARD,), t) or None,
    )
    return jax.shard_map(
        shard, mesh=mesh,
        in_specs=(tokens,) * count + (P(),) * len(whole),
        out_specs=tokens, check_vma=False,
    )(*per_token, *whole)


class HyperConnection(nn.Module):
    """The n-stream path around one sublayer: :meth:`read` gives the
    sublayer's ``[B, T, C]`` input and the mixing coefficients,
    :meth:`write` the next stream from the sublayer's output."""

    hidden_size: int
    streams: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    res_clamp: tuple[float, float] = (-30.0, 30.0)
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        n, width = self.streams, self.streams * self.hidden_size

        def phi(name: str, columns: int):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=width ** -0.5),
                    (la.EMBED, None),
                ),
                (width, columns),
                self.param_dtype,
            )

        def small(name: str, init, shape):
            # float32 like a norm's gain: a handful of numbers that scale
            # or shift every token's coefficients
            return self.param(
                name, nn.with_logical_partitioning(init, (None,) * len(shape)),
                shape, jnp.float32,
            )

        self.phi_pre = phi("phi_pre", n)
        self.phi_post = phi("phi_post", n)
        self.phi_res = phi("phi_res", n * n)
        constant = nn.initializers.constant
        self.a_pre = small("a_pre", constant(A_INIT), ())
        self.a_post = small("a_post", constant(A_INIT), ())
        self.a_res = small("a_res", constant(A_INIT), ())
        self.b_pre = small("b_pre", nn.initializers.zeros, (n,))
        self.b_post = small("b_post", nn.initializers.zeros, (n,))
        self.b_res = small(
            "b_res",
            lambda key, shape, dtype: B_RES_DIAGONAL * jnp.eye(n, dtype=dtype),
            (n, n),
        )

    def read(self, x: Array) -> tuple[Array, tuple[Array, Array]]:
        """``x [B, T, n, C]`` → the sublayer's input ``H_pre x [B, T, C]``
        and what :meth:`write` needs: ``H_post [n, B, T]`` and ``H_res
        [n, n, B, T]``, float32."""
        b, t, n, _ = x.shape
        with jax.named_scope("mhc/coef"):
            phi_t = jnp.concatenate(
                [self.phi_pre, self.phi_post, self.phi_res], axis=1
            ).astype(self.dtype).T
            phi_t = jnp.pad(
                phi_t, ((0, mhc.phi_rows(n) - phi_t.shape[0]), (0, 0)))
        # one call: the norm's square sum, the coefficients' product,
        # H_pre and the input mix (``mhc/norm``, ``mhc/coef``, ``mhc/pre``)
        with jax.named_scope("mhc/pre"):
            mixed, proj = _over_tokens(
                lambda x, phi_t, a_pre, b_pre: mhc.read(
                    _streams_first(x), phi_t, a_pre, b_pre, self.norm_eps),
                (x,), (phi_t, self.a_pre, self.b_pre),
            )
            # the call hands the input over in float32: the rounding is
            # here, where the compiler may fuse it into the input's reader
            mixed = mixed.astype(x.dtype)
        with jax.named_scope("mhc/coef"):
            proj = jnp.moveaxis(proj, -1, 0)  # [n n + 2 n, B, T]
            h_post = 2.0 * jax.nn.sigmoid(
                self.a_post * proj[n:2 * n] + self.b_post[:, None, None]
            )
            res = (
                self.a_res * proj[2 * n:].reshape(n, n, b, t)
                + self.b_res[:, :, None, None]
            )
        with jax.named_scope("mhc/sinkhorn"):
            h_res = sinkhorn(
                jnp.clip(res, *self.res_clamp), self.sinkhorn_iters, self.eps
            )
        return mixed, (h_post, h_res)

    def write(self, x: Array, out: Array, mix: tuple[Array, Array]) -> Array:
        """``H_res x + H_post^T out``: the next stream ``[B, T, n, C]``."""
        h_post, h_res = mix
        b, t, n, _ = x.shape
        with jax.named_scope("mhc/post"):
            # a token's coefficients a row: H_res row by row, then H_post
            h = jnp.moveaxis(
                jnp.concatenate([h_res.reshape(n * n, b, t), h_post]), 0, -1)
            (new,) = _over_tokens(
                lambda x, out, h: (
                    _streams_first(mhc.write(_streams_first(x), out, h)),),
                (x, out.astype(x.dtype), h), (),
            )
        return new
