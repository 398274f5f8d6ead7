"""Gated delta rule linear attention (recurrent + chunked forms).

TPU equivalent of the fla-core Triton kernels the reference wraps
(``chunk_gated_delta_rule`` imported at d9d/module/block/attention/linear/
gated_deltanet.py:6-8). The recurrence per head, with state
``S ∈ R^{d_k×d_v}``, log-decay ``g_t ≤ 0`` (α=exp g), write strength
``β_t ∈ (0,1)``:

    S_t = α_t·S_{t-1} + β_t·k_t·(v_t − α_t·S_{t-1}ᵀk_t)ᵀ
    o_t = S_tᵀ q_t

- :func:`gated_delta_rule_recurrent` — exact lax.scan over time; the
  correctness oracle, O(T) sequential steps (the scalar-decay case of
  :func:`kda_recurrent`, below).
- :func:`gated_delta_rule_chunked` — chunkwise WY form (Gated DeltaNet,
  arXiv 2412.06464): within a chunk the implicit per-token recursion is a
  C×C unit-lower-triangular solve; across chunks only the state carries.
  All inner products ride the MXU as [C,C] / [C,d] matmuls, and every
  exponential is of a non-positive number (cumulative decay differences),
  so the math is stable without rescaling tricks.

Shapes: ``q/k [B,T,H,Dk]``, ``v [B,T,H,Dv]``, ``g/beta [B,T,H]``.
Computation runs in fp32 regardless of input dtype (matching fla).

**Kimi delta attention** (Kimi Linear, arXiv 2510.26692; fla-core's
``chunk_kda`` / ``fused_recurrent_kda``) is the same rule with the decay
a vector a head, one number a key channel (``g [B,T,H,Dk]``), and a write
strength that may reach 2 (``I − β k kᵀ`` then has an eigenvalue in
(−1, 1)):

    S' = diag(e^{g_t})·S_{t-1}
    S_t = S' + β_t·k_t·(v_t − S'ᵀk_t)ᵀ          o_t = S_tᵀ q_t

It lives in this file and not in one of its own because the scalar-decay
oracle is its special case (``g[..., None]`` broadcasts over the
channels), so the Gated DeltaNet tests guard the shared recurrence:

- :func:`kda_recurrent` — the oracle, a ``lax.scan`` over time;
- :func:`kda_chunked` — chunks of ``chunk_size`` with the state threaded
  across them (prefill in ``generate``, the benchmark's comparison,
  training). A per-channel decay cannot be pulled out of ``k_iᵀk_j`` as
  ``e^{c_i−c_j}``; the pairwise products are built from sub-blocks of
  ``sub_block`` positions so that every exponential is of a non-positive
  number (see the function);
- :func:`kda_step` — one token for ``[B]`` rows: the decode step, which
  reads and writes ``state [B,H,Dk,Dv]`` float32 once (a Pallas call on
  the TPU, interpreted elsewhere) with ``Dv`` on the lanes, so that the
  decay is a broadcast along lanes and both read-outs are sums over
  sublanes.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from d9d_tpu.core.types import Array


def l2norm(x: Array, eps: float = 1e-6) -> Array:
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _prep(q, k, v, g, beta, use_qk_l2norm):
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    g = g.astype(jnp.float32)
    beta = beta.astype(jnp.float32)
    if use_qk_l2norm:
        q = l2norm(q)
        k = l2norm(k)
    q = q * (q.shape[-1] ** -0.5)
    return q, k, v, g, beta


def _state0(initial_state, shape) -> Array:
    if initial_state is None:
        return jnp.zeros(shape, jnp.float32)
    return initial_state.astype(jnp.float32)


def _step_reference(state, q, k, v, g, beta):
    """One token of the recurrence in ``jax.numpy``, ``q`` and ``k`` as
    it takes them (normalised and scaled): ``(o, new state)``. Two passes
    over the state and a write."""
    state = state * jnp.exp(g)[..., None]  # g [B,H,Dk|1]
    err = (v - jnp.sum(state * k[..., None], axis=-2)) * beta[..., None]
    state = state + k[..., None] * err[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def kda_recurrent(
    q: Array,
    k: Array,
    v: Array,
    g: Array,
    beta: Array,
    *,
    use_qk_l2norm: bool = True,
    initial_state: Array | None = None,
) -> tuple[Array, Array]:
    """Sequential oracle of the delta rule with a decay a key channel:
    ``g [B,T,H,Dk]`` (``[B,T,H,1]`` is one number a head). Returns
    ``(o [B,T,H,Dv], final_state [B,H,Dk,Dv])``."""
    q, k, v, g, beta = _prep(q, k, v, g, beta, use_qk_l2norm)
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, inputs):  # [B,H,D*] / [B,H]
        o_t, s = _step_reference(s, *inputs)
        return s, o_t

    xs = (
        q.transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2, 3),
        v.transpose(1, 0, 2, 3),
        g.transpose(1, 0, 2, 3),
        beta.transpose(1, 0, 2),
    )
    s_final, o = lax.scan(
        step, _state0(initial_state, (b, h, dk, dv)), xs
    )
    return o.transpose(1, 0, 2, 3), s_final


def gated_delta_rule_recurrent(
    q: Array,
    k: Array,
    v: Array,
    g: Array,
    beta: Array,
    *,
    use_qk_l2norm: bool = True,
    initial_state: Array | None = None,
) -> tuple[Array, Array]:
    """Sequential oracle, ``g [B,T,H]`` one number a head: the shared
    recurrence with the decay broadcast over a head's channels."""
    return kda_recurrent(
        q, k, v, g[..., None], beta,
        use_qk_l2norm=use_qk_l2norm, initial_state=initial_state,
    )


# d9d-lint: disable=D9D001 — standalone-use decorator; the train/serve paths trace this inside their tracked step programs
@functools.partial(jax.jit, static_argnames=("use_qk_l2norm", "chunk_size"))
def gated_delta_rule_chunked(
    q: Array,
    k: Array,
    v: Array,
    g: Array,
    beta: Array,
    *,
    use_qk_l2norm: bool = True,
    chunk_size: int = 64,
    initial_state: Array | None = None,
) -> tuple[Array, Array]:
    """Chunkwise WY form; numerically matches the recurrent oracle.

    Derivation: with c_i = Σ_{j≤i} g_j (within-chunk cumulative log decay)
    and S₀ the incoming state,

        u_i = v_i − e^{c_i}·S₀ᵀk_i − Σ_{j<i} e^{c_i−c_j}(k_iᵀk_j)β_j u_j
        o_i = e^{c_i}·S₀ᵀq_i + Σ_{j≤i} e^{c_i−c_j}(q_iᵀk_j)β_j u_j
        S_C = e^{c_C}·S₀ + Σ_i e^{c_C−c_i}·β_i·k_i u_iᵀ

    The u-recursion is ``(I + M)u = v − r`` with strictly-lower-triangular
    M — one triangular solve per chunk.
    """
    q, k, v, g, beta = _prep(q, k, v, g, beta, use_qk_l2norm)
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = chunk_size

    pad = (-t) % c
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        g = jnp.pad(g, ((0, 0), (0, pad), (0, 0)))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n_chunks = (t + pad) // c

    # [B,H,N,C,D*] chunked, head-major layouts
    def chunked(x):
        return x.reshape(b, n_chunks, c, h, -1).transpose(0, 3, 1, 2, 4)

    qc, kc, vc = chunked(q), chunked(k), chunked(v)
    gc = g.reshape(b, n_chunks, c, h).transpose(0, 3, 1, 2)
    bc = beta.reshape(b, n_chunks, c, h).transpose(0, 3, 1, 2)

    cum = jnp.cumsum(gc, axis=-1)  # c_i per chunk [B,H,N,C]
    # pairwise decay e^{c_i - c_j}, lower-triangular valid region
    diff = cum[..., :, None] - cum[..., None, :]  # [B,H,N,C,C]
    idx = jnp.arange(c)
    lower = idx[:, None] > idx[None, :]  # strict
    lower_eq = idx[:, None] >= idx[None, :]

    decay_strict = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    decay_incl = jnp.where(lower_eq, jnp.exp(jnp.where(lower_eq, diff, 0.0)), 0.0)

    kk = jnp.einsum("bhnik,bhnjk->bhnij", kc, kc)  # k_iᵀk_j
    qk = jnp.einsum("bhnik,bhnjk->bhnij", qc, kc)  # q_iᵀk_j
    m_mat = decay_strict * kk * bc[..., None, :]  # M_{ij} strict lower
    attn = decay_incl * qk * bc[..., None, :]  # A_{ij} incl diagonal

    eye = jnp.eye(c, dtype=jnp.float32)
    im = eye + m_mat  # unit lower-triangular

    s0 = _state0(initial_state, (b, h, dk, dv))

    def chunk_step(s, inputs):
        q_n, k_n, v_n, cum_n, beta_n, im_n, attn_n = inputs
        # r_i = e^{c_i} S₀ᵀ k_i
        r = jnp.exp(cum_n)[..., None] * jnp.einsum("bhkv,bhik->bhiv", s, k_n)
        rhs = v_n - r
        u = jax.scipy.linalg.solve_triangular(
            im_n, rhs, lower=True, unit_diagonal=True
        )
        o = (
            jnp.exp(cum_n)[..., None] * jnp.einsum("bhkv,bhik->bhiv", s, q_n)
            + jnp.einsum("bhij,bhjv->bhiv", attn_n, u)
        )
        # state to next chunk
        last = cum_n[..., -1]  # c_C
        w = jnp.exp(last[..., None] - cum_n) * beta_n  # e^{c_C - c_i} β_i
        s = jnp.exp(last)[..., None, None] * s + jnp.einsum(
            "bhik,bhiv->bhkv", k_n * w[..., None], u
        )
        return s, o

    xs = tuple(
        x.transpose(2, 0, 1, *range(3, x.ndim))
        for x in (qc, kc, vc, cum, bc, im, attn)
    )
    s_final, o = lax.scan(chunk_step, s0, xs)
    # o: [N,B,H,C,Dv] → [B,T,H,Dv]
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, t + pad, h, dv)
    return o[:, :t], s_final


# -- Kimi delta attention: a decay a key channel ------------------------------

HIGHEST = lax.Precision.HIGHEST


def _pairwise_decayed(a: Array, k: Array, cum: Array, sub: int) -> Array:
    """``P[i, j] = Σ_d a_i[d]·k_j[d]·e^{cum_i[d] − cum_j[d]}`` for ``j ≤ i``
    inside one chunk, zero above the diagonal. ``a, k, cum [..., C, Dk]``
    with ``cum`` the running sum of the log decays (non-increasing along
    ``C``) → ``[..., C, C]``.

    Every exponential is of a non-positive number. The chunk is cut into
    sub-blocks of ``sub`` positions. A pair inside one sub-block is taken
    directly, ``[sub, sub, Dk]`` exponentials of ``cum_i − cum_j`` masked
    to ``j ≤ i``. A pair across sub-blocks ``J < I`` goes through ``s``,
    the first position of ``I``: ``e^{cum_i − cum_s}·e^{cum_s − cum_j}``,
    both factors at most one, so it is a matrix product of ``a_i e^{cum_i
    − cum_s}`` with ``k_j e^{cum_s − cum_j}``."""
    *lead, c, dk = a.shape
    n = c // sub
    blocks = lambda x: x.reshape(*lead, n, sub, dk)  # noqa: E731
    a_b, k_b, cum_b = blocks(a), blocks(k), blocks(cum)

    idx = jnp.arange(sub)
    within = idx[:, None] >= idx[None, :]  # j <= i
    diff = cum_b[..., :, None, :] - cum_b[..., None, :, :]  # [.., n, i, j, Dk]
    decay = jnp.exp(jnp.where(within[..., None], diff, -jnp.inf))
    diagonal = jnp.sum(
        a_b[..., :, None, :] * k_b[..., None, :, :] * decay, axis=-1
    )  # [.., n, sub, sub]

    anchor = cum_b[..., :1, :]  # cum at each sub-block's first position
    a_to_anchor = a_b * jnp.exp(cum_b - anchor)  # [.., n(I), sub, Dk]
    earlier = jnp.arange(n)[:, None] > jnp.arange(n)[None, :]  # J < I
    from_anchor = jnp.exp(jnp.where(
        earlier[:, :, None, None],
        anchor[..., :, None, :, :] - cum_b[..., None, :, :, :], -jnp.inf,
    ))  # [.., n(I), n(J), sub(j), Dk]
    across = jnp.einsum(
        "...Iid,...IJjd->...IiJj", a_to_anchor,
        k_b[..., None, :, :, :] * from_anchor, precision=HIGHEST,
    )
    same = jnp.eye(n, dtype=bool)[:, None, :, None]
    pairs = jnp.where(same, diagonal[..., :, :, None, :], across)
    return pairs.reshape(*lead, c, c)


# d9d-lint: disable=D9D001 — standalone-use decorator; the train/serve paths trace this inside their tracked step programs
@functools.partial(
    jax.jit, static_argnames=("use_qk_l2norm", "chunk_size", "sub_block")
)
def kda_chunked(
    q: Array,
    k: Array,
    v: Array,
    g: Array,
    beta: Array,
    *,
    use_qk_l2norm: bool = True,
    chunk_size: int = 64,
    sub_block: int = 16,
    initial_state: Array | None = None,
) -> tuple[Array, Array]:
    """Chunkwise form of :func:`kda_recurrent`; ``g [B,T,H,Dk]``.

    With ``c_i = Σ_{j≤i} g_j`` (a vector of ``Dk``, within the chunk) and
    ``S₀`` the incoming state, the scalar form's derivation holds with
    the decay inside the inner products:

        u_i = v_i − S₀ᵀ(e^{c_i}⊙k_i) − Σ_{j<i} (k_iᵀ diag(e^{c_i−c_j}) k_j) β_j u_j
        o_i = S₀ᵀ(e^{c_i}⊙q_i) + Σ_{j≤i} (q_iᵀ diag(e^{c_i−c_j}) k_j) β_j u_j
        S_C = diag(e^{c_C})·S₀ + Σ_i (e^{c_C−c_i}⊙k_i) β_i u_iᵀ

    The two pair matrices come from :func:`_pairwise_decayed`; the
    u-recursion is one unit-lower-triangular solve a chunk, which holds
    whatever ``β`` is (to 2 and beyond: nothing here needs ``I − β k kᵀ``
    to be a contraction). ``T`` need not divide into chunks: the tail is
    padded with ``g = 0, β = 0`` steps, which leave the state as it is.
    Matrix products run at ``highest`` precision: a prefill's state is
    what every later step decays from.
    """
    q, k, v, g, beta = _prep(q, k, v, g, beta, use_qk_l2norm)
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk_size, -(-t // sub_block) * sub_block)
    if c % sub_block:
        raise ValueError(f"chunk_size {chunk_size} in sub-blocks of {sub_block}")
    pad = (-t) % c
    n_chunks = (t + pad) // c

    def chunks(x):  # [B,T,H,...] -> [N,B,H,C,...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n_chunks, c, h, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    eye = jnp.eye(c, dtype=jnp.float32)

    def chunk_step(s, inputs):
        q_n, k_n, v_n, g_n, beta_n = inputs  # [B,H,C,D*] / [B,H,C]
        cum = jnp.cumsum(g_n, axis=-2)
        into = jnp.exp(cum)  # e^{c_i}: what is left of S₀ at position i
        kk = _pairwise_decayed(k_n, k_n, cum, sub_block)
        qk = _pairwise_decayed(q_n, k_n, cum, sub_block)
        m_mat = jnp.where(strict, kk, 0.0) * beta_n[..., None, :]
        carried = jnp.einsum(
            "bhkv,bhik->bhiv", s, k_n * into, precision=HIGHEST
        )
        u = jax.scipy.linalg.solve_triangular(
            eye + m_mat, v_n - carried, lower=True, unit_diagonal=True
        )
        o = jnp.einsum(
            "bhkv,bhik->bhiv", s, q_n * into, precision=HIGHEST
        ) + jnp.einsum(
            "bhij,bhjv->bhiv", qk * beta_n[..., None, :], u, precision=HIGHEST
        )
        to_end = jnp.exp(cum[..., -1:, :] - cum) * beta_n[..., None]
        s = into[..., -1, :, None] * s + jnp.einsum(
            "bhik,bhiv->bhkv", k_n * to_end, u, precision=HIGHEST
        )
        return s, o

    s_final, o = lax.scan(
        chunk_step, _state0(initial_state, (b, h, dk, dv)),
        tuple(map(chunks, (q, k, v, g, beta))),
    )
    # o: [N,B,H,C,Dv] → [B,T,H,Dv]
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, t + pad, h, dv)
    return o[:, :t], s_final


# The decode step's kernel works on ``_STEP_HEADS`` heads of one row at a
# time: their four column operands (decay, k, β k, q: a number a key
# channel each) arrive as the 4 x 32 = 128 rows of one ``[128, Dk]`` tile,
# which one transpose turns into columns, Dk on the sublanes. Two or four
# rows a grid step read the same (3.38 ms a layer at 256 rows x 64 heads;
# my chip runs, PR 51), so a step takes one
_STEP_HEADS = 32
_STEP_VMEM_LIMIT = 64 * 1024 * 1024


def _kda_step_kernel(cols_ref, bv_ref, state_ref, o_ref, new_ref):
    heads = state_ref.shape[1]
    cols = cols_ref[0, 0].T  # [Dk, 4·heads]
    for h in range(heads):
        column = lambda c: cols[:, c * heads + h:c * heads + h + 1]  # noqa: E731,B023
        s = state_ref[0, h] * column(0)  # the decay, along the lanes
        err = bv_ref[0, h:h + 1, :] - jnp.sum(
            s * column(2), axis=0, keepdims=True
        )
        s = s + column(1) * err
        new_ref[0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(s * column(3), axis=0, keepdims=True)


def _step_pallas(state, q, k, v, g, beta, interpret):
    b, h, dk, dv = state.shape
    group = min(h, _STEP_HEADS)
    decay = jnp.broadcast_to(jnp.exp(g), k.shape)
    # [B, 4, H, Dk] -> [B, H/group, 4·group, Dk]: operand-major in a group
    cols = jnp.stack([decay, k, beta[..., None] * k, q], axis=1).reshape(
        b, 4, h // group, group, dk
    ).swapaxes(1, 2).reshape(b, h // group, 4 * group, dk)
    o, new = pl.pallas_call(
        _kda_step_kernel,
        grid=(b, h // group),
        in_specs=[
            pl.BlockSpec((1, 1, 4 * group, dk), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, group, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, group, dk, dv), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, group, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, group, dk, dv), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_STEP_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="kda_step",
    )(cols, beta[..., None] * v, state)
    return o, new


def kda_step(
    state: Array, q: Array, k: Array, v: Array, g: Array, beta: Array,
    *, use_qk_l2norm: bool = True,
) -> tuple[Array, Array]:
    """One token for ``[B]`` rows: ``state [B,H,Dk,Dv]``, ``q, k [B,H,Dk]``,
    ``v [B,H,Dv]``, ``g [B,H,Dk]`` (``[B,H,1]``: one number a head),
    ``beta [B,H]`` → ``(o [B,H,Dv], new state)``, float32.

    The state is read and written once: a Pallas call holds a row's heads
    in VMEM through the decay, the read-out ``S'ᵀk``, the rank-one write
    and the read-out ``Sᵀq``, and writes the new state over the old
    (``input_output_aliases``). What a head needs as columns (``e^g``,
    ``k``, ``β k``, ``q``) crosses the call's boundary as rows, lanes
    along ``Dk``, and is transposed inside; ``β v`` and the output are
    rows as they stand. On the TPU the call takes ``H`` in groups of 32
    and ``Dk``, ``Dv`` in whole lane tiles, and any other shape runs the
    oracle's step in ``jax.numpy`` (two passes over the state and a
    write); elsewhere the call is interpreted at any shape."""
    q, k, v, g, beta = _prep(q, k, v, g, beta, use_qk_l2norm)
    state = state.astype(jnp.float32)
    _, h, dk, dv = state.shape
    on_tpu = jax.default_backend() == "tpu"
    fits = (
        h % _STEP_HEADS == 0 and dk % 128 == 0 and dv % 128 == 0
        if on_tpu else h % min(h, _STEP_HEADS) == 0
    )
    if not fits:
        return _step_reference(state, q, k, v, g, beta)
    return _step_pallas(state, q, k, v, g, beta, not on_tpu)
