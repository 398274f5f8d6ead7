"""Pallas flash attention vs eager oracle (interpret mode on CPU).

Mirrors the reference's kernel-correctness strategy (SURVEY §4.1): every
feature combination checked numerically against the eager implementation,
forward and backward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytestmark = pytest.mark.e2e  # slow tier: heavy kernel/e2e parity


from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.ops.attention.pallas_flash import make_pallas_flash_sdpa
from tests.jaxpr_tools import equations


def rng(*shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype=jnp.float32)


flash = make_pallas_flash_sdpa(block_q=16, block_kv=16)


def check(q, k, v, rtol=2e-3, atol=2e-3, **kw):
    out_f = jax.jit(lambda q, k, v: flash(q, k, v, **kw))(q, k, v)
    out_e = jax.jit(lambda q, k, v: eager_sdpa(q, k, v, **kw))(q, k, v)
    np.testing.assert_allclose(out_f, out_e, rtol=rtol, atol=atol)


class TestForward:
    def test_causal(self):
        check(rng(2, 64, 4, 32), rng(2, 64, 4, 32, seed=1), rng(2, 64, 4, 32, seed=2))

    def test_non_causal(self):
        check(
            rng(1, 32, 2, 16), rng(1, 32, 2, 16, seed=1), rng(1, 32, 2, 16, seed=2),
            causal=False,
        )

    def test_gqa(self):
        check(rng(2, 48, 8, 16), rng(2, 48, 2, 16, seed=1), rng(2, 48, 2, 16, seed=2))

    def test_unaligned_seq_len(self):
        # 50 is not a multiple of block 16 — exercises padding/masking
        check(rng(1, 50, 2, 16), rng(1, 50, 2, 16, seed=1), rng(1, 50, 2, 16, seed=2))

    def test_window(self):
        check(
            rng(1, 64, 2, 16), rng(1, 64, 2, 16, seed=1), rng(1, 64, 2, 16, seed=2),
            window_size=20,
        )

    def test_sinks(self):
        sinks = jnp.array([0.5, -1.0])
        check(
            rng(1, 32, 2, 16), rng(1, 32, 2, 16, seed=1), rng(1, 32, 2, 16, seed=2),
            sinks=sinks,
        )

    def test_softmax_scale(self):
        check(
            rng(1, 32, 2, 16), rng(1, 32, 2, 16, seed=1), rng(1, 32, 2, 16, seed=2),
            softmax_scale=0.5,
        )

    def test_mask_falls_back_to_eager(self):
        q = rng(1, 8, 1, 8)
        k, v = rng(1, 8, 1, 8, seed=1), rng(1, 8, 1, 8, seed=2)
        mask = jnp.ones((1, 1, 8, 8), bool)
        out = flash(q, k, v, mask=mask)
        np.testing.assert_allclose(out, eager_sdpa(q, k, v, mask=mask), rtol=1e-5)


class TestBackward:
    @pytest.mark.parametrize(
        "case",
        ["causal", "gqa", "window", "sinks", "unaligned"],
    )
    def test_grads_match_eager(self, case):
        kw = {}
        t = 48
        hq = hkv = 2
        sinks = None
        if case == "gqa":
            hq = 4
        elif case == "window":
            kw["window_size"] = 17
        elif case == "sinks":
            sinks = jnp.array([0.3, -0.7])
        elif case == "unaligned":
            t = 37
        q = rng(2, t, hq, 16)
        k, v = rng(2, t, hkv, 16, seed=1), rng(2, t, hkv, 16, seed=2)

        def loss_flash(q, k, v, s):
            return (flash(q, k, v, sinks=s, **kw) ** 2).sum()

        def loss_eager(q, k, v, s):
            return (eager_sdpa(q, k, v, sinks=s, **kw) ** 2).sum()

        argnums = (0, 1, 2, 3) if sinks is not None else (0, 1, 2)
        gf = jax.jit(jax.grad(loss_flash, argnums=argnums))(q, k, v, sinks)
        ge = jax.jit(jax.grad(loss_eager, argnums=argnums))(q, k, v, sinks)
        for a, b in zip(gf, ge):
            np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)


def _packed_segments(b, t, n_docs, seed=3):
    """Random packed-document segment ids: non-decreasing ints per row."""
    key = jax.random.PRNGKey(seed)
    cuts = jax.random.randint(key, (b, t), 0, n_docs)
    return jnp.sort(cuts, axis=1).astype(jnp.int32)


class TestSegments:
    """Packed-sequence (varlen) parity — reference flash_attn_varlen_func
    (d9d/kernel/flash_attn/function.py:384)."""

    def test_forward_matches_eager(self):
        q = rng(2, 48, 2, 16)
        k, v = rng(2, 48, 2, 16, seed=1), rng(2, 48, 2, 16, seed=2)
        seg = _packed_segments(2, 48, 3)
        check(q, k, v, q_segments=seg, kv_segments=seg)

    def test_forward_unaligned(self):
        q = rng(1, 37, 2, 16)
        k, v = rng(1, 37, 2, 16, seed=1), rng(1, 37, 2, 16, seed=2)
        seg = _packed_segments(1, 37, 4)
        check(q, k, v, q_segments=seg, kv_segments=seg)

    def test_forward_with_window_and_gqa(self):
        q = rng(2, 64, 4, 16)
        k, v = rng(2, 64, 2, 16, seed=1), rng(2, 64, 2, 16, seed=2)
        seg = _packed_segments(2, 64, 3)
        check(q, k, v, q_segments=seg, kv_segments=seg, window_size=20)

    def test_sinks_with_segments(self):
        q = rng(2, 48, 2, 16)
        k, v = rng(2, 48, 2, 16, seed=1), rng(2, 48, 2, 16, seed=2)
        seg = _packed_segments(2, 48, 3)
        check(q, k, v, q_segments=seg, kv_segments=seg,
              sinks=jnp.array([0.4, -0.9]))

    @pytest.mark.parametrize("case", ["plain", "gqa_window", "sinks"])
    def test_grads_match_eager(self, case):
        kw = {}
        hq = hkv = 2
        sinks = None
        if case == "gqa_window":
            hq, kw["window_size"] = 4, 19
        elif case == "sinks":
            sinks = jnp.array([0.3, -0.7])
        q = rng(2, 48, hq, 16)
        k, v = rng(2, 48, hkv, 16, seed=1), rng(2, 48, hkv, 16, seed=2)
        seg = _packed_segments(2, 48, 3)

        def loss_flash(q, k, v, s):
            return (flash(q, k, v, sinks=s, q_segments=seg,
                          kv_segments=seg, **kw) ** 2).sum()

        def loss_eager(q, k, v, s):
            return (eager_sdpa(q, k, v, sinks=s, q_segments=seg,
                               kv_segments=seg, **kw) ** 2).sum()

        argnums = (0, 1, 2, 3) if sinks is not None else (0, 1, 2)
        gf = jax.jit(jax.grad(loss_flash, argnums=argnums))(q, k, v, sinks)
        ge = jax.jit(jax.grad(loss_eager, argnums=argnums))(q, k, v, sinks)
        for a, b in zip(gf, ge):
            np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)

    def test_mismatched_segments_raise(self):
        q = rng(1, 16, 1, 8)
        with pytest.raises(ValueError):
            flash(q, q, q, q_segments=_packed_segments(1, 16, 2))


class TestAttentionBlock:
    """flash_attention_block: the (o, lse) chunk primitive ring attention
    composes. Chunked calls at global offsets + an lse-combine must equal
    one full-sequence attention, fwd and bwd (the bwd exercises the dlse
    cotangent folding into delta)."""

    def _combine(self, parts):
        from d9d_tpu.ops.attention.pallas_flash import combine_attention_chunks

        o, lse = parts[0]
        for o2, lse2 in parts[1:]:
            o, lse = combine_attention_chunks(o, lse, o2, lse2)
        return o

    @pytest.mark.parametrize("n_chunks,kw", [
        (2, {}),
        (4, {"window_size": 13}),
        (2, {"causal": False}),
    ])
    @pytest.mark.slow  # ~10s/param compile-bound on the 2-core rig
    def test_chunked_matches_full(self, n_chunks, kw):
        from d9d_tpu.ops.attention.pallas_flash import flash_attention_block

        b, t, hq, hkv, d = 2, 64, 4, 2, 16
        q = rng(b, t, hq, d)
        k, v = rng(b, t, hkv, d, seed=1), rng(b, t, hkv, d, seed=2)
        seg = _packed_segments(b, t, 3)
        c = t // n_chunks

        def loss_chunked(q, k, v):
            parts = [
                flash_attention_block(
                    q, k[:, i * c:(i + 1) * c], v[:, i * c:(i + 1) * c],
                    q_offset=0, k_offset=i * c,
                    q_segments=seg, kv_segments=seg[:, i * c:(i + 1) * c],
                    block_q=16, block_kv=16, **kw)
                for i in range(n_chunks)
            ]
            return (self._combine(parts) ** 2).sum()

        def loss_full(q, k, v):
            return (eager_sdpa(q, k, v, q_segments=seg,
                               kv_segments=seg, **kw) ** 2).sum()

        lc, gc = jax.jit(jax.value_and_grad(loss_chunked, (0, 1, 2)))(q, k, v)
        le, ge = jax.jit(jax.value_and_grad(loss_full, (0, 1, 2)))(q, k, v)
        np.testing.assert_allclose(lc, le, rtol=2e-3, atol=2e-3)
        for a, b_ in zip(gc, ge):
            np.testing.assert_allclose(a, b_, rtol=5e-3, atol=5e-3)

    def test_fully_future_chunk_is_weightless(self):
        from d9d_tpu.ops.attention.pallas_flash import flash_attention_block

        q = rng(1, 16, 2, 8)
        k, v = rng(1, 16, 2, 8, seed=1), rng(1, 16, 2, 8, seed=2)
        # keys sit entirely in the causal future of every query
        o, lse = flash_attention_block(
            q, k, v, q_offset=0, k_offset=1024, block_q=16, block_kv=16)
        assert np.all(np.asarray(lse) < -1e29)


class TestFusedBackward:
    """One-pass backward (D9D_TPU_FLASH_BWD=fused): dq/dk/dv must match
    the split two-kernel backward (and hence the eager oracle) across the
    feature matrix. The fused kernel accumulates dq in a full-[g*Tq, d]
    VMEM scratch across the kv grid dim."""

    @pytest.mark.parametrize("case", [
        "causal", "gqa", "window", "segments", "sinks", "unaligned",
        "noncausal",
    ])
    def test_grads_match_split(self, case):
        kw = {}
        t, hq, hkv = 48, 2, 2
        sinks = None
        seg = None
        if case == "gqa":
            hq = 4
        elif case == "window":
            kw["window_size"] = 17
        elif case == "segments":
            seg = _packed_segments(2, 48, 3)
        elif case == "sinks":
            sinks = jnp.array([0.3, -0.7])
        elif case == "unaligned":
            t = 37
        elif case == "noncausal":
            kw["causal"] = False
        fused = make_pallas_flash_sdpa(
            block_q=16, block_kv=16, fused_bwd=True
        )
        split = make_pallas_flash_sdpa(
            block_q=16, block_kv=16, fused_bwd=False
        )
        q = rng(2, t, hq, 16)
        k, v = rng(2, t, hkv, 16, seed=1), rng(2, t, hkv, 16, seed=2)

        def loss(f, q, k, v, s):
            return (f(q, k, v, sinks=s, q_segments=seg,
                      kv_segments=seg, **kw) ** 2).sum()

        argnums = (0, 1, 2, 3) if sinks is not None else (0, 1, 2)
        gf = jax.jit(
            jax.grad(lambda *a: loss(fused, *a), argnums))(q, k, v, sinks)
        gs = jax.jit(
            jax.grad(lambda *a: loss(split, *a), argnums))(q, k, v, sinks)
        for a, b in zip(gf, gs):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("q_offset,k_offset", [(0, 0), (64, 32)])
    def test_ring_block_grads_with_fused(self, q_offset, k_offset):
        """flash_attention_block's VJP routes through the fused backward
        (exercising its offsets branch and the lse-cotangent path) and
        matches the split backward at nonzero global offsets."""
        from d9d_tpu.ops.attention import pallas_flash as pf

        q = rng(1, 32, 2, 16)
        k, v = rng(1, 32, 2, 16, seed=1), rng(1, 32, 2, 16, seed=2)

        def loss(q, k, v, fused):
            o, lse = pf.flash_attention_block(
                q, k, v, q_offset=q_offset, k_offset=k_offset,
                block_q=16, block_kv=16, fused_bwd=fused,
            )
            return (o.astype(jnp.float32) ** 2).sum() + lse.sum()

        grads = jax.jit(jax.grad(loss, (0, 1, 2)), static_argnums=3)
        g_split = grads(q, k, v, False)
        g_fused = grads(q, k, v, True)
        for a, b in zip(g_fused, g_split):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _pallas_grids(fn, *args):
    """The grids of the Pallas calls ``fn`` traces to, in program order."""
    return [
        tuple(eqn.params["grid_mapping"].grid)
        for eqn in equations(jax.make_jaxpr(fn)(*args).jaxpr)
        if eqn.primitive.name == "pallas_call"
    ]


class TestWindowBand:
    """Under a static window the sequential grid dimension is the window's
    band (``pallas_flash._Band``): outputs and the three gradients against
    the eager oracle wherever the band's edges fall, and the grids the
    calls really have against ``grid_visits``."""

    # t, block_q, block_kv, window, extras
    CASES = {
        "window_under_a_block": (64, 16, 16, 5, {}),
        "window_is_a_block": (64, 16, 16, 16, {}),
        "window_not_a_multiple": (64, 16, 16, 21, {}),
        "window_over_the_sequence": (48, 16, 16, 100, {}),
        "sequence_not_a_multiple": (50, 16, 16, 12, {}),
        "longer_q_blocks": (64, 32, 8, 13, {}),
        "longer_kv_blocks": (64, 8, 32, 13, {}),
        "gqa": (64, 16, 8, 19, {"hq": 4}),
        "segments": (64, 16, 16, 20, {"segments": True, "hq": 4}),
        "sinks": (48, 16, 16, 17, {"sinks": True}),
        "non_causal": (64, 16, 16, 20, {"causal": False}),
        # 6 q blocks of 8 beside 3 kv blocks of 16 under a window of 30: a
        # kv block computes 6 q blocks from its own first, so the last kv
        # block's band runs four visits past the last q block, whose rows
        # brought in again would pass the mask of the positions after them
        "clamped_visits": (48, 8, 16, 30, {"clamped": True}),
    }

    @pytest.mark.parametrize("case", list(CASES))
    def test_outputs_and_grads_match_eager(self, case):
        from d9d_tpu.ops.attention import pallas_flash as pf

        t, block_q, block_kv, window, extras = self.CASES[case]
        hq, hkv = extras.get("hq", 2), 2
        causal = extras.get("causal", True)
        sdpa = make_pallas_flash_sdpa(block_q=block_q, block_kv=block_kv)
        q = rng(2, t, hq, 16)
        k, v = rng(2, t, hkv, 16, seed=1), rng(2, t, hkv, 16, seed=2)
        kw = {"window_size": window, "causal": causal}
        if extras.get("segments"):
            kw["q_segments"] = kw["kv_segments"] = _packed_segments(2, t, 3)
        sinks = jnp.array([0.3, -0.7]) if extras.get("sinks") else None

        def out_and_grads(fn):
            def loss(q, k, v, s):
                o = fn(q, k, v, sinks=s, **kw)
                return (o ** 2).sum(), o

            argnums = (0, 1, 2, 3) if sinks is not None else (0, 1, 2)
            (_, o), grads = jax.jit(jax.value_and_grad(
                loss, argnums=argnums, has_aux=True))(q, k, v, sinks)
            return o, grads

        o_f, g_f = out_and_grads(sdpa)
        o_e, g_e = out_and_grads(eager_sdpa)
        np.testing.assert_allclose(o_f, o_e, rtol=2e-3, atol=2e-3)
        for a, b in zip(g_f, g_e):
            np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)

        # the band holds every pair the whole grid computes, and no more
        # visits than the whole grid
        cfg = pf._FlashConfig(
            causal=causal, scale=1.0, window=window, has_sinks=False,
            has_segments=False, block_q=block_q, block_kv=block_kv,
            seq_len=t, interpret=True,
        )
        n_q, n_kv = -(-t // block_q), -(-t // block_kv)
        whole = sum(
            not bool(pf._skip_block(cfg, iq, ik))
            for iq in range(n_q) for ik in range(n_kv)
        )
        for kernel in ("fwd", "dkv"):
            visited, computing = pf.grid_visits(cfg, t, t, kernel)
            assert computing == whole
            assert computing <= visited <= n_q * n_kv
        if extras.get("clamped"):
            band = pf._band(cfg, n_q, n_kv, dkv=True)
            assert band.first(n_kv - 1) + band.reach == band.n + 4

    @pytest.mark.parametrize("kind", ["window", "no_window", "positions"])
    def test_the_lowered_grids_are_what_grid_visits_counts(self, kind):
        """A windowed call lowers to its bands; a call with no window, and
        one with traced positions (ring attention) under a window, to the
        whole grids they had."""
        from d9d_tpu.ops.attention import pallas_flash as pf

        b, t, hq, hkv, bq, bkv = 1, 64, 4, 2, 16, 8
        window = None if kind == "no_window" else 12
        q = rng(b, t, hq, 16)
        k, v = rng(b, t, hkv, 16, seed=1), rng(b, t, hkv, 16, seed=2)
        if kind == "positions":
            def fn(q, k, v):
                o, _ = pf.flash_attention_block(
                    q, k, v, q_offset=jnp.int32(0), k_offset=jnp.int32(0),
                    window_size=window, block_q=bq, block_kv=bkv,
                    fused_bwd=False)
                return (o ** 2).sum()
        else:
            sdpa = make_pallas_flash_sdpa(
                block_q=bq, block_kv=bkv, fused_bwd=False)

            def fn(q, k, v):
                return (sdpa(q, k, v, window_size=window) ** 2).sum()

        fwd, dq, dkv = _pallas_grids(jax.grad(fn, (0, 1, 2)), q, k, v)
        cfg = pf._FlashConfig(
            causal=True, scale=1.0, window=window, has_sinks=False,
            has_segments=False, block_q=bq, block_kv=bkv, seq_len=t,
            interpret=True, has_positions=kind == "positions",
        )
        n_q, n_kv, g = t // bq, t // bkv, hq // hkv
        if kind == "window":
            # 16 queries back 11 keys: at most 4 kv blocks of 8 a q block,
            # and 2 q blocks a kv block
            assert fwd == dq == (b, hq, n_q, 4)
            assert dkv == (b, hkv, n_kv, g * 2)
        else:
            assert fwd == dq == (b, hq, n_q, n_kv)
            assert dkv == (b, hkv, n_kv, g * n_q)
        for grid, kernel in ((fwd, "fwd"), (dq, "fwd"), (dkv, "dkv")):
            per_head = np.prod(grid) // (b * hq)
            assert pf.grid_visits(cfg, t, t, kernel)[0] == per_head


class TestRematerialisedLayerKeepsTheCall:
    """A ``jax.checkpoint`` that may not be merged with the forward it
    repeats (``prevent_cse=True``) runs the forward kernel a second time
    unless its policy keeps the kernel's own residuals: ``_flash_fwd`` and
    ``_flash_ol_fwd`` name them ("sdpa_out", "sdpa_lse") and every policy
    of ``_remat_policy`` saves the names. Forward, dq and dk/dv: three
    calls, where the parent commit traced four; the gradients are the bits
    of the layer without ``jax.checkpoint``."""

    CASES = {
        "causal": {},
        "causal_window": {"window_size": 20},
        "sinks": {"sinks": True},
        "segments": {"segments": True},
        "flash_attention_block": {"block": True},
    }

    @staticmethod
    def layer(window_size=None, sinks=False, segments=False, block=False):
        from d9d_tpu.ops.attention.pallas_flash import flash_attention_block

        seg = _packed_segments(1, 32, 2) if segments else None
        sink_logits = jnp.array([0.5, -1.0]) if sinks else None

        def f(q, k, v):
            # the scalings stand for what a layer computes before and
            # after the call and has to recompute around the kept pair
            q, k, v = q * 1.25, k * 0.75, v * 1.5
            if block:
                o, lse = flash_attention_block(
                    q, k, v, q_offset=0, k_offset=0, block_q=16, block_kv=16)
                return (o.astype(jnp.float32) ** 2).sum() + lse.sum()
            o = flash(q, k, v, window_size=window_size, sinks=sink_logits,
                      q_segments=seg, kv_segments=seg)
            return (o.astype(jnp.float32) ** 2).sum()

        return f

    QKV = (rng(1, 32, 2, 16), rng(1, 32, 1, 16, seed=1),
           rng(1, 32, 1, 16, seed=2))
    _plain = {}

    def plain_gradients(self, case):
        """dq, dk, dv of the case's layer with no ``jax.checkpoint``, in a
        program of their own: once a case, for its three policies; with
        the count the parent commit had under every policy."""
        if case not in self._plain:
            f = self.layer(**self.CASES[case])
            # with nothing kept the forward kernel is traced twice
            bare = jax.grad(jax.checkpoint(f, prevent_cse=True), (0, 1, 2))
            assert len(_pallas_grids(bare, *self.QKV)) == 4
            self._plain[case] = jax.jit(jax.grad(f, (0, 1, 2)))(*self.QKV)
        return self._plain[case]

    @pytest.mark.parametrize("policy",
                             ["full", "dots_no_batch", "save_expensive"])
    @pytest.mark.parametrize("case", list(CASES))
    def test_three_calls_and_the_same_gradients(self, case, policy):
        from d9d_tpu.models.qwen3.dense import _remat_policy

        f = self.layer(**self.CASES[case])
        kept = jax.grad(
            jax.checkpoint(f, prevent_cse=True, policy=_remat_policy(policy)),
            (0, 1, 2))
        assert len(_pallas_grids(kept, *self.QKV)) == 3
        for got, want in zip(jax.jit(kept)(*self.QKV),
                             self.plain_gradients(case)):
            np.testing.assert_array_equal(got, want)
