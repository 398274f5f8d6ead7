"""Fused aligned-layout MoE FFN kernel (ops/moe_pallas.py): exact parity
with the reference XLA chain, forward and backward, on the CPU rig
(interpret mode)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from d9d_tpu.ops.moe import (
    permute_tokens,
    sort_tokens_by_expert,
    unpermute_combine,
    grouped_matmul,
)
from d9d_tpu.ops.moe_pallas import (
    aligned_metadata,
    fused_moe_ffn_apply,
)
from d9d_tpu.ops.swiglu import silu_mul


def _reference(x, probs, sort, wg, wu, wd, dtype):
    permuted_x, permuted_probs = permute_tokens(x, probs, sort)
    xx = permuted_x.astype(dtype)
    inter = wg.shape[-1]
    gate_up = jnp.concatenate([wg.astype(dtype), wu.astype(dtype)], axis=-1)
    h_gu = grouped_matmul(xx, gate_up, sort.group_sizes)
    hidden = silu_mul(h_gu[..., :inter], h_gu[..., inter:])
    y = grouped_matmul(hidden, wd.astype(dtype), sort.group_sizes)
    y = y * permuted_probs[:, None].astype(dtype)
    return unpermute_combine(y, sort, x.shape[0]).astype(x.dtype)


def _problem(seed=0, n=96, h=64, inter=32, e=8, k=2, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, h), dtype)
    wg = jnp.asarray(rng.randn(e, h, inter) * 0.1, dtype)
    wu = jnp.asarray(rng.randn(e, h, inter) * 0.1, dtype)
    wd = jnp.asarray(rng.randn(e, inter, h) * 0.1, dtype)
    ids = jnp.asarray(
        np.stack([rng.choice(e, size=k, replace=False) for _ in range(n)]),
        jnp.int32,
    )
    probs = jnp.asarray(rng.rand(n, k) + 0.1, jnp.float32)
    return x, ids, probs, wg, wu, wd


class TestAlignedMetadata:
    def test_layout_invariants(self):
        _, ids, _, *_ = _problem()
        e, bm = 8, 16
        sort = sort_tokens_by_expert(ids, e)
        meta = aligned_metadata(sort, e, bm)
        m = int(sort.dest.shape[0])
        assert meta.m_pad % bm == 0
        dest_aligned = np.asarray(meta.dest_aligned)
        # aligned rows are unique and in range
        assert len(set(dest_aligned.tolist())) == m
        assert dest_aligned.max() < meta.m_pad
        # each aligned row sits in a tile owned by its pair's expert
        gid = np.asarray(meta.gid)
        flat_ids = np.asarray(ids).reshape(-1)
        for pair, row in enumerate(dest_aligned.tolist()):
            assert gid[row // bm] == flat_ids[pair]
        # pair_src is the inverse map
        pair_src = np.asarray(meta.pair_src)
        for pair, row in enumerate(dest_aligned.tolist()):
            assert pair_src[row] == pair
        # pad rows marked -1
        assert (pair_src < 0).sum() == meta.m_pad - m

    def test_empty_and_full_groups(self):
        # all tokens on expert 3: other groups are empty, still consistent
        n, e, k, bm = 24, 6, 1, 8
        ids = jnp.full((n, k), 3, jnp.int32)
        sort = sort_tokens_by_expert(ids, e)
        meta = aligned_metadata(sort, e, bm)
        dest_aligned = np.asarray(meta.dest_aligned)
        assert len(set(dest_aligned.tolist())) == n
        gid = np.asarray(meta.gid)
        for row in dest_aligned.tolist():
            assert gid[row // bm] == 3


class TestFusedParity:
    @pytest.mark.parametrize("block_m", [8, 16, 64])
    @pytest.mark.parametrize("backend", ["pallas", "pallas_gather"])
    def test_forward_matches_reference(self, block_m, backend, monkeypatch):
        monkeypatch.setenv("D9D_TPU_MOE_FFN", backend)
        x, ids, probs, wg, wu, wd = _problem()
        e = wg.shape[0]
        sort = sort_tokens_by_expert(ids, e)
        ref = _reference(x, probs, sort, wg, wu, wd, jnp.float32)
        got = fused_moe_ffn_apply(
            x, probs, sort, wg, wu, wd, jnp.float32,
            num_experts=e, block_m=block_m, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.slow  # >10s compile-bound on the 2-core rig
    def test_gather_variant_gradients(self, monkeypatch):
        """The gather variant shares the reference backward; its custom
        fwd must still produce exact grads end to end."""
        monkeypatch.setenv("D9D_TPU_MOE_FFN", "pallas_gather")
        x, ids, probs, wg, wu, wd = _problem(seed=11)
        e = wg.shape[0]
        sort = sort_tokens_by_expert(ids, e)

        def loss(fn):
            def run(x_, wg_):
                return (fn(x_, wg_) ** 2).sum()
            return run

        fused = loss(lambda x_, wg_: fused_moe_ffn_apply(
            x_, probs, sort, wg_, wu, wd, jnp.float32,
            num_experts=e, block_m=16, interpret=True,
        ))
        ref = loss(lambda x_, wg_: _reference(
            x_, probs, sort, wg_, wu, wd, jnp.float32
        ))
        gf = jax.grad(fused, argnums=(0, 1))(x, wg)
        gr = jax.grad(ref, argnums=(0, 1))(x, wg)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
            )

    def test_gather_fit_gate_falls_back(self, monkeypatch):
        """Unaligned token counts (n % 8 != 0) must silently use the
        two-step aligned path, not the resident-x kernel."""
        from d9d_tpu.ops.moe_pallas import _gather_fits

        assert _gather_fits(96, 192, 64, 32, 16, 4, num_experts=8)
        assert not _gather_fits(
            97, 194, 64, 32, 16, 4, num_experts=8  # misaligned
        )
        # the SMEM estimate must count aligned_metadata's real pair_src
        # length ((ceil(m/bm) + E) * bm), so a huge expert count alone
        # can veto even when VMEM residency fits
        assert not _gather_fits(96, 192, 64, 32, 16, 4, num_experts=8192)
        monkeypatch.setenv("D9D_TPU_MOE_FFN_VMEM_BUDGET", "1024")
        assert not _gather_fits(
            96, 192, 64, 32, 16, 4, num_experts=8  # over budget
        )

    @pytest.mark.slow  # ~10s compile-bound on the 2-core rig
    def test_gradients_match_reference(self):
        x, ids, probs, wg, wu, wd = _problem(seed=3)
        e = wg.shape[0]
        sort = sort_tokens_by_expert(ids, e)
        cot = jnp.asarray(
            np.random.RandomState(9).randn(*x.shape), jnp.float32
        )

        def loss_ref(x_, probs_, wg_, wu_, wd_):
            return (
                _reference(x_, probs_, sort, wg_, wu_, wd_, jnp.float32)
                * cot
            ).sum()

        def loss_fused(x_, probs_, wg_, wu_, wd_):
            return (
                fused_moe_ffn_apply(
                    x_, probs_, sort, wg_, wu_, wd_, jnp.float32,
                    num_experts=e, block_m=16, interpret=True,
                )
                * cot
            ).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(
            x, probs, wg, wu, wd
        )
        g_fused = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(
            x, probs, wg, wu, wd
        )
        for a, b in zip(g_fused, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
            )

    @pytest.mark.slow  # ~9s compile-bound on the 2-core rig
    def test_under_remat(self):
        """jax.checkpoint replays the custom fwd; grads stay exact."""
        x, ids, probs, wg, wu, wd = _problem(seed=5)
        e = wg.shape[0]
        sort = sort_tokens_by_expert(ids, e)

        def f(x_):
            return fused_moe_ffn_apply(
                x_, probs, sort, wg, wu, wd, jnp.float32,
                num_experts=e, block_m=16, interpret=True,
            ).sum()

        g_plain = jax.grad(f)(x)
        g_remat = jax.grad(jax.checkpoint(f))(x)
        np.testing.assert_allclose(
            np.asarray(g_remat), np.asarray(g_plain), rtol=1e-6, atol=1e-6
        )

    def test_bf16_path(self):
        x, ids, probs, wg, wu, wd = _problem(seed=7, dtype=jnp.float32)
        e = wg.shape[0]
        sort = sort_tokens_by_expert(ids, e)
        ref = _reference(
            x.astype(jnp.bfloat16), probs, sort, wg, wu, wd, jnp.bfloat16
        )
        got = fused_moe_ffn_apply(
            x.astype(jnp.bfloat16), probs, sort, wg, wu, wd, jnp.bfloat16,
            num_experts=e, block_m=16, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            rtol=3e-2, atol=3e-2,
        )


@pytest.mark.e2e  # slow tier: 4-seed randomized sweep (r5 quick trim)
@pytest.mark.parametrize("seed", range(4))
def test_fused_parity_random_geometry(seed):
    """Randomized geometry sweep: token counts not divisible by block_m,
    skewed expert loads (including empty experts), k=1..3 — the aligned
    layout must stay exact everywhere."""
    rng = np.random.RandomState(100 + seed)
    e = int(rng.choice([3, 5, 8, 13]))
    k = int(rng.randint(1, min(4, e + 1)))
    n = int(rng.randint(17, 140))
    h = int(rng.choice([16, 48]))
    inter = int(rng.choice([8, 24]))
    block_m = int(rng.choice([8, 32]))
    x = jnp.asarray(rng.randn(n, h), jnp.float32)
    wg = jnp.asarray(rng.randn(e, h, inter) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.randn(e, h, inter) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.randn(e, inter, h) * 0.1, jnp.float32)
    # skewed routing: concentrate most tokens on few experts (hot set at
    # least k wide so the skew branch fires for EVERY seed — with
    # hot < e, some experts also stay empty)
    hot = rng.choice(e, size=max(k, e // 3), replace=False)
    ids_np = np.stack([
        rng.choice(hot, size=k, replace=False)
        if rng.rand() < 0.8
        else rng.choice(e, size=k, replace=False)
        for _ in range(n)
    ])
    ids = jnp.asarray(ids_np, jnp.int32)
    probs = jnp.asarray(rng.rand(n, k) + 0.05, jnp.float32)
    sort = sort_tokens_by_expert(ids, e)
    ref = _reference(x, probs, sort, wg, wu, wd, jnp.float32)
    got = fused_moe_ffn_apply(
        x, probs, sort, wg, wu, wd, jnp.float32,
        num_experts=e, block_m=block_m, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=3e-5, atol=3e-5
    )


class TestFusedCombine:
    """r7 gather-fused combine (ops/moe_pallas.py): the kernel emits the
    token-major combined [N, h] directly — parity fwd + grads vs the
    existing combine, the default-on env knob, and the fit gate."""

    @pytest.mark.parametrize("block_m", [8, 16, 64])
    def test_forward_matches_reference(self, block_m):
        x, ids, probs, wg, wu, wd = _problem(seed=21)
        e = wg.shape[0]
        sort = sort_tokens_by_expert(ids, e)
        ref = _reference(x, probs, sort, wg, wu, wd, jnp.float32)
        got = fused_moe_ffn_apply(
            x, probs, sort, wg, wu, wd, jnp.float32,
            num_experts=e, block_m=block_m, interpret=True,
            gather=True, combine=True,
        )
        # the in-kernel K-sum accumulates in expert-sorted order vs the
        # XLA path's slot order: ulp tolerance, same as the other paths
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_matches_uncombined_gather_variant(self):
        """combine on vs off over the SAME gather kernel inputs."""
        x, ids, probs, wg, wu, wd = _problem(seed=23)
        e = wg.shape[0]
        sort = sort_tokens_by_expert(ids, e)
        off = fused_moe_ffn_apply(
            x, probs, sort, wg, wu, wd, jnp.float32,
            num_experts=e, block_m=16, interpret=True,
            gather=True, combine=False,
        )
        on = fused_moe_ffn_apply(
            x, probs, sort, wg, wu, wd, jnp.float32,
            num_experts=e, block_m=16, interpret=True,
            gather=True, combine=True,
        )
        np.testing.assert_allclose(
            np.asarray(on), np.asarray(off), rtol=2e-5, atol=2e-5
        )

    def test_gradients_match_reference(self):
        """The combine variant rides the same custom_vjp backward (the
        XLA reference chain) — grads must match end to end."""
        x, ids, probs, wg, wu, wd = _problem(seed=25)
        e = wg.shape[0]
        sort = sort_tokens_by_expert(ids, e)
        cot = jnp.asarray(
            np.random.RandomState(9).randn(*x.shape), jnp.float32
        )

        def loss(fn):
            def run(x_, probs_, wg_, wu_, wd_):
                return (fn(x_, probs_, wg_, wu_, wd_) * cot).sum()
            return run

        ref = loss(lambda x_, p_, g_, u_, d_: _reference(
            x_, p_, sort, g_, u_, d_, jnp.float32
        ))
        fused = loss(lambda x_, p_, g_, u_, d_: fused_moe_ffn_apply(
            x_, p_, sort, g_, u_, d_, jnp.float32,
            num_experts=e, block_m=16, interpret=True,
            gather=True, combine=True,
        ))
        g_ref = jax.grad(ref, argnums=(0, 1, 2, 3, 4))(x, probs, wg, wu, wd)
        g_fused = jax.grad(fused, argnums=(0, 1, 2, 3, 4))(
            x, probs, wg, wu, wd
        )
        for a, b in zip(g_fused, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
            )

    def test_env_knob_defaults_on(self, monkeypatch):
        from d9d_tpu.ops.moe import fused_combine_enabled

        monkeypatch.delenv("D9D_TPU_MOE_COMBINE", raising=False)
        assert fused_combine_enabled()
        monkeypatch.setenv("D9D_TPU_MOE_COMBINE", "unfused")
        assert not fused_combine_enabled()

    def test_combine_fit_gate(self, monkeypatch):
        from d9d_tpu.ops.moe_pallas import _combine_fits, _gather_fits

        assert _combine_fits(96, 192, 64, 32, 16, 4, num_experts=8)
        # anything the gather gate rejects, the combine gate rejects
        assert not _combine_fits(97, 194, 64, 32, 16, 4, num_experts=8)
        # a budget that fits the gather residency but not the extra
        # [N, h] output residency routes to the uncombined variant
        gather_only = None
        for budget in range(20_000, 400_000, 10_000):
            monkeypatch.setenv("D9D_TPU_MOE_FFN_VMEM_BUDGET", str(budget))
            if _gather_fits(96, 192, 64, 32, 16, 4, num_experts=8):
                gather_only = budget
                break
        assert gather_only is not None
        assert not _combine_fits(96, 192, 64, 32, 16, 4, num_experts=8)

    def test_skewed_and_empty_experts(self):
        """Every token on one expert: pad tiles and the scatter loop's
        branchless pad handling must stay exact."""
        n, e, k = 32, 6, 2
        rng = np.random.RandomState(31)
        x = jnp.asarray(rng.randn(n, 64), jnp.float32)
        wg = jnp.asarray(rng.randn(e, 64, 32) * 0.1, jnp.float32)
        wu = jnp.asarray(rng.randn(e, 64, 32) * 0.1, jnp.float32)
        wd = jnp.asarray(rng.randn(e, 32, 64) * 0.1, jnp.float32)
        ids = jnp.stack(
            [jnp.full((n,), 3, jnp.int32), jnp.full((n,), 5, jnp.int32)],
            axis=1,
        )
        probs = jnp.asarray(rng.rand(n, k), jnp.float32)
        sort = sort_tokens_by_expert(ids, e)
        ref = _reference(x, probs, sort, wg, wu, wd, jnp.float32)
        got = fused_moe_ffn_apply(
            x, probs, sort, wg, wu, wd, jnp.float32,
            num_experts=e, block_m=8, interpret=True,
            gather=True, combine=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def test_unfused_gate_up_env_knob_exact(monkeypatch):
    """D9D_TPU_MOE_FUSED_GATE_UP=0 (two grouped matmuls, no runtime
    weight concat — the ub1/fp32 A/B tools/roofline.py motivates) must be
    numerically identical to the fused default."""
    import jax.numpy as jnp

    from d9d_tpu.nn.moe import grouped_swiglu_apply

    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(48, 32), jnp.float32)
    wg = jnp.asarray(rng.randn(4, 32, 16) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.randn(4, 32, 16) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.randn(4, 16, 32) * 0.1, jnp.float32)
    ids = jnp.asarray(rng.randint(0, 4, (48, 2)), jnp.int32)
    probs = jnp.asarray(rng.rand(48, 2), jnp.float32)
    sort = sort_tokens_by_expert(ids, 4)
    px, pp = permute_tokens(x, probs, sort)

    # pin the fused default so a leaked env var can't make this vacuous
    monkeypatch.setenv("D9D_TPU_MOE_FUSED_GATE_UP", "1")
    fused = grouped_swiglu_apply(
        px, pp, sort.group_sizes, wg, wu, wd, jnp.float32
    )
    monkeypatch.setenv("D9D_TPU_MOE_FUSED_GATE_UP", "0")
    unfused = grouped_swiglu_apply(
        px, pp, sort.group_sizes, wg, wu, wd, jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(unfused), np.asarray(fused), rtol=1e-6, atol=1e-6
    )


@pytest.mark.e2e  # slow tier: whole-layer double-run (r5 quick trim)
class TestLayerIntegration:
    def test_moe_layer_env_switch(self, monkeypatch):
        """MoELayer output is identical (to tolerance) with the pallas
        FFN backend selected."""
        from d9d_tpu.nn.moe import MoELayer

        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(2, 12, 64), jnp.float32)
        layer = MoELayer(
            hidden_dim=64,
            intermediate_dim_grouped=32,
            num_grouped_experts=8,
            top_k=2,
            dtype=jnp.float32,
        )
        params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
        base = layer.apply(params, x)
        monkeypatch.setenv("D9D_TPU_MOE_FFN", "pallas")
        fused = layer.apply(params, x)
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(base), rtol=2e-5, atol=2e-5
        )
