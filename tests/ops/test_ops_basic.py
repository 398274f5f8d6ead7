import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.lax import GatherScatterMode

from d9d_tpu.ops import (
    LM_IGNORE_INDEX,
    RopeStyle,
    apply_rope,
    compute_rope_frequencies,
    eager_sdpa,
    linear_cross_entropy,
    make_rope_cos_sin,
    rms_norm,
    silu_mul,
)
from tests.jaxpr_tools import count, equations


def rng(*shape, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype=dtype)


class TestRmsNorm:
    def test_matches_manual(self):
        x = rng(4, 16)
        w = rng(16, seed=1) * 0.1 + 1.0
        out = rms_norm(x, w)
        expected = (
            np.asarray(x)
            / np.sqrt(np.mean(np.asarray(x) ** 2, -1, keepdims=True) + 1e-6)
            * np.asarray(w)
        )
        np.testing.assert_allclose(out, expected, rtol=1e-5)

    def test_zero_centered(self):
        x = rng(4, 16)
        w = jnp.zeros(16)
        out = rms_norm(x, w, zero_centered=True)
        base = rms_norm(x, jnp.ones(16))
        np.testing.assert_allclose(out, base, rtol=1e-6)

    def test_preserves_dtype(self):
        x = rng(4, 16).astype(jnp.bfloat16)
        assert rms_norm(x, jnp.ones(16)).dtype == jnp.bfloat16


class TestSiluMul:
    def test_matches_torch(self):
        import torch

        g, u = rng(8, 32), rng(8, 32, seed=1)
        out = silu_mul(g, u)
        tg = torch.tensor(np.asarray(g))
        tu = torch.tensor(np.asarray(u))
        expected = (torch.nn.functional.silu(tg) * tu).numpy()
        np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)


class TestRope:
    def test_half_style_matches_hf(self):
        """HALF layout must match the HuggingFace Llama/Qwen implementation."""
        import torch

        b, t, h, d = 2, 5, 3, 8
        q = rng(b, t, h, d)
        inv_freq, scale = compute_rope_frequencies(d, 10000.0)
        assert scale == 1.0
        positions = jnp.arange(t)
        cos, sin = make_rope_cos_sin(positions, inv_freq, scale)
        out = apply_rope(q, cos[None], sin[None], RopeStyle.HALF)

        # HF oracle: rotate_half with cos/sin duplicated across both halves
        tq = torch.tensor(np.asarray(q)).permute(0, 2, 1, 3)  # [B,H,T,D]
        t_inv = torch.tensor(np.asarray(inv_freq))
        ang = torch.arange(t)[:, None].float() * t_inv[None, :]
        tcos = torch.cat([ang.cos(), ang.cos()], dim=-1)[None, None]
        tsin = torch.cat([ang.sin(), ang.sin()], dim=-1)[None, None]

        def rotate_half(x):
            x1, x2 = x.chunk(2, dim=-1)
            return torch.cat((-x2, x1), dim=-1)

        expected = (tq * tcos + rotate_half(tq) * tsin).permute(0, 2, 1, 3).numpy()
        np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)

    def test_interleaved_rotation_is_norm_preserving(self):
        q = rng(1, 7, 2, 16)
        inv_freq, s = compute_rope_frequencies(16, 1e6)
        cos, sin = make_rope_cos_sin(jnp.arange(7), inv_freq, s)
        out = apply_rope(q, cos[None], sin[None], RopeStyle.INTERLEAVED)
        np.testing.assert_allclose(
            jnp.linalg.norm(out, axis=-1), jnp.linalg.norm(q, axis=-1), rtol=1e-5
        )

    @pytest.mark.parametrize("name", ["linear", "ntk", "yarn"])
    def test_scalings(self, name):
        from d9d_tpu.ops import RopeScalingLinear, RopeScalingNtk, RopeScalingYarn

        scaling = {
            "linear": RopeScalingLinear(factor=4.0),
            "ntk": RopeScalingNtk(factor=4.0),
            "yarn": RopeScalingYarn(factor=4.0, original_max_position=128),
        }[name]
        inv_freq, scale = compute_rope_frequencies(32, 10000.0, scaling)
        base, _ = compute_rope_frequencies(32, 10000.0)
        assert inv_freq.shape == (16,)
        # scaled frequencies must not exceed base (context extension slows rotation)
        assert (np.asarray(inv_freq) <= np.asarray(base) + 1e-9).all()
        if name == "yarn":
            assert scale > 1.0


class TestEagerSdpa:
    def test_causal_matches_torch(self):
        import torch

        b, t, h, d = 2, 9, 4, 16
        q, k, v = rng(b, t, h, d), rng(b, t, h, d, seed=1), rng(b, t, h, d, seed=2)
        out = eager_sdpa(q, k, v, causal=True)
        tq, tk, tv = (
            torch.tensor(np.asarray(x)).permute(0, 2, 1, 3) for x in (q, k, v)
        )
        expected = (
            torch.nn.functional.scaled_dot_product_attention(tq, tk, tv, is_causal=True)
            .permute(0, 2, 1, 3)
            .numpy()
        )
        np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)

    def test_gqa_matches_torch(self):
        import torch

        q = rng(1, 6, 8, 8)
        k, v = rng(1, 6, 2, 8, seed=1), rng(1, 6, 2, 8, seed=2)
        out = eager_sdpa(q, k, v, causal=True)
        tq = torch.tensor(np.asarray(q)).permute(0, 2, 1, 3)
        tk = torch.tensor(np.asarray(k)).permute(0, 2, 1, 3)
        tv = torch.tensor(np.asarray(v)).permute(0, 2, 1, 3)
        expected = (
            torch.nn.functional.scaled_dot_product_attention(
                tq, tk, tv, is_causal=True, enable_gqa=True
            )
            .permute(0, 2, 1, 3)
            .numpy()
        )
        np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)

    def test_sliding_window(self):
        q = rng(1, 8, 1, 4)
        k, v = rng(1, 8, 1, 4, seed=1), rng(1, 8, 1, 4, seed=2)
        out_full = eager_sdpa(q, k, v, causal=True)
        out_win = eager_sdpa(q, k, v, causal=True, window_size=3)
        # early tokens (window not yet binding) identical, later differ
        np.testing.assert_allclose(out_win[:, :3], out_full[:, :3], rtol=1e-5)
        assert not np.allclose(out_win[:, 5:], out_full[:, 5:])

    def test_sinks_reduce_attention_mass(self):
        q = rng(1, 4, 2, 8)
        k, v = rng(1, 4, 2, 8, seed=1), rng(1, 4, 2, 8, seed=2)
        out_nosink = eager_sdpa(q, k, v, causal=True)
        out_sink = eager_sdpa(q, k, v, causal=True, sinks=jnp.full((2,), 10.0))
        # huge sink logit absorbs almost all probability mass
        assert np.abs(np.asarray(out_sink)).max() < np.abs(np.asarray(out_nosink)).max()

    def test_explicit_mask(self):
        q = rng(1, 4, 1, 4)
        k, v = rng(1, 4, 1, 4, seed=1), rng(1, 4, 1, 4, seed=2)
        mask = jnp.ones((1, 1, 4, 4), dtype=bool).at[..., 0].set(False)
        out = eager_sdpa(q, k, v, causal=True, mask=mask)
        assert np.isfinite(np.asarray(out)).all()

    def test_cross_attention_alignment(self):
        """T < S: last query aligns with last key (decode-style)."""
        q = rng(1, 1, 1, 4)
        k, v = rng(1, 6, 1, 4, seed=1), rng(1, 6, 1, 4, seed=2)
        out = eager_sdpa(q, k, v, causal=True)
        full_q = jnp.concatenate([rng(1, 5, 1, 4, seed=9), q], axis=1)
        out_full = eager_sdpa(full_q, k, v, causal=True)
        np.testing.assert_allclose(out[:, 0], out_full[:, -1], rtol=1e-5)


class TestLinearCrossEntropy:
    def _oracle(self, hidden, weight, labels):
        logits = np.asarray(hidden, np.float64) @ np.asarray(weight, np.float64).T
        lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) + logits.max(-1)
        correct = np.take_along_axis(logits, np.maximum(labels, 0)[:, None], -1)[:, 0]
        loss = lse - correct
        loss[np.asarray(labels) == LM_IGNORE_INDEX] = 0.0
        return loss

    def test_matches_oracle(self):
        h, w = rng(10, 8), rng(32, 8, seed=1)
        labels = jnp.array([0, 5, 31, LM_IGNORE_INDEX, 2, 7, 1, 0, 30, LM_IGNORE_INDEX])
        out = linear_cross_entropy(h, w, labels)  # fp32 inputs → exact path
        np.testing.assert_allclose(out, self._oracle(h, w, np.asarray(labels)), rtol=1e-5)

    def test_bf16_matmul_policy_close_to_fp32(self):
        """bf16 inputs select the bf16-in/fp32-accum MXU policy by default
        and stay within bf16 rounding of the fp32 path (the softmax math is
        fp32 in both)."""
        h, w = rng(64, 32), rng(128, 32, seed=1)
        labels = jnp.arange(64) % 128
        ref = linear_cross_entropy(h, w, labels)  # fp32 path
        out = linear_cross_entropy(
            h.astype(jnp.bfloat16), w.astype(jnp.bfloat16), labels
        )
        np.testing.assert_allclose(out, ref, rtol=0.05, atol=0.05)
        # and the dtype-inferred default equals the explicit policy
        explicit = linear_cross_entropy(
            h.astype(jnp.bfloat16), w.astype(jnp.bfloat16), labels,
            matmul_dtype="bf16",
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(explicit))

    def test_chunked_equals_unchunked(self):
        h, w = rng(100, 8), rng(64, 8, seed=1)
        labels = jnp.arange(100) % 64
        full = linear_cross_entropy(h, w, labels, chunk_size=1024)
        chunked = linear_cross_entropy(h, w, labels, chunk_size=16)
        np.testing.assert_allclose(full, chunked, rtol=1e-5)

    def test_grads_flow_and_match(self):
        h, w = rng(48, 8), rng(16, 8, seed=1)
        labels = jnp.arange(48) % 16

        def mean_loss(chunk):
            return lambda h, w: linear_cross_entropy(
                h, w, labels, chunk_size=chunk
            ).mean()

        g_full = jax.grad(mean_loss(1024), argnums=(0, 1))(h, w)
        g_chunk = jax.grad(mean_loss(8), argnums=(0, 1))(h, w)
        for a, b in zip(g_full, g_chunk):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)

    def test_softcap(self):
        h, w = rng(4, 8), rng(16, 8, seed=1)
        labels = jnp.array([0, 1, 2, 3])
        out = linear_cross_entropy(h, w, labels, logit_softcap=5.0)
        assert out.shape == (4,)
        assert np.isfinite(np.asarray(out)).all()


def _loop_carries(jaxpr):
    """Shape and dtype of every scan's loop state (its carry, not what
    it scans)."""
    shapes = []
    for eqn in equations(jaxpr):
        if eqn.primitive.name == "scan":
            consts, carry = eqn.params["num_consts"], eqn.params["num_carry"]
            shapes += [
                (v.aval.shape, v.aval.dtype)
                for v in eqn.invars[consts : consts + carry]
            ]
    return shapes


def _product_shapes(jaxpr):
    return [
        eqn.outvars[0].aval.shape
        for eqn in equations(jaxpr)
        if eqn.primitive.name == "dot_general"
    ]


# (tokens, vocabulary, single-slab tokens, slab budget in logits,
# logit_softcap): the module's two thresholds shrunk until toy shapes
# take the block loop
_BLOCK_CASES = {
    # V = 2^5 x 11: two blocks of 128 columns and a rest of 96
    "ragged-vocabulary": (48, 352, 8, 48 * 128, None),
    # 102 = 3 tiles of 34 tokens for 100: the last tile is padded
    "token-tiles-softcap": (100, 352, 8, 40 * 128, 5.0),
    "even-vocabulary-softcap": (48, 512, 8, 48 * 128, 5.0),
    # over the token threshold, under the slab's: one block, no rest
    "one-block": (48, 96, 8, 48 * 128, None),
}


class TestLinearCrossEntropyVocabBlocks:
    """``chunk_size="auto"`` above the single-slab size: a scan over
    blocks of the vocabulary, held to the dense float32 computation and
    to the token-chunk loop that ``chunk_size=<int>`` keeps."""

    D = 16

    @staticmethod
    def _shrink(monkeypatch, single_tokens, budget):
        import d9d_tpu.ops.linear_ce as lce

        monkeypatch.setattr(lce, "_AUTO_SINGLE_CHUNK_MAX", single_tokens)
        monkeypatch.setattr(lce, "_AUTO_SINGLE_CHUNK_MAX_LOGITS", budget)
        return lce

    def _inputs(self, n, v, dtype):
        h = rng(n, self.D, dtype=dtype)
        w = rng(v, self.D, seed=1, dtype=dtype)
        labels = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, v)
        labels = labels.at[jnp.array([3, n - 1])].set(LM_IGNORE_INDEX)
        labels = labels.at[0].set(v - 1).at[1].set(0)
        # a per-token cotangent that is not uniform: the task's weights
        cot = jnp.asarray(
            np.random.RandomState(0).choice([0.0, 1.0, 2.5], n), jnp.float32
        )
        return h, w, labels, cot

    @staticmethod
    def _dense(h, w, labels, cot, softcap):
        logits = h.astype(jnp.float32) @ w.astype(jnp.float32).T
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(labels, 0)[:, None], axis=-1
        )[:, 0]
        loss = jax.nn.logsumexp(logits, axis=-1) - picked
        return (jnp.where(labels == LM_IGNORE_INDEX, 0.0, loss) * cot).sum()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["fp32", "bf16"])
    @pytest.mark.parametrize("case", list(_BLOCK_CASES))
    def test_loss_and_gradients_match_dense_and_token_chunks(
        self, monkeypatch, case, dtype
    ):
        n, v, single_tokens, budget, softcap = _BLOCK_CASES[case]
        lce = self._shrink(monkeypatch, single_tokens, budget)
        h, w, labels, cot = self._inputs(n, v, dtype)

        def fused(chunk):
            def total(h, w):
                loss = linear_cross_entropy(
                    h, w, labels, chunk_size=chunk, logit_softcap=softcap
                )
                assert loss.shape == (n,) and loss.dtype == jnp.float32
                return (loss * cot).sum()
            return jax.jit(jax.value_and_grad(total, argnums=(0, 1)))(h, w)

        from unittest import mock

        with mock.patch.object(
            lce, "_block_stats", wraps=lce._block_stats
        ) as blocks, mock.patch.object(
            lce, "_chunk_loss", wraps=lce._chunk_loss
        ) as chunks:
            got, got_grads = fused("auto")
            assert blocks.called and not chunks.called
            slab = max(
                c.args[0].shape[0] * c.args[2].shape[0]
                for c in blocks.call_args_list
            )
            assert slab <= budget
            blocks.reset_mock()
            chunked, chunked_grads = fused(16)
            assert chunks.called and not blocks.called
        dense, dense_grads = jax.jit(
            jax.value_and_grad(self._dense, argnums=(0, 1)), static_argnums=4
        )(h, w, labels, cot, softcap)

        # bf16: the logits carry the operands' rounding (the policy), the
        # gradients one rounding to the parameter's dtype on top
        loss_tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(got, dense, rtol=loss_tol)
        np.testing.assert_allclose(got, chunked, rtol=1e-5)
        for mine, ref, loop in zip(got_grads, dense_grads, chunked_grads):
            assert mine.dtype == dtype and mine.shape == ref.shape
            scale = float(jnp.abs(ref).max())
            tol = (1e-5 if dtype == jnp.float32 else 2e-2) * scale
            np.testing.assert_allclose(
                mine.astype(jnp.float32), ref, atol=tol, rtol=0
            )
            np.testing.assert_allclose(
                mine.astype(jnp.float32), loop.astype(jnp.float32),
                atol=tol, rtol=0,
            )
        # rows of LM_IGNORE_INDEX and of weight 0 leave the hidden state's
        # gradient at exactly zero
        dead = (np.asarray(labels) == LM_IGNORE_INDEX) | (np.asarray(cot) == 0)
        assert dead.any()
        assert not np.asarray(got_grads[0].astype(jnp.float32))[dead].any()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["fp32", "bf16"])
    def test_weight_gradient_is_nearer_dense_than_the_chunk_loop(
        self, monkeypatch, dtype
    ):
        """One float32-accumulated product and one rounding a block where
        the token-chunk loop adds a chunk's product to a carry in the
        parameter's dtype once a chunk: never further from the dense
        gradient. The hidden state's gradient, summed over the blocks in
        float32 and rounded once, stays within one rounding of dense."""
        self._shrink(monkeypatch, 8, 64 * 128)
        n, v = 64, 512
        h, w, labels, cot = self._inputs(n, v, dtype)

        def grads(chunk):
            return jax.jit(jax.grad(
                lambda h, w: (
                    linear_cross_entropy(h, w, labels, chunk_size=chunk) * cot
                ).sum(),
                argnums=(0, 1),
            ))(h, w)

        dense = jax.jit(jax.grad(self._dense, argnums=(0, 1)), static_argnums=4)(
            h, w, labels, cot, None)
        err = lambda got, ref: float(
            jnp.abs(got.astype(jnp.float32) - ref).mean()
        )
        blocks, chunks = grads("auto"), grads(4)  # 16 additions to the carry
        # 1e-6: float32 summation order, where the chunk loop is exact
        assert err(blocks[1], dense[1]) <= err(chunks[1], dense[1]) + 1e-6
        # one rounding of the largest entries (bf16); float32 sums 512
        # columns in another order
        one_rounding = 1e-5 if dtype == jnp.float32 else 2.0**-7
        np.testing.assert_allclose(
            blocks[0].astype(jnp.float32), dense[0].astype(jnp.float32),
            rtol=0, atol=one_rounding * float(jnp.abs(dense[0]).max()),
        )

    def test_frozen_head_pays_for_no_weight_gradient(self, monkeypatch):
        """LoRA and every frozen-head PEFT stack differentiate the hidden
        state alone: the backward then holds no product whose output is a
        block of the weight (plain autodiff, no hand-written rule)."""
        n, v, single_tokens, budget, _ = _BLOCK_CASES["ragged-vocabulary"]
        self._shrink(monkeypatch, single_tokens, budget)
        h, w, labels, cot = self._inputs(n, v, jnp.float32)
        total = lambda h, w: (linear_cross_entropy(h, w, labels) * cot).sum()
        weight_blocks = {(128, self.D), (self.D, 128), (96, self.D),
                         (self.D, 96)}

        frozen = jax.make_jaxpr(jax.grad(total, argnums=0))(h, w).jaxpr
        assert not weight_blocks & set(_product_shapes(frozen))
        assert (n, self.D) in _product_shapes(frozen)
        trained = jax.make_jaxpr(jax.grad(total, argnums=(0, 1)))(h, w).jaxpr
        assert weight_blocks & set(_product_shapes(trained))
        np.testing.assert_allclose(
            jax.grad(total, argnums=0)(h, w),
            jax.grad(self._dense, argnums=0)(h, w, labels, cot, None),
            rtol=1e-4, atol=1e-6,
        )

    @pytest.mark.parametrize("v", [32768, 16384 + 128 * 3],
                             ids=["even", "ragged"])
    def test_backward_carries_no_weight_and_forward_no_logits(self, v):
        """At the module's own thresholds (nothing shrunk; a jaxpr runs
        nothing): above the single-slab size the backward's loop state
        is the hidden state's gradient in float32, never an array of the
        weight's shape, and no ``[N, V]`` array exists in either pass.
        The token-chunk loop carries the weight's whole gradient."""
        n, d = 4096, 8
        h = jax.ShapeDtypeStruct((n, d), jnp.bfloat16)
        w = jax.ShapeDtypeStruct((v, d), jnp.bfloat16)
        labels = jnp.zeros((n,), jnp.int32)

        def backward(chunk):
            return jax.make_jaxpr(jax.grad(
                lambda h, w: linear_cross_entropy(
                    h, w, labels, chunk_size=chunk
                ).sum(),
                argnums=(0, 1),
            ))(h, w).jaxpr

        whole = {(v, d), (d, v)}
        blocks = backward("auto")
        carries = _loop_carries(blocks)
        assert ((n, d), jnp.float32) in carries  # rounded once, at the end
        assert not whole & {shape for shape, _ in carries}
        assert all(
            var.aval.shape != (n, v) and var.aval.shape != (v, n)
            for eqn in equations(blocks) for var in eqn.outvars
        )
        # every block of the weight's gradient is one product over all
        # the tokens: its contraction is n long
        block_products = [
            eqn for eqn in equations(blocks)
            if eqn.primitive.name == "dot_general"
            and eqn.outvars[0].aval.shape[-1] == d
            and eqn.outvars[0].aval.shape != (n, d)
        ]
        assert block_products
        for eqn in block_products:
            (lhs_contract, _), _ = eqn.params["dimension_numbers"]
            assert eqn.invars[0].aval.shape[lhs_contract[0]] == n
        assert whole & {shape for shape, _ in _loop_carries(backward(512))}

    def test_tied_table_through_the_head(self, monkeypatch):
        """A tied head is handed the embedding table: through the block
        loop the gradient lands in the table, and in the hidden state."""
        from d9d_tpu.nn.heads import LanguageModellingHead

        n, v, single_tokens, budget, _ = _BLOCK_CASES["ragged-vocabulary"]
        lce = self._shrink(monkeypatch, single_tokens, budget)
        h, table, labels, cot = self._inputs(n, v, jnp.float32)
        head = LanguageModellingHead(
            vocab_ranges=(("default", v),), hidden_size=self.D, tied=True,
            dtype=jnp.float32,
        )

        def total(h, table):
            loss = head.apply(
                {}, h.reshape(2, n // 2, self.D), labels.reshape(2, n // 2),
                table,
            )
            return (loss.reshape(-1) * cot).sum()

        from unittest import mock

        with mock.patch.object(
            lce, "_block_stats", wraps=lce._block_stats
        ) as blocks:
            got = jax.grad(total, argnums=(0, 1))(h, table)
            assert blocks.called
        want = jax.grad(self._dense, argnums=(0, 1))(h, table, labels, cot, None)
        for mine, ref in zip(got, want):
            assert np.abs(ref).max() > 0
            np.testing.assert_allclose(mine, ref, rtol=1e-4, atol=1e-6)

    def test_row_sharded_head_on_a_mesh_keeps_the_token_chunk_program(
        self, monkeypatch, devices
    ):
        """Four devices, tokens and the head's rows sharded over
        ``dp_shard``: under the ambient mesh ``"auto"`` lowers to the
        token-chunk loop's program, so the head is gathered no more than
        before, and loss and gradients equal the unsharded call's."""
        from unittest import mock

        from jax.sharding import NamedSharding, PartitionSpec as P

        from d9d_tpu.core import compat
        from d9d_tpu.core.mesh import AXIS_DP_SHARD, MESH_AXIS_NAMES

        lce = self._shrink(monkeypatch, 8, 64 * 128)
        n, v = 64, 512
        h, w, labels, cot = self._inputs(n, v, jnp.float32)

        def step(chunk):
            def total(h, w, labels, cot):
                loss = linear_cross_entropy(h, w, labels, chunk_size=chunk)
                return (loss * cot).sum()
            return jax.value_and_grad(total, argnums=(0, 1))

        alone = jax.jit(step("auto"))(h, w, labels, cot)  # the block loop

        # MeshParameters(dp_shard=4).build(...)'s mesh, ambient for this
        # test alone (build() leaves its mesh set)
        mesh = jax.sharding.Mesh(
            np.asarray(devices[:4]).reshape(1, 1, 4, 1, 1, 1),
            MESH_AXIS_NAMES,
            **compat.mesh_axis_types_kwargs(len(MESH_AXIS_NAMES)),
        )
        rows = NamedSharding(mesh, P(AXIS_DP_SHARD))
        placed = [jax.device_put(x, rows) for x in (h, w, labels, cot)]
        with compat.set_mesh(mesh), mock.patch.object(
            lce, "_chunk_loss", wraps=lce._chunk_loss
        ) as chunks, mock.patch.object(
            lce, "_block_stats", wraps=lce._block_stats
        ) as blocks:
            compiled, parent = (
                jax.jit(step(chunk), out_shardings=(None, (rows, rows)))
                .lower(*placed).compile()
                for chunk in ("auto", 512)
            )
            assert chunks.called and not blocks.called
        sharded = compiled(*placed)
        gathers = lambda c: c.as_text().count("all-gather")
        assert gathers(compiled) <= gathers(parent)
        np.testing.assert_allclose(sharded[0], alone[0], rtol=1e-5)
        for mine, ref in zip(sharded[1], alone[1]):
            np.testing.assert_allclose(mine, ref, rtol=1e-4, atol=1e-6)


class TestPartialRope:
    def test_gqa_partial_rope_runs_and_passes_through(self):
        """rope_fraction=0.5: second half of head dims must be untouched by rotation."""
        import flax.linen as nn

        from d9d_tpu.nn.attention import GroupedQueryAttention

        d = 16
        module = GroupedQueryAttention(
            hidden_size=32, num_heads=2, num_kv_heads=2, head_dim=d,
            sdpa=eager_sdpa, rope_fraction=0.5, dtype=jnp.float32,
        )
        x = rng(1, 6, 32)
        inv_freq, s = compute_rope_frequencies(d // 2, 10000.0)
        cos, sin = make_rope_cos_sin(jnp.arange(6), inv_freq, s)
        params = module.init(jax.random.PRNGKey(0), x, cos[None], sin[None])
        out = module.apply(params, x, cos[None], sin[None])
        assert out.shape == (1, 6, 32)
        assert np.isfinite(np.asarray(out)).all()


class TestStableExpertOrder:
    """The sort-free grouping permutation must reproduce stable argsort
    exactly (ops/moe.py: one-hot -> cumsum -> scatter replaces the bitonic
    sort the MoE layer would otherwise run per layer per microbatch)."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_matches_stable_argsort(self, seed):
        from d9d_tpu.ops.moe import sort_tokens_by_expert, stable_expert_order

        r = np.random.RandomState(seed)
        n, k, e = r.randint(1, 200), r.randint(1, 9), r.randint(1, 65)
        ids = jnp.asarray(r.randint(0, e, size=(n, k)), jnp.int32)
        flat = ids.reshape(-1)
        got_idx, got_dest, got_sizes = stable_expert_order(flat, e)
        np.testing.assert_array_equal(
            np.asarray(got_dest)[np.asarray(got_idx)], np.arange(flat.shape[0])
        )
        np.testing.assert_array_equal(got_idx, jnp.argsort(flat, stable=True))
        np.testing.assert_array_equal(got_sizes, jnp.bincount(flat, length=e))
        ts = sort_tokens_by_expert(ids, e)
        np.testing.assert_array_equal(ts.token_idx, got_idx // k)

    def test_empty_experts_and_single_expert(self):
        from d9d_tpu.ops.moe import stable_expert_order

        # all pairs on one expert; other experts empty
        flat = jnp.full((7,), 3, jnp.int32)
        idx, _, sizes = stable_expert_order(flat, 8)
        np.testing.assert_array_equal(idx, np.arange(7))
        assert int(sizes[3]) == 7 and int(sizes.sum()) == 7


def test_stable_expert_order_argsort_fallback_matches(monkeypatch):
    """Above the M*E threshold the grouping falls back to a stable argsort
    (ADVICE r3: the one-hot's O(M*E) HBM traffic inverts at large expert
    counts); both paths must produce identical permutations."""
    import d9d_tpu.ops.moe as moe_ops

    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, 13, 2048).astype(np.int32))
    fast = moe_ops.stable_expert_order(ids, 13)
    monkeypatch.setattr(moe_ops, "_ONE_HOT_GROUPING_LIMIT", 0)
    slow = moe_ops.stable_expert_order(ids, 13)
    for a, b in zip(fast, slow):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _take_rows(x, idx, *unused, mode=None):
    """``permute_rows`` and ``spread_to_pairs`` as the plain gather."""
    return jnp.take(x, idx, axis=0, mode=mode)


def _take_and_fold(y, token_idx, dest, num_tokens, mode=None):
    """``combine_pairs`` as the plain gather and k-row sum."""
    return jnp.take(y, dest, axis=0, mode=mode).reshape(
        num_tokens, -1, y.shape[-1]
    ).sum(axis=1)


class TestRowMovementTransposes:
    """``permute_rows``, ``spread_to_pairs`` and ``combine_pairs`` carry
    their transposes as gathers (ops/moe.py): the same gradients as
    ``jnp.take`` autodiff, whose transposes are scatter-adds, on both
    branches of ``stable_expert_order``, with and without remat."""

    N, K, E, D = 37, 4, 8, 16

    def _sort(self, monkeypatch, grouping, routing):
        import d9d_tpu.ops.moe as moe_ops

        if grouping == "argsort":
            monkeypatch.setattr(moe_ops, "_ONE_HOT_GROUPING_LIMIT", 0)
        r = np.random.RandomState(4)
        if routing == "padded":
            # a receive buffer's labels: a fifth real, the padding rows
            # all clipped onto the last expert
            ids = np.full((self.N, self.K), self.E - 1)
            ids[: self.N // 5] = r.randint(0, self.E, (self.N // 5, self.K))
        else:
            ids = r.randint(0, self.E, (self.N, self.K))
        sort = moe_ops.sort_tokens_by_expert(jnp.asarray(ids, jnp.int32), self.E)
        # the precondition of every transpose below: a full permutation
        # and its inverse, whichever branch made them
        rows = np.arange(self.N * self.K)
        np.testing.assert_array_equal(np.asarray(sort.dest)[sort.sort_idx], rows)
        np.testing.assert_array_equal(np.asarray(sort.sort_idx)[sort.dest], rows)
        return moe_ops, sort

    @staticmethod
    def _grad(fn, operand, remat):
        weights = rng(*jax.eval_shape(fn, operand).shape, seed=9)
        fn = jax.checkpoint(fn) if remat else fn
        loss = lambda v: (jnp.tanh(fn(v)) * weights).sum()
        return jax.jit(jax.grad(loss))(operand)

    @pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
    @pytest.mark.parametrize("routing", ["random", "padded"])
    @pytest.mark.parametrize("grouping", ["one_hot", "argsort"])
    def test_gradients_match_take_autodiff(
        self, monkeypatch, grouping, routing, remat
    ):
        moe_ops, sort = self._sort(monkeypatch, grouping, routing)
        n = self.N
        pairs, tokens = rng(n * self.K, self.D, seed=1), rng(n, self.D, seed=2)

        cases = [  # (given transpose, plain take, indices, operand, bit-equal)
            (moe_ops.permute_rows, _take_rows,
             (sort.sort_idx, sort.dest), pairs, True),
            (moe_ops.permute_rows, _take_rows,
             (sort.dest, sort.sort_idx), pairs, True),
            (moe_ops.permute_rows, _take_rows,
             (sort.sort_idx, sort.dest), pairs[:, 0], True),
            (moe_ops.combine_pairs, _take_and_fold,
             (sort.token_idx, sort.dest, n), pairs, True),
            # the k-row fold adds a token's k cotangents in pair order,
            # the scatter-add in sorted order
            (moe_ops.spread_to_pairs, _take_rows,
             (sort.token_idx, sort.dest), tokens, False),
        ]
        for given_fn, plain_fn, indices, operand, bit_equal in cases:
            given = lambda v: given_fn(v, *indices)
            plain = lambda v: plain_fn(v, *indices)
            np.testing.assert_array_equal(given(operand), plain(operand))
            got = self._grad(given, operand, remat)
            want = self._grad(plain, operand, False)
            assert np.abs(want).max() > 0
            if bit_equal:
                np.testing.assert_array_equal(got, want)
            else:
                np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("grouping", ["one_hot", "argsort"])
    def test_local_path_backward_holds_no_scatter_add(
        self, monkeypatch, grouping
    ):
        moe_ops, sort = self._sort(monkeypatch, grouping, "random")
        x, probs = rng(self.N, self.D), rng(self.N, self.K, seed=3)

        def loss(x, probs):
            rows, row_probs = moe_ops.permute_tokens(x, probs, sort)
            y = jnp.tanh(rows) * row_probs[:, None]
            return (moe_ops.unpermute_combine(y, sort, self.N) ** 2).sum()

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, probs).jaxpr
        assert not count(jaxpr, lambda eqn: "scatter" in eqn.primitive.name)
        # three forward, three transposed
        assert count(jaxpr, lambda eqn: eqn.primitive.name == "gather") >= 6

    def test_forward_only_program_is_the_plain_take(self, monkeypatch):
        """Without a gradient the given transposes leave no mark: serving
        and ``generate`` lower to the program plain ``jnp.take`` gives."""
        moe_ops, sort = self._sort(monkeypatch, "one_hot", "random")
        n = self.N
        x, probs = rng(n, self.D), rng(n, self.K, seed=3)

        def lowered(spread, permute, combine):
            def forward(x, probs, token_idx, sort_idx, dest):
                pair_probs = permute(probs.reshape(-1), sort_idx, dest)
                rows = spread(x, token_idx, dest) * pair_probs[:, None]
                return combine(permute(rows, sort_idx, dest), token_idx, dest, n)

            return jax.jit(forward).lower(
                x, probs, sort.token_idx, sort.sort_idx, sort.dest
            ).as_text()

        given = lowered(
            moe_ops.spread_to_pairs, moe_ops.permute_rows, moe_ops.combine_pairs
        )
        clipped = functools.partial(_take_rows, mode="clip")
        plain = lowered(
            clipped, clipped, functools.partial(_take_and_fold, mode="clip")
        )
        assert "gather" in plain and given == plain

    @pytest.mark.parametrize(
        "name", ["permute_rows", "spread_to_pairs", "combine_pairs", "spread_held"]
    )
    def test_a_row_gather_leaves_no_fill_pass(self, monkeypatch, name):
        """Every index of the four row movements is a row by construction
        and the gather says so: neither the value nor the given transpose
        traces a gather of ``[rows, D]`` rows in ``jnp.take``'s default
        mode, which lowers to the gather and a ``select`` over all it
        gathered (NaN where an index was out of range), and values and
        gradients are, bit for bit, what the same movement through the
        fill gives."""
        moe_ops, sort = self._sort(monkeypatch, "one_hot", "random")
        n, k, d = self.N, self.K, self.D
        pairs, tokens = rng(n * k, d, seed=1), rng(n, d, seed=2)
        held_experts, buf_rows = 3, 96
        held = moe_ops.sort_held_pairs(
            jnp.minimum(
                jnp.asarray(
                    np.random.RandomState(5).randint(0, self.E, (n, k)),
                    jnp.int32,
                ),
                held_experts,
            ),
            held_experts, buf_rows,
        )
        assert 0 < int(held.rows_held) < buf_rows  # live rows and padding
        live = (jnp.arange(buf_rows) < held.rows_held)[:, None]
        # (the movement, its operand, the value and the transpose through
        # the fill)
        fn, operand, filled, filled_transpose = {
            "permute_rows": (
                lambda v: moe_ops.permute_rows(v, sort.sort_idx, sort.dest),
                pairs,
                lambda v: _take_rows(v, sort.sort_idx),
                lambda g: _take_rows(g, sort.dest)),
            "spread_to_pairs": (
                lambda v: moe_ops.spread_to_pairs(v, sort.token_idx, sort.dest),
                tokens,
                lambda v: _take_rows(v, sort.token_idx),
                lambda g: _take_and_fold(g, sort.token_idx, sort.dest, n)),
            "combine_pairs": (
                lambda v: moe_ops.combine_pairs(
                    v, sort.token_idx, sort.dest, n),
                pairs,
                lambda v: _take_and_fold(v, sort.token_idx, sort.dest, n),
                lambda g: _take_rows(g, sort.token_idx)),
            "spread_held": (
                lambda v: moe_ops.spread_held(v, held, k),
                tokens,
                lambda v: jnp.where(
                    live, _take_rows(v, held.token_of_row), 0.0),
                # the fold has clipped since PR 49: its own reference
                lambda g: moe_ops.fold_held(g, held, n, k)),
        }[name]
        cotangent = rng(*jax.eval_shape(fn, operand).shape, seed=9)

        def pull_back(v, g):
            return jax.vjp(fn, v)[1](g)[0]

        def filling_row_gathers(program, *args):
            return count(
                jax.make_jaxpr(program)(*args).jaxpr,
                lambda eqn: eqn.primitive.name == "gather"
                and eqn.params["mode"] == GatherScatterMode.FILL_OR_DROP
                and eqn.invars[0].aval.shape[1:] == (d,),
            )

        assert filling_row_gathers(filled, operand) == 1  # the fill is seen
        assert filling_row_gathers(fn, operand) == 0
        # (the pull-back's trace holds the forward too)
        assert filling_row_gathers(pull_back, operand, cotangent) == 0
        value, grad = fn(operand), pull_back(operand, cotangent)
        assert np.abs(value).max() > 0 and np.abs(grad).max() > 0
        np.testing.assert_array_equal(value, filled(operand))
        np.testing.assert_array_equal(grad, filled_transpose(cotangent))

    @pytest.mark.parametrize("held_experts", [0, 3], ids=["none_held", "held"])
    @pytest.mark.parametrize("grouping", ["one_hot", "argsort"])
    def test_every_gathered_index_is_a_row(
        self, monkeypatch, grouping, held_experts
    ):
        """What the clipping gathers promise, on both branches of
        ``stable_expert_order``: ``sort_idx`` and ``dest`` permute the
        pair rows, ``sort_idx // K`` is a token, and with padding behind
        the held rows (or nothing held at all) every row's token, every
        slot's pair and every slot's row still is one. A clip would read
        row 0 or the last row for an index that was not, and say nothing."""
        moe_ops, sort = self._sort(monkeypatch, grouping, "random")
        n, k = self.N, self.K
        pair_rows = np.arange(n * k)
        np.testing.assert_array_equal(np.sort(sort.sort_idx), pair_rows)
        np.testing.assert_array_equal(np.sort(sort.dest), pair_rows)
        np.testing.assert_array_equal(sort.token_idx, np.asarray(sort.sort_idx) // k)

        def within(values, size):
            values = np.asarray(values)
            return values.min() >= 0 and values.max() < size

        assert within(sort.token_idx, n)
        buf_rows = 96
        ids = np.random.RandomState(5).randint(0, self.E, (n, k))
        # a pair routed elsewhere carries the label ``num_held``; with no
        # expert held (one stands in for the group sizes) that is all of them
        local = np.minimum(ids, held_experts) if held_experts else ids * 0 + 1
        held = moe_ops.sort_held_pairs(
            jnp.asarray(local, jnp.int32), max(held_experts, 1), buf_rows
        )
        rows_held = int(held.rows_held)
        assert rows_held == (local < max(held_experts, 1)).sum() < buf_rows
        assert (rows_held > 0) == bool(held_experts)
        assert within(held.pair_of_row, n * k) and within(held.token_of_row, n)
        # token_of_slot is pair_of_slot // K: in range only if the pair is
        assert within(held.token_of_slot, n) and within(held.row_of_slot, n * k)
        # the live slots are the held pairs in pair order, and each finds
        # the row that holds it
        np.testing.assert_array_equal(
            np.asarray(held.pair_of_row)[np.asarray(held.row_of_slot)[:rows_held]],
            np.flatnonzero(local.reshape(-1) < max(held_experts, 1)),
        )
