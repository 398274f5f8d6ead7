"""StochasticAdamW: AdamW that keeps bf16 parameters via stochastic rounding.

TPU-native rebuild of the reference ``StochasticAdamW``
(d9d/optim/stochastic/adamw.py:43 + kernel/stochastic/adamw_step.py:97):
parameters live in bfloat16, the step is computed in fp32, and the write-back
rounds stochastically so the *expected* parameter trajectory matches fp32
training — no fp32 master copy needed. The RNG key is part of the optimizer
state (reference keeps its own RNG in state_dict), so checkpoints resume the
exact noise stream.

What a draw is: per step and leaf, ``fold_in(fold_in(key, count), leaf_index)``
keys ONE Threefry-2x32 block per element (``ops.stochastic.rounding_fields``),
and its 64 bits are carved into the 16-bit fields that round what is stored in
bf16: field 0 (low half of word 0) the parameter, field 1 (high half of word
0) ``mu``, field 2 (low half of word 1) ``nu``. With fp32 moments only field 0
is used. The generator, not the memory traffic, is what the pass costs on a
TPU, so the three roundings share a block instead of drawing one each.

The object satisfies the trainer's optimizer protocol (``init`` /
``update``) and additionally exposes ``apply_updates`` so the train step can
let the optimizer own the parameter write (required: ``optax.apply_updates``
would round-to-nearest on the final bf16 cast and destroy the stochastic
rounding).
"""

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax

from d9d_tpu.core.types import PyTree
from d9d_tpu.ops.stochastic import rounding_fields, stochastic_round_with_field


class StochasticAdamWState(NamedTuple):
    count: jax.Array
    mu: PyTree
    nu: PyTree
    key: jax.Array


class StochasticAdamW:
    """AdamW with bf16 params + stochastic-rounding write-back.

    ``learning_rate`` may be a float or an optax schedule. Moments default
    to fp32; pass ``moment_dtype=jnp.bfloat16`` to store them rounded too
    (stochastically, from other bits of the parameter's own Threefry block).
    """

    # the train step must NOT down-cast fp32 grads to param dtype for us
    accepts_fp32_grads = True

    def __init__(
        self,
        learning_rate: optax.ScalarOrSchedule,
        *,
        b1: float = 0.9,
        b2: float = 0.95,
        eps: float = 1e-8,
        weight_decay: float = 0.0,
        moment_dtype: jnp.dtype = jnp.float32,
        seed: int = 0,
    ):
        self.learning_rate = learning_rate
        self.b1 = b1
        self.b2 = b2
        self.eps = eps
        self.weight_decay = weight_decay
        self.moment_dtype = moment_dtype
        self.seed = seed

    # -- protocol ------------------------------------------------------

    def init(self, params: PyTree) -> StochasticAdamWState:
        zeros = lambda p: jnp.zeros(p.shape, self.moment_dtype)
        return StochasticAdamWState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree.map(zeros, params),
            nu=jax.tree.map(zeros, params),
            key=jax.random.PRNGKey(self.seed),
        )

    def update(
        self,
        grads: PyTree,
        state: StochasticAdamWState,
        params: PyTree,
    ) -> tuple[PyTree, StochasticAdamWState]:
        """Returns (new_params, new_state) — the "updates" ARE the new
        parameters; ``apply_updates`` below just substitutes them."""
        count = state.count + 1
        # schedules are evaluated at the 0-based step (optax convention);
        # bias correction uses the 1-based count (Adam convention)
        lr = (
            self.learning_rate(state.count)
            if callable(self.learning_rate)
            else self.learning_rate
        )
        c1 = 1.0 - self.b1**count.astype(jnp.float32)
        c2 = 1.0 - self.b2**count.astype(jnp.float32)

        step_key = jax.random.fold_in(state.key, count)

        def leaf_step(p, g, mu, nu, key):
            g32 = g.astype(jnp.float32)
            mu32 = self.b1 * mu.astype(jnp.float32) + (1 - self.b1) * g32
            nu32 = self.b2 * nu.astype(jnp.float32) + (1 - self.b2) * g32**2
            m_hat = mu32 / c1
            v_hat = nu32 / c2
            p32 = p.astype(jnp.float32)
            upd = m_hat / (jnp.sqrt(v_hat) + self.eps) + self.weight_decay * p32
            new_p32 = p32 - lr * upd

            f_p, f_mu, f_nu = rounding_fields(key, p.shape)
            new_p = self._round(new_p32, p.dtype, f_p)
            new_mu = self._round(mu32, self.moment_dtype, f_mu)
            new_nu = self._round(nu32, self.moment_dtype, f_nu)
            return new_p, new_mu, new_nu

        # work on flat leaf lists so tuple-structured param pytrees are safe
        p_leaves, treedef = jax.tree.flatten(params)
        g_leaves = treedef.flatten_up_to(grads)
        mu_leaves = treedef.flatten_up_to(state.mu)
        nu_leaves = treedef.flatten_up_to(state.nu)

        new_p, new_mu, new_nu = [], [], []
        for i, (p, g, mu, nu) in enumerate(
            zip(p_leaves, g_leaves, mu_leaves, nu_leaves)
        ):
            np_, nmu, nnu = leaf_step(p, g, mu, nu, jax.random.fold_in(step_key, i))
            new_p.append(np_)
            new_mu.append(nmu)
            new_nu.append(nnu)

        return treedef.unflatten(new_p), StochasticAdamWState(
            count=count,
            mu=treedef.unflatten(new_mu),
            nu=treedef.unflatten(new_nu),
            key=state.key,
        )

    @staticmethod
    def apply_updates(params: PyTree, updates: PyTree) -> PyTree:
        del params  # updates already carry the rounded new parameters
        return updates

    # -- helpers -------------------------------------------------------

    @staticmethod
    def _round(x32: jax.Array, dtype: Any, field: jax.Array) -> jax.Array:
        if dtype == jnp.bfloat16:
            return stochastic_round_with_field(x32, field)
        return x32.astype(dtype)
