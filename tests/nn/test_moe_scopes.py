"""Scopes on the expert matmuls: in the lowered HLO of a tiny train step
every ``ragged-dot``, forward and both transposes, carries an ``op_name``
that says which matmul it is, on the local and on the EP path, for both
model families. The scopes are metadata only."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.core import MeshParameters
from d9d_tpu.models.deepseek import DeepseekCausalLM, deepseek_v2_tiny
from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig
from d9d_tpu.ops.attention.eager import eager_sdpa
from tests.jaxpr_tools import scoped_equations

# 256 rows a call: past ``ops/moe.py FEW_ROWS_LIMIT``, so the local path is
# the grouped one these tests read (tests/nn/test_moe_few_rows.py has the other)
B, T, VOCAB = 4, 64, 256
MATMUL = re.compile(r"moe/experts/(gate_up|down)")


def qwen3(ep_axes=None):
    return Qwen3MoeCausalLM(
        config=Qwen3MoeConfig.tiny(ep_axes=ep_axes), sdpa=eager_sdpa,
        dtype=jnp.float32,
    )


def deepseek(ep_axes=None):
    assert ep_axes is None
    return DeepseekCausalLM(
        config=deepseek_v2_tiny(VOCAB), sdpa=eager_sdpa, dtype=jnp.float32
    )


@functools.lru_cache(maxsize=None)
def traced_train_step(family, ep_axes=None):
    """The loss and its gradient with respect to every parameter of a tiny
    model, traced and not yet lowered."""
    model = family(ep_axes)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (B, T)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    params = jax.eval_shape(  # the tree's shapes: a trace needs no numbers
        lambda: model.clone().init(
            jax.random.PRNGKey(0), tokens, positions, tokens)["params"])

    def loss(p):
        return model.apply({"params": p}, tokens, positions, tokens).sum()

    return jax.jit(jax.value_and_grad(loss)).trace(params)


@functools.lru_cache(maxsize=None)
def lowered_train_step(family, ep_axes=None):
    """That step lowered for the TPU (the CPU's lowering expands
    ``ragged_dot`` into plain dots; lowering for another platform needs no
    such device) and not yet compiled: StableHLO text with the locations
    that become ``op_name``."""
    traced = traced_train_step(family, ep_axes)
    return traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


def ragged_dots(text):
    """``op_name`` of every ragged-dot in lowered StableHLO text."""
    locations = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    return [
        locations.get(m[1], "") for m in re.finditer(
            r'"?chlo\.ragged_dot"?.* loc\((#loc\d+)\)', text
        )
    ]


@pytest.fixture(scope="module")
def ep_axes():
    ctx = MeshParameters(dp_shard=4, ep_shard=4).build(jax.devices()[:4])
    return tuple(ctx.ep_shard_axes)


@pytest.mark.parametrize("family,path", [
    (qwen3, "local"), (qwen3, "ep"), (deepseek, "local"),
], ids=["qwen3-local", "qwen3-ep", "deepseek-local"])
def test_every_ragged_dot_says_which_matmul_it_is(family, path, ep_axes):
    names = ragged_dots(
        lowered_train_step(family, ep_axes if path == "ep" else None)
    )
    # per expert layer: gate|up and down, each forward and two transposes;
    # on the dropless EP path that is per rung of the buffer ladder, and
    # the exchange's own VJP computes both once more inside the backward
    # branch (the recomputation a layer's remat would do)
    per_layer = 6 if path == "local" else 8
    assert len(names) >= per_layer and len(names) % per_layer == 0, names
    assert all(MATMUL.search(n) for n in names), names
    by = {
        which: [n for n in names if MATMUL.search(n)[1] == which]
        for which in ("gate_up", "down")
    }
    assert len(by["gate_up"]) == len(by["down"]) == len(names) // 2
    for group in by.values():
        backward = [n for n in group if "transpose(" in n]
        if path == "local":
            assert len(backward) == 2 * (len(group) - len(backward))
    if path == "ep":
        # inside shard_map a location is relative to the mapped body; the
        # differentiation wrappers join it when the program is compiled,
        # but for the exchange's own VJP, which differentiates inside it
        under = re.compile(r"ep/expert_compute\)*/moe/experts/")
        assert all(under.search(n) for n in names), names


def test_permute_and_combine_are_scoped_too(ep_axes):
    for axes, marks in ((None, ["moe/permute", "moe/combine"]),
                        (ep_axes, ["moe/permute", "moe/combine",
                                   "ep/dispatch_a2a"])):
        text = lowered_train_step(qwen3, axes)
        for mark in marks:
            assert f"{mark}/" in text, mark


def row_ops(jaxpr, primitives):
    """``(primitive, scope, operand aval)`` of every equation of
    ``primitives`` in ``jaxpr`` and the jaxprs nested in it."""
    for eqn, scope in scoped_equations(jaxpr):
        if eqn.primitive.name in primitives:
            yield eqn.primitive.name, scope, eqn.invars[0].aval


@pytest.mark.parametrize("family,path", [
    (qwen3, "local"), (qwen3, "ep"), (deepseek, "local"),
], ids=["qwen3-local", "qwen3-ep", "deepseek-local"])
def test_moe_backward_scatters_no_rows(family, path, ep_axes):
    """No scatter of float rows is left in the program that is lowered:
    the transposes of the MoE layer's row movements are gathers by the
    inverse permutation (``ops/moe.py permute_rows``, ``spread_to_pairs``,
    ``combine_pairs``). What stays: the int32 index scatters of
    ``stable_expert_order``, the router's ``[B, T, E]`` top-k transpose,
    and on this rig the transposes of the gather-based all-to-all
    emulation (``ep/dispatch_a2a``, ``ep/combine_a2a``; a collective on
    the TPU)."""
    jaxpr = traced_train_step(family, ep_axes if path == "ep" else None).jaxpr
    moved = [
        (name, scope, aval) for name, scope, aval in row_ops(
            jaxpr.jaxpr, ("scatter", "scatter-add", "gather")
        ) if re.search(r"moe/(permute|combine)", scope)
    ]
    floats = [
        (name, scope, aval.str_short()) for name, scope, aval in moved
        if jnp.issubdtype(aval.dtype, jnp.floating)
    ]
    assert [op for op in floats if op[0] != "gather"] == []
    # the mechanism engaged: the backward moves its rows by gathers
    assert any("transpose(" in scope for _, scope, _ in floats), floats
    index_scatters = [aval for name, _, aval in moved if name == "scatter"]
    assert index_scatters and all(
        aval.dtype == jnp.int32 and aval.ndim == 1 for aval in index_scatters
    )
