"""No new knob moved an existing model: the parameter tree each of the
benchmark's configurations builds (paths, shapes and types, from
``jax.eval_shape`` of its tiny preset with the file's overrides) is the
tree it built at the commit before ZAYA1's kind, router form and
residual scales joined the shared decoder (PR 56). The digests were
taken on that commit by this file's own function; the presets at their
published sizes were compared the same way once, by hand, and agreed
(CHANGES.md, PR 56). A PR that means to change a preset's tree changes
its digest here and says so."""

import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from flax.traverse_util import flatten_dict

from benchmarks.harness import build, manifest
from d9d_tpu.ops.attention.eager import eager_sdpa

# configuration -> (digest, leaves) at the parent of PR 56
TREES = {
    "qwen3-30b-a3b-l1": ("e1a98cdd6ff23d7f", 15),
    "qwen3-30b-a3b-decode": ("e1a98cdd6ff23d7f", 15),
    "deepseek-v2-lite-l2": ("9b5c6ae27a25bf03", 27),
    "qwen3-30b-a3b-ep4": ("e1a98cdd6ff23d7f", 15),
    "glm-4.7-flash-decode": ("8b88437d22fe8c77", 32),
    "jamba2-3b-decode": ("2e36df66920ed589", 28),
    "xing4.0-29b-a4b-share8": ("94b1e06587f00d67", 107),
    "mimo-v2-flash-share16-decode": ("bb162a7e0dfb0fac", 47),
    "laguna-xs.2-share8": ("cdbee22058f24344", 55),
    "granite-4.0-h-small-share4-decode": ("c89d46104805245e", 49),
    "solar-open2-250b-share8-decode": ("3c1b2097ec7fe0eb", 90),
}


def tree_digest(config: dict) -> tuple[str, int]:
    cfg = build.model_config(config, tiny=True)
    model = build.resolve(config["model_class"])(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    z = jnp.zeros((1, 8), jnp.int32)
    shapes = nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), z, z, z)["params"]))
    lines = sorted(
        "/".join(path) + " " + str(tuple(leaf.shape)) + " " + str(leaf.dtype)
        for path, leaf in flatten_dict(shapes).items())
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16], len(lines)


@pytest.mark.parametrize("name", TREES)
def test_the_preset_builds_the_tree_it_built(name):
    entry, = (c for c in manifest.manifest()["configs"] if c["name"] == name)
    config = manifest.load_json(manifest.ROOT / entry["file"])
    assert tree_digest(config) == TREES[name]
