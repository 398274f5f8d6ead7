"""The system under test, built from a configuration file.

The only module of the benchmark that knows how the program is put
together: presets and model classes are found by the dotted paths the
configuration file gives, the ``Trainer`` is built from providers as
``chip_smoke.py`` and ``example/qwen3_moe/pretrain.py`` build theirs,
and the ``ContinuousBatcher`` as its callers do. A new family is a new
configuration file and a new reference, not an edit here.
"""

import dataclasses
import importlib
import inspect
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from d9d_tpu.core import MeshParameters
from d9d_tpu.loop import (
    CausalLMTask,
    DatasetProvider,
    ModelProvider,
    Trainer,
    TrainerConfig,
)
from d9d_tpu.loop.control.providers import OptimizerProvider
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.nn.sdpa import build_sdpa_backend
from d9d_tpu.optim import StochasticAdamW
from d9d_tpu import parallel
from d9d_tpu.parallel.plan import logical_to_mesh_sharding


# ContinuousBatcher's own default: the fused K-step chunk the cells serve with
CHUNK_K = inspect.signature(ContinuousBatcher).parameters["chunk_size"].default


def resolve(dotted: str):
    """``pkg.mod.attr[.attr]`` -> the object."""
    parts = dotted.split(".")
    for cut in range(len(parts) - 1, 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:cut]))
        except ModuleNotFoundError:
            continue
        for attr in parts[cut:]:
            obj = getattr(obj, attr)
        return obj
    raise ImportError(f"cannot resolve {dotted!r}")


def model_config(config: dict, tiny: bool, **extra):
    """The program's model dataclass: the preset with the file's
    overrides (the ``tiny`` preset on the CPU rig)."""
    source = config["tiny"] if tiny else config
    preset = resolve(source["preset"])()
    return dataclasses.replace(preset, **source.get("overrides", {}), **extra)


def hf_view(cfg) -> dict:
    """The program's model dataclass under the Hugging Face keys the
    configuration files and the references use. At the real size it is
    compared with the file (``check_against_file``); at the tiny size it
    is what the reference is given."""
    view = {
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
    }
    if cfg.mla is None:
        view.update(head_dim=cfg.head_dim, num_experts=cfg.num_experts)
    else:
        scaling = cfg.rope_scaling
        view.update(
            n_routed_experts=cfg.num_experts,
            kv_lora_rank=cfg.mla.kv_lora_rank,
            q_lora_rank=cfg.mla.q_lora_rank,
            qk_nope_head_dim=cfg.mla.qk_nope_head_dim,
            qk_rope_head_dim=cfg.mla.qk_rope_head_dim,
            v_head_dim=cfg.mla.v_head_dim,
            intermediate_size=cfg.intermediate_size,
            first_k_dense_replace=len(cfg.mlp_only_layers),
            routed_scaling_factor=cfg.routed_scaling_factor,
            n_shared_experts=(
                cfg.shared_expert.intermediate_size
                // cfg.moe_intermediate_size
                if cfg.shared_expert is not None else 0
            ),
        )
        if hasattr(scaling, "factor"):
            view["rope_scaling"] = {
                "type": "yarn", "factor": scaling.factor,
                "beta_fast": scaling.beta_fast,
                "beta_slow": scaling.beta_slow,
                "original_max_position_embeddings":
                    scaling.original_max_position,
                # the preset folds mscale into its softmax scale: recover
                # it so the reference recomputes the scale independently
                "mscale_all_dim": _mscale_from_scale(cfg, scaling.factor),
            }
    return view


def _mscale_from_scale(cfg, factor: float) -> float:
    d_qk = cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
    if cfg.mla.softmax_scale is None or factor <= 1:
        return 0.0
    ratio = math.sqrt(cfg.mla.softmax_scale / d_qk ** -0.5)
    return (ratio - 1.0) / (0.1 * math.log(factor))


def _same(a, b) -> bool:
    """Equal, with numbers compared as numbers (1 == 1.0, 0.707 recovered
    from a product to six digits)."""
    numbers = (int, float)
    if (isinstance(a, numbers) and isinstance(b, numbers)
            and not isinstance(a, bool) and not isinstance(b, bool)):
        return abs(float(a) - float(b)) <= 1e-6 * max(1.0, abs(float(b)))
    return a == b


def check_against_file(cfg, config: dict) -> None:
    """Every size the program's dataclass and the file both state must
    agree: the file is the configuration *as it is run*."""
    wrong = {}
    for key, value in hf_view(cfg).items():
        if key not in config:
            continue
        want = config[key]
        if isinstance(value, dict):
            bad = {
                k: (v, want.get(k)) for k, v in value.items()
                if k in want and not _same(v, want[k])
            }
            if bad:
                wrong[key] = bad
        elif not _same(value, want):
            wrong[key] = (value, want)
    if wrong:
        raise ValueError(
            f"the preset and the configuration file disagree: {wrong}"
        )


def sizes(config: dict, tiny: bool):
    """``(the program's model dataclass, the sizes the reference reads)``.
    At the real size the reference reads the configuration file, which
    must agree with the dataclass; at the tiny size it reads the
    dataclass's own view."""
    cfg = model_config(config, tiny)
    if tiny:
        return cfg, hf_view(cfg)
    check_against_file(cfg, config)
    return cfg, config


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def reference_module(config: dict):
    """The family's plain reference: ``logits(params, hf, tokens)`` and
    the ``loss(params, hf, tokens, labels)`` it would be trained on."""
    reference = importlib.import_module(
        f"benchmarks.references.{config['reference']}"
    )
    assert callable(reference.logits) and callable(reference.loss)
    return reference


def unboxed(params):
    """Plain nested dict of arrays, as the references read it."""
    return nn.meta.unbox(params)


# -- training ----------------------------------------------------------------


class BenchModel(ModelProvider):
    def __init__(self, config: dict, cfg, ctx, *, sharded: bool):
        self.config, self.cfg, self.ctx, self.sharded = (
            config, cfg, ctx, sharded
        )

    def build_module(self, stage):
        dtype = dtype_of(self.config["dtype"])
        return resolve(self.config["model_class"])(
            config=self.cfg, sdpa=build_sdpa_backend(), stage=stage,
            act_sharding=self.ctx.batch_sharding() if self.sharded else None,
            dtype=dtype, param_dtype=dtype_of(self.config["param_dtype"]),
        )

    def build_plan(self, ctx):
        return getattr(parallel, self.config["plan"] + "_plan")(ctx)

    def sample_inputs(self, batch_size, seq_len):
        z = jnp.zeros((batch_size, seq_len), jnp.int32)
        return (z, z, z)


class IteratorDataset(DatasetProvider):
    """Hands the Trainer whatever the traffic generator yields."""

    def __init__(self, batches):
        self.batches = batches

    def build(self):
        return self.batches


class BenchOptimizer(OptimizerProvider):
    def __init__(self, moment_dtype):
        self.moment_dtype = moment_dtype

    def build(self, learning_rate):
        return StochasticAdamW(
            learning_rate, weight_decay=0.0, moment_dtype=self.moment_dtype
        )


def mesh_context(config: dict, devices):
    return MeshParameters(**config.get("mesh", {})).build(list(devices))


def sharded_model_config(config: dict, ctx, tiny: bool):
    """The dataclass a mesh needs: expert-parallel axes for an EP plan."""
    if config["plan"] != "fsdp_ep":
        return model_config(config, tiny)
    return model_config(
        config, tiny, ep_axes=ctx.ep_shard_axes,
        moe_token_axes=(ctx.batch_axes, ctx.sequence_axes),
    )


def seeded_params(module, sample: tuple, seed: int, mesh, plan):
    """The module's variables from the seed, each placed in its shard on
    ``mesh`` under ``plan``: ``model_factory.init_sharded_params`` with
    the key as an argument of the jitted init. The program's own closes
    over the key, so the seed is a constant of its init program and
    every new seed compiled it anew (16 s on one chip, 70 to 100 s on
    four; my chip runs, PR 24). This one is in the compile cache after
    a checkout's first run, whatever the seed."""

    def init(key):
        variables = module.init(key, *sample)
        return {k: v for k, v in variables.items() if k != "moe_stats"}

    key = jax.random.PRNGKey(seed % (2**31 - 1))
    spec = nn.get_partition_spec(jax.eval_shape(init, key))
    shardings = logical_to_mesh_sharding(spec, mesh, plan.rules)
    return nn.unbox(jax.jit(init, out_shardings=shardings)(key))


def build_trainer(config: dict, mix: dict, cfg, ctx, seed: int, batches,
                  *, total_steps: int) -> Trainer:
    """The Trainer with its weights from ``seed``. Its own seed is fixed,
    so the init program it compiles is one for every run, and the
    weights it made are replaced by ``seeded_params``; the optimizer
    state it made is zeros whatever the seed. The Trainer's weights are
    freed before the seeded ones are made, so that those take their
    place on the chip and leave no hole below the optimizer state: the
    one-layer Qwen3 step claims 88 % of the chip and needs the rest in
    one piece."""
    provider = BenchModel(
        config, cfg, ctx, sharded=config["plan"] != "replicate"
    )
    microbatch = mix["sequences"] // mix.get("microbatches", 1)
    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=mix["sequences"], microbatch_size=microbatch,
            seq_len=mix["seq_len"], total_steps=total_steps,
            seed=0, telemetry_console=False, **mix.get("trainer", {}),
        ),
        model_provider=provider,
        dataset_provider=IteratorDataset(batches),
        task=CausalLMTask(),
        optimizer_provider=BenchOptimizer(dtype_of(config["param_dtype"])),
    )
    trainer.params = None
    trainer.params = seeded_params(
        trainer.module, provider.sample_inputs(microbatch, mix["seq_len"]),
        seed, ctx.mesh, provider.build_plan(ctx),
    )
    return trainer


# -- serving -----------------------------------------------------------------


def decode_model(config: dict, cfg, decode_max_length: int):
    dtype = dtype_of(config["dtype"])
    return resolve(config["model_class"])(
        config=cfg, sdpa=build_sdpa_backend(), dtype=dtype,
        param_dtype=dtype_of(config["param_dtype"]),
        decode_max_length=decode_max_length,
    )


def seeded_weights(model, seed: int):
    """Weights on the device in one jitted call from the seed, in the
    type they are served in."""
    z = jnp.zeros((1, 8), jnp.int32)
    init = jax.jit(
        lambda key: model.clone(decode_max_length=0).init(key, z, z, z)
    )
    return init(jax.random.PRNGKey(seed % (2**31 - 1)))["params"]


def build_batcher(model, params, serving: dict) -> ContinuousBatcher:
    """``chunk_size`` is left at the batcher's default."""
    return ContinuousBatcher(
        model, params, batch_size=serving["slots"],
        page_size=serving["page_size"],
    )
