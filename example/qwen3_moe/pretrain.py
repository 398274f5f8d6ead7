"""Qwen3-MoE pretraining example — the user-entry-point parity target.

Reference: example/qwen3_moe/pretrain.py (the reference's only runnable
entry point, launched with torchrun). This TPU version is launched with
plain ``python``: single-controller JAX discovers the devices
(``jax.distributed.initialize`` on a pod). One JSON config wires mesh,
model, trainer, optimizer and LR schedule, exactly like the reference's
``ProjectConfig``.

Run on any machine (a virtual 8-device CPU mesh for a smoke test):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python example/qwen3_moe/pretrain.py example/qwen3_moe/pretrain.json

On a TPU slice just drop the env overrides.
"""

import json
import sys
from pathlib import Path

# run as a plain script from anywhere: d9d_tpu lives two levels up and is
# not pip-installed in this environment
sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

import jax
import jax.numpy as jnp
import numpy as np
import pydantic

from d9d_tpu.core import MeshParameters, init_distributed
from d9d_tpu.core.compile_cache import enable_compile_cache
from d9d_tpu.dataset import BufferSortedDataset, pad_stack_1d
from d9d_tpu.loop import (
    CausalLMTask,
    DatasetProvider,
    ModelProvider,
    StatefulDataLoader,
    Trainer,
    TrainerConfig,
)
from d9d_tpu.loop.auto import (
    LRSchedulerConfig,
    OptimizerConfig,
    build_lr_schedule,
    build_optimizer,
)
from d9d_tpu.loop.control.providers import OptimizerProvider
from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig
from d9d_tpu.nn.moe import SharedExpertParameters
from d9d_tpu.nn.sdpa import build_sdpa_backend
from d9d_tpu.parallel import fsdp_ep_plan
from d9d_tpu.tracker import build_tracker


# -----------------------------------
# Configuration schema (pydantic)
# -----------------------------------


class MeshConfig(pydantic.BaseModel):
    pp: int = 1
    dp_replicate: int = 1
    dp_shard: int = 1
    cp_shard: int = 1
    cp_replicate: int = 1
    tp: int = 1
    ep_shard: int = 1


class ModelConfig(pydantic.BaseModel):
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    remat: bool = True
    dtype: str = "bfloat16"
    # hybrid GDN:attention stacks (Qwen3-Next style) — e.g. [0, 1, 2] puts
    # linear attention on those layers; [] keeps pure attention
    linear_attention_layers: list[int] = []
    # q/k/v as one matmul (r4 single-chip MFU lever; must stay off when
    # the mesh has tp>1 — the model raises if violated)
    fused_qkv: bool = False
    # Qwen3-Next-style attention/norm features (example/qwen3_next uses
    # these; defaults match the plain Qwen3-MoE family)
    use_output_gate: bool = False
    rope_fraction: float = 1.0
    zero_centered_norms: bool = False
    # GDN geometry; 0 = derive from the attention dims
    gdn_qk_heads: int = 0
    gdn_v_heads: int = 0
    gdn_head_qk_dim: int = 0
    gdn_head_v_dim: int = 0
    gdn_conv_size: int = 4
    # always-on gated shared expert (0 = none)
    shared_expert_intermediate_size: int = 0
    shared_expert_gate: bool = True


class DataConfig(pydantic.BaseModel):
    num_documents: int
    max_len: int
    seed: int = 0
    presort_buffer_size: int = 256
    presort_pack_size: int = 32


class TrackerConfig(pydantic.BaseModel):
    kind: str = "jsonl"
    directory: str = "runs"


class ProjectConfig(pydantic.BaseModel):
    mesh: MeshConfig
    model: ModelConfig
    data: DataConfig
    trainer: TrainerConfig
    optimizer: OptimizerConfig
    lr_scheduler: LRSchedulerConfig
    tracker: TrackerConfig = TrackerConfig()
    export_to: str | None = None


# ----------------------
# Dataset implementation
# ----------------------


class SyntheticCorpus:
    """Variable-length 'documents' of a learnable arithmetic language
    (token_{i+1} = token_i + step mod V) — stands in for a tokenized HF
    dataset (the reference streams wikitext through a tokenizer here;
    swap ``__getitem__`` for real data)."""

    def __init__(self, cfg: DataConfig, vocab_size: int):
        self.cfg = cfg
        self.vocab = vocab_size

    def __len__(self) -> int:
        return self.cfg.num_documents

    def sort_key(self, index: int) -> int:
        return self._length(index)

    def _length(self, index: int) -> int:
        rng = np.random.default_rng(self.cfg.seed * 7919 + index)
        return int(rng.integers(self.cfg.max_len // 2, self.cfg.max_len + 1))

    def __getitem__(self, index: int) -> dict:
        rng = np.random.default_rng(self.cfg.seed * 7919 + index)
        length = int(rng.integers(self.cfg.max_len // 2, self.cfg.max_len + 1))
        start = int(rng.integers(0, self.vocab))
        step = int(rng.integers(1, 5))
        ids = (start + step * np.arange(length)) % self.vocab
        return {"input_ids": ids.astype(np.int64)}


class CorpusProvider(DatasetProvider):
    def __init__(self, cfg: DataConfig, vocab_size: int, trainer: TrainerConfig):
        self.cfg = cfg
        self.vocab_size = vocab_size
        self.trainer = trainer

    def build(self):
        corpus = SyntheticCorpus(self.cfg, self.vocab_size)
        sorted_ds = BufferSortedDataset(
            corpus,
            buffer_size=self.cfg.presort_buffer_size,
            pack_size=self.cfg.presort_pack_size,
            init_seed=self.cfg.seed,
        )

        def collate(items):
            ids = pad_stack_1d(
                [it["input_ids"] for it in items],
                pad_value=0,
                pad_to_multiple_of=None,
            )
            # clamp/pad to the static [B, seq_len+1] the task expects
            want = self.trainer.seq_len + 1
            if ids.shape[1] < want:
                ids = np.pad(ids, ((0, 0), (0, want - ids.shape[1])))
            ids = ids[:, :want]
            mask = (ids != 0).astype(np.int64)
            return {"input_ids": ids, "loss_mask": mask}

        return StatefulDataLoader(
            sorted_ds,
            self.trainer.global_batch_size,
            collate_fn=collate,
            shuffle=False,  # BufferSortedDataset already shuffles in packs
            num_epochs=None,
        )


# ----------------------
# Providers
# ----------------------


def build_model_config(
    c: ModelConfig,
    *,
    ep_axes=None,
    moe_token_axes=None,
    remat: bool | None = None,
) -> Qwen3MoeConfig:
    """ModelConfig (JSON schema) -> Qwen3MoeConfig — the ONE mapping both
    pretrain.py and generate.py use, so an exported checkpoint's parameter
    structure always matches what generate.py rebuilds (e.g. fused_qkv)."""
    return Qwen3MoeConfig(
        vocab_ranges=(("default", c.vocab_size),),
        hidden_size=c.hidden_size,
        num_layers=c.num_layers,
        num_heads=c.num_heads,
        num_kv_heads=c.num_kv_heads,
        head_dim=c.head_dim,
        moe_intermediate_size=c.moe_intermediate_size,
        num_experts=c.num_experts,
        num_experts_per_tok=c.num_experts_per_tok,
        remat=c.remat if remat is None else remat,
        fused_qkv=c.fused_qkv,
        linear_attention_layers=tuple(c.linear_attention_layers),
        use_output_gate=c.use_output_gate,
        rope_fraction=c.rope_fraction,
        zero_centered_norms=c.zero_centered_norms,
        gdn_qk_heads=c.gdn_qk_heads,
        gdn_v_heads=c.gdn_v_heads,
        gdn_head_qk_dim=c.gdn_head_qk_dim,
        gdn_head_v_dim=c.gdn_head_v_dim,
        gdn_conv_size=c.gdn_conv_size,
        shared_expert=SharedExpertParameters(
            intermediate_size=c.shared_expert_intermediate_size,
            enable_gate=c.shared_expert_gate,
        )
        if c.shared_expert_intermediate_size > 0
        else None,
        ep_axes=ep_axes,
        moe_token_axes=moe_token_axes,
    )


class MoEProvider(ModelProvider):
    def __init__(self, cfg: ModelConfig, ctx):
        self.cfg = cfg
        self.ctx = ctx

    def build_module(self, stage):
        c = self.cfg
        return Qwen3MoeCausalLM(
            config=build_model_config(
                c,
                ep_axes=self.ctx.ep_shard_axes,
                # ride the residual layout through the EP dispatch (no
                # boundary reshard; see MoELayer.token_axes)
                moe_token_axes=(self.ctx.batch_axes, self.ctx.sequence_axes),
            ),
            sdpa=build_sdpa_backend(),
            stage=stage,
            # pin the residual stream so SPMD never drifts into fused-batch
            # layouts that replicate-reshard at attention / the LM head
            act_sharding=self.ctx.batch_sharding(),
            dtype=jnp.dtype(c.dtype),
        )

    def build_plan(self, ctx):
        return fsdp_ep_plan(ctx)

    def sample_inputs(self, batch_size, seq_len):
        z = jnp.zeros((batch_size, seq_len), jnp.int32)
        return (z, z, z)


class ConfiguredOptimizerProvider(OptimizerProvider):
    def __init__(self, cfg: OptimizerConfig):
        self.cfg = cfg

    def build(self, learning_rate):
        return build_optimizer(self.cfg, learning_rate)


# ----------------------
# Main
# ----------------------


def main(config_path: str) -> None:
    enable_compile_cache()
    raw = json.loads(Path(config_path).read_text())
    cfg = ProjectConfig.model_validate(raw)

    # Multi-host pod bootstrap: no-op on a single host; on a pod slice
    # every host runs this same script (see d9d_tpu/core/distributed.py
    # for the launch story) and jax.devices() then spans the slice.
    init_distributed()

    mesh_params = MeshParameters(**cfg.mesh.model_dump())
    ctx = mesh_params.build()
    print(
        f"mesh: {dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))} "
        f"on {jax.device_count()} devices "
        f"(process {jax.process_index()}/{jax.process_count()})"
    )

    lr = build_lr_schedule(cfg.lr_scheduler, total_steps=cfg.trainer.total_steps)
    trainer = Trainer(
        ctx=ctx,
        config=cfg.trainer,
        model_provider=MoEProvider(cfg.model, ctx),
        dataset_provider=CorpusProvider(cfg.data, cfg.model.vocab_size, cfg.trainer),
        task=CausalLMTask(),
        optimizer_provider=ConfiguredOptimizerProvider(cfg.optimizer),
        learning_rate=lr,
        tracker=build_tracker(cfg.tracker.kind, directory=cfg.tracker.directory)
        if cfg.tracker.kind == "jsonl"
        else build_tracker(cfg.tracker.kind),
    )
    history = trainer.train()
    if history:
        print(
            f"trained {history[-1]['step']} steps: "
            f"loss {history[0]['loss']:.4f} -> {history[-1]['loss']:.4f}"
        )
    if cfg.export_to:
        trainer.export(Path(cfg.export_to))
        print(f"exported model weights to {cfg.export_to}")
    trainer.close()


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "example/qwen3_moe/pretrain.json")
