"""DeepSeek-V2 model family: MLA attention + shared-expert MoE.

Beyond-reference family (the reference ships only Qwen3 models,
d9d/module/model/): DeepSeek-V2's decoder is the Qwen3-MoE stack with
MultiHeadLatentAttention in place of GQA (``Qwen3MoeConfig.mla``),
dense first-k layers (``mlp_only_layers`` = HF
``first_k_dense_replace``), ungated shared experts
(``SharedExpertParameters(enable_gate=False)``, width =
``n_shared_experts * moe_intermediate_size``), and the
``routed_scaling_factor`` on the routed experts' output — so
sharding plans, pipelining stages, PEFT, generation (latent-cache
decode incl. the absorbed rank-space form) and serving
(ContinuousBatcher / speculative_generate) all apply unchanged.

Checkpoint-fidelity status vs transformers ``DeepseekV2ForCausalLM``:
router semantics match the HF configs (``norm_topk_prob=False`` raw
softmax weights; the 236B preset's ``group_limited_greedy`` routing via
``router_n_group/topk_group``), the yarn long-context scaling and its
mscale attention temperature are configured per the published configs
(``_yarn_mscale``), and the parameter LAYOUT maps 1:1 onto the MLA/MoE
blocks — but no HF weight mapper or logits-parity test exists yet, so
treat checkpoint loading as future work (the Qwen3/Llama/Next families
are the logits-parity-tested interop surface).

GLM-4.7-Flash (``glm4_moe_lite``) rides the same backbone with the
DeepSeek-V3 layer equations: q compression in MLA, d_qk == d_v, and the
sigmoid ``noaux_tc`` router with its selection bias
(``router_score_function``, ``router_expert_bias``); held to
``benchmarks/references/glm4_moe_lite.py`` in
``tests/models/test_glm4_moe_lite.py`` and, at published widths on the
chip, in the benchmark's ``glm-4.7-flash-decode`` cell.

Xing4.0-29B-A4B (``xing4_0``) is that block again (MLA with q
compression, the sigmoid ``noaux_tc`` router, one shared expert, two
leading dense layers) on a four-stream residual path mixed by Sinkhorn
iterations (``hc_mult``; nn/hyper_connections.py) with one trained
multi-token-prediction module (``num_mtp_modules``;
models/qwen3/moe.py MultiTokenPrediction), on the training path. A
preset may hold a chip's share of an expert-parallel job: a range of the
routed experts (``num_routed_experts``, ``first_held_expert``) and a
slice of the vocabulary. Logits, the module's logits, the whole loss and
its gradients are held to ``benchmarks/references/xing4_0.py`` in
``tests/models/test_xing4_0.py`` (with the shares adding up to the uncut
layer), the stream's mix in ``tests/nn/test_hyper_connections.py``, the
held range in ``tests/nn/test_moe_held_range.py``; at published widths
on the chip, logits and loss in the benchmark's
``xing4.0-29b-a4b-share8`` cell.
"""

import dataclasses

from d9d_tpu.models.qwen3.moe import (
    MLAParameters,
    Qwen3MoeBackbone as DeepseekBackbone,
    Qwen3MoeCausalLM as DeepseekCausalLM,
    Qwen3MoeConfig,
)
from d9d_tpu.nn.moe import SharedExpertParameters
from d9d_tpu.ops import RopeScalingYarn

DeepseekConfig = Qwen3MoeConfig  # same static surface; mla set


def _yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek yarn_get_mscale: the attention-temperature term the
    checkpoints fold into the softmax scale (mscale == mscale_all_dim
    in both published configs, so cos/sin stay unscaled and the scale
    adjustment is mscale(factor)**2 on d_qk**-0.5)."""
    import math

    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _deepseek_yarn(factor: float = 40.0) -> RopeScalingYarn:
    """The yarn scaling both published DeepSeek-V2 configs ship
    (factor 40 over a 4096 original context; attention_factor 1.0
    because the temperature rides the softmax scale instead)."""
    return RopeScalingYarn(
        factor=factor,
        original_max_position=4096,
        beta_fast=32.0,
        beta_slow=1.0,
        attention_factor=1.0,
    )


def deepseek_v2_tiny(vocab_size: int = 256) -> Qwen3MoeConfig:
    """CPU-runnable DeepSeek-V2-shaped config (tests / smoke): MLA on
    every layer, first layer dense, 1 ungated shared expert."""
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,  # unused by MLA; kept for config invariants
        head_dim=16,
        moe_intermediate_size=32,
        num_experts=8,
        num_experts_per_tok=2,
        intermediate_size=128,
        mlp_only_layers=(0,),
        shared_expert=SharedExpertParameters(
            intermediate_size=32, enable_gate=False
        ),
        mla=MLAParameters(
            kv_lora_rank=32,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=16,
            q_lora_rank=None,
        ),
        routed_scaling_factor=1.0,
        norm_topk_prob=False,
        qk_norm=False,
        rope_theta=10_000.0,
        remat=False,
    )


def deepseek_v2_lite(vocab_size: int = 102_400) -> Qwen3MoeConfig:
    """DeepSeek-V2-Lite geometry (15.7B total / 2.4B active): 27 layers,
    MLA with rank-512 latents and no q compression, 64 routed + 2
    shared experts, first layer dense."""
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=2048,
        num_layers=27,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        moe_intermediate_size=1408,
        num_experts=64,
        num_experts_per_tok=6,
        intermediate_size=10_944,
        mlp_only_layers=(0,),
        shared_expert=SharedExpertParameters(
            intermediate_size=2 * 1408, enable_gate=False
        ),
        mla=MLAParameters(
            kv_lora_rank=512,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            q_lora_rank=None,
            softmax_scale=(128 + 64) ** -0.5
            * _yarn_mscale(40.0, 0.707) ** 2,
        ),
        routed_scaling_factor=1.0,
        norm_topk_prob=False,
        qk_norm=False,
        rope_theta=10_000.0,
        rope_scaling=_deepseek_yarn(),
    )


def deepseek_v2(vocab_size: int = 102_400) -> Qwen3MoeConfig:
    """DeepSeek-V2 geometry (236B total / 21B active): 60 layers, MLA
    with q compression (rank 1536), 160 routed + 2 shared experts,
    routed output scaled 16x."""
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=5120,
        num_layers=60,
        num_heads=128,
        num_kv_heads=128,
        head_dim=128,
        moe_intermediate_size=1536,
        num_experts=160,
        num_experts_per_tok=6,
        intermediate_size=12_288,
        mlp_only_layers=(0,),
        shared_expert=SharedExpertParameters(
            intermediate_size=2 * 1536, enable_gate=False
        ),
        mla=MLAParameters(
            kv_lora_rank=512,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            q_lora_rank=1536,
            softmax_scale=(128 + 64) ** -0.5
            * _yarn_mscale(40.0, 0.707) ** 2,
        ),
        routed_scaling_factor=16.0,
        norm_topk_prob=False,
        router_n_group=8,
        router_topk_group=3,
        qk_norm=False,
        rope_theta=10_000.0,
        rope_scaling=_deepseek_yarn(),
    )


def glm4_moe_lite_tiny(vocab_size: int = 256) -> Qwen3MoeConfig:
    """CPU-runnable GLM-4.7-Flash-shaped config (tests, ``--tiny``
    benchmark runs): one dense and one expert layer, q compression on,
    d_qk == d_v, sigmoid ``noaux_tc`` router with its selection bias,
    renormalised weights times a routed scale."""
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,  # unused by MLA; kept for config invariants
        head_dim=32,
        moe_intermediate_size=32,
        num_experts=8,
        num_experts_per_tok=2,
        intermediate_size=128,
        mlp_only_layers=(0,),
        shared_expert=SharedExpertParameters(
            intermediate_size=32, enable_gate=False
        ),
        mla=MLAParameters(
            kv_lora_rank=32,
            qk_nope_head_dim=24,
            qk_rope_head_dim=8,
            v_head_dim=32,
            q_lora_rank=24,
        ),
        routed_scaling_factor=1.8,
        norm_topk_prob=True,
        router_score_function="sigmoid",
        router_expert_bias=True,
        qk_norm=False,
        rope_theta=1_000_000.0,
        norm_eps=1e-5,
        remat=False,
    )


def glm_4_7_flash(vocab_size: int = 154_880) -> Qwen3MoeConfig:
    """GLM-4.7-Flash geometry (``glm4_moe_lite``, 30B total / 3B active):
    47 layers, the first dense (10,240 wide), then 64 routed experts x
    1,536 top-4 plus one shared; the DeepSeek-V3 layer equations: MLA
    with q compression (rank 768), rank-512 latents, 192 + 64 query/key
    and 256 value dims a head over 20 heads, no rope scaling; sigmoid
    ``noaux_tc`` routing, renormalised weights times 1.8. The
    multi-token-prediction layer is not built."""
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=2048,
        num_layers=47,
        num_heads=20,
        num_kv_heads=20,
        head_dim=256,
        moe_intermediate_size=1536,
        num_experts=64,
        num_experts_per_tok=4,
        intermediate_size=10_240,
        mlp_only_layers=(0,),
        shared_expert=SharedExpertParameters(
            intermediate_size=1536, enable_gate=False
        ),
        mla=MLAParameters(
            kv_lora_rank=512,
            qk_nope_head_dim=192,
            qk_rope_head_dim=64,
            v_head_dim=256,
            q_lora_rank=768,
        ),
        routed_scaling_factor=1.8,
        norm_topk_prob=True,
        router_score_function="sigmoid",
        router_expert_bias=True,
        qk_norm=False,
        rope_theta=1_000_000.0,
        norm_eps=1e-5,
    )


def xing4_0_tiny(vocab_size: int = 256) -> Qwen3MoeConfig:
    """CPU-runnable Xing4.0-shaped config (tests, ``--tiny`` benchmark
    runs): one dense and one expert layer, 4 of 16 routed experts held
    (the first four: what ``build.hf_view`` cannot say, the benchmark's
    reference takes as 0), four residual streams at the published
    Sinkhorn rounds, clamp and eps, one multi-token-prediction module."""
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,  # unused by MLA; kept for config invariants
        head_dim=16,
        moe_intermediate_size=32,
        num_experts=4,
        num_routed_experts=16,
        first_held_expert=0,
        num_experts_per_tok=2,
        intermediate_size=128,
        mlp_only_layers=(0,),
        shared_expert=SharedExpertParameters(
            intermediate_size=32, enable_gate=False
        ),
        mla=MLAParameters(
            kv_lora_rank=32,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=16,
            q_lora_rank=24,
        ),
        routed_scaling_factor=2.0,
        norm_topk_prob=True,
        router_score_function="sigmoid",
        router_expert_bias=True,
        qk_norm=False,
        rope_theta=10_000.0,
        hc_mult=4,
        num_mtp_modules=1,
        remat=False,
    )


def xing4_0_29b_a4b(
    vocab_size: int = 131_072, num_experts: int = 64,
    first_held_expert: int = 0,
) -> Qwen3MoeConfig:
    """Xing4.0-29B-A4B geometry (``xing4_0``): 40 layers at 3,584, the
    first two dense (9,216 wide), then 64 routed experts x 1,024 top-4
    plus one shared, sigmoid ``noaux_tc`` routing, renormalised weights
    times 2; MLA with q compression (rank 768), rank-512 latents, 128 +
    64 query/key and 128 value dims a head over 32 heads, yarn factor 64
    with its temperature in the softmax scale; four residual streams
    mixed by 20 Sinkhorn rounds; one trained multi-token-prediction
    module; an untied 131,072-row vocabulary. ``num_experts`` below 64
    and a smaller ``vocab_size`` give one chip's share of an
    expert-parallel job: that many experts from ``first_held_expert`` on
    under the 64-wide router, and the vocabulary's first rows."""
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=3584,
        num_layers=40,
        num_heads=32,
        num_kv_heads=32,
        head_dim=128,
        moe_intermediate_size=1024,
        num_experts=num_experts,
        num_routed_experts=64,
        first_held_expert=first_held_expert,
        num_experts_per_tok=4,
        intermediate_size=9216,
        mlp_only_layers=(0, 1),
        shared_expert=SharedExpertParameters(
            intermediate_size=1024, enable_gate=False
        ),
        mla=MLAParameters(
            kv_lora_rank=512,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            q_lora_rank=768,
            softmax_scale=(128 + 64) ** -0.5
            * _yarn_mscale(64.0, 1.0) ** 2,
        ),
        routed_scaling_factor=2.0,
        norm_topk_prob=True,
        router_score_function="sigmoid",
        router_expert_bias=True,
        qk_norm=False,
        rope_theta=10_000.0,
        rope_scaling=_deepseek_yarn(64.0),
        norm_eps=1e-6,
        hc_mult=4,
        hc_sinkhorn_iters=20,
        hc_eps=1e-6,
        hc_res_clamp=(-30.0, 30.0),
        num_mtp_modules=1,
        mtp_loss_weight=0.3,
        remat_prevent_cse=True,
    )


def xing4_0_29b_a4b_share8() -> Qwen3MoeConfig:
    """One chip of the eight that share each layer of an 8-way
    expert-parallel Xing4.0-29B-A4B training job: experts 0 to 7 under
    the 64-wide router and vocabulary rows 0 to 16,383, every width as
    published. Five layers of the forty (one leading dense layer, four
    expert layers) beside the multi-token-prediction module, so that
    weights, gradient and moments (913 M parameters) fit the chip; the
    other layers are other pipeline stages' (the benchmark's
    ``xing4.0-29b-a4b-share8`` configuration)."""
    return dataclasses.replace(
        xing4_0_29b_a4b(vocab_size=16_384, num_experts=8),
        num_layers=5,
        mlp_only_layers=(0,),
    )
