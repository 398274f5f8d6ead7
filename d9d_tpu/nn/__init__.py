from d9d_tpu.nn.attention import (
    GroupedQueryAttention,
    LowRankProjection,
    MultiHeadLatentAttention,
)
from d9d_tpu.nn.cca import CompressedConvAttention
from d9d_tpu.nn.decoder import DecoderLayer
from d9d_tpu.nn.embedding import TokenEmbedding
from d9d_tpu.nn.heads import ClassificationHead, EmbeddingHead, LanguageModellingHead
from d9d_tpu.nn.hidden_states import (
    HiddenStatesAggregationMode,
    HiddenStatesAggregatorMean,
    HiddenStatesAggregatorNoOp,
    create_hidden_states_aggregator,
    masked_mean_pool,
)
from d9d_tpu.nn.linear_attention import (
    CausalShortConv1d,
    DecayGateKind,
    GatedDeltaNet,
    KimiDeltaAttention,
    LogSigmoidDecayGate,
    MambaDecayGate,
)
from d9d_tpu.nn.mamba import Mamba2Mixer, MambaMixer
from d9d_tpu.nn.mlp import SwiGLU
from d9d_tpu.nn.moe import (
    GroupedSwiGLU,
    MoELayer,
    SharedExpertParameters,
    SharedSwiGLU,
    TopKRouter,
)
from d9d_tpu.nn.norm import RMSNorm

__all__ = [
    "GroupedQueryAttention",
    "LowRankProjection",
    "MultiHeadLatentAttention",
    "CompressedConvAttention",
    "DecoderLayer",
    "TokenEmbedding",
    "ClassificationHead",
    "EmbeddingHead",
    "LanguageModellingHead",
    "HiddenStatesAggregationMode",
    "HiddenStatesAggregatorMean",
    "HiddenStatesAggregatorNoOp",
    "create_hidden_states_aggregator",
    "masked_mean_pool",
    "CausalShortConv1d",
    "DecayGateKind",
    "GatedDeltaNet",
    "KimiDeltaAttention",
    "LogSigmoidDecayGate",
    "MambaDecayGate",
    "Mamba2Mixer",
    "MambaMixer",
    "SwiGLU",
    "GroupedSwiGLU",
    "MoELayer",
    "SharedExpertParameters",
    "SharedSwiGLU",
    "TopKRouter",
    "RMSNorm",
]
