"""Operations and bytes of a training step of a stack whose attention
layers differ by kind, from the configuration file's own keys:
``layer_types`` (``full_attention`` or ``sliding_attention`` a layer),
``num_attention_heads_per_layer`` (a kind's count of query heads),
``sliding_window``, ``gating`` (one sigmoid gate logit a query head:
a ``d x H`` product a layer) and ``shared_expert_intermediate_size``. What
``train.mfu_by_kind_pct`` and the two ``kernel.flash_*_train_roofline``
metrics divide by; nothing is taken from the program and nothing
rematerialised is counted.

It is ``benchmarks/harness/costs.py`` with a layer's own heads and keys in
place of one ``num_attention_heads`` and the causal half of the sequence
for every layer: a layer's projections and its flash kernels are
``costs.py``'s own functions on that layer's view of the file, and
everything beside attention (experts held, router, shared expert, dense
layers, head, multi-token-prediction modules) is ``costs.py``'s. For a
file that states no kinds every layer is a full one at the plain count,
and each function here equals its twin there to the digit
(``tests/benchmarks/test_attention_kinds_train_cost.py`` holds the l1 and
Xing4.0 files to that), so that a ``benchmark`` PR can fold the per-kind
count into ``costs.py`` and retire this file.

A query of a causal sequence of ``S`` positions attends ``S / 2`` keys on
average (``costs.py``'s count); under a window ``W < S`` the first ``W``
queries see what they would, the others ``W``: ``W - W (W - 1) / 2S`` on
average (480.06 at ``S`` 4,096 and ``W`` 512). The flash kernels' bytes do
not depend on the window: q, k, v and o are read or written whole.
"""

import typing

from benchmarks.harness import costs, readers

FULL, SLIDING = "full_attention", "sliding_attention"


class Layer(typing.NamedTuple):
    kind: str  # FULL or SLIDING
    heads: int  # query heads
    window: int | None


def trained_layers(cfg: dict) -> list[Layer]:
    """The attention layers a training step runs: the stack's first
    ``num_hidden_layers`` entries of the file's lists, and one more plain
    full layer a multi-token-prediction module."""
    n = cfg["num_hidden_layers"]
    kinds = cfg.get("layer_types") or [FULL] * n
    heads = (cfg.get("num_attention_heads_per_layer")
             or [cfg["num_attention_heads"]] * n)
    stack = [
        Layer(kind, h, cfg["sliding_window"] if kind == SLIDING else None)
        for kind, h in zip(kinds[:n], heads[:n])
    ]
    plain = Layer(FULL, cfg["num_attention_heads"], None)
    return stack + [plain] * costs.n_mtp_modules(cfg)


def keys_per_query(seq_len: int, window: int | None) -> float:
    if window is None or window >= seq_len:
        return seq_len / 2
    return window - window * (window - 1) / (2 * seq_len)


def _view(cfg: dict, layer: Layer) -> dict:
    """The file as ``costs.py`` reads it, with this layer's heads."""
    return {**cfg, "num_attention_heads": layer.heads}


def attention_matmul_params(cfg: dict, layer: Layer) -> int:
    """Projection parameters of one attention layer, its gate's too."""
    gate = cfg["hidden_size"] * layer.heads if cfg.get("gating") else 0
    return costs.attention_matmul_params(_view(cfg, layer)) + gate


def shared_experts(cfg: dict) -> float:
    """Shared experts a token passes, in units of a routed expert."""
    width = cfg.get("shared_expert_intermediate_size")
    if width is None:
        return cfg.get("n_shared_experts", 0)
    return width / cfg["moe_intermediate_size"]


def active_matmul_params(cfg: dict) -> float:
    """``costs.active_matmul_params`` with each attention layer at its
    own kind's projections."""
    d = cfg["hidden_size"]
    dense, mtp = costs.n_dense_layers(cfg), costs.n_mtp_modules(cfg)
    per_expert = 3 * d * cfg["moe_intermediate_size"]
    sparse = (
        d * costs.published_experts(cfg)
        + (costs.routed_per_token(cfg) + shared_experts(cfg)) * per_expert
    )
    return (
        sum(attention_matmul_params(cfg, l) for l in trained_layers(cfg))
        + dense * 3 * d * cfg["intermediate_size"]
        + costs.n_trained_sparse_layers(cfg) * sparse
        + mtp * 2 * d * d
        + (1 + mtp) * d * cfg["vocab_size"]
    )


def attention_score_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs per token of QK^T and PV, every trained layer at its
    own heads and the keys its queries see."""
    one_full_head = costs.attention_score_flops_per_token(
        {**cfg, "num_attention_heads": 1, "num_hidden_layers": 1,
         "num_nextn_predict_layers": 0}, seq_len,
    )
    return sum(
        l.heads * one_full_head
        * (keys_per_query(seq_len, l.window) / (seq_len / 2))
        for l in trained_layers(cfg)
    )


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    return (
        2.0 * active_matmul_params(cfg)
        + attention_score_flops_per_token(cfg, seq_len)
    )


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward = 3 x forward, as ``costs.py`` counts."""
    return 3.0 * forward_flops_per_token(cfg, seq_len)


def flash_train(cfg: dict, sequences: int, seq_len: int, kind: str) -> dict:
    """The flash kernels of every trained layer of ``kind`` in one step:
    ``costs.flash_train`` a layer at the layer's heads, its operations
    scaled to the keys a query sees under the window."""
    total = {"flops": 0.0, "bytes": 0.0}
    for layer in trained_layers(cfg):
        if layer.kind != kind:
            continue
        one = costs.flash_train(_view(cfg, layer), sequences, seq_len)
        seen = keys_per_query(seq_len, layer.window) / (seq_len / 2)
        total["flops"] += one["flops"] * seen
        total["bytes"] += one["bytes"]
    return total


STEP = "train_step|jit_step"


def flash_roofline(run, kind: str, cost: str, scope: str):
    """What the two ``kernel.flash_*_train_roofline`` readers share: the
    Pallas custom calls of the step program whose own instruction's scope
    matches ``scope`` (``readers.kernel_roofline``), against the work of
    the ``kind`` layers per execution and device. A file without
    ``layer_types``, or a program with no such call, gives nothing."""
    if "layer_types" not in run.hf:
        return None
    o = run.observed

    def work(run) -> dict:
        all_layers = flash_train(
            run.hf, o.tokens_per_step // o.seq_len, o.seq_len, kind
        )
        return {k: v / o.chips for k, v in all_layers.items()}

    readers.KERNEL_COSTS[cost] = work
    return readers.kernel_roofline(
        run, cost=cost, module_pattern=STEP, scope=scope
    )
