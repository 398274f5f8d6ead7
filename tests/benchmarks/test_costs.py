"""``benchmarks/harness/costs.py``: the operations and bytes a cell's shapes
define. The six committed configurations read what they read at PR 33, to
the last digit; a chip's share of a stated deployment (a ``share`` block,
the ``model-configs`` guide's section 4), MLA query compression and a
trained multi-token-prediction module read the hand-reckoned numbers of
ISSUE 34. No device."""

import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import costs, readers

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

# What every function returned at the parent commit (PR 33) for each
# committed configuration, with the training cells' step (4 x 4,096
# tokens) and the serving cells' 64 slots and chunk of 8; an exception's
# class where the configuration lacks a key the function reads (the dense
# Jamba has no expert width). One departure, which ISSUE 34 asks for:
# GLM-4.7-Flash states ``q_lora_rank`` 768, so its query projection is
# 2,048 x 768 + 768 x 20 x 256 = 5,505,024 weights where the parent
# counted 2,048 x 20 x 256 = 10,485,760: ``attention_matmul_params``
# 26,738,688 -> 21,757,952, and with it ``active_matmul_params``
# 777,125,888 -> 747,241,472 (six layers) and ``train_flops_per_token``
# 5,417,730,048 -> 5,238,423,552. No metric of that (serving) cell reads
# any of the three.
AT_PR_33 = {
    "qwen3-30b-a3b-l1": {
        "n_dense_layers": 0,
        "n_routed_experts": 128,
        "attention_matmul_params": 18874368,
        "active_matmul_params": 368050176,
        "attention_score_flops_per_token": 33554432,
        "train_flops_per_token": 2308964352.0,
        "expert_mm_train": {"flops": 3710851743744.0, "bytes": 9261023232.0},
        "expected_experts_touched": 125.94234926707806,
        "expert_mm_decode": {"flops": 4831838208.0, "bytes": 1195881155.4256809},
        "flash_train": {"flops": 1924145348608.0, "bytes": 905969664.0},
        "reader_expert_mm_train": {"flops": 3710851743744.0, "bytes": 9261023232.0},
        "reader_flash_train": {"flops": 1924145348608.0, "bytes": 905969664.0},
        "reader_expert_mm_decode": {"flops": 38654705664.0, "bytes": 9567049243.405447},
    },
    "qwen3-30b-a3b-decode": {
        "n_dense_layers": 0,
        "n_routed_experts": 128,
        "attention_matmul_params": 18874368,
        "active_matmul_params": 652476416,
        "attention_score_flops_per_token": 201326592,
        "train_flops_per_token": 4518838272.0,
        "expert_mm_train": {"flops": 3710851743744.0, "bytes": 9261023232.0},
        "expected_experts_touched": 125.94234926707806,
        "expert_mm_decode": {"flops": 4831838208.0, "bytes": 1195881155.4256809},
        "flash_train": {"flops": 1924145348608.0, "bytes": 905969664.0},
        "reader_expert_mm_train": {"flops": 22265110462464.0, "bytes": 55566139392.0},
        "reader_flash_train": {"flops": 11544872091648.0, "bytes": 5435817984.0},
        "reader_expert_mm_decode": {"flops": 231928233984.0, "bytes": 57402295460.43268},
    },
    "deepseek-v2-lite-l2": {
        "n_dense_layers": 1,
        "n_routed_experts": 64,
        "attention_matmul_params": 13762560,
        "active_matmul_params": 373817344,
        "attention_score_flops_per_token": 41943040,
        "train_flops_per_token": 2368733184.0,
        "expert_mm_train": {"flops": 5102421147648.0, "bytes": 9059696640.0},
        "expected_experts_touched": 63.88249584410315,
        "expert_mm_decode": {"flops": 6643777536.0, "bytes": 1112734361.376734},
        "flash_train": {"flops": 1202590842880.0, "bytes": 1006632960.0},
        "reader_expert_mm_train": {"flops": 5102421147648.0, "bytes": 9059696640.0},
        "reader_flash_train": {"flops": 2405181685760.0, "bytes": 2013265920.0},
        "reader_expert_mm_decode": {"flops": 53150220288.0, "bytes": 8901874891.013872},
    },
    "qwen3-30b-a3b-ep4": {
        "n_dense_layers": 0,
        "n_routed_experts": 128,
        "attention_matmul_params": 18874368,
        "active_matmul_params": 538705920,
        "attention_score_flops_per_token": 134217728,
        "train_flops_per_token": 3634888704.0,
        "expert_mm_train": {"flops": 3710851743744.0, "bytes": 9261023232.0},
        "expected_experts_touched": 125.94234926707806,
        "expert_mm_decode": {"flops": 4831838208.0, "bytes": 1195881155.4256809},
        "flash_train": {"flops": 1924145348608.0, "bytes": 905969664.0},
        "reader_expert_mm_train": {"flops": 3710851743744.0, "bytes": 9261023232.0},
        "reader_flash_train": {"flops": 1924145348608.0, "bytes": 905969664.0},
        "reader_expert_mm_decode": {"flops": 154618822656.0, "bytes": 38268196973.62179},
    },
    "glm-4.7-flash-decode": {
        "n_dense_layers": 1,
        "n_routed_experts": 64,
        "attention_matmul_params": 21757952,
        "active_matmul_params": 747241472,
        "attention_score_flops_per_token": 251658240,
        "train_flops_per_token": 5238423552.0,
        "expert_mm_train": {"flops": 3710851743744.0, "bytes": 7650410496.0},
        "expected_experts_touched": 62.97117463353903,
        "expert_mm_decode": {"flops": 4831838208.0, "bytes": 1193784003.4256809},
        "flash_train": {"flops": 2405181685760.0, "bytes": 2013265920.0},
        "reader_expert_mm_train": {"flops": 18554258718720.0, "bytes": 38252052480.0},
        "reader_flash_train": {"flops": 14431090114560.0, "bytes": 12079595520.0},
        "reader_expert_mm_decode": {"flops": 193273528320.0, "bytes": 47751360137.02724},
    },
    "jamba2-3b-decode": {
        "n_dense_layers": 0,
        "n_routed_experts": 1,
        "attention_matmul_params": KeyError,
        "active_matmul_params": KeyError,
        "attention_score_flops_per_token": KeyError,
        "train_flops_per_token": KeyError,
        "expert_mm_train": KeyError,
        "expected_experts_touched": 1.0,
        "expert_mm_decode": KeyError,
        "flash_train": KeyError,
        "reader_expert_mm_train": KeyError,
        "reader_flash_train": KeyError,
        "reader_expert_mm_decode": KeyError,
    },
}


def measured(cfg: dict) -> dict:
    observed = types.SimpleNamespace(
        tokens_per_step=16_384, seq_len=4_096, chips=cfg["chips"], slots=64,
        chunk_k=8,
    )
    run = types.SimpleNamespace(hf=cfg, observed=observed)
    touched = lambda: costs.expected_experts_touched(cfg, 64)  # noqa: E731
    return {
        "n_dense_layers": lambda: costs.n_dense_layers(cfg),
        "n_routed_experts": lambda: costs.n_routed_experts(cfg),
        "attention_matmul_params": lambda: costs.attention_matmul_params(cfg),
        "active_matmul_params": lambda: costs.active_matmul_params(cfg),
        "attention_score_flops_per_token":
            lambda: costs.attention_score_flops_per_token(cfg, 4_096),
        "train_flops_per_token":
            lambda: costs.train_flops_per_token(cfg, 4_096),
        "expert_mm_train": lambda: costs.expert_mm_train(cfg, 16_384),
        "expected_experts_touched": touched,
        "expert_mm_decode":
            lambda: costs.expert_mm_decode(cfg, 64, touched()),
        "flash_train": lambda: costs.flash_train(cfg, 4, 4_096),
        "reader_expert_mm_train": lambda: readers._expert_mm_train(run),
        "reader_flash_train": lambda: readers._flash_train(run),
        "reader_expert_mm_decode": lambda: readers._expert_mm_decode(run),
    }


@pytest.mark.parametrize("pinned", AT_PR_33)
def test_a_committed_configuration_reads_what_it_read(pinned):
    """The six configurations of PR 33; a later one pins its readings in
    a test of its own (a share cut: ``test_mhc_train_cost.py``)."""
    config = next(c for c in BENCH["configs"] if c["name"] == pinned)
    cfg = json.loads((ROOT / config["file"]).read_text())
    for name, call in measured(cfg).items():
        want = AT_PR_33[pinned][name]
        if isinstance(want, type):
            with pytest.raises(want):
                call()
            continue
        got = call()
        assert got == want, name  # every digit: no tolerance


# -- a chip's share, q compression, a trained MTP module -----------------------

# ISSUE 34's cut, in numbers alone: one chip's share of a training job
# whose layers are each divided over 8 chips. MLA with q compression (768 /
# 512, 128 + 64 / 128, 32 heads) at hidden 3,584; 64 routed experts of
# 1,024 top-4 and one shared, 8 of the 64 held; one leading dense layer
# of 9,216 and four expert layers; an eighth of an untied 131,072-row
# vocabulary; one multi-token-prediction module.
SHARE_CUT = {
    "hidden_size": 3584, "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "intermediate_size": 9216, "num_attention_heads": 32,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "moe_intermediate_size": 1024, "vocab_size": 16_384,
    "num_nextn_predict_layers": 1,
    "reduced": ["num_hidden_layers", "first_k_dense_replace",
                "n_routed_experts", "vocab_size"],
    "share": {"published": {"n_routed_experts": 64, "vocab_size": 131_072}},
}
# what the parent's code read of the same file: no ``share`` block known
UNAWARE = {k: v for k, v in SHARE_CUT.items() if k != "share"}
UNAWARE["num_nextn_predict_layers"] = 0

ATTENTION = 28_409_856
EXPERT = 11_010_048
EXPERT_LAYER = 45_154_304
DENSE_LAYER = 127_500_288
HEAD = 58_720_256


def test_a_share_cut_counts_what_this_chip_multiplies():
    """By hand, weights a token is multiplied by (M = 1e6):

    attention, with q compression: 3,584 x 768 = 2.75 and 768 x 32 x 192 =
    4.72 for q; 3,584 x 576 = 2.06 and 512 x 32 x 256 = 4.19 for kv; 32 x
    128 x 3,584 = 14.68 for o: 28.41 M (the parent counted q as 3,584 x 32
    x 192 = 22.02: 42.96 M).
    an expert: 3 x 3,584 x 1,024 = 11.01 M.
    an expert layer: 28.41 of attention + 0.23 of router (3,584 x 64, its
    published width) + 11.01 shared + 4 x 11.01 / 8 routed (of a token's 4
    experts, 8 / 64 are held here) = 45.15 M, where the parent's code gives
    42.96 + 0.03 (3,584 x 8) + 5 x 11.01 = 98.04 M.
    the dense layer: 28.41 + 3 x 3,584 x 9,216 = 127.50 M.
    the head over the slice: 3,584 x 16,384 = 58.72 M.
    the MTP module: an expert layer, a 2 x 3,584 x 3,584 = 25.69 M merge
    and a second pass through the head."""
    d = 3584
    assert costs.attention_matmul_params(SHARE_CUT) == ATTENTION == (
        d * 768 + 768 * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d
    )
    assert ATTENTION + d * 64 + EXPERT + 4 * EXPERT // 8 == EXPERT_LAYER
    assert round(EXPERT_LAYER / 1e6, 1) == 45.2
    mtp = EXPERT_LAYER + 2 * d * d + HEAD
    assert costs.n_mtp_modules(SHARE_CUT) == 1
    assert costs.active_matmul_params(SHARE_CUT) == (
        DENSE_LAYER + 4 * EXPERT_LAYER + HEAD + mtp
    ) == 496_402_432
    # the parent's reading of the same file, a layer: 98.0 M
    parent_attention = ATTENTION - (d * 768 + 768 * 32 * 192) + d * 32 * 192
    parent_layer = parent_attention + d * 8 + (4 + 1) * EXPERT
    assert round(parent_layer / 1e6, 1) == 98.0
    no_compression = dict(UNAWARE, q_lora_rank=None)
    assert costs.active_matmul_params(no_compression) == (
        parent_attention + 3 * d * 9216 + 4 * parent_layer + HEAD
    )
    # and the FLOPs a token: 6 a weight, 6 x h x (d_qk + d_v) x T of
    # causal attention in the five layers and the module's own
    assert costs.train_flops_per_token(SHARE_CUT, 4096) == (
        6 * 496_402_432 + 3 * (5 + 1) * 32 * 4096 * (192 + 128)
    )


def test_a_share_cut_computes_an_eighth_of_the_routed_rows():
    """2 x 4,096 tokens a step route 32,768 pairs over 64 experts; the 8
    held here take an eighth: 4,096 rows a layer, through 8 experts'
    weights."""
    tokens = 2 * 4096
    assert costs.routed_per_token(SHARE_CUT) == 0.5
    work = costs.expert_mm_train(SHARE_CUT, tokens)
    rows = tokens * 4 // 8
    assert work["flops"] == 3 * 2 * rows * EXPERT
    assert work["bytes"] == 3 * 8 * EXPERT * 2 + 3 * rows * (
        2 * 3584 + 4 * 1024) * 2
    unaware = costs.expert_mm_train(UNAWARE, tokens)
    assert unaware["flops"] == 8 * work["flops"]  # what could read over 100 %
    # four expert layers and the module's, five attention layers and its
    observed = types.SimpleNamespace(
        tokens_per_step=tokens, seq_len=4096, chips=1)
    run = types.SimpleNamespace(hf=SHARE_CUT, observed=observed)
    assert readers._expert_mm_train(run)["flops"] == 5 * work["flops"]
    flash = costs.flash_train(SHARE_CUT, 2, 4096)
    assert readers._flash_train(run)["flops"] == 6 * flash["flops"]


def test_a_share_cut_serves_the_held_experts_only():
    """A decode step's 64 slots draw 4 of 64 experts each: a held expert
    is missed by one slot with probability 60 / 64, so 8 x (1 - (15 /
    16) ** 64) = 7.87 of the 8 held are touched, by 64 x 4 / 8 = 32 rows."""
    touched = costs.expected_experts_touched(SHARE_CUT, 64)
    assert touched == pytest.approx(8 * (1 - (15 / 16) ** 64), rel=1e-12)
    assert 7.8 < touched < 8
    work = costs.expert_mm_decode(SHARE_CUT, 64, touched)
    assert work["flops"] == 2 * 32 * EXPERT
    assert work["bytes"] == pytest.approx(
        touched * EXPERT * 2 + 32 * (2 * 3584 + 4 * 1024) * 2)
    # the whole layer, as the parent read the file: every row, 8 experts
    whole = costs.expert_mm_decode(UNAWARE, 64, 8.0)
    assert whole["flops"] == 8 * work["flops"]
    # a chunk of 8 steps runs the four expert layers and not the
    # multi-token-prediction module the file states: that one is trained
    observed = types.SimpleNamespace(slots=64, chunk_k=8, chips=1)
    run = types.SimpleNamespace(hf=SHARE_CUT, observed=observed)
    assert costs.n_sparse_layers(SHARE_CUT) == 4
    assert costs.n_trained_sparse_layers(SHARE_CUT) == 5
    assert readers._expert_mm_decode(run)["flops"] == 4 * 8 * work["flops"]


# -- the key a source counts its experts under ---------------------------------

# ISSUE 43's share: 18 of 72 experts of 768 held, top 10, under the key
# ``num_local_experts``; the expert width is stated as
# ``moe_intermediate_size`` beside whatever the source calls it
LOCAL_EXPERTS = {
    "hidden_size": 4096, "num_experts_per_tok": 10, "num_local_experts": 18,
    "moe_intermediate_size": 768, "vocab_size": 25_088,
    "reduced": ["num_hidden_layers", "num_local_experts", "vocab_size"],
    "share": {"published": {"num_local_experts": 72, "vocab_size": 100_352}},
}


def test_a_share_under_num_local_experts_reads_a_quarter_of_the_rows():
    """Of a token's 10 experts 18 / 72 are held: 2.5 rows a token land
    here, and 128 slots x 10 draws over 72 miss a held expert with
    probability (62 / 72) ** 128 = 5e-9, so all 18 are touched."""
    assert costs.n_routed_experts(LOCAL_EXPERTS) == 18
    assert costs.published_experts(LOCAL_EXPERTS) == 72
    assert costs.routed_per_token(LOCAL_EXPERTS) == 2.5
    touched = costs.expected_experts_touched(LOCAL_EXPERTS, 128)
    assert round(touched, 4) == 18.0 and touched < 18
    work = costs.expert_mm_decode(LOCAL_EXPERTS, 128, touched)
    assert work["flops"] == 2 * 128 * 2.5 * 3 * 4096 * 768
    # without the block the file would read as the whole layer: 10 rows
    alone = {k: v for k, v in LOCAL_EXPERTS.items() if k != "share"}
    assert costs.routed_per_token(alone) == 10


@pytest.mark.parametrize("key", costs.EXPERT_COUNTS)
def test_an_expert_count_is_read_under_any_of_its_four_keys(key):
    cfg = {key: 8, "num_experts_per_tok": 4,
           "share": {"published": {key: 64}}}
    assert costs.n_routed_experts(cfg) == 8
    assert costs.published_experts(cfg) == 64
    assert costs.routed_per_token(cfg) == 0.5
    assert costs.published_experts({key: 8}) == 8


def test_a_file_with_no_expert_count_names_the_four_keys_it_may_use():
    with pytest.raises(KeyError) as raised:
        costs.n_routed_experts({"hidden_size": 4096})
    for key in ("num_experts", "n_routed_experts", "num_local_experts",
                "moe_num_experts"):
        assert key in str(raised.value)
    with pytest.raises(KeyError):
        costs.published_experts({"share": {"published": {"vocab_size": 8}}})
