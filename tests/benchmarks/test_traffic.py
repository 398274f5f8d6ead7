"""The traffic generators: the seed chooses order and token ids, never
the amount of work. Standard library and numpy only."""

import collections
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.harness import traffic

TRAFFIC = Path(__file__).resolve().parents[2] / "benchmarks" / "traffic"
SEEDS = [0, 1, 7, 2**31 + 11]


def mix(name: str, tiny: bool = False) -> dict:
    with open(TRAFFIC / f"{name}.json") as f:
        return traffic.sized(json.load(f), tiny)


def one_pass(seed: int, vocab: int = 151_936):
    m = mix("serve-rollout-closed")
    n = traffic.table_work(m)["requests"]
    return list(itertools.islice(
        traffic.closed_loop_requests(m, seed, vocab), 2 * n
    )), n


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_work_per_pass_is_the_tables(seed):
    requests, n = one_pass(seed)
    work = traffic.table_work(mix("serve-rollout-closed"))
    for chunk in (requests[:n], requests[n:]):
        assert sum(len(r.prompt) for r in chunk) == work["prompt_tokens"]
        assert sum(r.max_new_tokens for r in chunk) == work["output_tokens"]
    assert work["requests"] == 64
    assert work["prompt_tokens"] == 8 * (16 + 22 + 30 + 41 + 56 + 76 + 104 + 128)
    assert work["output_tokens"] == 8 * (64 + 83 + 107 + 138 + 178 + 230 + 297 + 384)
    assert work["longest_request_steps"] == 128 + 384 - 1


@pytest.mark.parametrize("seed_a,seed_b", [(0, 1), (7, 2**31 + 11)])
def test_two_seeds_same_multiset_other_order_other_tokens(seed_a, seed_b):
    a, n = one_pass(seed_a)
    b, _ = one_pass(seed_b)

    def lengths(rs):
        return [(len(r.prompt), r.max_new_tokens) for r in rs]

    for lo in (0, n):  # every pass of the table, not just the first
        assert collections.Counter(lengths(a[lo:lo + n])) == \
            collections.Counter(lengths(b[lo:lo + n]))
    assert lengths(a[:n]) != lengths(b[:n])
    assert lengths(a[:n]) != lengths(a[n:])  # a new permutation each pass
    assert [r.prompt for r in a[:n]] != [r.prompt for r in b[:n]]


def test_same_seed_same_requests_and_ids_in_range():
    a, _ = one_pass(5, vocab=1000)
    b, _ = one_pass(5, vocab=1000)
    assert a == b
    assert [r.index for r in a] == list(range(len(a)))
    assert all(0 <= t < 1000 for r in a for t in r.prompt)


def test_every_pair_of_the_cross_product_once_per_pass():
    requests, n = one_pass(3)
    m = mix("serve-rollout-closed")
    want = set(itertools.product(m["prompt_lengths"], m["output_lengths"]))
    got = [(len(r.prompt), r.max_new_tokens) for r in requests[:n]]
    assert set(got) == want and len(got) == len(want)


@pytest.mark.parametrize("seed", SEEDS)
def test_every_block_of_eight_holds_each_length_once(seed):
    requests, n = one_pass(seed)
    m = mix("serve-rollout-closed")
    for lo in range(0, 2 * n, 8):
        block = requests[lo:lo + 8]
        assert sorted(len(r.prompt) for r in block) == m["prompt_lengths"]
        assert sorted(r.max_new_tokens for r in block) == m["output_lengths"]


@pytest.mark.parametrize("seed", SEEDS)
def test_train_batches_one_shape_any_seed(seed):
    m = mix("train-16k")
    batches = list(itertools.islice(traffic.train_batches(m, seed, 151_936), 3))
    for batch in batches:
        ids = batch["input_ids"]
        assert ids.shape == (4, 4097)  # 4 x 4,096 tokens and their labels
        assert ids.min() >= 0 and ids.max() < 151_936
    assert m["sequences"] * m["seq_len"] == 16_384
    assert not np.array_equal(batches[0]["input_ids"], batches[1]["input_ids"])


def test_train_batches_follow_the_seed():
    m = mix("train-16k", tiny=True)
    first = lambda s: next(traffic.train_batches(m, s, 512))["input_ids"]  # noqa: E731
    assert np.array_equal(first(9), first(9))
    assert not np.array_equal(first(9), first(10))


def test_balanced_blocks_need_a_square_table():
    m = dict(mix("serve-rollout-closed"), output_lengths=[8, 12, 16])
    with pytest.raises(ValueError):
        next(traffic.closed_loop_requests(m, 0, 10))


def test_the_mix_file_holds_no_knob_with_one_value():
    """Lengths, pre-roll and what is compared: nothing that names a rule
    the generator has only one of."""
    with open(TRAFFIC / "serve-rollout-closed.json") as f:
        keys = set(json.load(f))
    assert keys == {"kind", "why", "prompt_lengths", "output_lengths",
                    "preroll_tables", "checked_requests",
                    "checked_max_output", "logits_request", "tiny"}
