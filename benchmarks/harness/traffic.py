"""Traffic generators: a mix's parameters and a seed in, inputs out.

One generator per ``kind``; a traffic mix is a JSON file of that kind's
parameters under ``benchmarks/traffic/``. Nothing here imports jax or
the program: the program receives only what these functions yield.

The seed decides order and token ids, never the amount of work: a
training mix yields batches of one fixed shape, and a closed-loop mix
issues whole, balanced permutations of one fixed table of (prompt,
output) lengths, so any two seeds give the same multiset of lengths per
pass, and nearly the same in any stretch of a pass.
"""

import dataclasses
import itertools
from typing import Iterator

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int  # position in the issue order, from 0
    prompt: tuple[int, ...]
    max_new_tokens: int


def sized(mix: dict, tiny: bool) -> dict:
    """The mix's parameters, with its ``tiny`` overrides on the CPU rig."""
    out = {k: v for k, v in mix.items() if k != "tiny"}
    if tiny:
        out.update(mix.get("tiny", {}))
    return out


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent streams per purpose from one ``--seed`` (any size)."""
    return np.random.default_rng([int(seed), *stream.encode()])


def train_batches(mix: dict, seed: int, vocab_size: int) -> Iterator[dict]:
    """Endless ``{"input_ids": [sequences, seq_len + 1]}`` batches."""
    rng = rng_for(seed, "train_batches")
    shape = (mix["sequences"], mix["seq_len"] + 1)
    while True:
        yield {"input_ids": rng.integers(0, vocab_size, size=shape)}


def length_table(mix: dict) -> list[tuple[int, int]]:
    """Every prompt length with every output length."""
    return list(itertools.product(mix["prompt_lengths"], mix["output_lengths"]))


def balanced_pass(mix: dict, rng: np.random.Generator):
    """One pass of the table: every (prompt, output) pair once, in blocks
    of n requests that each hold every prompt length and every output
    length once (the n x n pairs split into n perfect matchings, a Latin
    square). So any few consecutive requests carry the table's mean work,
    not only a whole pass; a plain shuffle of the 64 pairs left tokens
    per second 0.46 % apart between seeds, this leaves 0.23 % (simulated
    at the cell's sizes; PERF.md, PR 24). The seed draws which matchings,
    their order and the order inside each."""
    prompts, outputs = mix["prompt_lengths"], mix["output_lengths"]
    n = len(prompts)
    if len(outputs) != n:
        raise ValueError("balanced blocks need as many prompt as output lengths")
    p_of, o_of = rng.permutation(n), rng.permutation(n)
    for b in rng.permutation(n):
        block = [(prompts[p_of[i]], outputs[o_of[(i + b) % n]]) for i in range(n)]
        for j in rng.permutation(n):
            yield block[j]


def closed_loop_requests(
    mix: dict, seed: int, vocab_size: int
) -> Iterator[Request]:
    """Endless requests: consecutive balanced passes of the whole table."""
    rng = rng_for(seed, "closed_loop_requests")
    index = 0
    while True:
        for n_prompt, n_out in balanced_pass(mix, rng):
            prompt = rng.integers(0, vocab_size, size=n_prompt)
            yield Request(index, tuple(int(t) for t in prompt), int(n_out))
            index += 1


def table_work(mix: dict) -> dict:
    """Slot-steps one pass of the table costs: the fixed amount of work."""
    table = length_table(mix)
    return {
        "requests": len(table),
        "prompt_tokens": sum(p for p, _ in table),
        "output_tokens": sum(o for _, o in table),
        # the step that consumes the last prompt token emits the first
        # output token, so a request holds its slot p + o - 1 steps
        "slot_steps": sum(p + o - 1 for p, o in table),
        "longest_request_steps": max(p + o - 1 for p, o in table),
    }
