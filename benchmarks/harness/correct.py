"""The comparisons that decide ``correct``: the program against each
family's plain float32 reference, outside the measured window, at the
widths the cell runs.

Tolerances, with their reasons. The configurations compute in bf16 with
bf16 weights; the reference reads the same weights and computes in
float32 at ``highest`` matmul precision. So the gap is the program's own
rounding: every activation is rounded to 8 bits of mantissa (relative
step 2^-8 = 0.0039) a few times per layer, and a router score that
rounds across a tie sends a token to another expert.

``LOGITS_REL_RMS_TOL``: root-mean-square of (program - reference) over
the sample's logits, over the reference's root-mean-square. The v5e gave
0.0025 on the one-layer Qwen3 step, 0.0051 through six layers of prefill
and cached decode (my chip runs, PR 24; the other cells' readings are in
PERF.md); 0.015 is three times the largest. Arithmetic one step coarser
than the configuration states (fp8 or int8 weights or cache: relative
step 2^-4 against bf16's 2^-8) reads sixteen times the bf16 figure,
above 0.04; a wrong mask, scale, position or expert moves logits by
their own size and reads near 1.

``LOSS_TOL``: the sample's loss, program (through the Trainer's task
and its fused cross-entropy) against the reference's own ``loss``. At
seeded init the predictions are near uniform (loss about ln(vocab)),
per-token errors are of order 1e-2 and average out over the sample; the
v5e gave at most 5e-5. 3e-3 leaves room for a seed and a deeper stack
and is far below what a mis-wired label or shift does (order 1).

``MULTICHIP_LOSS_TOL``: the four-chip forward loss against the one-chip
forward loss of the same weights and batch. Only the order of sums
differs; the v5e gave 0.0 (PR 21) and 1e-3 is chip_smoke's bound.

``LOGIT_TIE_TOL``: a served stream may leave ``generate``'s only where
the two candidate tokens' *reference* logits are within 0.02: about two
bf16 steps at |logit| in [1, 2), which is where these logits lie at
seeded init. The paged one-token-a-step path and generate's prefill
round in different orders, so near-ties swap: the v5e showed gaps of
1e-5 to 7e-4 at every divergence (my chip runs, PR 24). A wrong page,
position or mask moves logits by whole units.
"""

import jax
import jax.numpy as jnp
import numpy as np

from d9d_tpu.loop import CausalLMTask

from . import build

LOGITS_REL_RMS_TOL = 0.015
LOSS_TOL = 3e-3
MULTICHIP_LOSS_TOL = 1e-3
LOGIT_TIE_TOL = 0.02


def rel_rms(got: np.ndarray, want: np.ndarray) -> float:
    """Float32 differences, float64 sums: the arrays are a vocabulary
    wide and a 64-bit copy of each would cost seconds of every set-up."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    error = np.mean(np.square(got - want), dtype=np.float64)
    return float(np.sqrt(error / np.mean(np.square(want), dtype=np.float64)))


def reference_logits(reference, variables, hf: dict, tokens) -> np.ndarray:
    params = build.unboxed(variables["params"])
    fn = jax.jit(lambda p, t: reference.logits(p, hf, t))
    return np.asarray(fn(params, jnp.asarray(tokens)))


def training_reference(reference, variables, hf: dict, sample) -> dict:
    """What the reference says of the sample (``[rows, n + 1]`` ids). Its
    loss is the reference's own ``loss(params, hf, tokens, labels)``, so
    that a family that trains on more than the next-token loss (a
    multi-token-prediction term) is held to all of it. Logits and loss
    come from one program: the compiler keeps one of the two forward
    passes it is given."""
    tokens, labels = sample[:, :-1], sample[:, 1:]
    logits, loss = jax.jit(lambda p, t, l: (
        reference.logits(p, hf, t), reference.loss(p, hf, t, l)
    ))(build.unboxed(variables["params"]), jnp.asarray(tokens),
       jnp.asarray(labels))
    return {"logits": np.asarray(logits), "loss": float(loss)}


def training_system(module, variables, sample, task=None) -> dict:
    """The program on the same sample: logits through its ``logits``
    method, and the loss through ``task``, the one the Trainer trains
    with (the next-token task where there is no Trainer): ``loss_sum /
    weight`` of its ``loss_fn`` on the sample, so that whatever the task
    adds to the next-token loss is compared."""
    task = task or CausalLMTask()
    mb = task.prepare_batch({"input_ids": sample})
    logits = jax.jit(
        lambda v, t, p: module.apply(v, t, p, method="logits")
    )(variables, mb["tokens"], mb["positions"])
    loss_sum, weight, _ = jax.jit(
        lambda v, mb: task.loss_fn(module, v, mb, jax.random.PRNGKey(0))
    )(variables, mb)  # no dropout: the key is unused
    return {
        "logits": np.asarray(logits, np.float32),
        "loss": float(loss_sum) / float(weight),
    }


def compare_training(system: dict, reference: dict) -> dict:
    return {
        "logits_rel_rms": rel_rms(system["logits"], reference["logits"]),
        "loss": system["loss"],
        "reference_loss": reference["loss"],
        "loss_gap": abs(system["loss"] - reference["loss"]),
    }


def sample_failures(checks: dict):
    """The failures a sample comparison shows, as sentences."""
    if not checks["logits_rel_rms"] <= LOGITS_REL_RMS_TOL:
        yield (
            f"logits leave the reference by {checks['logits_rel_rms']:.4f} "
            f"relative RMS, beyond {LOGITS_REL_RMS_TOL}"
        )
    if "loss_gap" in checks and not checks["loss_gap"] <= LOSS_TOL:
        yield (
            f"loss {checks['loss']} against the reference "
            f"{checks['reference_loss']}: beyond {LOSS_TOL}"
        )


# -- serving ------------------------------------------------------------------


def cached_logits(model, params, ids: np.ndarray, n_prompt: int):
    """Logits of every position of ``ids [1, n]``: the first ``n_prompt``
    through one prefill, the rest one token a step through the cache."""
    n = ids.shape[1]

    @jax.jit
    def run(params, ids):
        pos = jnp.arange(n, dtype=jnp.int32)[None]
        head, state = model.apply(
            {"params": params}, ids[:, :n_prompt], pos[:, :n_prompt],
            method="logits", mutable=["cache"],
        )

        def step(cache, xs):
            tok, p = xs
            out, new = model.apply(
                {"params": params, "cache": cache}, tok[None, None],
                p[None, None], method="logits", mutable=["cache"],
            )
            return new["cache"], out[0, 0]

        _, tail = jax.lax.scan(
            step, state["cache"], (ids[0, n_prompt:], pos[0, n_prompt:])
        )
        return jnp.concatenate([head[0], tail], axis=0)

    return np.asarray(run(params, jnp.asarray(ids, jnp.int32)), np.float32)


def serving_logits(model, params, reference, hf, request) -> dict:
    """Prefill then cached decode over one request's prompt and served
    stream, against the reference's full forward."""
    prompt, stream = request
    ids = np.asarray([list(prompt) + list(stream[:-1])], np.int32)
    got = cached_logits(model, params, ids, len(prompt))
    want = reference_logits(reference, {"params": params}, hf, ids)[0]
    return {"logits_rel_rms": rel_rms(got, want), "logits_positions": ids.shape[1]}


def generate_streams(model, params, prompts, max_new_tokens: int,
                     width: int) -> np.ndarray:
    """``loop.generate`` on the left-padded prompts: the oracle streams."""
    from d9d_tpu.loop.generate import generate

    padded = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        padded[i, width - len(p):] = p
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    run = jax.jit(lambda prm, ids, lens: generate(
        model, prm, ids, max_new_tokens=max_new_tokens, prompt_lengths=lens,
    ))
    return np.asarray(run(params, jnp.asarray(padded), jnp.asarray(lengths)))


def stream_divergences(reference, params, hf, prompts, served, oracle,
                       width: int) -> list[dict]:
    """Where a served stream leaves generate's, and how far apart the two
    candidates are under the reference (``inf`` for a length mismatch)."""
    out = []
    for i, (prompt, got) in enumerate(zip(prompts, served)):
        want = oracle[i, :len(got)].tolist()
        if list(got) == want:
            continue
        pos = next(j for j, (a, b) in enumerate(zip(got, want)) if a != b)
        prefix = list(prompt) + list(got[:pos])
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(prefix)] = prefix  # causal: the padding is unseen
        row = reference_logits(
            reference, {"params": params}, hf, ids
        )[0, len(prefix) - 1]
        out.append({
            "request": i, "position": pos,
            "served_token": int(got[pos]), "generate_token": int(want[pos]),
            "logit_gap": float(abs(row[got[pos]] - row[want[pos]])),
        })
    return out
