"""What every model test of this directory needs of a tiny model, once:
seeded weights from one jitted ``init`` program, seeded token ids, the
sample through the loss the Trainer trains with (logits, loss, metrics
and gradients out of one compiled program) and through the plain
reference, and a greedy oracle that compiles one program whatever the
prompts' lengths.

The tests here are compile-bound, not arithmetic-bound: an un-jitted
``init``, ``apply`` or ``jax.grad`` is one backend compile an operation a
shape (1,444 programs in ``test_glm4_moe_lite.py`` alone when every test
ran them so, 74 % of the file's time). So whatever is the same program at
the same shape is built here once a process and kept
(``functools.lru_cache`` on the hashable flax module): under the driver's
``--dist load`` a module's tests fall to several workers, and each worker
pays for a program once.
"""

import functools
import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from d9d_tpu.core import MeshParameters
from d9d_tpu.loop import AdamWProvider, CausalLMTask, Trainer, TrainerConfig
from tests.loop.conftest import LMProvider, SeededBatches

VOCAB = 64
# Float32 program against the float32 reference: the same sums in another
# order (the program sorts tokens by expert, the reference evaluates every
# expert densely); the CPU gives 1e-8, 1e-5 leaves room for a backend.
# Jamba alone is held to 1e-4 (a chunked associative scan against a
# sequential one; tests/models/test_jamba.py says so where it sets it).
F32_REL_RMS = 1e-5


def ids(shape, seed=1, vocab=VOCAB):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, vocab, shape), jnp.int32)


def count(tree) -> int:
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


@functools.lru_cache(maxsize=None)
def _drawn(model, seed, touch):
    z = jnp.zeros((2, 8), jnp.int32)  # tokens, positions, labels
    params = nn.unbox(jax.jit(
        lambda key: model.init(key, z, z, z)["params"]
    )(jax.random.PRNGKey(seed)))
    if touch is not None:
        touch(params, np.random.RandomState(seed))
    return params


def seeded_params(model, seed=0, touch=None):
    """``model.init`` under ``jax.jit``, unboxed, then ``touch(params,
    rng)``: the family's own part (a selection bias, sink logits), drawn
    from ``RandomState(seed)``. One program for each distinct (model,
    seed) of a process. The containers are the caller's own (a test may
    pop or replace an entry); the arrays are shared and immutable."""
    return jax.tree.map(lambda a: a, _drawn(model, seed, touch))


# -- the sample through the Trainer's loss and through the reference ---------


def _with_grads(loss, grads: bool):
    """``loss(params, *rest) -> (scalar, aux)`` as ``(aux, gradient)``;
    the gradient is ``None`` where no test asks for it. What a test reads
    beside the loss (the logits) is computed outside the derivative, so
    that it is traced once and not linearised."""

    def run(*args):
        if not grads:
            return loss(*args)[1], None
        (_, aux), gradient = jax.value_and_grad(loss, has_aux=True)(*args)
        return aux, gradient

    return run


@functools.lru_cache(maxsize=None)
def _task_program(model, grads):
    task = CausalLMTask()

    def loss(params, mb):
        loss_sum, weight, metrics = task.loss_fn(
            model, {"params": params}, mb, jax.random.PRNGKey(0))  # no dropout
        return loss_sum / weight, (loss_sum, weight, metrics)

    def program(params, mb):
        logits = model.apply(
            {"params": params}, mb["tokens"], mb["positions"], method="logits")
        return logits, _with_grads(loss, grads)(params, mb)

    return task, jax.jit(program)


def loss_and_grads(model, params, sample, labels=None, grads=True) -> dict:
    """``sample [rows, n + 1]`` through ``CausalLMTask.loss_fn``, the loss
    the Trainer trains with, and the gradient of ``loss_sum / weight``:
    one compiled program a (model, shape), whatever it is asked. Reads
    as ``correct.training_system``'s result with ``metrics`` (the task's
    own, on the host), ``weight``, ``grads`` and ``mb`` beside it.
    ``labels`` replaces the batch's (``-100`` masks a position).
    ``grads=False`` is the forward alone, for a module none of whose
    tests reads a gradient at this shape: half the program."""
    task, run = _task_program(model, grads)
    mb = task.prepare_batch({"input_ids": np.asarray(sample)})
    if labels is not None:
        mb["labels"] = labels
    logits, ((loss_sum, weight, metrics), grads) = run(params, mb)
    return {
        "logits": np.asarray(logits, np.float32),
        "loss": float(loss_sum) / float(weight),
        "weight": float(weight),
        "metrics": task.metrics_postprocess({
            f"task/{k}": np.asarray(v) for k, v in metrics.items()}),
        "grads": grads,
        "mb": mb,
    }


@functools.lru_cache(maxsize=None)
def _reference_program(reference, hf_json, grads):
    hf = json.loads(hf_json)  # a view is what a configuration file holds

    def loss(params, tokens, labels):
        value = reference.loss(params, hf, tokens, labels)
        return value, value

    def program(params, tokens, labels):
        return (reference.logits(params, hf, tokens),
                _with_grads(loss, grads)(params, tokens, labels))

    return jax.jit(program)


def reference_loss_and_grads(reference, params, hf: dict, sample,
                             grads=True) -> dict:
    """The plain reference's twin of ``loss_and_grads``: its logits, its
    own ``loss`` and that loss's gradient, one compiled program a
    (reference, view, shape). ``params`` may lack a subtree the
    reference can do without (Xing4.0's ``mtp``)."""
    sample = jnp.asarray(sample)
    with jax.default_matmul_precision("highest"):
        logits, (loss, grads) = _reference_program(
            reference, json.dumps(hf, sort_keys=True), grads
        )(params, sample[:, :-1], sample[:, 1:])
    return {"logits": np.asarray(logits), "loss": float(loss), "grads": grads}


# -- gradient steps through the Trainer ---------------------------------------


def trainer(build_module, total_steps: int, *, one_batch: bool,
            weight_decay: float = 0.0) -> Trainer:
    """A ``Trainer`` on one device over ``build_module(stage)``: batches
    of four 16-token samples drawn from seed 0 (``one_batch``: the first,
    again every step, so that the loss must fall), the next-token task,
    AdamW at 1e-2."""
    return Trainer(
        ctx=MeshParameters().build(jax.devices()[:1]),
        config=TrainerConfig(
            global_batch_size=4, microbatch_size=4, seq_len=16,
            total_steps=total_steps, log_every=1, prefetch_batches=0,
            learning_rate=1e-2, telemetry_console=False,
        ),
        model_provider=LMProvider(build_module),
        dataset_provider=SeededBatches((4, 17), VOCAB, fresh=not one_batch),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(weight_decay=weight_decay),
    )


# -- serving ------------------------------------------------------------------


def greedy_oracle(logits_fn, params, prompts, n_new: int, width: int):
    """The greedy continuation of every prompt under a full forward,
    ``logits_fn(params, tokens [1, width]) -> [1, width, vocab]``: each
    context padded to ``width`` (causal: the rest is unseen), so one
    compiled program whatever the lengths."""
    full = jax.jit(logits_fn)
    streams = []
    for prompt in prompts:
        context = list(prompt)
        for _ in range(n_new):
            padded = np.zeros((1, width), np.int32)
            padded[0, :len(context)] = context
            row = full(params, jnp.asarray(padded))[0, len(context) - 1]
            context.append(int(np.argmax(row)))
        streams.append(context[len(prompt):])
    return streams
