"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py             # one TPU chip: sync, train, serve
    python3 chip_smoke.py --chips 4   # four chips: FSDP x EP against one chip

Run plainly it drives the main path once on one chip, in this one
process, through the entry points a user calls, at the full widths of
Qwen3-30B-A3B (``Qwen3MoeConfig.qwen3_30b_a3b()`` with only ``num_layers``
cut; weights and data made from ``--seed``):

- ``distributed``: ``init_distributed()`` is a no-op on this host;
- ``sync``: a chain of large bf16 matmuls timed around
  ``block_until_ready`` comes out at or below the chip's peak FLOP/s,
  i.e. the call waits for the device;
- ``train``: a ``Trainer`` built from providers as
  ``example/qwen3_moe/pretrain.py`` builds its own (bf16 params,
  ``StochasticAdamW``) takes a few steps on a repeated batch: losses
  finite and falling, the step-0 loss within a tolerance of the same
  forward recomputed with eager attention, the Pallas flash kernels in
  the compiled step's HLO;
- ``serve``: the same weights in decode mode through ``ContinuousBatcher``
  (paged KV, fused K-step chunks) answer ragged requests; the streams are
  compared with ``loop.generate``, the flash-decode kernel is in the HLO
  and nothing compiles after warm-up.

With ``--chips 4`` it runs only the four-chip phase and what that is
compared with: the same seeded model and batch stepped on
``MeshParameters(dp_shard=4, ep_shard=4)`` with ``fsdp_ep_plan`` (the first
execution of ``lax.ragged_all_to_all`` in this repo) and then on one chip
with the local MoE path.

Every phase prints one JSON line; a phase that fails raises, and the
script exits non-zero. The last line of a good run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script prints ``"ok": false`` and exits 4: there is no
CPU path when it is run as a script (``tests/core/test_chip_smoke.py``
calls the phases as functions at tiny widths instead).
"""

import argparse
import dataclasses
import gc
import inspect
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from d9d_tpu.core import MeshParameters, init_distributed  # noqa: E402
from d9d_tpu.core.compile_cache import enable_compile_cache  # noqa: E402
from d9d_tpu.loop import (  # noqa: E402
    CausalLMTask,
    DatasetProvider,
    ModelProvider,
    Trainer,
    TrainerConfig,
)
from d9d_tpu.loop.control.providers import OptimizerProvider  # noqa: E402
from d9d_tpu.loop.generate import generate  # noqa: E402
from d9d_tpu.loop.serve import ContinuousBatcher  # noqa: E402
from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig  # noqa: E402
from d9d_tpu.nn.sdpa import SdpaEagerConfig, build_sdpa_backend  # noqa: E402
from d9d_tpu.optim import StochasticAdamW  # noqa: E402
from d9d_tpu.parallel import fsdp_ep_plan, replicate_plan  # noqa: E402
from d9d_tpu.telemetry import introspect  # noqa: E402
from d9d_tpu.telemetry.flops import device_peak_flops  # noqa: E402

DTYPE = jnp.bfloat16

# ContinuousBatcher's own default: the fused K-step chunk the smoke serves with
CHUNK_K = inspect.signature(ContinuousBatcher).parameters["chunk_size"].default

# |loss(flash) - loss(eager)| on the same bf16 parameters and batch. Both
# are means of fp32 per-token cross-entropies over 16k tokens. At seeded
# init the predictions are near uniform (loss ~ ln(vocab) = 11.93), which
# bf16 rounding inside attention barely moves: the v5e gave 2e-6 between
# the two paths (PR 21). 1e-3 leaves room for another seed and still sits
# an order of magnitude under what a wrong mask or scale does to the loss.
LOSS_TOL = 1e-3

# Largest gap between the two candidates' reference logits at which a
# serving stream may leave generate's: the fused decode path (one token
# per step through the paged flash-decode kernel) and generate's prefill
# (whole prompt through flash/eager) round bf16 activations in different
# orders, so two near-tied logits can swap. 2^-4 = 0.0625 is two bf16
# ulps at |logit| in [4, 8); a wrong page, position or mask moves logits
# by whole units.
LOGIT_TIE_TOL = 0.0625

# First-steps loss agreement between the four-chip FSDP x EP run and the
# one-chip run of the same seeded model and batch. Step 0 sees identical
# parameters, so only reduction order differs (per-chip partial sums,
# expert rows grouped per shard): LOSS_TOL applies, and the v5e gave 0.0.
# Later steps compare parameters that each went through bf16 stochastic
# rounding of differently-ordered fp32 gradient sums while the loss falls
# by ~2 a step; the v5e gave gaps up to 7e-5 over five steps (PR 21).
# 5e-3 is two orders above that and two under one step's change in loss.
MULTICHIP_LOSS_TOL = 5e-3


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def require(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def emit(line: dict) -> dict:
    print(json.dumps(line), flush=True)
    return line


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that is cut to fit; widths come from ``model_config``."""

    tiny: bool
    num_layers: int
    seq_len: int
    batch: int  # sequences per optimizer step, one microbatch
    train_steps: int
    learning_rate: float
    sync_n: int  # matmul side of the sync check
    sync_chain: int
    serve_slots: int
    page_size: int
    decode_max_length: int
    prompt_lens: tuple[int, ...]
    new_tokens: tuple[int, ...]

    @staticmethod
    def full() -> "Sizes":
        # One layer of Qwen3-30B-A3B is 1.245 B parameters. With bf16
        # params and moments (7.5 GB), the fp32 gradient accumulator
        # (5 GB) and four 4096-token sequences in one microbatch, the
        # compiled step claims 14.87 GB of the v5e's 16.91 GB (the
        # compiler's memory_analysis, the same compile-only and on the
        # chip, PR 21). A second layer does not fit, nor does a second
        # microbatch: the accumulation scan keeps a second gradient copy
        # (17.3 GB at batch 1 x 2 microbatches, compile-only).
        return Sizes(
            tiny=False, num_layers=1, seq_len=4096, batch=4, train_steps=5,
            learning_rate=1e-3, sync_n=8192, sync_chain=64,
            serve_slots=4, page_size=64, decode_max_length=128,
            prompt_lens=(5, 17, 33, 64, 9, 48),
            new_tokens=(24, 16, 32, 8, 24, 16),
        )

    @staticmethod
    def tiny_cpu() -> "Sizes":
        return Sizes(
            tiny=True, num_layers=1, seq_len=64, batch=4, train_steps=3,
            learning_rate=1e-2, sync_n=256, sync_chain=4,
            serve_slots=2, page_size=16, decode_max_length=48,
            prompt_lens=(3, 9, 16, 5), new_tokens=(8, 6, 10, 4),
        )


def model_config(sizes: Sizes, **overrides) -> Qwen3MoeConfig:
    if sizes.tiny:
        base = Qwen3MoeConfig(
            vocab_ranges=(("default", 512),), hidden_size=64, num_layers=1,
            num_heads=4, num_kv_heads=2, head_dim=16,
            moe_intermediate_size=64, num_experts=8, num_experts_per_tok=2,
        )
    else:
        base = Qwen3MoeConfig.qwen3_30b_a3b()
    return dataclasses.replace(
        base, num_layers=sizes.num_layers, **overrides
    )


def param_count(tree) -> int:
    return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))


def pallas_calls(hlo_texts, scope: str) -> int:
    """Pallas (Mosaic) kernels in compiled HLO whose op name holds
    ``scope``. Interpret mode lowers a kernel to plain HLO, and XLA's own
    ragged-dot kernels are ``tpu_custom_call``s too, so the count asks for
    the custom call, for ``pallas_call`` and for the module path."""
    n = 0
    for text in hlo_texts:
        for line in text.splitlines():
            if 'custom_call_target="tpu_custom_call"' not in line:
                continue
            op_name = re.search(r'op_name="([^"]*)"', line)
            if op_name and "pallas_call" in op_name[1] and scope in op_name[1]:
                n += 1
    return n


def compile_seconds(name_prefix: str, since: int = 0) -> float:
    return round(sum(
        r.lower_s + r.compile_s
        for r in introspect.inventory()[since:]
        if r.name.startswith(name_prefix)
    ), 2)


def memory_stats(device) -> dict:
    """The allocator's view. On the v5e it counts live buffers (arguments
    and results) but not a running program's temporaries, so a step's
    whole claim is read from the compiler: :func:`program_hbm_bytes`."""
    stats = device.memory_stats() or {}
    return {
        k: int(stats[k])
        for k in ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")
        if k in stats
    }


def program_hbm_bytes(name: str, since: int = 0) -> int:
    """Arguments + results + temporaries + code, less aliased (donated)
    bytes, of the newest executable compiled under ``name``: the
    compiler's ``memory_analysis()`` of the program that ran."""
    records = [r for r in introspect.inventory()[since:] if r.name == name]
    return int(records[-1].hbm_peak_bytes)


# -- providers: the same shapes the example hands the Trainer --


class SmokeModel(ModelProvider):
    def __init__(self, cfg: Qwen3MoeConfig, ctx, *, sharded: bool):
        self.cfg, self.ctx, self.sharded = cfg, ctx, sharded

    def build_module(self, stage):
        return Qwen3MoeCausalLM(
            config=self.cfg, sdpa=build_sdpa_backend(), stage=stage,
            act_sharding=self.ctx.batch_sharding() if self.sharded else None,
            dtype=DTYPE, param_dtype=DTYPE,
        )

    def build_plan(self, ctx):
        return fsdp_ep_plan(ctx) if self.sharded else replicate_plan(ctx)

    def sample_inputs(self, batch_size, seq_len):
        z = jnp.zeros((batch_size, seq_len), jnp.int32)
        return (z, z, z)


class RepeatedBatch(DatasetProvider):
    def __init__(self, batch: dict, steps: int):
        self.batch, self.steps = batch, steps

    def build(self):
        for _ in range(self.steps):
            yield self.batch


class SmokeOptimizer(OptimizerProvider):
    def build(self, learning_rate):
        return StochasticAdamW(
            learning_rate, weight_decay=0.0, moment_dtype=DTYPE
        )


def make_batch(cfg: Qwen3MoeConfig, sizes: Sizes, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(
        0, cfg.vocab_size, size=(sizes.batch, sizes.seq_len + 1)
    )}


def build_trainer(ctx, cfg, sizes: Sizes, seed: int, batch: dict, *,
                  sharded: bool) -> Trainer:
    return Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=sizes.batch, microbatch_size=sizes.batch,
            seq_len=sizes.seq_len, total_steps=sizes.train_steps,
            learning_rate=sizes.learning_rate, seed=seed, log_every=1,
            telemetry_console=False,
        ),
        model_provider=SmokeModel(cfg, ctx, sharded=sharded),
        dataset_provider=RepeatedBatch(batch, sizes.train_steps),
        task=CausalLMTask(),
        optimizer_provider=SmokeOptimizer(),
    )


def take_steps(trainer: Trainer, mark: int) -> dict:
    """``train()`` to the end; what the run showed. ``mark`` is the
    inventory length before the trainer was built."""
    history = trainer.train()
    trainer.close()
    walls = [0.0] + [h["wall_s"] for h in history]
    return {
        "losses": [h["loss"] for h in history],
        "step_s": [round(b - a, 3) for a, b in zip(walls, walls[1:])],
        "compile_s": compile_seconds("train_step", mark),
        "train_step_hbm_bytes": program_hbm_bytes("train_step", mark),
    }


def eager_model(cfg: Qwen3MoeConfig) -> Qwen3MoeCausalLM:
    """The reference the chip runs are held to: eager attention, no cache."""
    return Qwen3MoeCausalLM(
        config=cfg, sdpa=build_sdpa_backend(SdpaEagerConfig()),
        dtype=DTYPE, param_dtype=DTYPE,
    )


# -- phases ----------------------------------------------------------------


def check_device() -> dict:
    """The device as jax reports it; exits 4 with ``"ok": false`` unless
    it is a TPU (not 2 or 3, which the chip tool uses for its own
    refusals)."""
    devices = jax.devices()
    device = devices[0]
    info = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(devices),
    }
    if device.platform != "tpu":
        print(json.dumps({"ok": False, "device": info,
                          "error": "chip_smoke.py needs a TPU"}))
        sys.exit(4)
    return info


def phase_distributed() -> dict:
    """``init_distributed()`` exactly as the example calls it: on one host
    it must find nothing to join and leave one process."""
    env = {
        k: v for k, v in os.environ.items()
        if k.startswith(("TPU_", "MEGASCALE_", "D9D_", "JAX_", "XLA_"))
        or k in ("MASTER_ADDR", "WORLD_SIZE", "RANK")
    }
    initialized = init_distributed()
    require(not initialized, "init_distributed() joined a cluster on one host")
    require(jax.process_count() == 1, f"{jax.process_count()} processes")
    return emit({
        "phase": "distributed", "initialized": initialized,
        "process_count": jax.process_count(), "env": env,
    })


def phase_sync(sizes: Sizes) -> dict:
    """Does ``block_until_ready`` wait? A chain of dependent bf16 matmuls
    cannot finish faster than the chip's peak allows."""
    n, chain = sizes.sync_n, sizes.sync_chain
    key_a, key_b = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(key_a, (n, n), DTYPE)
    # scaled so the chain neither overflows nor underflows in bf16
    b = jax.random.normal(key_b, (n, n), DTYPE) / math.sqrt(n)
    matmul = jax.jit(lambda x, y: x @ y)
    jax.block_until_ready(matmul(a, b))
    t0 = time.perf_counter()
    x = a
    for _ in range(chain):
        x = matmul(x, b)
    enqueued = time.perf_counter() - t0
    jax.block_until_ready(x)
    total = time.perf_counter() - t0
    achieved = chain * 2 * n**3 / total
    peak = device_peak_flops()
    require(
        bool(jnp.isfinite(x.astype(jnp.float32)).all()),
        "matmul chain not finite",
    )
    if peak is not None:
        require(
            achieved <= peak,
            f"{achieved:.3e} FLOP/s exceeds the chip's peak {peak:.3e}: "
            "block_until_ready returned before the device finished",
        )
    return emit({
        "phase": "sync", "n": n, "chain": chain,
        "enqueue_s": round(enqueued, 4), "total_s": round(total, 4),
        "achieved_flops": achieved, "peak_flops": peak,
        "waits": peak is None or achieved <= peak,
    })


def eager_reference_loss(cfg, variables, batch: dict) -> float:
    """The batch's mean loss from a forward with eager attention: same
    parameters, same task, one sequence at a time so that the [H, T, T]
    logits of one sequence are all that is alive."""
    module = eager_model(cfg)
    task = CausalLMTask()
    prepared = task.prepare_batch(batch)

    @jax.jit
    def row_loss(variables, row):
        loss_sum, weight, _ = task.loss_fn(
            module, variables, row, jax.random.PRNGKey(0)
        )
        return loss_sum, weight

    loss_sum = weight = 0.0
    for i in range(prepared["tokens"].shape[0]):
        row = {k: jnp.asarray(v[i:i + 1]) for k, v in prepared.items()}
        s, w = row_loss(variables, row)
        loss_sum += float(s)
        weight += float(w)
    return loss_sum / weight


def phase_train(sizes: Sizes, seed: int, *, expect_kernels: bool):
    """Returns (json line, trained parameter tree)."""
    device = jax.devices()[0]
    ctx = MeshParameters().build([device])
    cfg = model_config(sizes)
    batch = make_batch(cfg, sizes, seed)
    mark = len(introspect.inventory())
    trainer = build_trainer(ctx, cfg, sizes, seed, batch, sharded=False)
    n_params = param_count(trainer.params)

    eager_loss = eager_reference_loss(cfg, trainer.params, batch)
    flash_loss = trainer.loss_on_batch(batch)
    run = take_steps(trainer, mark)
    losses, step_hbm = run["losses"], run["train_step_hbm_bytes"]
    stats = memory_stats(device)

    require(len(losses) == sizes.train_steps, f"took {len(losses)} steps")
    require(all(math.isfinite(v) for v in losses), f"losses {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    require(
        abs(losses[0] - eager_loss) <= LOSS_TOL
        and abs(flash_loss - eager_loss) <= LOSS_TOL,
        f"step-0 loss {losses[0]} / forward loss {flash_loss} against "
        f"eager attention {eager_loss}: beyond {LOSS_TOL}",
    )
    flash_kernels = pallas_calls(
        introspect.compiled_hlo("train_step"), "self_attn"
    )
    if expect_kernels:
        # forward, and the split backward's dq and dk/dv kernels
        require(
            flash_kernels >= 3,
            f"{flash_kernels} Pallas attention kernels in the train "
            "step's HLO: a reference path stood in for flash",
        )
        require(
            step_hbm >= 0.5 * stats["bytes_limit"],
            f"the train step claims {step_hbm} bytes, under half of the "
            f"chip's memory: {stats}",
        )
    line = emit({
        "phase": "train", "model": "qwen3_30b_a3b widths" if not sizes.tiny
        else "tiny", "num_layers": cfg.num_layers,
        "hidden": cfg.hidden_size, "experts": cfg.num_experts,
        "top_k": cfg.num_experts_per_tok, "vocab": cfg.vocab_size,
        "params": n_params, "batch": sizes.batch, "seq_len": sizes.seq_len,
        **run, "eager_forward_loss": eager_loss,
        "flash_forward_loss": flash_loss, "loss_tol": LOSS_TOL,
        "flash_kernels_in_hlo": flash_kernels, **stats,
    })
    params = trainer.params["params"]
    # free the optimizer state before the next phase needs the memory
    del trainer
    gc.collect()
    return line, params


def _prompts(cfg, sizes: Sizes, batch: dict) -> list[list[int]]:
    """Ragged prompts: prefixes of the rows the model just trained on."""
    ids = np.asarray(batch["input_ids"])
    return [
        ids[i % ids.shape[0], :n].tolist()
        for i, n in enumerate(sizes.prompt_lens)
    ]


def phase_serve(sizes: Sizes, seed: int, params=None, *,
                expect_kernels: bool) -> dict:
    cfg = model_config(sizes)
    model = Qwen3MoeCausalLM(
        config=cfg, sdpa=build_sdpa_backend(), dtype=DTYPE,
        param_dtype=DTYPE, decode_max_length=sizes.decode_max_length,
    )
    if params is None:  # the phase alone: weights straight from the seed
        z = jnp.zeros((1, 8), jnp.int32)
        params = model.clone(decode_max_length=0).init(
            jax.random.PRNGKey(seed), z, z, z
        )["params"]
    prompts = _prompts(cfg, sizes, make_batch(cfg, sizes, seed))
    budgets = list(sizes.new_tokens)

    batcher = ContinuousBatcher(
        model, params, batch_size=sizes.serve_slots,
        page_size=sizes.page_size,
    )
    k = CHUNK_K
    # warm-up: a budget of two chunks and more compiles both fused
    # variants (with and without admission) before the window
    batcher.submit(prompts[0], max_new_tokens=2 * k + 2)
    t0 = time.perf_counter()
    batcher.drain()
    warmup_s = time.perf_counter() - t0
    batcher.reset_measurement()
    mark = len(introspect.inventory())

    rids = [
        batcher.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)
    ]
    t0 = time.perf_counter()
    batcher.drain()
    drain_s = time.perf_counter() - t0
    streams = [list(batcher.outputs[r]) for r in rids]
    compiles_after_warmup = len(introspect.inventory()) - mark
    chunks = batcher.stats.host_dispatches
    fused_hlo = [
        text
        for name in (f"serve/fused_k{k}_paged", f"serve/fused_k{k}_paged_admit")
        for text in introspect.compiled_hlo(name)
    ]
    decode_kernels = pallas_calls(fused_hlo, "self_attn")
    batcher.close()

    # the reference streams: one jitted prefill + scan over the ragged,
    # left-padded batch
    width, gen = max(sizes.prompt_lens), max(budgets)
    padded = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        padded[i, width - len(p):] = p
    reference = jax.jit(lambda prm, ids, lens: generate(
        model, prm, ids, max_new_tokens=gen, prompt_lengths=lens,
    ))
    want = np.asarray(reference(
        params, jnp.asarray(padded),
        jnp.asarray(sizes.prompt_lens, jnp.int32),
    ))
    # teacher-forced logits for a divergence: compiled only if one occurs,
    # and once for all of them (every prefix is padded to one width)
    eager = eager_model(cfg)
    teacher_logits = jax.jit(lambda prm, ids, pos: eager.apply(
        {"params": prm}, ids, pos, method="logits"
    ))
    divergences = [
        d for i, (got, n) in enumerate(zip(streams, budgets))
        if (d := _divergence(
            lambda ids, pos: teacher_logits(params, ids, pos),
            sizes.decode_max_length, prompts[i], got,
            want[i, :n].tolist(), i,
        )) is not None
    ]

    require(
        all(len(s) == n for s, n in zip(streams, budgets)),
        f"stream lengths {[len(s) for s in streams]} against {budgets}",
    )
    require(
        compiles_after_warmup == 0,
        f"{compiles_after_warmup} compiles after warm-up",
    )
    for d in divergences:
        print(json.dumps({"phase": "serve", "divergence": d}), flush=True)
    require(
        all(d["logit_gap"] <= LOGIT_TIE_TOL for d in divergences),
        f"serving streams leave generate's beyond a {LOGIT_TIE_TOL} "
        f"logit tie: {divergences}",
    )
    if expect_kernels:
        require(
            decode_kernels >= 2,
            f"{decode_kernels} Pallas flash-decode kernels in the fused "
            "serving programs' HLO: the eager gather stood in",
        )
    tokens = sum(budgets)
    return emit({
        "phase": "serve", "slots": sizes.serve_slots, "chunk_k": k,
        "page_size": sizes.page_size,
        "decode_max_length": sizes.decode_max_length,
        "requests": len(prompts), "prompt_lens": list(sizes.prompt_lens),
        "new_tokens": budgets, "tokens": tokens,
        "streams_equal_generate": len(prompts) - len(divergences),
        "divergences": len(divergences), "logit_tie_tol": LOGIT_TIE_TOL,
        "warmup_s": round(warmup_s, 2),
        "compile_s": compile_seconds("serve/"),
        "drain_s": round(drain_s, 3), "chunks": chunks,
        "chunk_s": round(drain_s / max(chunks, 1), 4),
        "compiles_after_warmup": compiles_after_warmup,
        "decode_kernels_in_hlo": decode_kernels,
        **memory_stats(jax.devices()[0]),
    })


def _divergence(teacher_logits, width: int, prompt, got, want, index):
    """None when the streams agree; else where they part and how far
    apart the two candidates' logits are under ``teacher_logits(ids,
    positions)``, a forward of the shared prefix right-padded to
    ``width`` (causal attention keeps the padding out of the last real
    position)."""
    if got == want:
        return None
    pos = next(
        (j for j, (a, b) in enumerate(zip(got, want)) if a != b),
        min(len(got), len(want)),
    )
    if pos >= min(len(got), len(want)):
        return {"request": index, "position": pos, "logit_gap": math.inf,
                "served": len(got), "generate": len(want)}
    prefix = list(prompt) + list(got[:pos])
    ids = np.zeros((1, width), np.int32)
    ids[0, :len(prefix)] = prefix
    positions = np.arange(width, dtype=np.int32)[None]
    logits = teacher_logits(jnp.asarray(ids), jnp.asarray(positions))
    logits = logits[0, len(prefix) - 1].astype(jnp.float32)
    served, reference = float(logits[got[pos]]), float(logits[want[pos]])
    return {
        "request": index, "position": pos,
        "served_token": got[pos], "generate_token": want[pos],
        "served_logit": served, "generate_logit": reference,
        "top_logit": float(logits.max()),
        "logit_gap": abs(served - reference),
    }


def _placement(params, devices) -> dict:
    """How the parameter tree sits on ``devices``: expert leaves split on
    their expert dim, FSDP leaves on their embed dim, one shard per chip."""
    want = {d.id for d in devices}
    expert_split = dense_split = whole = 0
    sample = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        shards = leaf.addressable_shards
        on = {s.device.id for s in shards}
        split = shards[0].data.shape != leaf.shape
        if split:
            require(on == want, f"{name} has shards on {on}, not {want}")
        if "grouped_experts" in name:
            require(
                split and shards[0].data.shape[0] * len(devices)
                == leaf.shape[0],
                f"expert leaf {name} {leaf.shape} is not split over "
                f"{len(devices)} chips: {shards[0].data.shape}",
            )
            expert_split += 1
            sample.setdefault("expert", {
                "leaf": name, "shape": list(leaf.shape),
                "shard": list(shards[0].data.shape),
            })
        elif split:
            dense_split += 1
            sample.setdefault("fsdp", {
                "leaf": name, "shape": list(leaf.shape),
                "shard": list(shards[0].data.shape),
            })
        else:
            whole += 1
    require(expert_split > 0 and dense_split > 0,
            f"nothing sharded: {expert_split} expert, {dense_split} dense")
    return {
        "expert_leaves_split": expert_split, "fsdp_leaves_split": dense_split,
        "replicated_leaves": whole, "devices": sorted(want), **sample,
    }


def phase_four_chip(sizes: Sizes, seed: int, devices, *,
                    expect_kernels: bool) -> dict:
    """FSDP x EP over ``devices`` against the same model on one of them."""
    n = len(devices)
    batch = make_batch(model_config(sizes), sizes, seed)

    # (a) one process drives all the chips
    ctx = MeshParameters(dp_shard=n, ep_shard=n).build(list(devices))
    cfg = model_config(
        sizes, ep_axes=ctx.ep_shard_axes,
        moe_token_axes=(ctx.batch_axes, ctx.sequence_axes),
    )
    mark = len(introspect.inventory())
    trainer = build_trainer(ctx, cfg, sizes, seed, batch, sharded=True)
    n_params = param_count(trainer.params)
    sharded = take_steps(trainer, mark)
    placement = _placement(trainer.params, devices)
    hlo = "\n".join(introspect.compiled_hlo("train_step"))
    collectives = {
        kind: len(re.findall(r"\s" + kind + r"(?:-start)?\(", hlo))
        for kind in ("ragged-all-to-all", "all-gather", "all-reduce")
    }
    # the TPU compiler emits a reduce-scatter as an all-reduce-scatter fusion
    collectives["reduce-scatter"] = len(re.findall(
        r"calls=%all-reduce-scatter|\sreduce-scatter(?:-start)?\(", hlo
    ))
    flash_kernels = pallas_calls([hlo], "self_attn")
    # read before the one-chip leg: every chip has held only its share
    per_device = [memory_stats(d) for d in devices]
    del trainer, hlo
    gc.collect()

    # (b) the same seed, batch and steps on one chip, local MoE path
    ctx = MeshParameters().build([devices[0]])
    mark = len(introspect.inventory())
    trainer = build_trainer(
        ctx, model_config(sizes), sizes, seed, batch, sharded=False
    )
    single = take_steps(trainer, mark)
    del trainer
    gc.collect()

    sharded_losses, single_losses = sharded["losses"], single["losses"]
    gaps = [abs(a - b) for a, b in zip(sharded_losses, single_losses)]
    require(
        len(sharded_losses) == len(single_losses) == sizes.train_steps
        and all(math.isfinite(v) for v in sharded_losses + single_losses),
        f"losses {sharded_losses} / {single_losses}",
    )
    require(
        gaps[0] <= LOSS_TOL and max(gaps) <= MULTICHIP_LOSS_TOL,
        f"FSDP x EP losses {sharded_losses} against one chip "
        f"{single_losses}: gaps {gaps} beyond {LOSS_TOL} at step 0 or "
        f"{MULTICHIP_LOSS_TOL} later",
    )
    require(sharded_losses[-1] < sharded_losses[0], f"{sharded_losses}")
    if expect_kernels:
        missing = [k for k in ("ragged-all-to-all", "all-gather",
                               "reduce-scatter") if not collectives[k]]
        require(not missing, f"not in the step's HLO: {missing}")
        require(flash_kernels >= 3, f"{flash_kernels} flash kernels in HLO")
        # the chips' shares of the sharded run must look alike
        peaks = [m["peak_bytes_in_use"] for m in per_device]
        require(
            max(peaks) <= 1.25 * min(peaks),
            f"per-chip peaks differ: {per_device}",
        )
    return emit({
        "phase": "fsdp_ep", "chips": n, "mesh": {"dp_shard": n, "ep_shard": n},
        "params": n_params, "batch": sizes.batch, "seq_len": sizes.seq_len,
        "sharded": sharded, "one_chip": single,
        "loss_gaps": gaps, "tol_step0": LOSS_TOL,
        "tol_later": MULTICHIP_LOSS_TOL,
        "collectives_in_hlo": collectives,
        "flash_kernels_in_hlo": flash_kernels,
        "placement": placement, "memory_per_device": per_device,
    })


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    t0 = time.perf_counter()
    device = check_device()
    sizes = Sizes.full()
    emit({"phase": "start", "device": device, "compile_cache": cache_dir,
          "jax": jax.__version__, "seed": args.seed, "chips": args.chips})
    phase_distributed()
    if args.chips == 4:
        require(device["count"] >= 4, f"--chips 4 on {device['count']} chips")
        phase_four_chip(
            sizes, args.seed, jax.devices()[:4], expect_kernels=True
        )
    else:
        phase_sync(sizes)
        _, params = phase_train(sizes, args.seed, expect_kernels=True)
        phase_serve(sizes, args.seed, params, expect_kernels=True)
    emit({"phase": "done", "wall_s": round(time.perf_counter() - t0, 1),
          "compile_s_total": compile_seconds("")})
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
