"""Built-in task implementations."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from d9d_tpu.core.types import Array, PyTree
from d9d_tpu.loop.control.task import PipelineTrainTask, TrainTask
from d9d_tpu.nn.moe import EP_BUFFER_STATS
from d9d_tpu.ops import LM_IGNORE_INDEX


def _moe_load_metrics(updates: PyTree) -> dict[str, Array]:
    """Expert load-balance statistics from sown ``moe_stats``
    (reference tokens_per_expert buffer, module/block/moe/layer.py:16).

    Emits the raw per-expert assignment-count vector (summed over layers)
    so the engine's microbatch scan sums it exactly; the max/total ratio
    is taken host-side in ``metrics_postprocess`` — taking max per
    microbatch first would bias the share upward with small microbatches.
    Under expert parallelism the receive buffer's use rides along the same
    way: rows taken, rows needed, fallbacks and dispatches, summed over
    layers here and over microbatches by the scan, turned into shares
    host-side. Covers the logged step (not the whole log window).
    Single-program path only: under pipeline parallelism the executor's
    metric channel carries last-stage loss statistics and this metric is
    absent. Empty dict for dense models."""
    stats = updates.get("moe_stats") if updates else None
    if not stats:
        return {}
    by_name: dict[str, list[Array]] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
        stats, is_leaf=lambda x: isinstance(x, tuple)
    ):
        leaf = leaf[0] if isinstance(leaf, tuple) else leaf
        name = getattr(path[-1], "key", path[-1])
        by_name.setdefault(f"moe_{name}", []).append(
            leaf.astype(jnp.float32)
        )
    return {name: sum(leaves) for name, leaves in by_name.items()}


class CausalLMTask(PipelineTrainTask):
    """Next-token prediction with token-count loss weighting.

    Equivalent of the reference example's SFT task
    (example/qwen3_moe/pretrain.py): expects batches with ``input_ids``
    [B, T+1] (and optional ``loss_mask`` [B, T+1]); shifts internally.
    The model must be a CausalLM returning per-token loss given
    (tokens, positions, labels).
    """

    def prepare_batch(self, batch: PyTree) -> PyTree:
        input_ids = np.asarray(batch["input_ids"])
        tokens = input_ids[:, :-1]
        labels = input_ids[:, 1:].copy()
        if "loss_mask" in batch:
            labels = np.where(
                np.asarray(batch["loss_mask"])[:, 1:] != 0, labels, LM_IGNORE_INDEX
            )
        b, t = tokens.shape
        positions = np.broadcast_to(np.arange(t, dtype=np.int32), (b, t)).copy()
        return {"tokens": tokens, "labels": labels, "positions": positions}

    def loss_fn(
        self, module: nn.Module, params: PyTree, mb: PyTree, rng: Array
    ) -> tuple[Array, Array, dict[str, Array]]:
        per_token, updates = module.apply(
            params, mb["tokens"], mb["positions"], mb["labels"],
            mutable=["moe_stats"],
        )
        valid = (mb["labels"] != LM_IGNORE_INDEX).astype(jnp.float32)
        loss_sum = per_token.sum()
        weight = valid.sum()
        metrics = {"tokens": weight}
        metrics.update(_moe_load_metrics(updates))
        return loss_sum, weight, metrics

    def metrics_postprocess(self, metrics):
        counts = metrics.pop("task/moe_tokens_per_expert", None)
        if counts is not None:
            counts = np.asarray(counts, np.float64)
            # heaviest expert's share of routed assignments (layer-summed);
            # 1/num_experts = perfectly balanced routing
            metrics["task/moe_load_max_frac"] = float(
                counts.max() / max(counts.sum(), 1.0)
            )
        taken, needed, fallbacks, dispatches = (
            metrics.pop(f"task/moe_{name}", None) for name in EP_BUFFER_STATS
        )
        if dispatches:
            # dropless EP: the share of layer-steps whose receive buffer was
            # the worst case, and how full the buffers taken were
            metrics["moe/ep_fallback_share"] = float(fallbacks / dispatches)
            metrics["moe/ep_buffer_fill"] = float(needed / max(taken, 1.0))
        # a held range of a wider router's experts (MoELayer): the routed
        # pairs that landed on the experts held, and all routed pairs
        for name in ("rows_held", "rows_routed"):
            count = metrics.pop(f"task/moe_{name}", None)
            if count is not None:
                metrics[f"moe/{name}"] = float(count)
        # a model that trains on more than the next-token loss sows the
        # terms' sums beside the experts' counts: per-token means here
        tokens = metrics.get("task/tokens")
        for name in ("next_token", "mtp"):
            total = metrics.pop(f"task/moe_loss_{name}", None)
            if total is not None and tokens:
                metrics[f"loss/{name}"] = float(total / tokens)
        return metrics

    # -- pipeline surface (PipelineTrainTask) --------------------------
    # carry = token ids on stage 0, hidden states after; positions ride
    # kwargs (every stage's RoPE needs them); labels ride last-stage state.

    def sample_microbatch(self, microbatch_size: int, seq_len: int) -> PyTree:
        z = np.zeros((microbatch_size, seq_len), np.int32)
        return {"tokens": z, "labels": z, "positions": z}

    def split_microbatch(self, mb: PyTree) -> tuple[PyTree, PyTree, PyTree]:
        return (
            mb["tokens"],
            {"positions": mb["positions"]},
            {"labels": mb["labels"]},
        )

    def stage_forward(
        self, module: nn.Module, params: PyTree, carry: PyTree, kwargs: PyTree
    ) -> PyTree:
        return module.apply(params, carry, kwargs["positions"])

    def last_stage_loss(self, module, params, carry, kwargs, state):
        per_token = module.apply(
            params, carry, kwargs["positions"], state["labels"]
        )
        valid = (state["labels"] != LM_IGNORE_INDEX).astype(jnp.float32)
        return per_token.sum(), valid.sum(), {"tokens": valid.sum()}

    def stage_init(self, module, rng, carry, kwargs, state, is_last):
        if is_last:
            return module.init(
                rng, carry, kwargs["positions"], state["labels"]
            )
        return module.init(rng, carry, kwargs["positions"])


class SequenceClassificationTask(TrainTask):
    """Fine-tune a classification-head model (reference task surface,
    loop/control/task.py:180 + the ClassificationHead model family).

    Batches: ``input_ids`` [B, T] (+ optional ``attention_mask`` [B, T])
    and integer ``class_labels`` [B]. The model must map
    (tokens, positions, pooling_mask) → logits [B, C]. Per-class confusion
    counts are reduced on device inside the step; the ConfusionMatrixMetric
    aggregates them across the log window and processes.
    """

    def __init__(self, num_classes: int):
        self.num_classes = num_classes

    def prepare_batch(self, batch: PyTree) -> PyTree:
        tokens = np.asarray(batch["input_ids"])
        b, t = tokens.shape
        out = {
            "tokens": tokens,
            "labels": np.asarray(batch["class_labels"]).astype(np.int32),
            "positions": np.broadcast_to(
                np.arange(t, dtype=np.int32), (b, t)
            ).copy(),
        }
        if "attention_mask" in batch:
            out["pooling_mask"] = np.asarray(batch["attention_mask"])
        else:
            out["pooling_mask"] = np.ones((b, t), np.int32)
        return out

    def loss_fn(self, module, params, mb, rng):
        logits = module.apply(
            params, mb["tokens"], mb["positions"], mb["pooling_mask"]
        ).astype(jnp.float32)
        labels = mb["labels"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        loss_sum = -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()
        b = labels.shape[0]
        pred = jnp.argmax(logits, axis=-1)
        pred_1h = jax.nn.one_hot(pred, self.num_classes, dtype=jnp.float32)
        true_1h = jax.nn.one_hot(labels, self.num_classes, dtype=jnp.float32)
        tp = (pred_1h * true_1h).sum(0)
        fp = (pred_1h * (1 - true_1h)).sum(0)
        fn = ((1 - pred_1h) * true_1h).sum(0)
        tn = ((1 - pred_1h) * (1 - true_1h)).sum(0)
        metrics = {
            "correct": (pred == labels).sum().astype(jnp.float32),
            "examples": jnp.asarray(b, jnp.float32),
            "confusion": jnp.stack([tp, fp, tn, fn]),  # [4, C]
        }
        return loss_sum, jnp.asarray(b, jnp.float32), metrics

    def metrics_postprocess(self, metrics):
        # per-step console view; the windowed truth rides the Metric objects
        if "task/correct" in metrics and "task/examples" in metrics:
            metrics["task/accuracy"] = metrics["task/correct"] / max(
                metrics["task/examples"], 1.0
            )
            metrics.pop("task/confusion", None)
        return metrics

    def metrics(self):
        from d9d_tpu.metric import ConfusionMatrixMetricBuilder

        return {
            "accuracy": (
                ConfusionMatrixMetricBuilder()
                .multiclass(self.num_classes)
                .with_accuracy()
                .micro()
                .build()
            ),
        }

    def update_metrics(self, metric_objs, stats):
        tp, fp, tn, fn = np.asarray(stats["confusion"])
        metric_objs["accuracy"].update_counts(tp=tp, fp=fp, tn=tn, fn=fn)


class EmbeddingContrastiveTask(TrainTask):
    """In-batch contrastive (InfoNCE) training for the embedding head
    (reference embedding task, loop/control/task.py:262 family).

    Batches: ``input_ids_a``/``input_ids_b`` [B, T] paired views. The
    model must map (tokens, positions, pooling_mask) → L2-normalized
    embeddings [B, D]. Loss is symmetric InfoNCE over the in-batch
    similarity matrix; retrieval@1 counts ride the metric window.
    """

    def __init__(self, temperature: float = 0.05):
        self.temperature = temperature

    def prepare_batch(self, batch: PyTree) -> PyTree:
        a = np.asarray(batch["input_ids_a"])
        b_ids = np.asarray(batch["input_ids_b"])
        bsz, t = a.shape
        positions = np.broadcast_to(np.arange(t, dtype=np.int32), (bsz, t))
        return {
            "tokens_a": a,
            "tokens_b": b_ids,
            "positions": positions.copy(),
            "pooling_mask": np.asarray(
                batch.get("attention_mask", np.ones((bsz, t), np.int32))
            ),
        }

    def loss_fn(self, module, params, mb, rng):
        emb_a = module.apply(
            params, mb["tokens_a"], mb["positions"], mb["pooling_mask"]
        ).astype(jnp.float32)
        emb_b = module.apply(
            params, mb["tokens_b"], mb["positions"], mb["pooling_mask"]
        ).astype(jnp.float32)
        sim = emb_a @ emb_b.T / self.temperature  # [B, B]
        bsz = sim.shape[0]
        targets = jnp.arange(bsz)
        logp_ab = jax.nn.log_softmax(sim, axis=-1)
        logp_ba = jax.nn.log_softmax(sim.T, axis=-1)
        diag = jnp.diag_indices(bsz)
        loss_sum = -(logp_ab[diag].sum() + logp_ba[diag].sum()) / 2.0
        hits = (jnp.argmax(sim, axis=-1) == targets).sum()
        metrics = {
            "retrieval_hits": hits.astype(jnp.float32),
            "examples": jnp.asarray(bsz, jnp.float32),
        }
        return loss_sum, jnp.asarray(bsz, jnp.float32), metrics

    def metrics(self):
        from d9d_tpu.metric import WeightedMeanMetric

        return {"retrieval_at_1": WeightedMeanMetric()}

    def update_metrics(self, metric_objs, stats):
        # WeightedMeanMetric computes Σ(value·weight)/Σweight, so feed the
        # per-window hit *rate* with the example count as its weight
        examples = np.asarray(stats["examples"], np.float32)
        hits = np.asarray(stats["retrieval_hits"], np.float32)
        metric_objs["retrieval_at_1"].update(
            values=hits / np.maximum(examples, 1.0),
            weights=examples,
        )
