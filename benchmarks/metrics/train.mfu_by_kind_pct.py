"""Model FLOPs per token x tokens per second per chip over the peak, the
FLOPs counted by a layer's attention kind
(``attention_kinds_train_cost``): ``train.mfu_pct`` for a stack whose
layers differ in query heads and in the keys a query sees. A file that
states no ``layer_types`` has nothing here that ``train.mfu_pct`` does
not say."""

from benchmarks.harness import readers
from benchmarks.metrics import attention_kinds_train_cost as by_kind


def read(run):
    if "layer_types" not in run.hf:
        return None
    per_token = by_kind.train_flops_per_token(run.hf, run.observed.seq_len)
    return 100.0 * per_token * readers.train_rate(run) / run.peak.bf16_flops
