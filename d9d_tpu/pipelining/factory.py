"""Schedule factory: pydantic config → program builder.

Reference: d9d/pipelining/factory/{config.py:6-78, registry.py, factory.py:92}
— a discriminated-union schedule config resolved through a registry. The
TPU build keeps the config surface; "building" a schedule is composing the
program + comm injection + validation (the executor is wired by the loop).
"""

from typing import Annotated, Literal, Union

import pydantic

from d9d_tpu.pipelining.program.builders import (
    DualPipeVProgramBuilder,
    GPipeProgramBuilder,
    Interleaved1F1BProgramBuilder,
    InferenceProgramBuilder,
    LoopedBFSProgramBuilder,
    ProgramBuilder,
    ZeroBubbleVProgramBuilder,
)

__all__ = [
    "DualPipeVScheduleConfig",
    "GPipeScheduleConfig",
    "Interleaved1F1BScheduleConfig",
    "InferenceScheduleConfig",
    "LoopedBFSScheduleConfig",
    "PipelineScheduleConfig",
    "ZeroBubble1PScheduleConfig",
    "ZeroBubbleVScheduleConfig",
    "build_program_builder",
]


class _RuntimeChoice(pydantic.BaseModel):
    """Executor selection, shared by every schedule config.

    "fused" is the compiled-run MPMD executor (runtime/fused.py): a few
    device-resident programs per step. "legacy" keeps the per-action
    interpreter (runtime/executor.py) — the bit-exact parity oracle,
    scheduled for removal one release after the fused default landed.
    """

    runtime: Literal["fused", "legacy"] = "fused"


class GPipeScheduleConfig(_RuntimeChoice):
    kind: Literal["gpipe"] = "gpipe"
    residual_policy: Literal["remat", "cache_full", "cache_acts"] = "remat"


class InferenceScheduleConfig(_RuntimeChoice):
    kind: Literal["inference"] = "inference"
    stages_per_rank: int = 1


class LoopedBFSScheduleConfig(_RuntimeChoice):
    kind: Literal["looped_bfs"] = "looped_bfs"
    residual_policy: Literal["remat", "cache_full", "cache_acts"] = "remat"
    stages_per_rank: int = 1


class Interleaved1F1BScheduleConfig(_RuntimeChoice):
    kind: Literal["interleaved_1f1b"] = "interleaved_1f1b"
    residual_policy: Literal["remat", "cache_full", "cache_acts"] = "remat"
    stages_per_rank: int = 1


# Zero-bubble schedules default to cache_full: under remat each dI and dW
# phase recomputes the stage forward (two extra forwards a microbatch
# against 1F1B's one), cache_full runs the fused backward once. remat
# remains available for memory-bound real-PP runs where filling bubbles
# with W-compute pays.
#
# "cache_acts" is the true zero-bubble split (dW at the W slot from saved
# residuals, 1F1B FLOPs; see runtime/stage.py). The dependency-level
# simulation (tools/pp_makespan.py) has it dominating both other policies
# at every multi-rank config; it stays opt-in until the residual
# write+read tax between the I and W jits is measured on chip (ROADMAP
# R-P, `zb-residual-policies`; not measured yet).


class ZeroBubble1PScheduleConfig(_RuntimeChoice):
    kind: Literal["zero_bubble_1p"] = "zero_bubble_1p"
    residual_policy: Literal["remat", "cache_full", "cache_acts"] = "cache_full"
    stages_per_rank: int = 1


class ZeroBubbleVScheduleConfig(_RuntimeChoice):
    kind: Literal["zero_bubble_v"] = "zero_bubble_v"
    residual_policy: Literal["remat", "cache_full", "cache_acts"] = "cache_full"


class DualPipeVScheduleConfig(_RuntimeChoice):
    kind: Literal["dual_pipe_v"] = "dual_pipe_v"
    residual_policy: Literal["remat", "cache_full", "cache_acts"] = "cache_full"


PipelineScheduleConfig = Annotated[
    Union[
        GPipeScheduleConfig,
        InferenceScheduleConfig,
        LoopedBFSScheduleConfig,
        Interleaved1F1BScheduleConfig,
        ZeroBubble1PScheduleConfig,
        ZeroBubbleVScheduleConfig,
        DualPipeVScheduleConfig,
    ],
    pydantic.Field(discriminator="kind"),
]


def build_program_builder(
    config: PipelineScheduleConfig, pp: int
) -> ProgramBuilder:
    if isinstance(config, GPipeScheduleConfig):
        return GPipeProgramBuilder(pp)
    if isinstance(config, InferenceScheduleConfig):
        return InferenceProgramBuilder(pp, config.stages_per_rank)
    if isinstance(config, LoopedBFSScheduleConfig):
        return LoopedBFSProgramBuilder(pp, config.stages_per_rank)
    if isinstance(config, Interleaved1F1BScheduleConfig):
        return Interleaved1F1BProgramBuilder(pp, config.stages_per_rank)
    if isinstance(config, ZeroBubble1PScheduleConfig):
        return Interleaved1F1BProgramBuilder(
            pp, config.stages_per_rank, zero_bubble=True
        )
    if isinstance(config, ZeroBubbleVScheduleConfig):
        return ZeroBubbleVProgramBuilder(pp)
    if isinstance(config, DualPipeVScheduleConfig):
        return DualPipeVProgramBuilder(pp)
    raise TypeError(f"unknown schedule config {config!r}")
