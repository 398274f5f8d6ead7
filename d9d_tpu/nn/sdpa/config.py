"""SDPA backend configs — pydantic discriminated union.

Reference pattern: d9d/module/block/attention/sdpa/config.py:8-76 and the
backend-selection DEP (deps/0008-dep-backend-selection.md): every backend
family gets a typed config union + a factory with auto-detection + one env
override channel carrying a JSON-encoded config.
"""

from typing import Annotated, Literal, Union

import pydantic


class SdpaEagerConfig(pydantic.BaseModel):
    """Pure-XLA attention. Full feature surface; the correctness oracle."""

    type: Literal["eager"] = "eager"


class SdpaPallasFlashConfig(pydantic.BaseModel):
    """Pallas flash-attention kernel (TPU only)."""

    type: Literal["pallas_flash"] = "pallas_flash"
    block_q: int = 1024
    block_kv: int = 512
    # one-pass backward (see ops/attention/pallas_flash._bwd_fused_kernel);
    # None = env D9D_TPU_FLASH_BWD ("fused"/"split"), default split
    fused_bwd: bool | None = None
    # mesh axes the batch / head dims are split over: on a multi-device
    # mesh the kernel shard_maps itself over them (Mosaic kernels are not
    # auto-partitionable). Sequence-sharded runs use SdpaRingConfig.
    batch_axes: tuple[str, ...] = ("dp_r", "dp_s")
    head_axes: tuple[str, ...] = ("tp",)


class SdpaRingConfig(pydantic.BaseModel):
    """Ring attention over the context-parallel mesh axis (ops/attention/
    ring.py). Requires the model's sequence dim sharded over ``seq_axis``."""

    type: Literal["ring"] = "ring"
    seq_axis: str = "cp_s"
    batch_axes: tuple[str, ...] = ("dp_r", "dp_s")
    head_axes: tuple[str, ...] = ("tp",)


SdpaBackendConfig = Annotated[
    Union[SdpaEagerConfig, SdpaPallasFlashConfig, SdpaRingConfig],
    pydantic.Field(discriminator="type"),
]
