"""SDPA backend factory with auto-detection and env override.

Reference: d9d/module/block/attention/sdpa/factory.py:42 (auto order
flash4 > flash2 > torch > eager, env ``D9D_BACKEND_AUTO_SDPA``). Here the
order is pallas_flash (TPU) > eager, and the override channel is
``D9D_TPU_BACKEND_SDPA`` carrying a JSON-encoded config.
"""

import functools
import json
import os

import jax
import pydantic

from d9d_tpu.nn.sdpa.config import (
    SdpaBackendConfig,
    SdpaEagerConfig,
    SdpaPallasFlashConfig,
    SdpaRingConfig,
)
from d9d_tpu.nn.sdpa.protocol import SdpaBackend

ENV_OVERRIDE = "D9D_TPU_BACKEND_SDPA"

_adapter = pydantic.TypeAdapter(SdpaBackendConfig)


def _auto_config() -> SdpaBackendConfig:
    if os.environ.get(ENV_OVERRIDE):
        return _adapter.validate_python(json.loads(os.environ[ENV_OVERRIDE]))
    if jax.default_backend() == "tpu":
        return SdpaPallasFlashConfig()
    return SdpaEagerConfig()


def build_sdpa_backend(config: SdpaBackendConfig | None = None) -> SdpaBackend:
    """Build a backend; ``None`` = auto-detect (env override wins)."""
    if config is None:
        config = _auto_config()
    if isinstance(config, SdpaEagerConfig):
        from d9d_tpu.ops.attention.eager import eager_sdpa

        return eager_sdpa
    if isinstance(config, SdpaPallasFlashConfig):
        from d9d_tpu.ops.attention.pallas_flash import make_pallas_flash_sdpa

        return make_pallas_flash_sdpa(
            block_q=config.block_q, block_kv=config.block_kv,
            fused_bwd=config.fused_bwd,
            batch_axes=config.batch_axes, head_axes=config.head_axes,
        )
    if isinstance(config, SdpaRingConfig):
        from d9d_tpu.core.mesh import resolve_ambient_mesh
        from d9d_tpu.ops.attention.ring import make_ring_sdpa

        mesh = resolve_ambient_mesh(
            (config.seq_axis, *config.batch_axes, *config.head_axes),
            what="ring sdpa",
        )
        return make_ring_sdpa(
            mesh,
            seq_axis=config.seq_axis,
            batch_axes=config.batch_axes,
            head_axes=config.head_axes,
        )
    raise TypeError(f"unknown sdpa config: {config!r}")


@functools.cache
def default_sdpa_backend() -> SdpaBackend:
    return build_sdpa_backend()
