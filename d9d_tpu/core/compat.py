"""One spelling for the ambient-mesh and compiled-analysis calls.

The repository runs on one installation (jax 0.9, ``pyproject.toml``),
so every helper here is the native call; they exist so that callers
share one keyword surface (``shard_map``'s optional arguments) and one
normalized shape for what a backend returns from its analyses.
"""

import jax

__all__ = [
    "HAS_MODERN_JAX",
    "compiled_cost_analysis",
    "compiled_memory_analysis",
    "device_hbm_capacity",
    "get_abstract_mesh",
    "mesh_axis_types_kwargs",
    "set_mesh",
    "shard_map",
]

# Constant since the 0.4.x emulation went; the test skips that still read
# it go with the Design item ``jax-0.4-compat`` (ROADMAP.md).
HAS_MODERN_JAX = True

set_mesh = jax.set_mesh
get_abstract_mesh = jax.sharding.get_abstract_mesh


def mesh_axis_types_kwargs(n_axes: int) -> dict:
    """``axis_types=(Auto,) * n``: ``jax.make_mesh`` defaults to Explicit
    (sharding-in-types), which rejects plain ``jit`` use."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


# -- compiled-executable introspection (telemetry/introspect.py) --------

_MEMORY_FIELDS = (
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "generated_code_size_in_bytes",
    "alias_size_in_bytes",
)


def compiled_cost_analysis(compiled) -> dict | None:
    """``Compiled.cost_analysis()`` as one flat ``{str: float}`` dict, or
    None when the backend returns nothing."""
    ca = compiled.cost_analysis()
    if not ca:
        return None
    return {str(k): float(v) for k, v in ca.items()}


def compiled_memory_analysis(compiled) -> dict | None:
    """``Compiled.memory_analysis()`` as ``{field: int bytes}`` over the
    standard CompiledMemoryStats size fields, or None when the backend
    returns nothing."""
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    return {field: int(getattr(ma, field)) for field in _MEMORY_FIELDS}


def device_hbm_capacity() -> int | None:
    """Per-chip accelerator memory capacity in bytes (``bytes_limit``
    from the device's memory stats), or None where the backend exposes
    none (CPU rigs) — callers skip the budget gauge then."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        return None
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None,
              axis_names=None):
    """``jax.shard_map`` with the keyword surface this repo uses: the two
    optional arguments are passed only when given."""
    kwargs = {}
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    if axis_names is not None:
        kwargs["axis_names"] = axis_names
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )
