"""Production mesh construction.

Axis semantics (DESIGN.md §4):
  pod   — cross-pod data parallelism (DCN; gradient all-reduce / top-k merge)
  data  — in-pod batch + ZeRO/fsdp sharding (ICI)
  model — tensor/expert/sequence/corpus parallelism (ICI)

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax

from jax import make_mesh
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Degenerate 1-chip mesh with the full axis set (CPU tests / examples)."""
    n = len(jax.devices())
    if n >= 4:
        # spread over whatever local devices exist (e.g. XLA host-device tests)
        model = 2
        data = n // 2
        return make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    return make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def make_serving_mesh(spec: str):
    """Corpus-serving mesh from a ``--mesh`` CLI spec like ``"1x8"``.

    The rightmost axes of (pod, data, model) are used: ``"8"`` -> 8-way
    ``model``, ``"1x8"`` -> (data=1, model=8), ``"2x2x2"`` -> all three.
    LEMUR's corpus sharding spans every axis (``dist.serve.corpus_axes``),
    so the split across names only matters when serving shares the mesh
    with batch-parallel work."""
    shape = parse_mesh_spec(spec)
    axes = ("pod", "data", "model")[3 - len(shape):]
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def parse_mesh_spec(spec: str) -> tuple[int, ...]:
    """``"1x8"`` -> (1, 8).  1-3 ``x``-separated positive ints."""
    try:
        shape = tuple(int(p) for p in str(spec).lower().split("x"))
    except ValueError:
        raise ValueError(f"bad --mesh spec {spec!r}; want e.g. '8' or '1x8'")
    if not 1 <= len(shape) <= 3 or any(s < 1 for s in shape):
        raise ValueError(f"bad --mesh spec {spec!r}; want 1-3 positive ints")
    return shape


def ensure_devices(n: int) -> None:
    """Make sure ``n`` devices exist for a ``--mesh`` request.  On the CPU
    platform (``JAX_PLATFORMS=cpu``) XLA host devices are forced, which
    works as long as no jax backend has been initialized yet (the flag is
    read then).  On any other platform the ``n`` devices must be real."""
    import os

    cpu = (jax.config.jax_platforms or "").split(",")[0] == "cpu"
    if n > 1 and cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={n}".strip())
    have = len(jax.devices())
    if have < n:
        hint = (f"launch with XLA_FLAGS=--xla_force_host_platform_"
                f"device_count={n}" if cpu else
                f"run on a {n}-device host, or set JAX_PLATFORMS=cpu to "
                f"rehearse on {n} forced host devices")
        raise RuntimeError(
            f"--mesh needs {n} devices but only {have} "
            f"{jax.devices()[0].platform} device(s) are visible; {hint}")


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def n_devices(mesh) -> int:
    import numpy as np

    return int(np.prod(list(mesh.shape.values())))
