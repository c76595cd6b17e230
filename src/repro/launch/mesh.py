"""Production mesh construction.

A function (not a module constant) so importing never touches jax device
state. Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis carries
cross-pod data parallelism (the scarce-bandwidth axis at 1000+ nodes — see
optim/compression.py for the cross-pod gradient path)."""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """A mesh whose axes are all `AxisType.Auto`: the stack's sharding rules
    are partition specs that the compiler propagates (`sharding.rules`),
    while `jax.make_mesh` defaults to explicit axes."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))


def auto_spmv_mesh() -> jax.sharding.Mesh:
    """Auto-factored host mesh for the sharded SpMV engine: the model axis
    gets 2 when the device count allows (so both mesh axes are exercised),
    the data axis the rest (8 devices -> (data=4, model=2); 1 -> (1, 1)).
    The single source of the factoring rule — `ShardedSpMVEngine`'s default
    mesh and ``serve --mesh data,model`` both resolve here."""
    n = len(jax.devices())
    return make_host_mesh(model_axis=2 if n > 1 and n % 2 == 0 else 1)


# The (data, model) grid normalization lives in core.runtime (core must not
# depend on launch); re-exported here so CLI-side mesh consumers find every
# mesh helper in one module.
from repro.core.runtime import data_model_grid  # noqa: E402,F401


def parse_mesh_spec(spec: str) -> jax.sharding.Mesh:
    """Mesh from a CLI spec for the sharded SpMV path.

    Two forms:
      * ``"data,model"`` (axis *names*) — auto-factor all visible devices
        via `auto_spmv_mesh`.
      * ``"4,2"`` / ``"4x2"`` (axis *sizes*) — explicit (data, model) shape;
        raises if more devices are requested than exist (fewer is fine: the
        mesh takes a prefix of the device list).
    """
    parts = [p.strip() for p in spec.replace("x", ",").split(",") if p.strip()]
    if parts == ["data", "model"]:
        return auto_spmv_mesh()
    try:
        sizes = [int(p) for p in parts]
    except ValueError:
        raise ValueError(
            f"--mesh expects 'data,model' or explicit sizes like '4,2', "
            f"got {spec!r}"
        )
    if len(sizes) != 2 or any(s < 1 for s in sizes):
        raise ValueError(
            f"--mesh sizes must be two positive ints (data, model), "
            f"got {spec!r}"
        )
    d, m = sizes
    devices = jax.devices()
    if d * m > len(devices):
        raise ValueError(
            f"--mesh {d},{m} needs {d * m} devices but only "
            f"{len(devices)} exist (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU)"
        )
    import numpy as np

    grid = np.asarray(devices[: d * m]).reshape(d, m)
    return jax.sharding.Mesh(grid, ("data", "model"))
