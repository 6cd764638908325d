"""Production mesh definitions.

A *function*, not a module-level constant: importing this module never
touches jax device state (required so smoke tests see 1 CPU device while
the dry-run sees 512 placeholders).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes.  The sharding rules of this repo
    (``with_sharding_constraint`` on logical axes, gathers over sharded
    operands) leave placement to the compiler's propagation, which is
    what Auto axes mean; ``jax.make_mesh`` makes Explicit axes by
    default, under which those gathers raise ``ShardingTypeError``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods.

    Axes: "pod" (DP across pods, ICI/DCN boundary), "data" (DP + FSDP
    weight sharding within a pod), "model" (TP/EP/SP).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(devices: int | None = None):
    """Small mesh over whatever devices exist (tests: 8 fake CPU devices)."""
    n = devices or len(jax.devices())
    model = 2 if n % 2 == 0 else 1
    return make_mesh((n // model, model), ("data", "model"))


def make_tp_mesh(tp: int):
    """Tensor-parallel serving mesh: ("data"=1, "model"=tp) over the
    first ``tp`` devices.  On CPU, simulate devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    initializes (the serve/benchmark entry points set it for you when
    ``--tp`` is passed)."""
    import numpy as np
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < tp:
        raise RuntimeError(
            f"tp={tp} needs {tp} devices, found {len(devs)}; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp}")
    return Mesh(np.asarray(devs[:tp]).reshape(1, tp), ("data", "model"))


def make_tp_dp_mesh(tp: int, dp: int, offset: int = 0):
    """Composed serving mesh: ("data"=dp, "model"=tp) over the first
    ``dp * tp`` devices (from ``offset`` on: one mesh per router
    replica on a larger host).  The "model" axis KV-head-shards the paged
    pools (tensor parallelism); the "data" axis batch-shards the
    *slot* dimension of every paged attention call, so a step's compute
    splits across data shards while the pools (replicated over "data")
    and the host page tables stay bit-identical on every shard.  On
    CPU, simulate ``dp * tp`` devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
    initializes (the entry points do this when ``--tp``/``--dp`` are
    passed)."""
    import numpy as np
    from jax.sharding import Mesh
    if tp < 1 or dp < 1:
        raise ValueError(f"tp and dp must be >= 1, got tp={tp} dp={dp}")
    devs = jax.devices()[offset:]
    need = dp * tp
    if len(devs) < need:
        raise RuntimeError(
            f"tp={tp} x dp={dp} needs {need} devices, found {len(devs)}; "
            f"on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    return Mesh(np.asarray(devs[:need]).reshape(dp, tp),
                ("data", "model"))
