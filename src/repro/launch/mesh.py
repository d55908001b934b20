"""Mesh construction + sharding-constraint helpers.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). Models call ``shard(x, ...)`` which is a no-op unless a
mesh has been activated — so the same model code runs on 1 CPU device in
tests and on the 512-chip production mesh in the dry-run/launcher.

Axis convention:
  single-pod : (data=16, model=16)            axes ("data", "model")
  multi-pod  : (pod=2, data=16, model=16)     axes ("pod", "data", "model")
``pod`` is the outer data-parallel axis (gradient all-reduce crosses DCI);
``BATCH`` below shards over ("pod", "data") when both exist.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

AxisName = Union[str, tuple, None]

# canonical logical axes used throughout the model code
BATCH = ("pod", "data", "pool")  # batch / data-parallel (pool is inner DP)
MODEL = "model"  # tensor-parallel
POOL = "pool"  # weight-pooling cluster (shared-L2 analogue) — ZeRO shard axis


def make_production_mesh(*, multi_pod: bool = False, pool: int = 0) -> Mesh:
    """Production mesh: 256 chips/pod as (data=16, model=16); 2 pods = 512.

    ``pool=k`` factors the data axis into (data=16/k, pool=k): a k-device
    weight-pooling cluster (the paper's k-core shared-L2 cluster). Batch
    shards over (pod, data, pool) either way, so total DP is unchanged.
    """
    if pool:
        assert 16 % pool == 0, pool
        shape = (2, 16 // pool, pool, 16) if multi_pod else (16 // pool, pool, 16)
        axes = ("pod", "data", "pool", "model") if multi_pod else ("data", "pool", "model")
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests / CPU smoke).

    ``model`` must divide the host device count exactly: the old behavior
    (``n // model``) silently dropped the remainder devices from the mesh,
    which is never what a caller sizing a model axis wants.
    """
    n = len(jax.devices())
    if model < 1 or n % model != 0:
        dropped = n % model if model >= 1 else n
        raise ValueError(
            f"model={model} does not divide the {n} available devices; "
            f"an (n // model, model) mesh would silently drop {dropped} "
            "device(s). Pick a model-axis size that divides the device count."
        )
    return _auto_mesh((n // model, model), ("data", "model"))


def _auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: its default is ``Explicit``
    axes, which ``with_sharding_constraint`` (``shard`` below) refuses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_serving_mesh(model: int = 1) -> Mesh:
    """1-D ``("model",)`` mesh over the first ``model`` local devices.

    The sharded serving engine's mesh: unlike :func:`make_host_mesh` it
    does NOT require the model axis to divide the host device count — a
    2-shard replica on an 8-device host simply uses 2 devices (the other
    6 belong to other replicas). CPU-testable under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    devs = jax.devices()
    if model < 1 or model > len(devs):
        raise ValueError(
            f"model={model} shards need {model} devices; host has {len(devs)}"
        )
    return Mesh(np.asarray(devs[:model]), ("model",))


def shard_model_params(params, mesh: Mesh, axis: str = MODEL):
    """Place a parameter pytree on ``mesh`` with each leaf's LAST axis
    sharded over ``axis`` when divisible, replicated otherwise — the
    ``with_sharding_constraint``-style tensor-parallel layout, applied at
    placement time so every later jitted step computes on sharded operands
    without per-call constraint calls. On a 1-device mesh this is a pure
    device_put: values (and therefore decoded tokens) are bit-identical to
    the unsharded engine."""
    return jax.device_put(params, model_shardings(params, mesh, axis))


def model_shardings(tree, mesh: Mesh, axis: str = MODEL):
    """The :func:`shard_model_params` layout as a pytree of shardings, for
    arrays or shape stand-ins (``jax.eval_shape``) alike."""
    size = int(mesh.shape[axis])

    def one(x):
        if getattr(x, "ndim", 0) >= 1 and size > 1 and x.shape[-1] % size == 0:
            return NamedSharding(mesh, P(*([None] * (x.ndim - 1) + [axis])))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, tree)


# ---------------------------------------------------------------------------
# active-mesh context (thread-local; no global jax state)

_local = threading.local()


def active_mesh() -> Optional[Mesh]:
    return getattr(_local, "mesh", None)


@contextlib.contextmanager
def activate(mesh: Optional[Mesh]):
    prev = active_mesh()
    _local.mesh = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _local.mesh = prev


def _filter_spec(axes: Sequence[AxisName], mesh: Mesh) -> P:
    names = set(mesh.axis_names)
    out = []
    for a in axes:
        if a is None:
            out.append(None)
        elif isinstance(a, tuple):
            kept = tuple(n for n in a if n in names)
            out.append(kept if kept else None)
        else:
            out.append(a if a in names else None)
    return P(*out)


def spec(*axes: AxisName, mesh: Optional[Mesh] = None) -> P:
    """PartitionSpec with axes not present in the mesh dropped."""
    mesh = mesh or active_mesh()
    if mesh is None:
        return P(*axes)
    return _filter_spec(axes, mesh)


def shard(x: jax.Array, *axes: AxisName) -> jax.Array:
    """with_sharding_constraint if a mesh is active, else identity.

    Divisibility-aware: any requested axis whose size does not divide the
    corresponding array dimension is dropped (e.g. 15 query heads on a 16-way
    model axis stay replicated rather than erroring).
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    names = set(mesh.axis_names)
    out = []
    for i, a in enumerate(axes):
        if a is None or i >= x.ndim:
            out.append(None)
            continue
        parts = a if isinstance(a, tuple) else (a,)
        kept = tuple(n for n in parts if n in names)
        total = 1
        for n in kept:
            total *= sizes[n]
        if not kept or total == 0 or x.shape[i] % total != 0:
            out.append(None)
        else:
            out.append(kept if isinstance(a, tuple) else kept[0])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*out)))


def named(mesh: Mesh, *axes: AxisName) -> NamedSharding:
    return NamedSharding(mesh, _filter_spec(axes, mesh))


def tree_shardings(mesh: Mesh, specs) -> "jax.tree_util.PyTreeDef":
    """Map a pytree of PartitionSpecs to NamedShardings on ``mesh``."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, _filter_spec(tuple(s), mesh)),
        specs,
        is_leaf=lambda s: isinstance(s, P),
    )
