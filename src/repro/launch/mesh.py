"""Production mesh construction.

A function (not a module constant) so importing never touches jax device
state.  Single pod: 16×16 = 256 chips ("data","model").  Multi-pod: 2 pods
of 256 ("pod","data","model").  At 1000+-node scale the same axes extend
(pod count grows; the code only ever names axes, never sizes).
"""
from __future__ import annotations

import jax


def auto_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto: the compiler propagates
    shardings through gathers and scatters, as the SPMD code here expects
    (Explicit axes, the default for a bare `make_mesh`, reject them)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2):
    """Small mesh over whatever devices exist (tests on CPU hosts)."""
    return auto_mesh((data, model), ("data", "model"))


def make_shard_mesh(shards: int | None = None):
    """1-D mesh for the block-aligned conflict-free training tier.

    `sgd.train_epoch_scheduled` shard_maps the D×D-blocked tier over the
    single ``"shard"`` axis (one device per col block, row blocks ring-
    rotating).  Defaults to all local devices; the trainer falls back to
    the single-device replay when only one device exists."""
    shards = shards or jax.device_count()
    return auto_mesh((shards,), ("shard",))


def serve_shard_count(request: int | str) -> int:
    """Resolve `ServeConfig.shards` to a device count for the sharded
    serving tier.

    ``0`` → 1 (single-device oracle path); ``"auto"`` → the largest
    power of two ≤ the local device count; an explicit int must be a
    power of two ≤ the device count.  Power-of-two only: the serving
    top-N tree reduce is an XOR-partner butterfly (`service.recommend`'s
    ppermute halving merge), whose disjoint-coverage invariant — no
    candidate ever counted twice — needs 2^k participants."""
    avail = jax.device_count()
    if request == "auto":
        return 1 << max(avail.bit_length() - 1, 0)
    d = int(request)
    if d == 0:
        return 1
    if d < 1 or d & (d - 1):
        raise ValueError(f"serve shards must be a power of two, got {d}")
    if d > avail:
        raise ValueError(f"serve shards={d} exceeds the {avail} local "
                         f"device(s)")
    return d
