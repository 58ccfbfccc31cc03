"""Production mesh factory (assignment MULTI-POD DRY-RUN §1).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips of a
v5e pod; multi-pod: (pod=2, data=16, model=16) = 512 chips, the `pod` axis
crossing DCI.

`make_mesh` front-loads shape/axis validation: shard_map's own failures
on a malformed mesh surface deep inside jaxpr lowering ("NamedSharding
axis ... undefined", size-mismatch asserts), so the factory rejects the
request with an actionable message instead — wrong arity, non-positive or
non-divisible dims, duplicate or misspelled axis names (suggesting the
closest known spelling).
"""
from __future__ import annotations

import difflib

import jax

# Axis names the repo's shard_map programs bind (core/distributed.py,
# launch/fvs_dryrun.py): misspelling one of these is the typo class the
# validator catches — any OTHER novel name is legal, just unknown.
KNOWN_AXES = ("pod", "data", "model", "shard")


def validate_mesh_request(shape: tuple[int, ...], axes: tuple[str, ...],
                          num_devices: int | None = None) -> None:
    """Raise ValueError (with the fix spelled out) on a bad mesh request.

    `num_devices=None` checks shape/axes consistency only — the abstract
    multi-pod dry-run builds 512-chip meshes from a CPU container, so
    device-count checks must stay opt-in.
    """
    if len(shape) != len(axes):
        raise ValueError(
            f"mesh shape {tuple(shape)} has {len(shape)} dims but "
            f"{len(axes)} axis names {tuple(axes)} — one name per dim")
    for dim, name in zip(shape, axes):
        if int(dim) < 1:
            raise ValueError(
                f"mesh axis {name!r} has non-positive size {dim}; every "
                "axis needs at least one device")
    dupes = {a for a in axes if list(axes).count(a) > 1}
    if dupes:
        raise ValueError(
            f"duplicate mesh axis name(s) {sorted(dupes)} in {tuple(axes)}"
            " — collectives bind by name, so names must be unique")
    for name in axes:
        if name not in KNOWN_AXES:
            close = difflib.get_close_matches(name, KNOWN_AXES, n=1)
            hint = f" — did you mean {close[0]!r}?" if close else ""
            raise ValueError(
                f"unknown mesh axis name {name!r}{hint} (known axes: "
                f"{KNOWN_AXES}; a shard_map program binding the intended "
                "axis would fail to find it at lowering time)")
    if num_devices is not None:
        total = 1
        for dim in shape:
            total *= int(dim)
        if num_devices % total != 0:
            raise ValueError(
                f"mesh shape {tuple(shape)} needs {total} devices but "
                f"{num_devices} are available — {num_devices} is not "
                f"divisible by {total}; shrink an axis (e.g. shard over "
                f"{num_devices} or a divisor) or free devices")


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              check_devices: bool = False):
    """Validated mesh construction; `check_devices=True` additionally
    checks the request against the live `jax.devices()` count (leave off
    for abstract dry-run meshes)."""
    validate_mesh_request(
        shape, axes,
        num_devices=len(jax.devices()) if check_devices else None)
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
