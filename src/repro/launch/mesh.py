"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialization, and everything else sees the real device count.

Axis sizes are validated eagerly: jax's own mesh builders silently
construct a mesh over a *subset* of the devices when the requested
shape's product merely fits under ``jax.device_count()`` (e.g. a (3, 2)
request on 8 devices yields a 6-device mesh with 2 chips idle — or, at
worst, a 1-device mesh). Production meshes must cover the machine, so a
shape whose product does not divide the device count raises with the
factorizations that would.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax

from jax.sharding import AxisType


def _factorizations(n: int, k: int) -> Tuple[Tuple[int, ...], ...]:
    """All ordered k-tuples of positive ints whose product is n."""
    if k == 1:
        return ((n,),)
    out = []
    for d in range(1, n + 1):
        if n % d == 0:
            out.extend((d,) + rest for rest in _factorizations(n // d, k - 1))
    return tuple(out)


def validate_mesh_shape(shape: Sequence[int], axes: Sequence[str]) -> None:
    """Raise unless ``prod(shape)`` exactly divides the device count."""
    shape = tuple(int(s) for s in shape)
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} has {len(shape)} dims but "
                         f"{len(axes)} axis names {tuple(axes)}")
    n = 1
    for s in shape:
        if s < 1:
            raise ValueError(f"mesh axis sizes must be >= 1, got {shape}")
        n *= s
    dc = jax.device_count()
    if n > dc or dc % n != 0:
        opts = _factorizations(dc, len(shape))
        raise ValueError(
            f"mesh shape {shape} needs {n} devices but jax.device_count() "
            f"is {dc}; pick a {len(shape)}-axis factorization of {dc}: "
            f"{list(opts[:16])}"
            + (" …" if len(opts) > 16 else ""))


def make_mesh(shape, axes):
    """Generic validated mesh (small CPU meshes for tests and probing)."""
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    validate_mesh_shape(shape, axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def probe_axis_names(shape) -> Tuple[str, ...]:
    """Axis names for a probing mesh: ('dev',) or ('dev0', 'dev1', …)."""
    return ("dev",) if len(shape) == 1 else \
        tuple(f"dev{i}" for i in range(len(shape)))


def parse_mesh_arg(arg) -> Tuple[int, ...]:
    """CLI mesh shape: '8' -> (8,), '2x4' or '2,4' -> (2, 4); None/''
    -> () (no mesh)."""
    if not arg:
        return ()
    parts = [p for p in str(arg).replace("x", ",").split(",") if p.strip()]
    try:
        return tuple(int(p) for p in parts)
    except ValueError:
        raise ValueError(f"bad --mesh {arg!r}: expected e.g. '8' or '2x4'")


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
