"""The JAX API points this repo routes through one module.

The repo is written for jax/jaxlib 0.9.0 (``requirements.txt`` pins
it). What lives here:

* mesh construction with every axis declared ``AxisType.Auto`` (the
  repo-wide convention: shardings are explicit NamedShardings +
  shard_map, never inferred Explicit-mode axes);
* ``shard_map`` with vma checking off: ``pallas_call`` inside
  ``shard_map`` can't declare vma on its ``out_shape``
  ShapeDtypeStructs — the escape hatch the error message itself
  recommends;
* multi-process bring-up: the CPU backend needs its collectives
  implementation switched to ``gloo`` before it initializes;
* the device-placement queries (which mesh axes cross processes) that
  every layer from the FFT schedule engine up needs.

All mesh construction, every ``shard_map``, and the cluster bootstrap
(``repro.runtime.cluster``) route through here.
"""
from __future__ import annotations

from typing import Sequence

import jax


def _auto_axes(axis_names: Sequence[str]):
    return (jax.sharding.AxisType.Auto,) * len(tuple(axis_names))


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=_auto_axes(axis_names), **kwargs)


def make_explicit_mesh(devices, axis_names: Sequence[str]):
    """``Mesh`` over an exactly-placed device ndarray — no reordering.

    ``jax.make_mesh`` may permute devices for collective efficiency,
    which would silently destroy a process-major DCN×ICI layout; the
    raw ``Mesh`` constructor honors placement verbatim. Axes are
    ``Auto``, as in ``make_mesh``.
    """
    return jax.sharding.Mesh(devices, tuple(axis_names),
                             axis_types=_auto_axes(axis_names))


def shard_map(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with vma checking disabled."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_crosses_processes(mesh, axis_name: str) -> bool:
    """True when moving along ``axis_name`` can change the owning
    process — i.e. a collective over that axis crosses the host
    interconnect (DCN) rather than staying on-node (ICI).

    Decided from device placement alone (``Device.process_index``
    along each ring of the mesh's device array), so it is correct for
    any mesh however it was built. Lives here — below every layer —
    because both the core FFT schedule engine and the runtime/launch
    layers need it.
    """
    axes = list(mesh.axis_names)
    ax = axes.index(axis_name)
    devs = mesh.devices                      # ndarray shaped like the mesh
    moved = devs.swapaxes(0, ax).reshape(devs.shape[ax], -1)
    for col in range(moved.shape[1]):
        procs = {d.process_index for d in moved[:, col]}
        if len(procs) > 1:
            return True
    return False


def mesh_process_topology(mesh):
    """Axis name → crosses-processes, for every axis of ``mesh``."""
    return {name: axis_crosses_processes(mesh, name)
            for name in mesh.axis_names}


def mesh_process_span(mesh):
    """The sorted process indices owning ``mesh``'s devices — the set
    that decides whether a collective over the mesh is safe (span ==
    whole cluster), process-local (span of one), or the forbidden
    strict subset (``transit.require_producer_spans_cluster``, the
    sweep gating in ``core/fft/plan.py``, and the rescale gating in
    ``runtime/elastic.py`` all key off it)."""
    return sorted({int(d.process_index) for d in mesh.devices.flat})


def backend_initialized() -> bool:
    """True when a JAX backend already exists in this process — past
    that point, bring-up configuration (the gloo collectives selector,
    ``jax.distributed.initialize``) silently stops taking effect, so
    cluster init must detect it explicitly (``jax.config.update`` still
    *succeeds* on an initialized backend)."""
    from jax._src import xla_bridge
    return bool(xla_bridge.backends_are_initialized())


def enable_cpu_collectives() -> bool:
    """Switch the CPU backend's cross-process collectives to gloo.

    Multi-process CPU clusters fail at the first collective with
    "Multiprocess computations aren't implemented on the CPU backend"
    unless the gloo implementation is selected BEFORE the backend
    initializes. Returns False (rather than raising) when the update
    is refused, so callers can surface a clear bring-up error instead
    of the XLA one.
    """
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        return True
    except (AttributeError, ValueError, RuntimeError):
        return False


def distributed_shutdown() -> None:
    """``jax.distributed.shutdown``; a no-op when never initialized or
    already down."""
    try:
        jax.distributed.shutdown()
    except RuntimeError:
        pass
