"""Multi-process cluster bootstrap — ``jax.distributed`` made boring.

Everything in this repo below the launch layer is already written
against *global* meshes and collectives; the only thing standing
between the single-host reproduction and the paper's actual deployment
shape (an FFT running across the machines producing the data) is
process bring-up. This module owns exactly that:

* **Discovery** — ``ClusterConfig.from_env()`` reads the
  ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID``
  environment contract that ``tools/launch_multihost.py`` exports, and
  ``add_cluster_args``/``config_from_args`` expose the same knobs as
  CLI flags for schedulers that prefer argv over env.
* **Initialization** — ``init_cluster()`` is idempotent, a no-op for
  single-process runs, and selects gloo CPU collectives through
  ``repro.compat`` before ``jax.distributed.initialize``. It must run
  BEFORE the first JAX backend use; on CPU the per-process device
  count additionally needs
  ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` set before
  the first ``import jax`` (the launcher does both).
* **Topology queries** — ``axis_crosses_processes(mesh, axis)`` is the
  primitive behind the schedule engine's host-crossing ``AllToAll``
  annotation (see ``core/fft/schedule.py``): an exchange over a mesh
  axis whose device ring spans more than one process pays DCN latency,
  not ICI, which is exactly the regime where the slab/pencil tradeoff
  inverts (Verma et al., arXiv:2202.12756).

Deployment guide with the full bootstrap walkthrough:
``docs/multihost.md``.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional

import jax

from repro import compat

ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"

_STATE: Dict[str, object] = {"initialized": False, "config": None}


def _read_env(e) -> tuple:
    """The raw, UNVALIDATED ``REPRO_*`` read — the single definition of
    the env contract's defaults, shared by ``ClusterConfig.from_env``
    and ``config_from_args`` so the env- and flag-driven bring-up
    paths cannot drift. Returns (coordinator, num_processes,
    process_id)."""
    return (e.get(ENV_COORDINATOR) or None,
            int(e.get(ENV_NUM_PROCESSES, "1")),
            int(e.get(ENV_PROCESS_ID, "0")))


def _require_complete(coordinator, num_processes: int, *,
                      nprocs_given: bool, pid_given: bool) -> None:
    """A half-configured cluster must fail loudly at bring-up, not hang
    at the first collective — shared by the env and flag paths so
    neither can smuggle an incomplete config past validation."""
    if coordinator is not None and not nprocs_given:
        raise ValueError(
            f"a coordinator is set ({ENV_COORDINATOR} or --coordinator) "
            f"but the process count is not — set {ENV_NUM_PROCESSES} or "
            f"--num-processes (and a distinct rank per process)")
    if num_processes > 1 and not pid_given:
        # without an explicit rank every process defaults to 0 and
        # bring-up deadlocks waiting for the other ranks
        raise ValueError(
            f"num_processes={num_processes} but no rank is set — give "
            f"each process a distinct {ENV_PROCESS_ID} or --process-id "
            f"(0..{num_processes - 1})")


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    """One process's view of the cluster.

    ``coordinator`` is ``host:port`` of process 0's coordination
    service (every process passes the SAME address, including process
    0 itself); ``num_processes``/``process_id`` complete the contract.
    The default instance describes a single-process run, for which
    ``init_cluster`` does nothing — launch code can call it
    unconditionally.
    """
    coordinator: Optional[str] = None
    num_processes: int = 1
    process_id: int = 0

    @property
    def multiprocess(self) -> bool:
        return self.num_processes > 1

    @classmethod
    def from_env(cls, env: Optional[Dict[str, str]] = None
                 ) -> "ClusterConfig":
        """Read the ``REPRO_*`` environment contract (the launcher's
        export format). Unset variables yield the single-process
        default; a coordinator with no process count is an error (a
        half-configured cluster should fail loudly at bring-up, not
        hang at the first collective)."""
        e = os.environ if env is None else env
        coord, nprocs, pid = _read_env(e)
        _require_complete(coord, nprocs,
                          nprocs_given=ENV_NUM_PROCESSES in e,
                          pid_given=ENV_PROCESS_ID in e)
        return cls(coordinator=coord, num_processes=nprocs, process_id=pid)


def add_cluster_args(parser) -> None:
    """Attach the flag-driven discovery knobs to an argparse parser
    (the env contract's CLI twin; flags win over env when both set)."""
    parser.add_argument("--coordinator", default=None,
                        help="host:port of process 0's coordination "
                             "service (default: $REPRO_COORDINATOR)")
    parser.add_argument("--num-processes", type=int, default=None,
                        help="total processes in the cluster "
                             "(default: $REPRO_NUM_PROCESSES)")
    parser.add_argument("--process-id", type=int, default=None,
                        help="this process's rank "
                             "(default: $REPRO_PROCESS_ID)")


def config_from_args(args, env: Optional[Dict[str, str]] = None
                     ) -> ClusterConfig:
    """Merge ``add_cluster_args`` flags over the env contract. The
    completeness checks run on the MERGED values (flags may complete a
    partial env, and vice versa), so a flag-driven bring-up that
    forgets ``--process-id`` fails loudly here instead of every
    process defaulting to rank 0 and deadlocking at initialize."""
    e = os.environ if env is None else env
    ecoord, enprocs, epid = _read_env(e)
    coord = getattr(args, "coordinator", None)
    nprocs = getattr(args, "num_processes", None)
    pid = getattr(args, "process_id", None)
    merged = ClusterConfig(
        coordinator=coord if coord is not None else ecoord,
        num_processes=nprocs if nprocs is not None else enprocs,
        process_id=pid if pid is not None else epid)
    _require_complete(
        merged.coordinator, merged.num_processes,
        nprocs_given=nprocs is not None or ENV_NUM_PROCESSES in e,
        pid_given=pid is not None or ENV_PROCESS_ID in e)
    return merged


def init_cluster(config: Optional[ClusterConfig] = None) -> ClusterConfig:
    """Initialize ``jax.distributed`` from ``config`` (default:
    ``ClusterConfig.from_env()``). Idempotent: the first call wins and
    later calls return its config (re-initializing a live distributed
    runtime is not supported by JAX). Single-process configs skip
    backend initialization entirely, so every entry point can call this
    unconditionally at startup."""
    if _STATE["initialized"]:
        return _STATE["config"]          # type: ignore[return-value]
    cfg = ClusterConfig.from_env() if config is None else config
    if cfg.multiprocess:
        if cfg.coordinator is None:
            raise ValueError(
                "multi-process ClusterConfig needs a coordinator "
                "address (host:port of process 0)")
        # bring-up config must precede backend init — past that point
        # the gloo selector and distributed.initialize silently stop
        # taking effect (jax.config.update still "succeeds"), so the
        # mis-ordering needs an explicit probe, not a return value
        if compat.backend_initialized():
            raise RuntimeError(
                "init_cluster() must run before any JAX backend use, "
                "but a backend is already initialized in this process "
                "— collective/distributed bring-up configuration can "
                "no longer take effect, and the first cross-process "
                "collective would fail cryptically. Move init_cluster() "
                "ahead of the first device query / jnp operation.")
        # gate on the PRIMARY platform: "cuda,cpu" is a cuda cluster
        # with a cpu fallback and never needs gloo. Unset counts as
        # CPU (jax auto-selects it on accelerator-less machines); an
        # accelerator cluster can set JAX_PLATFORMS to bypass
        primary = (os.environ.get("JAX_PLATFORMS", "")
                   .split(",")[0].strip().lower())
        if not compat.enable_cpu_collectives() and primary in ("", "cpu"):
            # the gloo selector was refused — surface the clear
            # bring-up error the compat shim promises instead of XLA's
            # cryptic first-collective failure (the launcher maps this
            # to its "unsupported environment" exit, so tests SKIP)
            raise RuntimeError(
                "multi-process CPU bring-up needs the gloo collectives "
                "knob (jax_cpu_collectives_implementation), which this "
                "JAX release lacks — upgrade jax. Without it every "
                "collective dies with XLA's \"Multiprocess computations "
                "aren't implemented on the CPU backend\". (On an "
                "accelerator cluster, set JAX_PLATFORMS to your "
                "platform to bypass this CPU-only check.)")
        jax.distributed.initialize(coordinator_address=cfg.coordinator,
                                   num_processes=cfg.num_processes,
                                   process_id=cfg.process_id)
    _STATE["initialized"] = True
    _STATE["config"] = cfg
    return cfg


def is_initialized() -> bool:
    return bool(_STATE["initialized"])


def shutdown_cluster() -> None:
    """Tear down the distributed runtime (tests/launcher epilogue);
    safe to call when never initialized."""
    cfg = _STATE["config"]
    if cfg is not None and cfg.multiprocess:  # type: ignore[union-attr]
        compat.distributed_shutdown()
    _STATE["initialized"] = False
    _STATE["config"] = None


def cluster_info() -> Dict[str, object]:
    """This process's runtime view — what ``docs/multihost.md`` tells
    operators to log first when a bring-up misbehaves."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
        "platform": jax.devices()[0].platform,
        "initialized": is_initialized(),
    }


# ---------------------------------------------------------------------------
# Mesh topology queries — which axes cross hosts
# ---------------------------------------------------------------------------
# The primitives live in repro.compat (below every layer, so the core
# FFT schedule engine can use them without importing runtime); this is
# their documented runtime-facing home.
axis_crosses_processes = compat.axis_crosses_processes
mesh_process_topology = compat.mesh_process_topology
mesh_process_span = compat.mesh_process_span
