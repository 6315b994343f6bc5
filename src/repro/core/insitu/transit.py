"""M→N in-transit bridge — distinct producer and consumer meshes.

The paper's future-work deployment (§2.1, "in-transit") separates the
M processes producing data from the N processes analyzing it. The
staged chain mode already reshards *within* one mesh; this module is
the cross-mesh hop: a ``TransitBridge`` takes each field of a
``BridgeData`` sharded over a **producer** mesh and delivers it
sharded over a disjoint **consumer** mesh, where the FFT chain (or any
consumer-side computation) runs without ever touching producer
devices. ``launch/mesh.make_transit_meshes`` builds the two meshes;
``tools/launch_multihost.py --demo transit`` runs the whole topology
end to end on a real multi-process cluster.

Two transports, picked by ``via`` (default ``"auto"``):

* ``device_put`` — direct resharding. Valid only when this process
  addresses every device of both meshes (the single-process case:
  placeholder devices, or one host's GPUs split in two). Zero host
  round-trip; XLA moves exactly the bytes that change owners.
* ``host`` — the portable path for real multi-process clusters, where
  neither side can even *construct* arrays on the other's devices.
  Producer participants lower only the shards they OWN to host memory
  — (bounds, flat payload) pairs, padded to the cluster-wide maximum —
  and ``process_allgather`` moves those, so the transient footprint is
  O(processes × local shard bytes) plus one global-size reconstruction
  buffer on CONSUMER processes only (non-consumers keep just a bool
  coverage mask), not O(processes × global bytes). Consumers
  then rebuild the global field by taking, element-wise, the
  contribution of the lowest-ranked process whose shards cover it —
  **bit-identical** by construction, with replicated regions
  deduplicated deterministically; consumer participants finally
  re-shard the reconstruction onto the consumer mesh from their own
  addressable slices. Non-consumer processes get ``None`` for the
  delivered arrays (they hold no piece of them).

The multi-process call contract mirrors every other collective in the
repo: ALL processes call ``send`` per field, producer participants
passing the producer-mesh ``jax.Array``s, everyone else passing
same-shaped placeholders (e.g. ``np.zeros``; only ``shape``/``dtype``
are read). ``report()`` accounts fields, per-array bytes moved, wall
seconds, and which transport ran — the in-transit analogue of the
chain's reshard accounting. ``bytes_moved`` counts LOGICAL field
bytes (one full copy of every delivered array): the host transport
gathers roughly that many payload bytes across the cluster, while
``device_put`` may move fewer on the wire (XLA relocates only the
shards that change owners).

``send`` blocks the producer for the full hop; ``send_async`` does
not: it snapshots the (still in-flight, JAX-async-dispatched) device
buffers onto a bounded single-worker queue and runs the gather/
reconstruct there — the ``HostPipeline`` executor discipline applied
to transit. In-order delivery, backpressure at ``depth``, failure
containment on the next ``send_async``/``drain_async``, and an
``overlap_efficiency`` row under ``report()["async"]``. Drivers
expose it as ``--transit-async`` (train/solver).

Drivers that run their main jitted loop on the producer mesh (train/
serve behind ``--transit-consumers``) must call
``require_producer_spans_cluster`` first: a producer mesh that
excludes some processes strands those processes in the jitted step —
the "subset collectives hang" failure mode of ``docs/multihost.md``.

A bridge is immutable: it pins one producer/consumer mesh pair. When
the consumer side rescales at runtime, ``runtime/elastic.py`` builds
a **new** bridge over the surviving devices and routes subsequent
sends through it (``ElasticController.send``); in-flight serving
requests on the old mesh drain or fail-contained first
(``docs/elastic.md``).
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import mesh_process_span
from repro.core.insitu.bridge import BridgeData
from repro.core.insitu.pipeline import PipelineError

VIAS = ("auto", "device_put", "host")

_STOP = object()


class _AsyncHop:
    """The async transit executor: one bounded queue, ONE ordered
    worker running the bridge's (collective) hop off the producer's
    critical path — the ``HostPipeline`` discipline applied to transit.

    ``submit`` snapshots the field by reference: the arrays are live
    ``jax.Array``s whose computation JAX is still dispatching — the
    worker's host gather blocks on them *there*, so the producer's
    jitted loop keeps running. One worker per process + submission
    order = every process executes the Nth send's collectives as its
    Nth hop, keeping the cluster's collective ordering consistent
    (drivers must not interleave OTHER global host collectives with
    in-flight async sends — drain first; ``ElasticController`` does).

    Failure containment mirrors ``HostPipeline``: a hop failure is
    captured as :class:`PipelineError`, re-raised to the producer on
    the next ``submit``/``drain``; queued fields behind it are dropped
    and counted, and the producer never deadlocks on a dead consumer.
    """

    def __init__(self, bridge: "TransitBridge", depth: int,
                 on_result: Optional[Callable[[BridgeData], Any]]):
        if depth < 1:
            raise ValueError(f"transit async depth must be >= 1, "
                             f"got {depth}")
        self.bridge = bridge
        self.depth = depth
        self.on_result = on_result
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._lock = threading.Lock()
        self._error: Optional[PipelineError] = None
        self._closed = False
        self._submitted = 0
        self._completed = 0
        self._dropped = 0
        self._backpressure_s = 0.0    # producer blocked on the full queue
        self._drain_wait_s = 0.0      # producer blocked in drain()
        self._hop_busy_s = 0.0        # worker inside the collective hop
        self._results: List[BridgeData] = []
        self._thread = threading.Thread(target=self._work,
                                        name="transit-async", daemon=True)
        self._thread.start()

    def submit(self, data: BridgeData) -> None:
        if self._error is not None:
            raise self._error
        if self._closed:
            raise RuntimeError("async transit hop is closed")
        t0 = time.perf_counter()
        self._q.put(data)
        with self._lock:
            self._backpressure_s += time.perf_counter() - t0
            self._submitted += 1

    def drain(self, *, raise_error: bool = True) -> List[BridgeData]:
        t0 = time.perf_counter()
        self._q.join()
        with self._lock:
            self._drain_wait_s += time.perf_counter() - t0
            out, self._results = self._results, []
        if raise_error and self._error is not None:
            raise self._error
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._q.put(_STOP)
        self._thread.join()

    def _work(self) -> None:
        while True:
            item = self._q.get()
            try:
                if item is _STOP:
                    return
                if self._error is not None:
                    with self._lock:
                        self._dropped += 1
                    continue
                t0 = time.perf_counter()
                out = self.bridge.send(item)
                if self.on_result is not None:
                    self.on_result(out)
                with self._lock:
                    self._hop_busy_s += time.perf_counter() - t0
                    self._completed += 1
                    if self.on_result is None:
                        # delivery-by-drain mode: retain for the caller
                        self._results.append(out)
            except Exception as err:  # noqa: BLE001 — re-raised at submit
                with self._lock:
                    if self._error is None:
                        step = getattr(item, "step", "?")
                        self._error = PipelineError(step, "transit", err)
                    self._dropped += 1
                    self._hop_busy_s += time.perf_counter() - t0
            finally:
                self._q.task_done()

    def report(self) -> Dict[str, Any]:
        """Async accounting incl. ``overlap_efficiency``: the fraction
        of the hop's busy time hidden from the producer —
        ``1 - producer_blocked_s / hop_busy_s`` (clamped to [0, 1]),
        where the producer only blocks on backpressure and drain. A
        blocking ``send`` loop scores ~0 (the producer eats every hop
        second); a fully overlapped run approaches 1."""
        with self._lock:
            blocked = self._backpressure_s + self._drain_wait_s
            busy = self._hop_busy_s
            eff = 0.0
            if busy > 0.0:
                eff = min(1.0, max(0.0, 1.0 - blocked / busy))
            return {
                "depth": self.depth,
                "submitted": self._submitted,
                "completed": self._completed,
                "dropped": self._dropped,
                "backpressure_s": self._backpressure_s,
                "drain_wait_s": self._drain_wait_s,
                "hop_busy_s": busy,
                "producer_blocked_s": blocked,
                "overlap_efficiency": eff,
                "error": str(self._error) if self._error else None,
            }


def _mesh_addressable(mesh) -> bool:
    me = jax.process_index()
    return all(d.process_index == me for d in mesh.devices.flat)


def _participates(mesh) -> bool:
    me = jax.process_index()
    return any(d.process_index == me for d in mesh.devices.flat)


def require_producer_spans_cluster(producer_mesh,
                                   flag: str = "--transit-consumers") -> None:
    """Guard for drivers whose main (jitted) loop runs on the producer
    mesh: on a multi-process cluster EVERY process must own at least
    one producer device, or the excluded processes either fail to
    place the step (no addressable devices in the mesh) or hang the
    cluster at its first collective (``docs/multihost.md``, "subset
    collectives hang"). Raises ``ValueError`` naming ``flag`` when the
    split is invalid; single-process runs always pass."""
    nproc = jax.process_count()
    if nproc <= 1:
        return
    span = mesh_process_span(producer_mesh)
    if len(span) < nproc:
        raise ValueError(
            f"{flag}: the producer mesh spans only processes {span} of a "
            f"{nproc}-process cluster — processes outside it would hang "
            f"in the jitted main loop (subset collectives, see "
            f"docs/multihost.md). Pick a consumer count that leaves "
            f"every process at least one producer device, or run the "
            f"M→N split single-process.")


class TransitBridge:
    """Move fields from a producer mesh onto a disjoint consumer mesh.

    ``spec_map`` overrides the consumer-side ``PartitionSpec`` per
    array name; ``default_spec`` covers the rest (default: shard the
    leading axis over the consumer mesh's first axis when divisible,
    else fully replicate — small monitor products replicate, big
    fields split). Meshes must be device-disjoint: sharing devices
    would make "in transit" a no-op and the accounting a lie.
    """

    def __init__(self, producer_mesh, consumer_mesh, *,
                 spec_map: Optional[Dict[str, P]] = None,
                 default_spec: Optional[P] = None, via: str = "auto"):
        if via not in VIAS:
            raise ValueError(f"via must be one of {VIAS}, got {via!r}")
        overlap = ({d.id for d in producer_mesh.devices.flat}
                   & {d.id for d in consumer_mesh.devices.flat})
        if overlap:
            raise ValueError(
                f"producer and consumer meshes share devices {sorted(overlap)}"
                f" — transit requires disjoint meshes")
        self.producer_mesh = producer_mesh
        self.consumer_mesh = consumer_mesh
        self.spec_map = dict(spec_map or {})
        self.default_spec = default_spec
        if via == "auto":
            via = ("device_put"
                   if (_mesh_addressable(producer_mesh)
                       and _mesh_addressable(consumer_mesh)) else "host")
        self.via = via
        self._fields = 0
        self._bytes = 0
        self._wall_s = 0.0
        self._per_array: Dict[str, int] = {}
        self._async: Optional[_AsyncHop] = None

    # -- participation ------------------------------------------------------
    def is_producer(self) -> bool:
        """True when this process owns producer-mesh devices."""
        return _participates(self.producer_mesh)

    def is_consumer(self) -> bool:
        """True when this process owns consumer-mesh devices — i.e.
        whether ``send``'s outputs are usable here."""
        return _participates(self.consumer_mesh)

    # -- spec resolution ----------------------------------------------------
    def _consumer_sharding(self, name: str, shape) -> NamedSharding:
        spec = self.spec_map.get(name, self.default_spec)
        if spec is None:
            ax0 = self.consumer_mesh.axis_names[0]
            n0 = self.consumer_mesh.shape[ax0]
            spec = P(ax0) if shape and shape[0] % n0 == 0 else P()
        return NamedSharding(self.consumer_mesh, spec)

    # -- transports ---------------------------------------------------------
    def _move_device_put(self, name: str, x):
        return jax.device_put(x, self._consumer_sharding(name, x.shape))

    def _move_host(self, name: str, x):
        """The allgather hop (see module docstring). ``x`` is a
        producer-mesh array on producer participants and a shape/dtype
        placeholder everywhere else. Only OWNED shards travel — each
        process gathers (bounds, flat payload) pairs padded to the
        cluster-wide maximum, never a dense global buffer per peer."""
        from jax.experimental.multihost_utils import process_allgather

        shape, dtype = tuple(x.shape), np.dtype(x.dtype)
        ndim = len(shape)

        def gather(a):
            """``process_allgather`` with bit-exact transport: the
            multi-process path routes arrays through ``device_put``,
            which CANONICALIZES dtypes (int64→int32, float64→float32
            under default x64-disabled jax) — a silent precision loss
            that would break the bit-identical contract. Gather the
            raw bytes instead and reinterpret on arrival."""
            u8 = np.ascontiguousarray(a).view(np.uint8)
            g = np.asarray(process_allgather(u8))
            return g.reshape((jax.process_count(),) + u8.shape) \
                .view(a.dtype)

        rows, flats, seen = [], [], set()
        if isinstance(x, jax.Array):
            for s in x.addressable_shards:
                bounds = tuple(
                    (0 if sl.start is None else int(sl.start),
                     n if sl.stop is None else int(sl.stop))
                    for sl, n in zip(s.index, shape))
                if bounds in seen:       # in-process replicated copy
                    continue
                seen.add(bounds)
                rows.append(np.asarray(bounds, np.int64).reshape(-1))
                flats.append(np.ascontiguousarray(
                    np.asarray(s.data)).ravel())
        bounds = (np.stack(rows) if rows
                  else np.zeros((0, 2 * ndim), np.int64))
        payload = np.concatenate(flats) if flats else np.zeros(0, dtype)
        counts = gather(np.asarray([bounds.shape[0], payload.size],
                                   np.int64))
        pad_b = np.zeros((int(counts[:, 0].max()), 2 * ndim), np.int64)
        pad_b[:bounds.shape[0]] = bounds
        pad_p = np.zeros(int(counts[:, 1].max()), dtype)
        pad_p[:payload.size] = payload
        gbounds, gpayload = gather(pad_b), gather(pad_p)

        consumer = self.is_consumer()
        # non-consumers join every gather above (they are collectives)
        # and still verify coverage via the bool mask, but skip
        # materializing the global-size field they would discard
        full = np.zeros(shape, dtype) if consumer else None
        filled = np.zeros(shape, bool)
        for p in range(gbounds.shape[0]):
            off = 0
            for row in gbounds[p][: int(counts[p, 0])]:
                idx = tuple(slice(int(row[2 * d]), int(row[2 * d + 1]))
                            for d in range(ndim))
                bshape = tuple(int(row[2 * d + 1] - row[2 * d])
                               for d in range(ndim))
                n = int(np.prod(bshape, dtype=np.int64))
                if consumer:
                    block = gpayload[p][off:off + n].reshape(bshape)
                    # element-wise lowest-rank-wins dedup:
                    # deterministic, hence bit-identical everywhere
                    keep = ~filled[idx]
                    full[idx] = np.where(keep, block, full[idx])
                off += n
                filled[idx] = True
        if not filled.all():
            raise ValueError(
                f"transit array {name!r}: no process contributed "
                f"{int((~filled).sum())} of {filled.size} elements — was "
                f"send() called with the producer-mesh array on every "
                f"producer participant?")
        if not consumer:
            return None
        sh = self._consumer_sharding(name, shape)
        local = [jax.device_put(full[idx], d) for d, idx
                 in sh.addressable_devices_indices_map(shape).items()]
        return jax.make_array_from_single_device_arrays(shape, sh, local)

    # -- the hop ------------------------------------------------------------
    def send(self, data: BridgeData) -> BridgeData:
        """Deliver one field's arrays onto the consumer mesh.

        Returns a ``BridgeData`` with the same keys/structure whose
        leaves live on the consumer mesh (``None`` leaves on
        non-consumer processes under the ``host`` transport). Grid
        metadata, step, domain and layout tags pass through untouched —
        transit moves bytes, it does not reinterpret them."""
        t0 = time.perf_counter()
        move = (self._move_device_put if self.via == "device_put"
                else self._move_host)
        out: Dict[str, Any] = {}
        for name, v in data.arrays.items():
            moved = jax.tree.map(lambda x, n=name: move(n, x), v)
            nbytes = sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                         for x in jax.tree.leaves(v))
            self._per_array[name] = self._per_array.get(name, 0) + nbytes
            self._bytes += nbytes
            out[name] = moved
        self._fields += 1
        self._wall_s += time.perf_counter() - t0
        return data.replace(arrays=out,
                            meta={**data.meta, "transit_via": self.via})

    # -- async hop ----------------------------------------------------------
    def send_async(self, data: BridgeData, *,
                   on_result: Optional[Callable[[BridgeData], Any]] = None,
                   depth: int = 2) -> None:
        """Enqueue one field for the bounded background hop and return
        immediately — the producer's next jitted step overlaps the
        gather/reconstruct (the arrays are async-dispatch snapshots;
        the worker blocks on them, not the producer).

        Delivery is in submission order. ``on_result`` (fixed at the
        first call, like ``depth``) runs on the worker with each
        delivered ``BridgeData`` — the consumer-side chain hook; without
        it, delivered fields are retained and returned by
        ``drain_async``. Blocks only when ``depth`` fields are already
        in flight (backpressure). Raises the contained
        :class:`PipelineError` of an earlier failed hop. The
        multi-process contract is ``send``'s, one level up: every
        process calls ``send_async`` for the same fields in the same
        order, and no other global host collective may run while sends
        are in flight (``drain_async`` first — docs/multihost.md)."""
        if self._async is None:
            self._async = _AsyncHop(self, depth, on_result)
        self._async.submit(data)

    def drain_async(self, *, raise_error: bool = True) -> List[BridgeData]:
        """Block until every async send completed; return the delivered
        fields retained since the last drain (empty when ``on_result``
        consumes them). Re-raises a contained hop failure unless
        ``raise_error=False``. No-op without pending async sends."""
        if self._async is None:
            return []
        return self._async.drain(raise_error=raise_error)

    def close_async(self) -> None:
        """Drain (never raising) and stop the async worker — called by
        the elastic controller before it swaps in a new bridge, so an
        orphaned worker can never run a stale mesh's collectives."""
        if self._async is not None:
            self._async.drain(raise_error=False)
            self._async.close()

    # -- accounting ---------------------------------------------------------
    def reset_stats(self) -> None:
        """Zero the accounting (fields/bytes/wall) without touching
        configuration — call after warm-up so ``report()`` covers
        steady state, matching ``InSituChain.reset_stats()``."""
        self._fields = 0
        self._bytes = 0
        self._wall_s = 0.0
        self._per_array.clear()

    def report(self) -> Dict[str, Any]:
        """Transit accounting: fields/bytes/seconds moved, transport,
        and both meshes' process spans — the M→N analogue of
        ``InSituChain.marshaling_report()``'s reshard accounting."""
        def span(mesh):
            return {"shape": dict(mesh.shape),
                    "processes": sorted({d.process_index
                                         for d in mesh.devices.flat})}
        rep = {
            "via": self.via,
            "fields": self._fields,
            "bytes_moved": self._bytes,
            "bytes_per_array": dict(self._per_array),
            "wall_s": self._wall_s,
            "producer": span(self.producer_mesh),
            "consumer": span(self.consumer_mesh),
        }
        if self._async is not None:
            # incl. the overlap_efficiency row — how much of the hop
            # the producer never saw (see _AsyncHop.report)
            rep["async"] = self._async.report()
        return rep
