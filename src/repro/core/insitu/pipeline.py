"""Pipelined chain execution — bounded host offload + overlap accounting.

Serial chain modes leave wall-clock on the table in two places the
multi-node FFT literature (Verma et al., arXiv:2202.12756) calls out:
host endpoints (writer, visualization) block the next device step, and
consecutive fields serialize through one pipeline even though JAX
dispatch is asynchronous. ``InSituChain(mode="pipelined")`` closes both
gaps; this module is the host half of that mode:

* The chain launches field N+1's fused device stages **without
  blocking** on field N (JAX async dispatch; optionally donating the
  stale input buffer so XLA double-buffers in place).
* Each launched field is handed to a :class:`HostPipeline` — a bounded
  background executor that materializes the device results
  (``jax.device_get``, i.e. *it* blocks on the in-flight XLA program,
  not the producer) and runs the chain's host tail on them, in
  submission order by default.
* The queue bound is the **backpressure**: when host endpoints fall
  more than ``depth`` fields behind, ``submit`` blocks the producer
  instead of buffering unboundedly (each queued field pins its device
  output alive).
* Everything is accounted: per-endpoint host timings, materialization
  wait (and within it the copies' time and bytes), backpressure
  stalls, queue-depth stats, and completed/dropped field counts feed
  ``chain.marshaling_report()``'s overlap-efficiency numbers; in a
  profiler trace the same steps are host spans
  (``docs/architecture.md``, "Tracing").

Ordering and failure semantics:

* One worker (the default) preserves submission order end to end —
  required by endpoints declaring ``ordered = True`` (the writer's
  file list, any streaming reducer). ``workers > 1`` is allowed only
  when every host endpoint declares ``thread_safe = True`` and
  ``ordered = False``.
* A host-endpoint exception is captured as :class:`PipelineError`,
  re-raised to the producer on the next ``submit``/``drain`` call;
  fields already queued behind the failure are dropped (counted, not
  silently lost) so ``close``/``finalize`` always completes cleanly.

See ``docs/architecture.md`` (mode diagrams) and ``docs/endpoints.md``
(declaration contract) for the full picture.
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.core.insitu.bridge import BridgeData
from repro.core.insitu.endpoint import Endpoint

_STOP = object()
_SECS = ("wait_s", "d2h_s", "backpressure_s")   # HostPipeline's counters


class PipelineError(RuntimeError):
    """A host endpoint failed inside the pipeline worker.

    Carries the failing step, endpoint name, and original exception
    (``cause``); raised to the producer on the next ``submit`` or
    ``drain`` so asynchronous failures cannot pass silently.
    """

    def __init__(self, step, endpoint: str, cause: BaseException):
        super().__init__(
            f"host endpoint {endpoint!r} failed at step {step}: "
            f"{type(cause).__name__}: {cause}")
        self.step = step
        self.endpoint = endpoint
        self.cause = cause


class HostPipeline:
    """Bounded background executor for a chain's host endpoint tail.

    ``submit(data)`` enqueues one field's device-stage output (blocking
    when ``depth`` fields are already queued — the backpressure);
    worker threads materialize the arrays and run ``host_eps`` on them.
    ``drain()`` blocks until every submitted field completed;
    ``close()`` drains and joins the workers. ``report()`` returns the
    accounting snapshot at any time, including after ``close``.
    """

    def __init__(self, host_eps: Sequence[Endpoint], *, depth: int = 2,
                 workers: int = 1):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        if workers < 1:
            raise ValueError(f"pipeline workers must be >= 1, got {workers}")
        if workers > 1:
            for ep in host_eps:
                if ep.ordered or not ep.thread_safe:
                    raise ValueError(
                        f"endpoint {ep.name!r} declares ordered="
                        f"{ep.ordered}/thread_safe={ep.thread_safe}; "
                        f"workers={workers} needs every host endpoint "
                        f"ordered=False and thread_safe=True")
        self.host_eps = list(host_eps)
        self.depth = depth
        self.workers = workers
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._lock = threading.Lock()
        self._error: Optional[PipelineError] = None
        self._closed = False
        self._submitted = 0
        self._done = 0
        self._dropped = 0
        # wait_s: blocked materializing device results; d2h_s: the
        # copies alone, within wait_s; backpressure_s: producer blocked
        # on the full queue
        self._secs = dict.fromkeys(_SECS, 0.0)
        self._d2h_bytes = 0           # what the copies landed on the host
        self._host_s: Dict[str, float] = {}   # per-endpoint busy time
        self._depth_max = 0
        self._depth_sum = 0
        self._last_out: Optional[BridgeData] = None
        self._threads = [threading.Thread(target=self._work,
                                          name=f"insitu-host-{i}",
                                          daemon=True)
                         for i in range(workers)]
        for t in self._threads:
            t.start()

    # -- producer side ---------------------------------------------------------
    def submit(self, data: BridgeData, *, step=None) -> None:
        """Enqueue one field's device output for host processing.

        Blocks while ``depth`` fields are in flight (backpressure).
        ``step`` labels the field's spans in a profiler trace (default
        ``data.step``; see :func:`span_step`). Raises the stored
        :class:`PipelineError` if a previous field failed, and
        ``RuntimeError`` after ``close``.
        """
        if self._error is not None:
            raise self._error
        if self._closed:
            raise RuntimeError("pipeline is closed; re-initialize the chain")
        step = span_step(data.step if step is None else step)
        with timed_span("insitu.submit", step, self._lock, self._secs,
                        "backpressure_s"):
            self._q.put((data, step))
        with self._lock:
            self._submitted += 1
            d = self._q.qsize()
            self._depth_max = max(self._depth_max, d)
            self._depth_sum += d

    def drain(self, *, raise_error: bool = True) -> Optional[BridgeData]:
        """Block until every submitted field's host work completed.

        Returns the last completed host-side ``BridgeData`` (or None).
        With ``raise_error`` (default) re-raises a worker failure.
        """
        self._q.join()
        if raise_error and self._error is not None:
            raise self._error
        return self._last_out

    def close(self, *, drain: bool = True) -> None:
        """Drain (optionally) and join the workers. Never raises for a
        worker failure — ``report()['error']`` keeps the record — so
        ``finalize()`` stays clean after mid-pipeline exceptions."""
        if self._closed:
            return
        if drain:
            self.drain(raise_error=False)
        self._closed = True
        for _ in self._threads:
            self._q.put(_STOP)
        for t in self._threads:
            t.join()

    # -- worker side -----------------------------------------------------------
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
                self._run_one(*item)
            finally:
                self._q.task_done()

    def _run_one(self, data: BridgeData, step: int) -> None:
        ep_name = "<device_get>"
        lock, secs = self._lock, self._secs
        try:
            # Materialize the in-flight device results HERE, off the
            # producer's critical path: wait for the XLA program, then
            # land host copies every endpoint can share (each
            # np.asarray afterwards is free).
            with timed_span("insitu.wait_device", step, lock, secs,
                            "wait_s"):
                jax.block_until_ready(data.arrays)
            with timed_span("insitu.d2h", step, lock, secs,
                            "wait_s", "d2h_s") as d2h:
                data = data.replace(arrays=jax.device_get(data.arrays))
                nbytes = sum(np.asarray(x).nbytes
                             for x in jax.tree.leaves(data.arrays))
                d2h.set_metadata(bytes=nbytes)
            with lock:
                self._d2h_bytes += nbytes
            for ep in self.host_eps:
                ep_name = ep.name
                with timed_span(f"insitu.host.{ep.name}", step, lock,
                                self._host_s, ep.name):
                    data = ep.execute(data)
            with self._lock:
                self._done += 1
                self._last_out = data
        except Exception as err:  # noqa: BLE001 — recorded, re-raised at submit
            with self._lock:
                if self._error is None:
                    self._error = PipelineError(_step_of(data), ep_name, err)
                self._dropped += 1

    # -- accounting ------------------------------------------------------------
    def report(self) -> Dict[str, Any]:
        """Accounting snapshot: field counts, waits, queue-depth stats,
        per-endpoint host busy time, and any captured error."""
        with self._lock:
            subs = self._submitted
            rep = {
                "depth": self.depth,
                "workers": self.workers,
                "submitted": subs,
                "completed": self._done,
                "dropped": self._dropped,
                **self._secs,
                "d2h_bytes": self._d2h_bytes,
                "host_timings_s": dict(self._host_s),
                "queue_depth_max": self._depth_max,
                "queue_depth_mean": (self._depth_sum / subs) if subs else 0.0,
                "error": str(self._error) if self._error else None,
            }
        return rep

    def reset_stats(self) -> None:
        """Zero the accounting (counts, waits, timings) without touching
        queued work — call after warm-up so reports cover the steady
        state only."""
        with self._lock:
            self._submitted = self._done = self._dropped = 0
            self._secs.update(dict.fromkeys(_SECS, 0.0))
            self._d2h_bytes = 0
            self._host_s.clear()
            self._depth_max = self._depth_sum = 0


@contextlib.contextmanager
def timed_span(name: str, step: int, lock, counters: Dict[str, float],
               *keys: str):
    """``name`` as a host span (argument ``step``) in a profiler trace;
    once the body returns, its elapsed seconds are also added, under
    ``lock``, to ``counters[k]`` for each of ``keys``. One interval
    feeds both the trace and ``HostPipeline.report()``."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name, step=step) as span:
        yield span
    dt = time.perf_counter() - t0
    with lock:
        for k in keys:
            counters[k] = counters.get(k, 0.0) + dt


def span_step(step) -> int:
    """A field's step as a span argument: the producer's host integer,
    or -1 where it is a device scalar (reading that would wait for the
    device)."""
    return int(step) if isinstance(step, (int, np.integer)) else -1


def _step_of(data) -> Any:
    """Best-effort step id for error messages (the step may be an
    in-flight device scalar)."""
    try:
        return int(data.step)
    except Exception:  # noqa: BLE001
        return "?"


def overlap_stats(*, wall_s: float, dispatch_s: float,
                  device_probe_s: float,
                  pipeline_report: Dict[str, Any]) -> Dict[str, Any]:
    """Derive the overlap-efficiency numbers for ``marshaling_report``.

    In-pipeline measurements alone cannot price the overlap: the
    worker's materialization wait is small exactly *because* the device
    work it waited on ran during earlier fields' host work. The chain
    therefore calibrates ``device_probe_s`` — the synchronous
    (dispatch + device compute) cost of ONE field, measured by blocking
    on a single early execute — and estimates

        serialized_s = completed × device_probe_s + host_busy_s

    i.e. what the same fields would cost with no overlap at all (the
    fused-serial oracle). ``overlap_efficiency = 1 - wall_s /
    serialized_s`` (clamped to [0, 1]) is then the fraction of that
    serial cost the pipeline hid: ~0 for a serial run, 0.5 when the
    pipeline halved the wall-clock. It is an *estimate* — the probe
    rides one field and assumes per-field device cost is stable."""
    host_busy = sum(pipeline_report.get("host_timings_s", {}).values())
    fields = pipeline_report.get("completed", 0)
    serialized = fields * device_probe_s + host_busy
    eff = 0.0
    if serialized > 0.0 and wall_s > 0.0:
        eff = min(1.0, max(0.0, 1.0 - wall_s / serialized))
    return {"wall_s": wall_s, "dispatch_s": dispatch_s,
            "device_probe_s": device_probe_s,
            "host_busy_s": host_busy, "serialized_s": serialized,
            "overlap_efficiency": eff}
