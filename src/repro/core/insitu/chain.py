"""In-situ chain composition — the paper's multi-stage daisy-chain.

Three execution modes, mirroring the paper's deployment scenarios
(§2.1) plus the async pipeline the scaling literature calls for:

* **in-situ (fused)** — all device endpoints trace into ONE jitted XLA
  program: stage handoffs are zero-copy by fusion (the TPU answer to the
  paper's zero-copy marshaling goal, §5). Host endpoints (writer,
  visualization) run afterwards on the (small) materialized results.
* **in-transit (staged)** — each device endpoint jits separately, and
  between stages the chain performs the M→N redistribution
  (``reshard``) when the next stage's required sharding differs —
  producer ranks and consumer ranks need not match, which is exactly
  the paper's future-work scenario. Reshard byte counts are accounted
  in ``chain.marshaling_report()``.
* **pipelined** — the fused device program is *launched* per field but
  never blocked on: JAX async dispatch lets field N+1's device stages
  run while field N's results are still in flight, and the host tail
  (writer, visualization, reductions) runs on a bounded background
  executor (``pipeline.HostPipeline``) with backpressure and ordered
  finalize/flush semantics. ``execute`` returns the device-stage
  output immediately; ``drain()`` (or ``finalize()``) waits for the
  host side. Optional ``donate_buffers=True`` donates each field's
  input arrays to XLA so successive fields double-buffer in place —
  only enable it when the producer does not reuse the arrays it hands
  over. The serial modes remain the correctness oracle.

``docs/architecture.md`` diagrams all three modes.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax

from repro.core.insitu.bridge import BridgeData
from repro.core.insitu.endpoint import Endpoint
from repro.core.insitu.pipeline import HostPipeline, overlap_stats, span_step

MODES = ("insitu", "intransit", "pipelined")


def _scoped(idx: int, ep: Endpoint, d: BridgeData) -> BridgeData:
    """Endpoint ``idx`` of the chain under its ``named_scope`` in
    traces, ``insitu.<idx>.<name>`` (``insitu.0.fft``, ...)."""
    with jax.named_scope(f"insitu.{idx}.{ep.name}"):
        return ep.execute(d)


class InSituChain:
    """An ordered list of endpoints run as one processing chain.

    ``mode`` picks the execution strategy (see the module docstring);
    ``pipeline_depth``/``pipeline_workers``/``donate_buffers`` only
    apply to ``mode="pipelined"``.
    """

    def __init__(self, endpoints: List[Endpoint], mesh=None, *,
                 mode: str = "insitu", pipeline_depth: int = 2,
                 pipeline_workers: int = 1, donate_buffers: bool = False):
        assert mode in MODES, f"mode must be one of {MODES}, got {mode!r}"
        self.endpoints = endpoints
        self.mesh = mesh
        self.mode = mode
        self.pipeline_depth = pipeline_depth
        self.pipeline_workers = pipeline_workers
        self.donate_buffers = donate_buffers
        self._compiled = None
        self._staged_fns: Dict[int, Any] = {}   # endpoint idx -> jitted
        self._reshard_bytes = 0
        self._timings: Dict[str, float] = {}
        self._pipeline: Optional[HostPipeline] = None
        self._pipe_fn = None                    # fused+donating device launch
        self._pipe_t0: Optional[float] = None   # pipelined wall-clock origin
        self._pipe_wall = 0.0
        self._pipe_report: Optional[Dict[str, Any]] = None  # kept post-close
        self._dispatch_s = 0.0
        self._pipe_calls = 0
        self._device_probe_s: Optional[float] = None  # calibration, see below
        self._probe_prev = None     # field-0 output held until the probe
        self._pipe_finalized = False

    # -- lifecycle -------------------------------------------------------------
    def initialize(self, grid=None):
        """(Re-)initialize every endpoint; drops ALL compiled/pipelined
        state first. Endpoint state (plans, masks) is baked into traced
        programs as constants — and in pipelined mode fields may still
        be in flight — so re-initialization drains the pipeline and
        invalidates every compiled callable rather than silently running
        against stale endpoint state."""
        self._shutdown_pipeline()
        self._compiled = None
        self._pipe_fn = None
        self._staged_fns.clear()
        self._timings.clear()
        self._dispatch_s = 0.0
        self._pipe_t0 = None
        self._pipe_wall = 0.0
        self._pipe_report = None
        self._pipe_calls = 0
        self._device_probe_s = None
        self._probe_prev = None
        self._pipe_finalized = False
        for ep in self.endpoints:
            ep.initialize(self.mesh, grid)
        return self

    def finalize(self) -> Dict[str, Any]:
        """Drain any pipelined work, then finalize every endpoint.

        Returns ``{endpoint_name: finalize_summary}``; chains with
        repeated endpoint names get ``name#idx`` keys for the later
        occurrences (nothing is silently dropped). Never raises for a
        pipeline worker failure — that surfaced on ``execute``/``drain``
        and stays visible in ``marshaling_report()``."""
        self._shutdown_pipeline()
        self._pipe_finalized = True
        out: Dict[str, Any] = {}
        for idx, ep in enumerate(self.endpoints):
            key = ep.name if ep.name not in out else f"{ep.name}#{idx}"
            out[key] = ep.finalize()
        return out

    def drain(self) -> Optional[BridgeData]:
        """Pipelined mode: block until every submitted field's host work
        completed; re-raises a host-endpoint failure. Returns the last
        host-side ``BridgeData`` (None in the serial modes, which have
        nothing in flight)."""
        if self._pipeline is None:
            return None
        try:
            return self._pipeline.drain()
        finally:
            # freeze even when re-raising a worker failure — otherwise
            # post-failure idle time leaks into wall_s
            self._freeze_wall()

    def _freeze_wall(self) -> None:
        """Record the pipelined wall-clock at the end of a batch (drain/
        shutdown). Only when submits happened since the last freeze —
        idle time between a drain and a later report/finalize must not
        count into wall_s (it would corrupt overlap_efficiency)."""
        if self._pipe_t0 is not None and self._pipe_wall == 0.0:
            self._pipe_wall = time.perf_counter() - self._pipe_t0

    def _shutdown_pipeline(self) -> None:
        if self._pipeline is None:
            return
        self._pipeline.close(drain=True)
        self._freeze_wall()
        self._pipe_report = self._pipeline.report()
        self._pipeline = None

    # -- execution ---------------------------------------------------------------
    def _device_prefix(self) -> List[Endpoint]:
        """The maximal leading run of device endpoints — what the fused
        and pipelined modes compile into one XLA program."""
        out = []
        for ep in self.endpoints:
            if ep.host:
                break
            out.append(ep)
        return out

    def execute(self, data: BridgeData) -> BridgeData:
        """Run one field through the chain.

        Serial modes return the fully-processed ``BridgeData``. The
        pipelined mode returns the (possibly still in-flight) device
        output immediately and hands the host tail to the background
        pipeline — call ``drain()``/``finalize()`` for its effects."""
        if self.mode == "insitu":
            return self._execute_fused(data)
        if self.mode == "pipelined":
            return self._execute_pipelined(data)
        return self._execute_staged(data)

    def _device_fn(self, donate: bool):
        """Jit the device prefix as one program (shared by the fused and
        pipelined modes; the latter may donate the input buffers)."""
        device_eps = self._device_prefix()

        def run(d: BridgeData) -> BridgeData:
            for idx, ep in enumerate(device_eps):
                d = _scoped(idx, ep, d)
            return d
        return jax.jit(run, donate_argnums=(0,) if donate else ())

    def _execute_fused(self, data: BridgeData) -> BridgeData:
        """One jitted program for the device prefix, host tail inline."""
        device_eps = self._device_prefix()
        host_eps = self.endpoints[len(device_eps):]

        if self._compiled is None:
            self._compiled = self._device_fn(False)

        t0 = time.perf_counter()
        out = self._compiled(data)
        jax.block_until_ready(jax.tree.leaves(out.arrays))
        self._timings["device"] = time.perf_counter() - t0
        for ep in host_eps:
            t0 = time.perf_counter()
            out = ep.execute(out)
            self._timings[ep.name] = time.perf_counter() - t0
        return out

    def _execute_pipelined(self, data: BridgeData) -> BridgeData:
        """Launch the device prefix without blocking; offload the host
        tail. Field N+1's device stages run while field N's results are
        still materializing on the pipeline worker."""
        if self._pipe_finalized:
            # finalize() happened (with or without a host pipeline):
            # silently restarting would run finalized endpoints and drop
            # any captured failure from the accounting
            raise RuntimeError(
                "pipelined chain was finalized; call initialize() before "
                "executing again")
        device_eps = self._device_prefix()
        host_eps = self.endpoints[len(device_eps):]

        if self._pipe_fn is None:
            self._pipe_fn = self._device_fn(self.donate_buffers)
        if self._pipeline is None and host_eps:
            self._pipeline = HostPipeline(host_eps,
                                          depth=self.pipeline_depth,
                                          workers=self.pipeline_workers)
        now = time.perf_counter()
        if self._pipe_t0 is None:
            self._pipe_t0 = now
        elif self._pipe_wall != 0.0:
            # resuming after a frozen batch: shift the origin so wall_s
            # accumulates active batch windows only — idle time between
            # a drain and the next execute must not count
            self._pipe_t0 = now - self._pipe_wall
            self._pipe_wall = 0.0

        probing = (device_eps and self._pipe_calls == 1
                   and self._device_probe_s is None)
        if probing and self._probe_prev is not None:
            # overlap-efficiency calibration, part 2: first let field 0
            # clear the device queue (untimed), so the probe below times
            # ONE field, not the backlog
            jax.block_until_ready(jax.tree.leaves(self._probe_prev))
            self._probe_prev = None
        step = span_step(data.step)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("insitu.dispatch", step=step):
            out = self._pipe_fn(data) if device_eps else data
        # async dispatch: this measures LAUNCH cost, not device compute
        self._dispatch_s += time.perf_counter() - t0
        if probing:
            # calibration, part 3: block on exactly this one field (the
            # SECOND — the first call pays compilation) to learn the
            # synchronous per-field device cost; every other field stays
            # async. See pipeline.overlap_stats.
            jax.block_until_ready(jax.tree.leaves(out.arrays))
            self._device_probe_s = time.perf_counter() - t0
        elif device_eps and self._pipe_calls == 0 \
                and self._device_probe_s is None:
            # calibration, part 1: keep field 0's output so the next
            # call can drain it before probing
            self._probe_prev = jax.tree.leaves(out.arrays)
        self._pipe_calls += 1
        if self._pipeline is not None:
            self._pipeline.submit(out, step=step)   # backpressure here
        return out

    def _staged_fn(self, idx: int, ep: Endpoint):
        """Per-endpoint jitted execute, built once per chain — NOT per
        ``execute()`` call. ``jax.jit(ep.execute)`` returns a fresh
        wrapper each time, so rebuilding it every step forced a
        re-trace/compile on every chain execution."""
        fn = self._staged_fns.get(idx)
        if fn is None:
            fn = self._staged_fns[idx] = jax.jit(
                lambda d: _scoped(idx, ep, d))
        return fn

    def _execute_staged(self, data: BridgeData) -> BridgeData:
        """Per-endpoint jit with accounted resharding between stages
        (the in-transit M→N path); blocks after every device stage."""
        out = data
        for idx, ep in enumerate(self.endpoints):
            want = ep.in_sharding(self.mesh)
            if want is not None and not ep.host:
                out = out.replace(arrays={
                    k: self._reshard_tree(v, want)
                    for k, v in out.arrays.items()})
            t0 = time.perf_counter()
            if ep.host:
                out = ep.execute(out)
            else:
                out = self._staged_fn(idx, ep)(out)
                jax.block_until_ready(jax.tree.leaves(out.arrays))
            self._timings[ep.name] = (self._timings.get(ep.name, 0.0)
                                      + time.perf_counter() - t0)
        return out

    def _reshard_tree(self, v, sharding):
        """Move every mismatched array in a subtree onto ``sharding``,
        accounting the moved bytes."""
        def move(x):
            if hasattr(x, "sharding") and x.sharding != sharding:
                self._reshard_bytes += x.size * x.dtype.itemsize
                return jax.device_put(x, sharding)
            return x
        return jax.tree.map(move, v)

    # -- reporting ------------------------------------------------------------
    def reset_stats(self) -> None:
        """Zero all timing/accounting state (including the pipelined
        wall-clock origin) without touching compiled programs or queued
        work — call after warm-up so reports cover steady state."""
        self._timings.clear()
        self._reshard_bytes = 0
        self._dispatch_s = 0.0
        self._pipe_t0 = None
        self._pipe_wall = 0.0
        self._pipe_report = None
        if self._pipeline is not None:
            self._pipeline.reset_stats()

    def marshaling_report(self) -> Dict[str, Any]:
        """Accounting across modes: reshard bytes and per-stage timings,
        plus (pipelined) queue/backpressure stats and the derived
        overlap-efficiency numbers — see ``pipeline.overlap_stats`` for
        their exact definitions."""
        rep = {"mode": self.mode,
               "reshard_bytes": self._reshard_bytes,
               "timings_s": dict(self._timings)}
        pr = (self._pipeline.report() if self._pipeline is not None
              else self._pipe_report)
        if pr is not None:
            # frozen batch wall (set at drain/shutdown) when available;
            # the live clock only while work may still be in flight
            wall = self._pipe_wall
            if wall == 0.0 and self._pipe_t0 is not None \
                    and self._pipeline is not None:
                wall = time.perf_counter() - self._pipe_t0
            pipe = dict(pr)
            pipe.update(overlap_stats(
                wall_s=wall, dispatch_s=self._dispatch_s,
                device_probe_s=self._device_probe_s or 0.0,
                pipeline_report=pr))
            rep["pipeline"] = pipe
            rep["timings_s"].update(pr.get("host_timings_s", {}))
        return rep

    # -- training integration ---------------------------------------------------
    def as_step_hook(self):
        """A jit-friendly callable over training tensors: used by
        train/step.py to run spectral monitoring inside the step."""
        device_eps = self._device_prefix()

        def hook(payload: Dict[str, Any]) -> Dict[str, Any]:
            d = BridgeData(arrays=dict(payload), domain="spatial")
            for idx, ep in enumerate(device_eps):
                d = _scoped(idx, ep, d)
            return {k: v for k, v in d.arrays.items()
                    if k.startswith("insitu_")}
        return hook
