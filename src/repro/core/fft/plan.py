"""FFTW-style plan lifecycle over jit compilation: cached + measured.

The paper's endpoint wraps FFTW's ``allocate - plan - execute - destroy``
paradigm (Listing 3). The JAX analogue: *planning is compilation*. An
``FFTPlan`` captures (global shape, mesh, decomposition, direction,
backend, real/complex, batch rank, wire dtype), builds the matching
``Schedule`` (see ``schedule.py``), compiles the generic executor over
it once, and ``execute`` runs it on device arrays.

Three FFTW behaviors are reproduced on top of that:

* **Plan cache** — FFTW never re-plans for a (shape, flags) pair it has
  seen; neither do we. ``plan_dft``/``plan_rfft`` consult a
  process-wide cache keyed by every compile-relevant field (including
  the mesh's axis extents and device ids), so in-situ chains that
  re-create endpoints every step still reuse one compiled plan.
  ``plan_cache_stats()`` exposes hit/miss/skip counters;
  ``plan_cache_clear()`` empties it (e.g. after ``jax.clear_caches``).

* **FFTW_ESTIMATE** — ``backend="auto"`` picks a reasonable algorithm
  from the dispatch heuristics in ``dft.local_fft`` without measuring.

* **FFTW_MEASURE** — ``backend="measure"`` sweeps the *schedule
  variant space* on first use and pins the fastest: every combination
  of local-FFT backend × overlap chunking × wire dtype the requested
  decomposition's schedule supports (``schedule.CAPS``), for batched
  and real plans too:

      backend        ∈ {fourstep, stockham (pow-2 grids), jnp}
      overlap_chunks ∈ {0, 2, 4}   (any overlap-capable schedule)
      wire_dtype     ∈ {None, bfloat16} ∪ {per-stage profile}
                      ∪ {per-stage int8 / block-scaled-int8 codec
                         tuples on host-crossing exchanges, each
                         gated by the wire_tol error budget against
                         the exact-wire oracle — see docs/wire.md}

  The per-stage wire candidate is TOPOLOGY-aware: when the schedule's
  exchanges have a mixed host-crossing profile (some cross DCN, some
  stay on ICI/intra-host — the ``crosses_hosts`` annotation), the
  sweep adds the tuple that casts ONLY the cross-host hops to bfloat16
  and keeps the on-host exchanges exact — e.g. ``(None, "bfloat16")``
  for a pencil whose second rotation crosses hosts. On topologies
  where that tuple would duplicate a uniform candidate (single host,
  or a one-exchange schedule) it is skipped AND recorded, never timed
  redundantly; ``plan_cache_stats()["wire_profile_candidates"]``
  counts the sweeps that generated one.

  Each candidate is compiled and timed on a zero input of the right
  sharded shape; the winner's knobs are cached per (shape, mesh,
  decomp, direction, real, batch) so later ``measure`` plans skip the
  sweep. Candidates that fail to build (e.g. a chunk count that does
  not divide the local extent, or a schedule with no overlap site) are
  RECORDED, not silently dropped: ``autotune_skips()`` returns the
  skipped variants with their errors and ``plan_cache_stats()`` counts
  them, so a mis-tuned plan is debuggable. Note
  ``wire_dtype="bfloat16"`` trades ~3 decimal digits of accuracy for
  half the collective bytes; pass ``allow_reduced_wire=False`` to keep
  the sweep exact. Full guide: ``docs/tuning.md``.

* **Wisdom** — FFTW's measured winners outlive the process
  (``fftw_export_wisdom``); so do ours. When a wisdom store is
  configured (``set_wisdom(path, mode)``, or the ``REPRO_WISDOM_FILE``
  / ``REPRO_WISDOM_MODE`` env contract), both measured sweeps become
  read-through/write-behind over ``core/fft/wisdom.py``: a recorded
  winner for this (shape, knobs, mesh TOPOLOGY, jax/sweep revision)
  skips the timed sweep entirely — zero candidates timed, zero sweep
  collectives — and a freshly measured winner is persisted exactly as
  agreed cluster-wide, so every rank writes identical wisdom. Stale
  or invalid wisdom (version bump, unknown backend, corrupt file)
  falls through to a normal measurement, deterministically on every
  rank. ``plan_cache_stats()`` reports ``wisdom_hits`` /
  ``wisdom_misses`` / ``wisdom_stale`` and ``sweep_candidates_timed``
  (the warm-start assertion signal: a wisdom-warm bring-up shows
  hits > 0 and zero timed candidates). Full guide: ``docs/wisdom.md``.

Decompositions (``decomp=``): ``slab`` (2-D, 1 mesh axis), ``slab3d``
(3-D, 1 mesh axis), ``pencil`` (3-D, 2 mesh axes), ``pencil_tf``
(transpose-free pencil — output in the documented digit-permuted
x-layout), ``pencil2d`` (2-D grids tiled over BOTH axes of a 2-D
mesh), ``fourstep1d`` (1-D). All but ``fourstep1d`` have r2c/c2r
schedules, so ``plan_rfft`` works on every mesh shape — including 3-D
grids on 1-axis meshes (``slab3d``) and the transpose-free layout.
``_infer`` picks by grid rank, and for 3-D grids picks ``pencil`` on
≥2-axis meshes and ``slab3d`` on 1-axis meshes; 2-D grids default to
``slab`` (the ``decomp="measure"`` sweep races ``pencil2d`` against
it on 2-axis meshes).

**Topology awareness** (multi-host): every built schedule carries a
host-crossing annotation per ``AllToAll`` (``FFTPlan.topology()``),
the plan/tune caches key on per-device *process* placement — not just
device ids — and ``decomp="measure"`` sweeps the layout-compatible
decompositions (slab3d vs pencil for 3-D grids) and pins the fastest
*for this topology*: one big cross-host exchange and two smaller
ones order differently once all_to_all leaves the host (Verma et
al., arXiv:2202.12756). When the tuned mesh spans multiple
processes, both measured sweeps (decomp and knobs) broadcast process
0's winner before caching — per-process timings never decide alone,
because divergent winners would compile divergent collective
programs and deadlock the next ``execute``; process-local meshes
keep tuning locally (see ``_agree_choice``). See
``docs/multihost.md``.

Real-input plans (``plan_rfft``, or ``real=True``) use the Hermitian
half-spectrum schedules in ``rfft.py``: forward ``execute(x)`` maps a
real field to a half-spectrum (re, im) pair, backward ``execute(re,
im)`` maps it back to a real field. Half the local FFT work, half the
all_to_all wire bytes.

Batched plans (``batch_ndim=k``) transform arrays with ``k`` extra
leading dims — a whole stack of fields per step under ONE compiled
plan, the in-situ chain's steady-state shape. Overlap chunking
composes with both (it is an executor property, not a per-schedule
special case).

**Locking contract** (the serve engine's worker threads share these
caches): every module-level structure — ``_PLAN_CACHE``,
``_TUNE_CACHE``, ``_DECOMP_CACHE``, ``_TUNE_SKIPS``, ``_STATS`` — is
guarded by one re-entrant module lock (``_LOCK``); all reads and
writes go through it, so ``plan_cache_stats()`` /
``autotune_skips()`` / ``plan_cache_clear()`` are safe from any
thread. Cache *population* is **single-flight per key**
(``_single_flight``): the first thread to request an uncached plan
(or measured sweep) installs an in-flight marker and builds it
OUTSIDE the lock — compilation and timing never serialize unrelated
plans — while every other thread asking for the SAME key blocks on
the marker and then reads the cached result. First-toucher measures,
everyone else hits; a key is never compiled (or swept) twice, and a
builder that raises clears its marker so a waiter retries the build
rather than hanging. ``plan_cache_stats()["thread_waits"]`` counts
the calls that blocked on another thread's in-flight build. Measured
sweeps on multi-process meshes issue collectives; the single-flight
discipline also guarantees only ONE thread per process enters them,
keeping cluster-wide agreement (``_agree_choice``) unambiguous.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.fft import rfft as rfft_mod
from repro.core.fft import wire as wire_lib
from repro.core.fft import wisdom as wisdom_mod
from repro.core.fft.dft import to_complex, to_pair
from repro.core.fft.schedule import (CAPS, Schedule, build_schedule,
                                     exchange_topology, execute_schedule,
                                     overlap_site)

FORWARD = "forward"
BACKWARD = "backward"

MEASURE = "measure"                   # backend/decomp sentinel: autotune

# decompositions the decomp="measure" sweep may substitute for each
# other: same natural index order per rank (the SHARDING the winner
# publishes may differ — callers place data via plan.input_sharding()).
# The cyclic/digit-permuted family (pencil_tf, fourstep1d) is excluded —
# swapping one in would silently change the data layout the caller
# sees, which is a correctness change, not a tuning choice.
_SWEEP_DECOMPS = {2: ("slab", "pencil2d"), 3: ("pencil", "slab3d")}

# ---------------------------------------------------------------------------
# Process-wide plan cache
# ---------------------------------------------------------------------------

_PLAN_CACHE: Dict[tuple, "FFTPlan"] = {}
_TUNE_CACHE: Dict[tuple, dict] = {}
_DECOMP_CACHE: Dict[tuple, str] = {}
_TUNE_SKIPS: List[dict] = []
_STATS = {"hits": 0, "misses": 0, "wire_profile_candidates": 0,
          "wire_codec_candidates": 0,
          "thread_waits": 0, "sweep_candidates_timed": 0,
          "wisdom_hits": 0, "wisdom_misses": 0, "wisdom_stale": 0}

# Compressed-wire candidate policy for the measured sweep (a test/bench
# hook, NOT a tuning input — it never enters cache or wisdom keys):
#   "auto"   — codec candidates only on host-crossing exchanges (prod)
#   "always" — treat every exchange as crossing (single-host testing of
#              the codec path + error-budget gate without a cluster)
#   "never"  — no codec candidates at all
_WIRE_SWEEP_POLICY = "auto"

# Persistent wisdom (core/fft/wisdom.py). None until first use: the
# explicit set_wisdom() wins; otherwise the REPRO_WISDOM_FILE /
# REPRO_WISDOM_MODE env contract is consulted once, lazily. The store
# deliberately survives plan_cache_clear() — persistence across cache
# resets is its entire point.
_WISDOM: Optional[wisdom_mod.WisdomStore] = None
_WISDOM_INIT = False

# One re-entrant lock guards every module-level structure above (see
# the module docstring's locking contract); _PENDING holds the
# in-flight single-flight markers, keyed by (cache name, cache key).
_LOCK = threading.RLock()
_PENDING: Dict[tuple, threading.Event] = {}


def _single_flight(cache_name: str, cache: dict, key, build):
    """Return ``(value, was_cached)`` for ``cache[key]``, building at
    most once across threads. The builder runs OUTSIDE ``_LOCK`` (so
    unrelated keys compile concurrently); threads racing the same key
    wait on the builder's in-flight marker instead of re-building. A
    builder that raises clears its marker — the exception propagates
    to it alone, and one waiter becomes the next builder (retry, not
    hang)."""
    while True:
        with _LOCK:
            if key in cache:
                return cache[key], True
            ev = _PENDING.get((cache_name, key))
            if ev is None:
                _PENDING[(cache_name, key)] = threading.Event()
                break
            _STATS["thread_waits"] += 1
        ev.wait()
    try:
        value = build()
    except BaseException:
        with _LOCK:
            _PENDING.pop((cache_name, key)).set()
        raise
    with _LOCK:
        cache[key] = value
        _PENDING.pop((cache_name, key)).set()
    return value, False


def _record_skip(entry: dict) -> None:
    with _LOCK:
        _TUNE_SKIPS.append(entry)


def _mesh_key(mesh: Mesh) -> tuple:
    # process indices make the key TOPOLOGY-aware: the same device ids
    # laid out across different hosts must not share cached tuning —
    # a sweep's winner depends on which exchanges cross DCN
    return (tuple(mesh.shape.items()),
            tuple(d.id for d in mesh.devices.flat),
            tuple(d.process_index for d in mesh.devices.flat))


def _wire_name(wire_dtype):
    """Hashable/canonical wire spec: codec names pass verbatim (they
    are already canonical strings — see ``wire.py``), dtype specs
    canonicalize through ``jnp.dtype``."""
    def one(w):
        if w is None or wire_lib.is_codec(w):
            return w
        return jnp.dtype(w).name
    if wire_dtype is None:
        return None
    if isinstance(wire_dtype, (tuple, list)):
        return tuple(one(w) for w in wire_dtype)
    return one(wire_dtype)


def _plan_key(shape, direction, mesh, decomp, axis_names, backend,
              overlap_chunks, real, batch_ndim, wire,
              measure_flag=None) -> tuple:
    return (shape, direction, _mesh_key(mesh), decomp, axis_names,
            backend, overlap_chunks, real, batch_ndim, wire, measure_flag)


def plan_cache_stats() -> Dict[str, int]:
    """Planner counters: ``hits``/``misses``/``size`` (plan cache),
    ``autotune_skipped`` (recorded sweep exclusions, see
    ``autotune_skips()``), ``decomp_sweeps`` (cached topology sweeps),
    and ``wire_profile_candidates`` (per-stage wire tuples the knob
    sweep generated from a mixed ICI/DCN topology — 0 on single-host
    meshes, where the candidate is skip-recorded instead) /
    ``wire_codec_candidates`` (compressed int8/block-scaled wire
    tuples generated on host-crossing exchanges, each vetted by the
    ``wire_tol`` error-budget gate before timing — docs/wire.md), plus
    ``thread_waits`` (calls that blocked on another thread's
    in-flight build of the same key — the shared-warm-cache signal:
    N serve workers racing one cold plan show N-1 waits and ONE
    miss). ``sweep_candidates_timed`` counts individual candidates the
    measured sweeps actually timed — zero on a wisdom-warm bring-up —
    and ``wisdom_hits``/``wisdom_misses``/``wisdom_stale`` account the
    persistent-wisdom read-through (all zero when no store is
    configured). Guides: ``docs/tuning.md``, ``docs/wisdom.md``."""
    with _LOCK:
        return dict(_STATS, size=len(_PLAN_CACHE),
                    autotune_skipped=len(_TUNE_SKIPS),
                    decomp_sweeps=len(_DECOMP_CACHE))


def autotune_skips() -> List[dict]:
    """Variants the FFTW_MEASURE sweep could not build/run, with the
    error that excluded each — the anti-silent-mis-tuning record."""
    with _LOCK:
        return list(_TUNE_SKIPS)


def plan_cache_clear() -> None:
    """Empty every in-memory planner structure — the three caches, the
    sweep-skip record, and ALL stats counters (generically, so a newly
    added counter can never survive a clear as a ghost of the previous
    session). The persistent wisdom store is NOT touched: outliving
    cache resets is its entire point — the next measured plan after a
    clear warm-starts from wisdom instead of re-sweeping."""
    with _LOCK:
        _PLAN_CACHE.clear()
        _TUNE_CACHE.clear()
        _DECOMP_CACHE.clear()
        _TUNE_SKIPS.clear()
        for k in _STATS:
            _STATS[k] = 0


def plan_cache_evict(mesh: Mesh) -> int:
    """Drop every cached plan, knob-sweep winner, and decomp winner
    keyed on ``mesh``; return how many entries went. The elastic
    controller (``runtime/elastic.py``) calls this on every rescale:
    cached plans pin compiled programs and shardings of a mesh that no
    longer exists (or is being freshly brought up), and the honest
    bring-up path for the rescaled mesh is plan-cache miss → wisdom
    read-through — which is exactly what the warm-rescale acceptance
    (``wisdom_hits > 0``, ``sweep_candidates_timed == 0``) measures.
    Stats counters and the wisdom store are untouched."""
    mk = _mesh_key(mesh)
    evicted = 0
    with _LOCK:
        # all three caches key as (shape, direction, mesh_key, ...)
        for cache in (_PLAN_CACHE, _TUNE_CACHE, _DECOMP_CACHE):
            doomed = [k for k in cache if k[2] == mk]
            for k in doomed:
                del cache[k]
            evicted += len(doomed)
    return evicted


def set_wire_sweep_policy(policy: str) -> str:
    """Set the compressed-wire candidate policy (``auto`` / ``always``
    / ``never`` — see ``_WIRE_SWEEP_POLICY``) and return the previous
    one. ``always`` exists so single-host tests and benches can drive
    the codec candidates + error-budget gate without a multi-process
    cluster; production leaves this on ``auto`` (ICI stays exact)."""
    global _WIRE_SWEEP_POLICY
    if policy not in ("auto", "always", "never"):
        raise ValueError(f"wire sweep policy {policy!r} not in "
                         f"auto/always/never")
    with _LOCK:
        prev, _WIRE_SWEEP_POLICY = _WIRE_SWEEP_POLICY, policy
    return prev


def set_wisdom(path, mode: str = "readwrite"):
    """Configure persistent wisdom for this process: ``path`` names the
    store file, ``mode`` ∈ ``off|read|readwrite``. ``set_wisdom(None)``
    (or ``mode="off"``) disables it. An explicit call overrides the
    ``REPRO_WISDOM_FILE``/``REPRO_WISDOM_MODE`` env contract; drivers
    expose this as ``--wisdom``/``--wisdom-mode``. Returns the active
    store (or None)."""
    global _WISDOM, _WISDOM_INIT
    store = None
    if path is not None and mode != "off":
        store = wisdom_mod.WisdomStore(path, mode=mode)
    with _LOCK:
        _WISDOM, _WISDOM_INIT = store, True
    return store


def wisdom_store() -> Optional[wisdom_mod.WisdomStore]:
    """The active wisdom store: whatever ``set_wisdom`` configured, or
    (checked once, lazily) the env contract. None ⇒ wisdom off and the
    sweeps run exactly as they did before wisdom existed."""
    global _WISDOM, _WISDOM_INIT
    with _LOCK:
        if not _WISDOM_INIT:
            _WISDOM = wisdom_mod.store_from_env()
            _WISDOM_INIT = True
        return _WISDOM


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FFTPlan:
    shape: Tuple[int, ...]            # transform (grid) shape, no batch dims
    direction: str
    mesh: Mesh
    decomp: str                       # key into schedule.CAPS
    axis_names: Tuple[str, ...]
    backend: str = "auto"
    overlap_chunks: int = 0           # >1: chunked overlap pipelining
    real: bool = False                # r2c (fwd) / c2r (bwd) half-spectrum
    batch_ndim: int = 0               # extra leading batch dims at execute
    wire_dtype: Optional[object] = None  # name or per-stage name tuple
    _fn: Optional[Callable] = None
    _sched: Optional[Schedule] = None

    # -- plan ---------------------------------------------------------------
    def schedule(self) -> Schedule:
        """The stage schedule this plan runs (built lazily, no jit)."""
        if self._sched is None:
            self._sched = build_schedule(
                self.decomp, self.shape, self.mesh, self.axis_names,
                inverse=self.direction == BACKWARD, backend=self.backend,
                wire_dtype=self.wire_dtype, real=self.real)
        return self._sched

    def topology(self) -> Tuple[dict, ...]:
        """The plan's wire profile: one ``{axis_name, shards,
        wire_dtype, crosses_hosts}`` dict per exchange, in execution
        order. ``crosses_hosts=True`` exchanges pay DCN latency —
        the signal behind the ``decomp="measure"`` sweep."""
        return exchange_topology(self.schedule())

    def scope(self) -> str:
        """The plan's ``named_scope`` in traces: ``plan.r2c``,
        ``plan.c2r``, ``plan.fwd`` or ``plan.bwd``."""
        forward = self.direction == FORWARD
        if self.real:
            return "plan.r2c" if forward else "plan.c2r"
        return "plan.fwd" if forward else "plan.bwd"

    def compile(self) -> "FFTPlan":
        sched = self.schedule()
        if self.overlap_chunks and self.overlap_chunks > 1:
            overlap_site(sched)       # raise a clear error at plan time
        mesh, chunks, scope = self.mesh, self.overlap_chunks, self.scope()

        def fn(*arrays):
            with jax.named_scope(scope):
                return execute_schedule(sched, mesh, *arrays,
                                        overlap_chunks=chunks)

        self._fn = jax.jit(fn)
        return self

    # -- sharding contracts --------------------------------------------------
    def _spec(self, *tail) -> P:
        return P(*((None,) * self.batch_ndim), *tail)

    def input_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self._spec(*self.schedule().in_spec))

    def output_sharding(self) -> NamedSharding:
        """Where ``execute`` leaves the data (the next stage's input)."""
        return NamedSharding(self.mesh,
                             self._spec(*self.schedule().out_spec))

    def place(self, x):
        """Device-put onto the plan's input sharding. Real forward plans
        take the real field itself; everything else takes/returns split
        (re, im) pairs."""
        sh = self.input_sharding()
        if self.real and self.direction == FORWARD:
            return (jax.device_put(jnp.asarray(x, jnp.float32), sh),)
        re, im = to_pair(x)
        return jax.device_put(re, sh), jax.device_put(im, sh)

    # -- execute --------------------------------------------------------------
    def execute(self, *arrays):
        """Run the compiled transform.

        complex plans / real backward:  ``execute(re, im)``
        real forward:                   ``execute(x)`` → (re, im)
        real backward returns the real field alone."""
        if self._fn is None:
            self.compile()
        return self._fn(*arrays)

    def execute_complex(self, x):
        out = self.execute(*self.place(x))
        return to_complex(out) if isinstance(out, tuple) else out


# ---------------------------------------------------------------------------
# Planner entry points (cached)
# ---------------------------------------------------------------------------

def _infer(shape, decomp, axis_names, mesh):
    if decomp is None:
        if len(shape) == 1:
            decomp = "fourstep1d"
        elif len(shape) == 2:
            decomp = "slab"
        else:
            # pencil wants two mesh axes; a 1-axis mesh still gets 3-D
            # grids via the one-exchange slab3d schedule
            decomp = "pencil" if len(mesh.axis_names) >= 2 else "slab3d"
    if axis_names is None:
        names = tuple(mesh.axis_names)
        caps = CAPS.get(decomp)
        take = caps.mesh_axes if caps is not None else 1
        axis_names = names[:take]
    return decomp, tuple(axis_names)


def plan_dft(shape, direction: str, mesh: Mesh, *,
             decomp: Optional[str] = None,
             axis_names: Optional[Tuple[str, ...]] = None,
             backend: str = "auto", overlap_chunks: int = 0,
             real: bool = False, batch_ndim: int = 0,
             wire_dtype=None, allow_reduced_wire: bool = True,
             wire_tol: float = 1e-2) -> FFTPlan:
    """``fftw_mpi_plan_dft_*`` equivalent: decomposition inference, a
    process-wide plan cache, and ``backend="measure"`` autotuning.
    Identical arguments return the SAME compiled plan object.
    ``wire_tol`` is the measured sweep's error budget for compressed
    wire candidates (max rel-err vs the exact-wire oracle; over-budget
    candidates are skip-recorded, never selected — docs/wire.md)."""
    shape = tuple(int(s) for s in shape)
    wire_tol = float(wire_tol)
    if decomp == MEASURE:
        axis_names = tuple(axis_names) if axis_names is not None else None
        decomp = _autotune_decomp(shape, direction, mesh, backend=backend,
                                  overlap_chunks=overlap_chunks,
                                  wire_dtype=wire_dtype,
                                  real=real, batch_ndim=batch_ndim,
                                  allow_reduced_wire=allow_reduced_wire,
                                  axis_names=axis_names,
                                  wire_tol=wire_tol)
        if axis_names is not None and decomp in CAPS:
            # the sweep raced each candidate over the prefix of the
            # caller's axes it needs — build the winner the same way
            axis_names = axis_names[: CAPS[decomp].mesh_axes]
    decomp, axis_names = _infer(shape, decomp, axis_names, mesh)
    wire = _wire_name(wire_dtype)

    key = _plan_key(shape, direction, mesh, decomp, axis_names, backend,
                    overlap_chunks, real, batch_ndim, wire,
                    (allow_reduced_wire, wire_tol)
                    if backend == MEASURE else None)

    def _build() -> FFTPlan:
        if backend == MEASURE:
            tuned = _autotune(shape, direction, mesh, decomp, axis_names,
                              real=real, batch_ndim=batch_ndim,
                              allow_reduced_wire=allow_reduced_wire,
                              wire_tol=wire_tol)
            return plan_dft(shape, direction, mesh, decomp=decomp,
                            axis_names=axis_names, real=real,
                            batch_ndim=batch_ndim, **tuned)
        return FFTPlan(shape, direction, mesh, decomp, axis_names,
                       backend, overlap_chunks, real, batch_ndim,
                       wire).compile()

    plan, cached = _single_flight("plan", _PLAN_CACHE, key, _build)
    with _LOCK:
        _STATS["hits" if cached else "misses"] += 1
    return plan


def plan_rfft(shape, direction: str, mesh: Mesh, **kw) -> FFTPlan:
    """Real-input plan (FFTW's ``plan_dft_r2c``/``c2r``): forward maps a
    real field to its Hermitian half-spectrum, backward inverts it."""
    return plan_dft(shape, direction, mesh, real=True, **kw)


# ---------------------------------------------------------------------------
# FFTW_MEASURE-style autotuner — sweeps schedule variants
# ---------------------------------------------------------------------------

def _pow2(n: int) -> bool:
    return n & (n - 1) == 0


def _process_span(mesh: Mesh) -> set:
    return {d.process_index for d in mesh.devices.flat}


def _subset_span(span: set) -> bool:
    """True for a mesh spanning a strict subset of >1 processes — the
    documented subset-collectives hazard (``docs/multihost.md``). The
    measured sweeps must not even START on such a mesh: timing a
    candidate executes subset cross-process collectives (the hang
    itself), and no safe collective exists afterwards to agree on the
    winner. Callers skip the sweep and pin the untimed default
    deterministically on every process — mis-tuned beats deadlocked."""
    return 1 < len(span) < jax.process_count()


def _sweep_ok(ok: bool, span: set) -> bool:
    """Collective AND over the mesh's processes: True only when EVERY
    process reports ``ok``. The sweeps call this around each timed
    candidate because timing executes the candidate's collectives — a
    candidate failing on one process only (per-host OOM, transient XLA
    error) would otherwise desynchronize the loop's collective control
    flow: the failing process moves on to the next candidate's
    all_to_alls while the others still sit inside this one's, and the
    cluster deadlocks. Single-process span: plain pass-through, no
    collective."""
    if len(span) <= 1:
        return ok
    from jax.experimental.multihost_utils import process_allgather
    flags = process_allgather(jnp.asarray([1 if ok else 0], jnp.int32))
    return bool(flags.min() == 1)


def _agree_choice(options: list, choice, span: set):
    """Cross-process agreement for measured sweeps. ``_time_plan`` is
    per-process wall clock, so on a multi-process cluster timing noise
    (or a candidate failing on one process only) can hand different
    processes different winners — after which they build DIVERGENT
    collective programs and the next ``execute`` deadlocks or corrupts
    data. Process 0's pick wins everywhere (FFTW's broadcast-the-wisdom
    discipline): the winner is encoded as an index into ``options``
    (deterministic, shape-derived, hence identical on every process)
    and broadcast before anything is cached.

    Agreement is scoped to the MESH's process span, not the cluster: a
    span of 1 (single-process runs, or a process-local mesh inside a
    cluster, e.g. a transit consumer's shard-local analysis) keeps
    local timing authoritative — joining a global collective the other
    processes never call would itself hang the cluster. A mesh
    spanning every process broadcasts via ``broadcast_one_to_all``, a
    global collective all processes reach (measure-planning on a
    global mesh is itself collective). Strict-subset meshes never get
    here — their sweeps are skipped up front (``_subset_span``)."""
    if len(span) <= 1:
        return choice
    from jax.experimental.multihost_utils import broadcast_one_to_all
    idx = options.index(choice)
    return options[int(broadcast_one_to_all(jnp.int32(idx)))]


# ---------------------------------------------------------------------------
# Persistent wisdom read-through (core/fft/wisdom.py)
# ---------------------------------------------------------------------------

_WISDOM_BACKENDS = {"auto", "jnp", "fourstep", "stockham", "pallas"}
_WISDOM_BLOB_BYTES = 1024


def _tune_from_wisdom(value):
    """Validate + normalize a recorded knob dict. JSON round-trips wire
    tuples to lists (normalized back here); anything structurally off —
    or naming a backend this build no longer has — is STALE wisdom and
    returns None, sending the caller into a normal measured sweep."""
    if not isinstance(value, dict):
        return None
    try:
        backend = value["backend"]
        overlap = int(value["overlap_chunks"])
        wire = value["wire_dtype"]
    except (KeyError, TypeError, ValueError):
        return None
    if backend not in _WISDOM_BACKENDS or overlap < 0:
        return None

    def _wire_ok(w) -> bool:
        # a wire entry must be a known codec or a real dtype name —
        # wisdom recorded by a build with other codecs is stale here
        if w is None or wire_lib.is_codec(w):
            return True
        try:
            jnp.dtype(w)
            return True
        except TypeError:
            return False

    if isinstance(wire, (list, tuple)):
        wire = tuple(None if w is None else str(w) for w in wire)
        if not all(_wire_ok(w) for w in wire):
            return None
    elif wire is not None and (not isinstance(wire, str)
                               or not _wire_ok(wire)):
        return None
    return {"backend": backend, "overlap_chunks": overlap,
            "wire_dtype": wire}


def _agree_wisdom_value(value, span):
    """Broadcast process 0's wisdom value verbatim (JSON bytes in a
    fixed 1 KiB length-prefixed buffer — every rank must contribute an
    identically shaped array to ``broadcast_one_to_all``). Same
    discipline as ``_agree_choice``, different payload: here the
    options list lives in a FILE that may have drifted between hosts,
    so an index is not enough — the value itself must travel. Every
    rank decodes the same bytes, so a decode failure (oversized or
    mangled value) returns None on every rank at once: a deterministic
    cluster-wide fall-through to the measured sweep, never divergence."""
    if len(span) <= 1:
        return value
    from jax.experimental.multihost_utils import broadcast_one_to_all
    buf = np.zeros(_WISDOM_BLOB_BYTES, np.uint8)
    blob = json.dumps(value, sort_keys=True).encode()
    if len(blob) <= _WISDOM_BLOB_BYTES - 2:
        buf[0] = len(blob) & 0xFF
        buf[1] = len(blob) >> 8
        buf[2:2 + len(blob)] = np.frombuffer(blob, np.uint8)
    # element-wise cast back: some backends widen small dtypes for the
    # collective (uint8 arrives as int32), so reinterpret VALUES, not
    # raw bytes
    out = np.asarray(broadcast_one_to_all(jnp.asarray(buf)))
    out = out.astype(np.uint8)
    n = int(out[0]) | (int(out[1]) << 8)
    if n == 0:
        return None
    try:
        return json.loads(out[2:2 + n].tobytes().decode())
    except Exception:  # noqa: BLE001 — same bytes, same failure, all ranks
        return None


def _wisdom_sweep_hit(kind: str, key: str, span: set, decode):
    """The read-through: an agreed, validated wisdom hit for this
    sweep, or None (⇒ measure as usual). The hit must be
    ALL-or-nothing across the mesh's processes (``_sweep_ok``): a
    mixed hit/miss would send some ranks into the timed sweep's
    collectives while the rest skip them — the same desync the sweeps
    guard every candidate against. On an agreed hit, process 0's
    recorded value is broadcast and used verbatim everywhere
    (``_agree_wisdom_value``), so per-host wisdom files that drifted
    can never compile divergent collective programs. Invalid recorded
    values are re-booked as stale (here and in the store) and fall
    through to measurement, deterministically on every rank."""
    store = wisdom_store()
    if store is None:
        return None
    raw = store.lookup(kind, key)
    value = decode(raw) if raw is not None else None
    if raw is not None and value is None:
        store.count_stale()
        with _LOCK:
            _STATS["wisdom_stale"] += 1
    if len(span) > 1:
        # agree the hit, then agree the value itself
        if not _sweep_ok(value is not None, span):
            value = None
        else:
            agreed = _agree_wisdom_value(value, span)
            value = decode(agreed) if agreed is not None else None
    with _LOCK:
        _STATS["wisdom_hits" if value is not None else
               "wisdom_misses"] += 1
    return value


def _wisdom_record(kind: str, key: str, value) -> None:
    """The write-behind: persist a freshly AGREED winner. Called after
    ``_agree_choice``, so the value is identical on every rank of the
    mesh — all ranks write byte-identical wisdom (last atomic replace
    wins, content already agreed)."""
    store = wisdom_store()
    if store is not None:
        store.record(kind, key, value)


def _time_plan(plan: FFTPlan, args, iters: int = 3) -> float:
    with _LOCK:
        # the warm-start signal: a wisdom-warm bring-up times ZERO
        # candidates (see docs/wisdom.md and the fft_wisdom_* benches)
        _STATS["sweep_candidates_timed"] += 1
    jax.block_until_ready(plan.execute(*args))            # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = plan.execute(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _dummy_args(shape, direction, mesh, decomp, axis_names, real,
                batch_ndim):
    probe = FFTPlan(shape, direction, mesh, decomp, axis_names,
                    real=real, batch_ndim=batch_ndim)
    full = (2,) * batch_ndim + tuple(shape)
    if real and direction == BACKWARD:
        # half-spectrum input: last grid dim at the decomposition's
        # padded half extent (padding differs per decomp — slab3d's
        # half axis never travels and is unpadded, pencil2d's is split
        # by BOTH mesh axes)
        full = full[:-1] + (rfft_mod.spectral_half_extent(
            decomp, shape[-1], mesh, axis_names),)
    sh = probe.input_sharding()
    zero = jax.device_put(jnp.zeros(full, jnp.float32), sh)
    if real and direction == FORWARD:
        return (zero,)
    return (zero, zero)


def _oracle_args(shape, direction, mesh, decomp, axis_names, real,
                 batch_ndim):
    """Deterministic NON-zero sweep input for the wire error-budget
    oracle. ``_dummy_args`` times on zeros — fine for walls, useless
    for error measurement (every codec is exact on zeros). The fill is
    a fixed sum of per-axis cosines computed INSIDE jit from iota, so
    it is bit-identical on every process with no host-array transfer
    ambiguity, and elementwise, so each array keeps its sweep-input
    sharding."""
    args = _dummy_args(shape, direction, mesh, decomp, axis_names, real,
                       batch_ndim)

    @jax.jit
    def fill(z, seed):
        out = z
        for d in range(z.ndim):
            idx = jax.lax.broadcasted_iota(jnp.float32, z.shape, d)
            out = out + jnp.cos((0.37 + 0.11 * seed) * (d + 1) * idx + 0.1)
        return out

    return tuple(fill(z, jnp.float32(i)) for i, z in enumerate(args))


def _max_rel_err(got, want) -> float:
    """max |got - want| / max |want| over the (re, im) pair — a single
    replicated scalar, identical on every process (same global arrays,
    same reduction), so budget decisions never diverge."""
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    num = 0.0
    den = 0.0
    for g, w in zip(got, want):
        num = max(num, float(jnp.max(jnp.abs(g - w))))
        den = max(den, float(jnp.max(jnp.abs(w))))
    return num / max(den, 1e-30)


def _wire_codec_variant(wire_dtype) -> bool:
    """True when a wire spec carries any compressed codec entry (these
    are the candidates the error-budget gate must vet)."""
    entries = wire_dtype if isinstance(wire_dtype, tuple) else (wire_dtype,)
    return any(wire_lib.is_codec(w) for w in entries)


def _wire_codec_candidates(shape, direction, mesh, decomp, axis_names,
                           real):
    """Compressed-wire candidates for the measured sweep: one per-stage
    tuple per stock int8 codec, compressing ONLY the host-crossing
    exchanges (ICI stays exact — intra-host wire is cheap and
    quantizing it buys nothing). Under the ``always`` policy every
    exchange counts as crossing, so single-host tests can exercise the
    full codec path. Returns a (possibly empty) list of wire tuples;
    derived from mesh placement only, hence identical on every process
    (the sweep's collective control flow depends on that)."""
    if _WIRE_SWEEP_POLICY == "never":
        return []
    sched = build_schedule(decomp, shape, mesh, axis_names,
                           inverse=direction == BACKWARD, real=real)
    flags = [bool(t["crosses_hosts"]) for t in exchange_topology(sched)]
    if _WIRE_SWEEP_POLICY == "always":
        flags = [True] * len(flags)
    if not any(flags):
        return []
    profs = []
    for codec in ("int8", f"int8_block{wire_lib.DEFAULT_BLOCK}"):
        profs.append(tuple(codec if f else None for f in flags))
    return profs


def _wire_profile_candidate(shape, direction, mesh, decomp, axis_names,
                            real):
    """The topology-aware per-stage wire tuple: cast ONLY the
    exchanges whose device ring crosses processes (the DCN hops), keep
    the ICI / intra-host exchanges exact. Returns the tuple when the
    schedule's wire profile is MIXED, else a skip-reason string — a
    schedule with no cross-host exchange (or nothing but cross-host
    exchanges) would make the per-stage candidate a redundant duplicate
    of a uniform one, and timing duplicates is pure sweep waste."""
    sched = build_schedule(decomp, shape, mesh, axis_names,
                           inverse=direction == BACKWARD, real=real)
    flags = [bool(t["crosses_hosts"])
             for t in exchange_topology(sched)]
    if len(flags) < 2:
        return (f"per-stage wire needs >=2 exchanges to differ from "
                f"uniform wire ({decomp} has {len(flags)})")
    if not any(flags):
        return ("no cross-host exchange on this topology; the "
                "per-stage candidate would duplicate the uniform "
                "candidates")
    if all(flags):
        return ("every exchange crosses hosts; the per-stage candidate "
                "would duplicate the uniform bfloat16 candidate")
    return tuple("bfloat16" if f else None for f in flags)


def _schedule_variants(shape, decomp, *, allow_reduced_wire,
                       direction=FORWARD, mesh=None, axis_names=None,
                       real=False, record_skip=None) -> List[dict]:
    """The sweep space: every (backend, overlap_chunks, wire_dtype) the
    decomposition's schedules might support, straight from
    ``schedule.CAPS``. Ineligible combinations are discovered by
    *trying* them — failures are recorded in ``autotune_skips()``
    rather than pre-filtered, so the record shows what was ruled out
    and why.

    Beyond the two uniform wires, a third **per-stage** candidate is
    generated when the schedule's exchanges have a mixed host-crossing
    profile on ``mesh`` (``_wire_profile_candidate``): bfloat16 on the
    DCN hops only. On single-host meshes (or schedules with one
    exchange) that candidate degenerates into a duplicate of a uniform
    one, so it is SKIPPED and the reason recorded via ``record_skip``
    instead of being timed twice. The mesh's device placement is
    identical on every process, so the candidate list — and with it
    the sweep's collective control flow — stays deterministic
    cluster-wide.

    Compressed-wire candidates (``_wire_codec_candidates``): per-stage
    int8 / block-scaled-int8 tuples on host-crossing exchanges only,
    each later vetted by the sweep's error-budget gate against the
    exact-wire oracle before it may be timed, let alone win. To keep
    the variant-count explosion in check they sweep at
    ``overlap_chunks=0`` only — a codec's win is wire bytes, which
    overlap chunking does not change (and chunked encode would change
    block boundaries, i.e. the error being budget-checked)."""
    caps = CAPS[decomp]
    backends = ["fourstep", "jnp"]
    if all(_pow2(s) for s in shape):
        backends.append("stockham")
    overlaps = [0, 2, 4] if caps.overlap else [0]
    wires = [None]
    codec_wires = []
    if allow_reduced_wire and caps.wire:
        wires.append("bfloat16")
        if mesh is not None:
            try:
                prof = _wire_profile_candidate(shape, direction, mesh,
                                               decomp, axis_names, real)
            except Exception as e:  # noqa: BLE001 — schedule unbuildable
                prof = f"{type(e).__name__}: {e}"
            if isinstance(prof, tuple):
                wires.append(prof)
                with _LOCK:
                    _STATS["wire_profile_candidates"] += 1
            elif record_skip is not None:
                record_skip(prof)
            try:
                codec_wires = _wire_codec_candidates(
                    shape, direction, mesh, decomp, axis_names, real)
            except Exception:  # noqa: BLE001 — schedule unbuildable
                codec_wires = []
            with _LOCK:
                _STATS["wire_codec_candidates"] += len(codec_wires)
    variants = [{"backend": be, "overlap_chunks": ov, "wire_dtype": wr}
                for be in backends for ov in overlaps for wr in wires]
    variants.extend({"backend": be, "overlap_chunks": 0, "wire_dtype": wr}
                    for be in backends for wr in codec_wires)
    return variants


def _autotune_decomp(shape, direction, mesh, *, backend, overlap_chunks,
                     wire_dtype, real, batch_ndim,
                     allow_reduced_wire, axis_names=None,
                     wire_tol: float = 1e-2) -> str:
    """``decomp="measure"``: time every layout-compatible decomposition
    for this (grid, mesh TOPOLOGY, knobs) and return the fastest.

    The sweep exists because the slab/pencil tradeoff inverts with
    topology (one big exchange vs two smaller ones — which wins
    depends on whether the exchanges cross hosts), so results cache
    per ``_mesh_key`` — which includes per-device process indices —
    and never leak between topologies. Candidates are timed under the
    CALLER's knobs (overlap/wire can themselves invert the ordering,
    so they are part of the race and of the cache key); with
    ``backend="measure"`` each candidate is instead knob-tuned first
    by ``_autotune``, making the comparison best-vs-best.
    Ineligible/failed candidates land in ``autotune_skips()`` like any
    other ruled-out variant. Caller-specified ``axis_names`` are
    honored (each candidate is timed over the prefix it needs, so the
    plan the winner builds is the plan that raced) and are part of the
    cache key — a measurement for one axis layout never decides
    another. On multi-process clusters the local winner is only a
    vote: ``_agree_choice`` broadcasts process 0's pick before it is
    cached or returned, and ``_sweep_ok`` keeps the loop's collective
    control flow synchronized around candidates that fail on a subset
    of processes."""
    rank = len(shape)
    candidates = _SWEEP_DECOMPS.get(rank)
    if candidates is None:
        # rank 1 has only the cyclic-layout four-step; nothing to sweep
        return _infer(shape, None, None, mesh)[0]
    dkey = (shape, direction, _mesh_key(mesh), axis_names, real,
            batch_ndim, backend, overlap_chunks, _wire_name(wire_dtype),
            allow_reduced_wire, float(wire_tol))

    def _sweep() -> str:
        fallback = _infer(shape, None, None, mesh)[0]
        span = _process_span(mesh)
        if _subset_span(span):
            # timing candidates here would BE the subset-collectives
            # hang — pin the untimed default before any sweep starts
            return fallback
        wkey = wisdom_mod.wisdom_key(
            "decomp", mesh, shape=shape, direction=direction,
            axis_names=axis_names, real=real, batch_ndim=batch_ndim,
            backend=backend, overlap_chunks=overlap_chunks,
            wire_dtype=_wire_name(wire_dtype),
            allow_reduced_wire=allow_reduced_wire,
            wire_tol=float(wire_tol))

        def _decode(value):
            # a recorded decomp must still be a legal substitution for
            # this rank — anything else is stale wisdom, not a winner
            if isinstance(value, str) and (value in candidates
                                           or value == fallback):
                return value
            return None

        hit = _wisdom_sweep_hit("decomp", wkey, span, _decode)
        if hit is not None:
            return hit
        best, best_t = None, float("inf")
        for decomp in candidates:
            caps = CAPS[decomp]

            def skip(err):
                _record_skip({
                    "shape": shape, "direction": direction,
                    "decomp": decomp, "real": real,
                    "batch_ndim": batch_ndim, "backend": backend,
                    "sweep": "decomp", "error": err})

            cand, args, err = None, None, None
            try:  # build phase — no candidate collectives executed yet
                if caps.mesh_axes > len(mesh.axis_names):
                    raise ValueError(
                        f"{decomp} needs {caps.mesh_axes} mesh axes, "
                        f"mesh has {len(mesh.axis_names)}")
                if real and not caps.real:
                    raise ValueError(
                        f"{decomp} has no r2c/c2r schedules")
                # each candidate races over the axes the CALLER's plan
                # will actually use (the prefix it needs of them)
                cand_axes = tuple(axis_names if axis_names is not None
                                  else mesh.axis_names)[: caps.mesh_axes]
                if backend == MEASURE:
                    tuned = _autotune(
                        shape, direction, mesh, decomp, cand_axes,
                        real=real, batch_ndim=batch_ndim,
                        allow_reduced_wire=allow_reduced_wire,
                        wire_tol=wire_tol)
                else:
                    tuned = {"backend": backend,
                             "overlap_chunks": overlap_chunks,
                             "wire_dtype": wire_dtype}
                cand = FFTPlan(shape, direction, mesh, decomp, cand_axes,
                               tuned["backend"], tuned["overlap_chunks"],
                               real, batch_ndim,
                               _wire_name(tuned["wire_dtype"])).compile()
                args = _dummy_args(shape, direction, mesh, decomp,
                                   cand_axes, real, batch_ndim)
            except Exception as e:  # noqa: BLE001 — candidate unsupported
                err = f"{type(e).__name__}: {e}"
            # every process must agree the candidate built before ANY
            # of them enters the timed collectives, and that timing
            # succeeded everywhere after — see _sweep_ok
            if not _sweep_ok(err is None, span):
                skip(err or "candidate failed on another process")
                continue
            try:
                t = _time_plan(cand, args)
            except Exception as e:  # noqa: BLE001 — candidate unsupported
                err = f"{type(e).__name__}: {e}"
            if not _sweep_ok(err is None, span):
                skip(err or "timing failed on another process")
                continue
            if t < best_t:
                best, best_t = decomp, t
        if best is None:
            best = fallback
        # multi-process: every process of the mesh must cache the SAME
        # winner (see _agree_choice) — per-process timings are a vote
        agreed = _agree_choice([*candidates, fallback], best, span)
        # persist exactly the agreed winner: all ranks write identical
        # wisdom, and the next boot of this topology skips the sweep
        _wisdom_record("decomp", wkey, agreed)
        return agreed

    best, _ = _single_flight("decomp", _DECOMP_CACHE, dkey, _sweep)
    return best


def _autotune(shape, direction, mesh, decomp, axis_names, *, real,
              batch_ndim, allow_reduced_wire,
              wire_tol: float = 1e-2) -> dict:
    """Sweep the schedule variant space, return the fastest knob
    setting. Results cache per (shape, mesh, decomp, direction, real,
    batch) so only the first measure-plan pays the sweep; skipped
    variants land in ``autotune_skips()``.

    Compressed-wire candidates are additionally gated by an
    **error budget**: before a codec variant may be timed, the sweep
    executes it and the exact-wire reference on the same deterministic
    non-zero input (``_oracle_args``) and skips it with reason
    ``"wire-error-budget"`` when its max rel-err exceeds ``wire_tol``
    — a lossy wire may win on speed, never on accuracy it does not
    have. The measured error and the budget are recorded in the skip
    entry (and ``max_rel_err`` on nothing: in-budget candidates carry
    their error into the timed phase only)."""
    tkey = (shape, direction, _mesh_key(mesh), decomp, axis_names, real,
            batch_ndim, allow_reduced_wire, float(wire_tol))

    def _sweep() -> dict:
        fallback = {"backend": "auto", "overlap_chunks": 0,
                    "wire_dtype": None}
        span = _process_span(mesh)
        if _subset_span(span):
            # timing variants here would BE the subset-collectives hang
            # — pin the untimed default before any sweep work starts
            return fallback
        wkey = wisdom_mod.wisdom_key(
            "tune", mesh, shape=shape, direction=direction,
            decomp=decomp, axis_names=axis_names, real=real,
            batch_ndim=batch_ndim, allow_reduced_wire=allow_reduced_wire,
            wire_tol=float(wire_tol))
        hit = _wisdom_sweep_hit("tune", wkey, span, _tune_from_wisdom)
        if hit is not None:
            return hit
        err = None
        try:
            args = _dummy_args(shape, direction, mesh, decomp,
                               axis_names, real, batch_ndim)
        except Exception as e:  # noqa: BLE001 — per-process input failure
            err = f"{type(e).__name__}: {e}"
        # agreed BEFORE the variant loop: a process whose dummy input
        # failed must not escape to an outer control point while its
        # peers issue per-variant flag collectives below — the int32
        # flags would pair up across different control points and every
        # later agreement would exchange values with the wrong partners
        if not _sweep_ok(err is None, span):
            _record_skip({
                "shape": shape, "direction": direction, "decomp": decomp,
                "real": real, "batch_ndim": batch_ndim, "sweep": "knobs",
                "error": err or "dummy input failed on another process"})
            return fallback

        def _record_wire_skip(reason):
            _record_skip({
                "shape": shape, "direction": direction, "decomp": decomp,
                "real": real, "batch_ndim": batch_ndim,
                "sweep": "wire-profile", "wire_dtype": "per-stage",
                "error": reason})

        variants = _schedule_variants(
            shape, decomp, allow_reduced_wire=allow_reduced_wire,
            direction=direction, mesh=mesh, axis_names=axis_names,
            real=real, record_skip=_record_wire_skip)
        # error-budget oracle: the exact-wire reference output on a
        # deterministic non-zero input, built lazily at the first
        # codec candidate that survives its build gate. The candidate
        # list and every gate below are cluster-agreed, so all
        # processes build (or fail) the oracle at the same loop point.
        oracle = {"tried": False, "args": None, "want": None}

        def _oracle_ready() -> bool:
            if not oracle["tried"]:
                oracle["tried"] = True
                oerr = None
                try:
                    oracle["args"] = _oracle_args(
                        shape, direction, mesh, decomp, axis_names,
                        real, batch_ndim)
                    ref = FFTPlan(shape, direction, mesh, decomp,
                                  axis_names, real=real,
                                  batch_ndim=batch_ndim).compile()
                    oracle["want"] = ref.execute(*oracle["args"])
                    jax.block_until_ready(oracle["want"])
                except Exception as e:  # noqa: BLE001 — per-process
                    oerr = f"{type(e).__name__}: {e}"
                if not _sweep_ok(oerr is None, span):
                    oracle["want"] = None
            return oracle["want"] is not None

        best, best_t, best_plan = None, float("inf"), None
        for variant in variants:
            cand = FFTPlan(shape, direction, mesh, decomp, axis_names,
                           variant["backend"], variant["overlap_chunks"],
                           real, batch_ndim, variant["wire_dtype"])
            err, t = None, None
            try:  # build phase: schedule construction + overlap checks
                # — deterministic errors, no collectives executed yet
                cand.compile()
            except Exception as e:  # noqa: BLE001 — variant unsupported
                err = f"{type(e).__name__}: {e}"
            # same two sync points as the decomp sweep: agree the
            # variant built everywhere before any process enters its
            # timed collectives, and that timing succeeded everywhere
            if not _sweep_ok(err is None, span):
                _record_skip({
                    "shape": shape, "direction": direction,
                    "decomp": decomp, "real": real,
                    "batch_ndim": batch_ndim, **variant,
                    "error": err or "variant failed on another process"})
                continue
            if _wire_codec_variant(variant["wire_dtype"]):
                # the error-budget gate: a compressed wire must prove
                # itself within wire_tol of the exact oracle BEFORE it
                # is timed — never selected over budget (docs/wire.md)
                if not _oracle_ready():
                    _record_skip({
                        "shape": shape, "direction": direction,
                        "decomp": decomp, "real": real,
                        "batch_ndim": batch_ndim, **variant,
                        "error": "wire-oracle-unavailable"})
                    continue
                rel = None
                try:
                    rel = _max_rel_err(cand.execute(*oracle["args"]),
                                       oracle["want"])
                except Exception as e:  # noqa: BLE001 — cand collective
                    err = f"{type(e).__name__}: {e}"
                if not _sweep_ok(err is None, span):
                    _record_skip({
                        "shape": shape, "direction": direction,
                        "decomp": decomp, "real": real,
                        "batch_ndim": batch_ndim, **variant,
                        "error": err
                        or "wire oracle failed on another process"})
                    continue
                # rel is a reduction over replicated global arrays —
                # identical on every process, so this branch is too
                if rel > wire_tol:
                    _record_skip({
                        "shape": shape, "direction": direction,
                        "decomp": decomp, "real": real,
                        "batch_ndim": batch_ndim, **variant,
                        "error": "wire-error-budget",
                        "max_rel_err": rel, "wire_tol": wire_tol})
                    continue
            try:
                t = _time_plan(cand, args)
            except Exception as e:  # noqa: BLE001 — variant unsupported
                err = f"{type(e).__name__}: {e}"
            if not _sweep_ok(err is None, span):
                _record_skip({
                    "shape": shape, "direction": direction,
                    "decomp": decomp, "real": real,
                    "batch_ndim": batch_ndim, **variant,
                    "error": err or "timing failed on another process"})
                continue
            if t < best_t:
                best, best_t, best_plan = dict(variant), t, cand
        if best is None:
            best, best_plan = fallback, None
        # multi-process: knobs, like decomps, must agree across the
        # mesh's processes (see _agree_choice) or they compile
        # divergent programs
        agreed = _agree_choice([*variants, fallback], best, span)
        # persist exactly the agreed knobs (post-broadcast): all ranks
        # write identical wisdom for the next boot of this topology
        _wisdom_record("tune", wkey, agreed)
        if agreed == best and best_plan is not None:
            # the winner is already compiled and warm — seed the plan
            # cache so the follow-up plan_dft doesn't trace it again
            with _LOCK:
                _PLAN_CACHE.setdefault(
                    _plan_key(shape, direction, mesh, decomp, axis_names,
                              best["backend"], best["overlap_chunks"],
                              real, batch_ndim, best["wire_dtype"]),
                    best_plan)
        return agreed

    agreed, _ = _single_flight("tune", _TUNE_CACHE, tkey, _sweep)
    return agreed
