"""Stage-schedule FFT engine: distributed FFTs as data, not code.

Every distributed FFT in this repo is the same few moves in different
orders: local FFT passes along unsharded dims, ``all_to_all``
distribution exchanges, twiddle multiplies, and local index reorders.
Historically each decomposition hand-rolled its own ``shard_map`` body,
so every optimization (overlap pipelining, reduced-precision wire,
r2c) had to be re-implemented — or was missing — per decomposition.

Here a decomposition is a ``Schedule``: a list of *stages* plus the
input/output ``PartitionSpec`` tails, executed by ONE generic
``execute_schedule`` inside ``shard_map``. The stage IR:

* ``LocalFFT(axis, inverse, backend)``   — 1-D FFT along one local axis
* ``LocalRFFT(pad_to, backend)`` / ``LocalIRFFT(n, half, backend)`` —
  real (r2c / c2r) endcaps along the last axis; the half-spectrum is
  padded to ``pad_to`` (a multiple of the shard count) for the tiled
  all_to_all
* ``AllToAll(axis_name, split, concat, shards, wire_dtype,
  crosses_hosts, wire_codec)`` — the distribution exchange, with
  optional reduced-precision transport (e.g. ``"bfloat16"`` halves the
  dominant collective bytes; compute stays f32), optional *compressed*
  transport (``wire_codec`` names a ``wire.py`` codec: the payload is
  encoded — e.g. block-scaled int8 + f32 scales, ~3.6x fewer bytes —
  packed into ONE byte buffer, moved through a single tiled
  all_to_all, and unpacked + decoded on arrival; every codec carries a
  documented error bound the planner budget-checks) and a host-crossing annotation:
  ``build_schedule`` marks every exchange with whether its mesh axis
  spans processes (DCN) or stays on one host (ICI) —
  ``exchange_topology`` summarizes a schedule's wire profile and the
  planner sweeps decompositions per topology (``decomp="measure"``)
* ``Twiddle(axis, axis_name, shards, sign)`` — the four-step
  inter-shard twiddle ``exp(sign·2πi·p·k/N)``, ``p`` = shard index
* ``Reorder(op, axis[, parts])`` — named local index reorders
  (``expand`` / ``merge`` / ``fold_T`` / ``unfold_T``), kept as data so
  schedules stay hashable and comparable

All stage axes are NEGATIVE (counted from the trailing transform
dims), so any leading dims are batch for free: one schedule serves
unbatched and batched plans alike.

**Overlap (compute/communication pipelining)** is a property of the
*executor*, not of any one schedule: ``execute_schedule(...,
overlap_chunks=C)`` splits everything up to and including the first
``AllToAll`` into C chunks along that exchange's concat axis, so chunk
i's local FFT overlaps chunk i-1's collective (the dependency slack
XLA async collectives need). It applies to every schedule whose
pre-exchange stages don't transform the chunk axis — slab 2-D/3-D,
pencil, transpose-free pencil, and the r2c/c2r paths, batched or not.
``overlap_site`` validates eligibility statically and raises
``ValueError`` otherwise (the four-step exchange concatenates onto a
singleton axis, so it is ineligible; the planner's autotuner records
such skips).

Builders for the six stock decompositions live here
(``slab_2d/slab_3d/pencil_3d/pencil_tf_3d/pencil_2d/fourstep_1d``);
the r2c/c2r builders live in ``rfft.py`` (they own the half-spectrum
arithmetic) and cover every decomposition but the 1-D four-step —
``RFFT_BUILDERS`` there mirrors ``_BUILDERS`` here. ``build_schedule``
dispatches by decomposition name and is what ``plan.py`` compiles.
Adding a decomposition = writing one ~20-line builder and registering
its ``Caps``; overlap, wire casting, batching, and the planner sweep
come for free.

``pencil_2d`` is the 2-axis decomposition of 2-D grids: input tiled
``P(a0, a1)`` over BOTH mesh axes (the natural layout of a 2-D
domain-decomposed simulation), output ``P(None, (a1, a0))`` in natural
frequency order — three small exchanges instead of the slab's one
P0-way exchange, each over a single mesh axis, so on a DCN×ICI mesh
only the ``a0`` rotation crosses hosts.

Transpose-free pencil (after Chatterjee & Verma, arXiv:1406.5597): the
second full distribution transpose of the standard pencil schedule is
replaced by a four-step-style exchange along the still-sharded first
grid axis, so the output stays x-sharded in a *documented* permuted
layout: position ``g'`` along axis 0 holds bin
``fourstep_freq_of_position(N0, P0)[g']`` (see ``distributed.py`` for
the maps; the input's axis 0 must be in cyclic order, exactly like
``fourstep_fft_1d``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import axis_crosses_processes, shard_map
from repro.core.fft import wire as wire_mod
from repro.core.fft.dft import cmul, fft_along, local_fft

# A wire spec entry is a dtype NAME ("bfloat16"), a wire CODEC name
# ("int8", "int8_block64", "bf16" — see wire.py), or None (exact).
WireSpec = Union[None, str, Tuple[Optional[str], ...]]


# ---------------------------------------------------------------------------
# Stage IR
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LocalFFT:
    """1-D FFT along one (negative) local axis."""
    axis: int
    inverse: bool = False
    backend: str = "auto"

    def apply(self, state):
        re, im = state
        return fft_along(re, im, self.axis, inverse=self.inverse,
                         backend=self.backend)


@dataclasses.dataclass(frozen=True)
class LocalRFFT:
    """r2c endcap: real field → padded half-spectrum pair (last axis).

    ``backend="jnp"`` is XLA's ``rfft``. Every other backend runs the
    plan's local c2c FFT on the field (zero imaginary part) and keeps
    bins 0..n/2, so both endcaps take the DFT path of the plan's
    ``LocalFFT`` stages."""
    pad_to: int
    backend: str = "auto"

    def apply(self, state):
        (x,) = state
        x = x.astype(jnp.float32)
        if self.backend == "jnp":
            z = jnp.fft.rfft(x, axis=-1)
            re = jnp.real(z).astype(jnp.float32)
            im = jnp.imag(z).astype(jnp.float32)
        else:
            half = x.shape[-1] // 2 + 1
            re, im = local_fft(x, jnp.zeros_like(x), backend=self.backend)
            re, im = re[..., :half], im[..., :half]
        pad = [(0, 0)] * (x.ndim - 1) + [(0, self.pad_to - re.shape[-1])]
        return jnp.pad(re, pad), jnp.pad(im, pad)


@dataclasses.dataclass(frozen=True)
class LocalIRFFT:
    """c2r endcap: padded half-spectrum pair → real field of extent n.

    ``backend="jnp"`` is XLA's ``irfft``. Every other backend rebuilds
    the Hermitian full spectrum (bins half..n-1 are the conjugates of
    bins n-half..1) and keeps the real part of the plan's local inverse
    c2c FFT; like ``irfft`` it ignores the imaginary parts of the DC
    and Nyquist bins, which only add an imaginary term.

    Both run on a 2-D (rows, bins) view: on a TPU v5e, either endcap
    fed a 3-D half-spectrum of more than 128 MiB can come out about
    0.34 off in relative L2 (at 512³, depending on the program around
    it), while the same data as 2-D rows is always right."""
    n: int
    half: int
    backend: str = "auto"

    def apply(self, state):
        re, im = state
        lead = re.shape[:-1]
        zr = re[..., : self.half].reshape(-1, self.half)
        zi = im[..., : self.half].reshape(-1, self.half)
        if self.backend == "jnp":
            x = jnp.fft.irfft(zr + 1j * zi, n=self.n, axis=-1)
        else:
            m = self.n - self.half
            fr = jnp.concatenate([zr, zr[:, m:0:-1]], axis=-1)
            fi = jnp.concatenate([zi, -zi[:, m:0:-1]], axis=-1)
            x = local_fft(fr, fi, inverse=True, backend=self.backend)[0]
        return (x.astype(jnp.float32).reshape(lead + (self.n,)),)


@dataclasses.dataclass(frozen=True)
class AllToAll:
    """Tiled all_to_all over one mesh axis, optional reduced wire.

    ``crosses_hosts`` annotates whether this exchange's device ring
    spans more than one process — DCN wire, not ICI. It is *metadata*
    (filled in by ``annotate_topology`` from device placement; None =
    unknown, e.g. a hand-built schedule): execution is identical either
    way, but the planner records it and the autotuner's decomposition
    sweep exists because of it — the slab/pencil tradeoff inverts once
    the exchange crosses hosts (Verma et al., arXiv:2202.12756).
    """
    axis_name: str
    split: int
    concat: int
    shards: int
    wire_dtype: Optional[str] = None        # dtype NAME (hashable)
    crosses_hosts: Optional[bool] = None    # None = not annotated
    wire_codec: Optional[str] = None        # codec NAME (wire.py)

    def __post_init__(self):
        # builders pass one wire spec entry positionally as wire_dtype;
        # codec names ("int8", "int8_block64", "bf16") reroute to the
        # codec slot so the two lossy paths stay distinct downstream
        if self.wire_dtype is not None and self.wire_codec is None \
                and wire_mod.is_codec(self.wire_dtype):
            object.__setattr__(self, "wire_codec", self.wire_dtype)
            object.__setattr__(self, "wire_dtype", None)

    def _one(self, x):
        s, c = self.split % x.ndim, self.concat % x.ndim
        if self.wire_codec is not None:
            codec = wire_mod.get_codec(self.wire_codec)
            parts = codec.encode_wire(x)
            if len(parts) == 1:
                moved = (jax.lax.all_to_all(
                    parts[0], self.axis_name, split_axis=s,
                    concat_axis=c, tiled=True),)
            else:
                # Payload and scales ride ONE packed collective: as
                # separate all_to_alls their differing message sizes
                # can cross-pair on the CPU gloo transport when XLA
                # schedules them concurrently (flaky preamble-length
                # aborts), and one collective is one message of wire
                # latency anyway.
                last = parts[0].ndim - 1
                packed, meta = wire_mod.pack_wire(
                    parts, self.shards, split_last=(s == last),
                    concat_last=(c == last))
                movedp = jax.lax.all_to_all(
                    packed, self.axis_name, split_axis=s, concat_axis=c,
                    tiled=True)
                moved = wire_mod.unpack_wire(movedp, meta)
            return codec.decode(moved, x.dtype)
        wd = None if self.wire_dtype is None else jnp.dtype(self.wire_dtype)
        if wd is not None and x.dtype != wd:
            y = jax.lax.all_to_all(x.astype(wd), self.axis_name,
                                   split_axis=s, concat_axis=c, tiled=True)
            return y.astype(x.dtype)
        return jax.lax.all_to_all(x, self.axis_name, split_axis=s,
                                  concat_axis=c, tiled=True)

    def apply(self, state):
        return tuple(self._one(x) for x in state)


@dataclasses.dataclass(frozen=True)
class Twiddle:
    """Inter-shard four-step twiddle exp(sign·2πi·p·k/N) along ``axis``;
    N = shards · local extent, p = this shard's index on ``axis_name``."""
    axis: int
    axis_name: str
    shards: int
    sign: float

    def apply(self, state):
        re, im = state
        ax = self.axis % re.ndim
        m = re.shape[ax]
        total = m * self.shards
        p = jax.lax.axis_index(self.axis_name).astype(jnp.float32)
        k = jnp.arange(m, dtype=jnp.float32)
        ang = self.sign * 2.0 * math.pi * p * k / total
        bshape = [1] * re.ndim
        bshape[ax] = m
        tr = jnp.cos(ang).reshape(bshape)
        ti = jnp.sin(ang).reshape(bshape)
        return cmul(re, im, tr, ti)


@dataclasses.dataclass(frozen=True)
class Reorder:
    """Named local index reorder.

    op ∈ {"expand", "merge", "fold_T", "unfold_T"}:
      expand    — insert a singleton at ``axis`` (jnp.expand_dims)
      merge     — merge axes (axis, axis+1) row-major
      fold_T    — swap (axis, axis+1) then merge: the four-step's
                  column-major output flatten
      unfold_T  — split ``axis`` into (n/parts, parts) then swap →
                  (parts, n/parts): fold_T's exact inverse
    """
    op: str
    axis: int
    parts: int = 0

    def _one(self, x):
        if self.op == "expand":
            return jnp.expand_dims(x, self.axis)
        ax = self.axis % x.ndim
        if self.op == "merge":
            return x.reshape(x.shape[:ax]
                             + (x.shape[ax] * x.shape[ax + 1],)
                             + x.shape[ax + 2:])
        if self.op == "fold_T":
            y = jnp.swapaxes(x, ax, ax + 1)
            return y.reshape(y.shape[:ax]
                             + (y.shape[ax] * y.shape[ax + 1],)
                             + y.shape[ax + 2:])
        if self.op == "unfold_T":
            m = x.shape[ax]
            y = x.reshape(x.shape[:ax] + (m // self.parts, self.parts)
                          + x.shape[ax + 1:])
            return jnp.swapaxes(y, ax, ax + 1)
        raise ValueError(self.op)

    def apply(self, state):
        return tuple(self._one(x) for x in state)


# ---------------------------------------------------------------------------
# Schedule
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Schedule:
    """A distributed transform as data: stages + sharding contract.

    ``in_spec``/``out_spec`` are PartitionSpec *tails* over the
    transform dims (entries: mesh axis name or None); the executor
    prepends replicated batch dims. ``in_arity``/``out_arity`` count
    the arrays flowing in/out (2 = split (re, im) pair, 1 = real
    field)."""
    name: str
    rank: int
    stages: Tuple
    in_spec: Tuple
    out_spec: Tuple
    in_arity: int = 2
    out_arity: int = 2


@dataclasses.dataclass(frozen=True)
class Caps:
    """Planner-visible capabilities of one decomposition's schedules."""
    rank: int
    mesh_axes: int
    overlap: bool = True          # eligible for chunked overlap
    wire: bool = True             # a2a wire dtype is a tunable knob
    real: bool = False            # has r2c/c2r builders in rfft.py


def _bspec(nb: int, *tail) -> P:
    return P(*((None,) * nb), *tail)


def _wire_entry(w) -> Optional[str]:
    """Normalize ONE wire spec entry: None, a codec name (verbatim —
    see ``wire.py``), or a dtype name canonicalized via ``jnp.dtype``."""
    if w is None:
        return None
    if wire_mod.is_codec(w):
        return w
    return jnp.dtype(w).name


def _wire_tuple(wire_dtype: WireSpec, n_a2a: int
                ) -> Tuple[Optional[str], ...]:
    """Normalize a wire spec to one dtype/codec NAME per AllToAll stage.

    Accepts None (exact everywhere), a single dtype/codec name (applied
    to every exchange), or a tuple with one entry per exchange
    (per-stage wire: e.g. compress only the host-crossing rotation of a
    pencil)."""
    if isinstance(wire_dtype, tuple):
        if len(wire_dtype) != n_a2a:
            raise ValueError(
                f"wire_dtype tuple has {len(wire_dtype)} entries for "
                f"{n_a2a} all_to_all stages")
        return tuple(_wire_entry(w) for w in wire_dtype)
    return (_wire_entry(wire_dtype),) * n_a2a


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

def overlap_site(sched: Schedule) -> Tuple[int, int]:
    """Validate + locate the overlap point: (index of the first
    AllToAll, chunk axis = its concat axis). Raises ValueError when the
    schedule is ineligible (no exchange, degenerate concat axis, or a
    pre-exchange stage transforms/reshapes the chunk axis)."""
    for k, st in enumerate(sched.stages):
        if isinstance(st, AllToAll):
            break
    else:
        raise ValueError(f"{sched.name}: no all_to_all stage to overlap")
    t, s = st.concat, st.split
    if t == s:
        raise ValueError(f"{sched.name}: degenerate exchange axes")
    for pre in sched.stages[:k]:
        if isinstance(pre, (LocalFFT, Twiddle)):
            if pre.axis == t:
                raise ValueError(
                    f"{sched.name}: pre-exchange stage transforms the "
                    f"chunk axis {t}")
        elif isinstance(pre, (LocalRFFT, LocalIRFFT)):
            if t == -1:
                raise ValueError(
                    f"{sched.name}: real endcap owns the chunk axis")
        else:
            raise ValueError(
                f"{sched.name}: overlap unsupported across "
                f"{type(pre).__name__} stages")
    return k, t


def _apply(i: int, stage, state):
    """Stage ``i`` of a schedule under its ``named_scope`` in traces,
    ``<i>.<StageClass>`` (``0.LocalRFFT``, ``1.AllToAll``, ...)."""
    with jax.named_scope(f"{i}.{type(stage).__name__}"):
        return stage.apply(state)


def _run_overlap(sched: Schedule, state, k: int, t: int, chunks: int):
    """Chunked pipeline: stages[:k+1] per chunk along axis t, then
    un-interleave and run the rest. The unchunked all_to_all orders the
    concat axis (shard, chunk, row); per-chunk exchanges concatenate as
    (chunk, shard, row) — one reshape/swap restores the exact unchunked
    result, so overlap is bit-compatible with the plain executor."""
    a2a = sched.stages[k]
    ext = state[0].shape[t]
    if ext % chunks:
        raise ValueError(
            f"{sched.name}: overlap axis extent {ext} not divisible by "
            f"chunks={chunks}")
    c = ext // chunks
    tpos = t % state[0].ndim
    parts = []
    for j in range(chunks):
        sub = tuple(jax.lax.slice_in_dim(x, j * c, (j + 1) * c, axis=tpos)
                    for x in state)
        for i, st in enumerate(sched.stages[: k + 1]):
            sub = _apply(i, st, sub)
        parts.append(sub)
    arity = len(parts[0])
    state = tuple(jnp.concatenate([p[i] for p in parts], axis=t)
                  for i in range(arity))

    pn = a2a.shards

    def fix(x):
        ax = t % x.ndim
        shp = x.shape
        y = x.reshape(shp[:ax] + (chunks, pn, c) + shp[ax + 1:])
        y = jnp.swapaxes(y, ax, ax + 1)
        return y.reshape(shp)

    state = tuple(fix(x) for x in state)
    for i, st in enumerate(sched.stages[k + 1:], start=k + 1):
        state = _apply(i, st, state)
    return state


def execute_schedule(sched: Schedule, mesh: Mesh, *arrays,
                     overlap_chunks: int = 0):
    """Run any schedule inside shard_map. Leading dims beyond
    ``sched.rank`` are batch (replicated in the specs). With
    ``overlap_chunks > 1`` the first exchange pipelines against the
    local stages before it — for every eligible schedule, batched and
    real included."""
    if len(arrays) != sched.in_arity:
        raise ValueError(f"{sched.name}: expected {sched.in_arity} "
                         f"arrays, got {len(arrays)}")
    nb = arrays[0].ndim - sched.rank
    if nb < 0:
        raise ValueError(f"rank-{arrays[0].ndim} input for a "
                         f"rank-{sched.rank} transform")
    in_spec = _bspec(nb, *sched.in_spec)
    out_spec = _bspec(nb, *sched.out_spec)
    chunks = int(overlap_chunks or 0)
    site = overlap_site(sched) if chunks > 1 else None

    def body(*arrs):
        state = tuple(arrs)
        if site is not None:
            state = _run_overlap(sched, state, site[0], site[1], chunks)
        else:
            for i, st in enumerate(sched.stages):
                state = _apply(i, st, state)
        return state if len(state) > 1 else state[0]

    in_specs = (in_spec,) * sched.in_arity
    out_specs = (out_spec,) * sched.out_arity \
        if sched.out_arity > 1 else out_spec
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs)(*arrays)


# ---------------------------------------------------------------------------
# Builders — complex (c2c) decompositions
# ---------------------------------------------------------------------------

def slab_2d(mesh: Mesh, axis_name: str = "data", *, inverse: bool = False,
            backend: str = "auto", wire_dtype: WireSpec = None) -> Schedule:
    """FFTW-MPI's slab: local FFT, one exchange, local FFT.
    forward P(ax, None) → P(None, ax); inverse mirrors."""
    pn = mesh.shape[axis_name]
    (w,) = _wire_tuple(wire_dtype, 1)
    if inverse:
        stages = (LocalFFT(-2, True, backend),
                  AllToAll(axis_name, -2, -1, pn, w),
                  LocalFFT(-1, True, backend))
        return Schedule("slab2d_inv", 2, stages,
                        (None, axis_name), (axis_name, None))
    stages = (LocalFFT(-1, False, backend),
              AllToAll(axis_name, -1, -2, pn, w),
              LocalFFT(-2, False, backend))
    return Schedule("slab2d", 2, stages,
                    (axis_name, None), (None, axis_name))


def slab_3d(mesh: Mesh, axis_name: str = "data", *, inverse: bool = False,
            backend: str = "auto", wire_dtype: WireSpec = None) -> Schedule:
    """3-D slab on ONE mesh axis: three local passes, one exchange —
    3-D grids without a 2-axis mesh.
    forward P(ax, None, None) → P(None, ax, None); inverse mirrors."""
    pn = mesh.shape[axis_name]
    (w,) = _wire_tuple(wire_dtype, 1)
    if inverse:
        stages = (LocalFFT(-3, True, backend),
                  AllToAll(axis_name, -3, -2, pn, w),
                  LocalFFT(-2, True, backend),
                  LocalFFT(-1, True, backend))
        return Schedule("slab3d_inv", 3, stages,
                        (None, axis_name, None), (axis_name, None, None))
    stages = (LocalFFT(-1, False, backend),
              LocalFFT(-2, False, backend),
              AllToAll(axis_name, -2, -3, pn, w),
              LocalFFT(-3, False, backend))
    return Schedule("slab3d", 3, stages,
                    (axis_name, None, None), (None, axis_name, None))


def pencil_3d(mesh: Mesh, axes: Tuple[str, str] = ("data", "model"), *,
              inverse: bool = False, backend: str = "auto",
              wire_dtype: WireSpec = None) -> Schedule:
    """Standard pencil: three local passes, two full rotations.
    forward P(a0, a1, None) → P(None, a0, a1); inverse mirrors."""
    a0, a1 = axes
    p0, p1 = mesh.shape[a0], mesh.shape[a1]
    w0, w1 = _wire_tuple(wire_dtype, 2)
    if inverse:
        stages = (LocalFFT(-3, True, backend),
                  AllToAll(a0, -3, -2, p0, w0),
                  LocalFFT(-2, True, backend),
                  AllToAll(a1, -2, -1, p1, w1),
                  LocalFFT(-1, True, backend))
        return Schedule("pencil_inv", 3, stages,
                        (None, a0, a1), (a0, a1, None))
    stages = (LocalFFT(-1, False, backend),
              AllToAll(a1, -1, -2, p1, w0),
              LocalFFT(-2, False, backend),
              AllToAll(a0, -2, -3, p0, w1),
              LocalFFT(-3, False, backend))
    return Schedule("pencil", 3, stages,
                    (a0, a1, None), (None, a0, a1))


def pencil_tf_3d(mesh: Mesh, axes: Tuple[str, str] = ("data", "model"), *,
                 inverse: bool = False, backend: str = "auto",
                 wire_dtype: WireSpec = None) -> Schedule:
    """Transpose-free pencil (Chatterjee-Verma-style): the second full
    rotation is replaced by a four-step exchange along the still-sharded
    first grid axis.

    forward: input x[n0, n1, n2] P(a0, a1, None), **axis 0 in cyclic
    order over a0** (global element g = m·P0 + p on shard p, exactly
    ``fourstep_fft_1d``'s contract; ``distributed.cyclic_order`` builds
    it) → output P(a0, None, a1) where position g' along axis 0 holds
    bin ``fourstep_freq_of_position(n0, P0)[g']`` and axes 1, 2 are in
    natural frequency order. Requires P0 | (n0 / P0). The x-axis
    sharding never moves — that is the "transpose-free" part; only
    M0/P0-deep bricks travel in the second exchange's four-step pattern.
    inverse: exact mirror, back to the cyclic spatial layout."""
    a0, a1 = axes
    p0, p1 = mesh.shape[a0], mesh.shape[a1]
    wa, wb = _wire_tuple(wire_dtype, 2)
    if inverse:
        stages = (Reorder("unfold_T", -3, p0),       # x: (M0)→(P0, M0/P0)
                  LocalFFT(-4, True, backend),       # length-P0 pass
                  AllToAll(a0, -4, -3, p0, wa),      # → (1, M0, ...)
                  Reorder("merge", -4),
                  Twiddle(-3, a0, p0, +1.0),
                  LocalFFT(-3, True, backend),       # x local
                  LocalFFT(-2, True, backend),       # y
                  AllToAll(a1, -2, -1, p1, wb),      # y ↔ z rotation
                  LocalFFT(-1, True, backend))       # z
        return Schedule("pencil_tf_inv", 3, stages,
                        (a0, None, a1), (a0, a1, None))
    stages = (LocalFFT(-1, False, backend),          # z
              AllToAll(a1, -1, -2, p1, wa),          # z ↔ y rotation
              LocalFFT(-2, False, backend),          # y
              LocalFFT(-3, False, backend),          # x local (cyclic)
              Twiddle(-3, a0, p0, -1.0),
              Reorder("expand", -4),
              AllToAll(a0, -3, -4, p0, wb),          # four-step exchange
              LocalFFT(-4, False, backend),          # length-P0 pass
              Reorder("fold_T", -4))                 # column-major flatten
    return Schedule("pencil_tf", 3, stages,
                    (a0, a1, None), (a0, None, a1))


def pencil_2d(mesh: Mesh, axes: Tuple[str, str] = ("data", "model"), *,
              inverse: bool = False, backend: str = "auto",
              wire_dtype: WireSpec = None) -> Schedule:
    """2-axis decomposition of 2-D grids over 2-D meshes — huge 2-D
    grids stop being stuck with the P0-way slab: the input is tiled
    P(a0, a1) (the natural layout of a 2-D domain-decomposed
    simulation) and all P0·P1 devices participate.

    forward: gather axis 1 over a1 (axis 0 picks up a1 as its minor
    sharding factor), FFT it, scatter the frequency axis back over a1,
    then one rotation over a0 gathers axis 0 and scatters k1's minor
    factor — P(a0, a1) → P(None, (a1, a0)), both frequency axes in
    natural order. Three exchanges, but each moves only the 1/(P0·P1)
    local tile, and they split across the two mesh axes: on a DCN×ICI
    mesh only the a0 rotation crosses hosts, which is exactly what the
    per-stage wire sweep keys on. Requires P0·P1 | N0 and P0·P1 | N1.
    inverse mirrors."""
    a0, a1 = axes
    p0, p1 = mesh.shape[a0], mesh.shape[a1]
    w0, w1, w2 = _wire_tuple(wire_dtype, 3)
    if inverse:
        stages = (LocalFFT(-2, True, backend),
                  AllToAll(a0, -2, -1, p0, w0),   # undo the k0 gather
                  AllToAll(a1, -2, -1, p1, w1),   # regroup axis 1
                  LocalFFT(-1, True, backend),
                  AllToAll(a1, -1, -2, p1, w2))   # re-scatter axis 1
        return Schedule("pencil2d_inv", 2, stages,
                        (None, (a1, a0)), (a0, a1))
    stages = (AllToAll(a1, -2, -1, p1, w0),       # gather axis 1 locally
              LocalFFT(-1, False, backend),
              AllToAll(a1, -1, -2, p1, w1),       # scatter k1 over a1
              AllToAll(a0, -1, -2, p0, w2),       # gather axis 0 / split k1
              LocalFFT(-2, False, backend))
    return Schedule("pencil2d", 2, stages,
                    (a0, a1), (None, (a1, a0)))


def fourstep_1d(mesh: Mesh, axis_name: str = "data", *,
                inverse: bool = False, backend: str = "auto",
                wire_dtype: WireSpec = None) -> Schedule:
    """Bailey's four-step across the mesh: cyclic input layout, output
    in transposed digit order (``fourstep_freq_of_position``)."""
    pn = mesh.shape[axis_name]
    (w,) = _wire_tuple(wire_dtype, 1)
    if inverse:
        stages = (Reorder("unfold_T", -1, pn),
                  LocalFFT(-2, True, backend),
                  AllToAll(axis_name, -2, -1, pn, w),
                  Reorder("merge", -2),
                  Twiddle(-1, axis_name, pn, +1.0),
                  LocalFFT(-1, True, backend))
        return Schedule("fourstep1d_inv", 1, stages,
                        (axis_name,), (axis_name,))
    stages = (LocalFFT(-1, False, backend),
              Twiddle(-1, axis_name, pn, -1.0),
              Reorder("expand", -2),
              AllToAll(axis_name, -1, -2, pn, w),
              LocalFFT(-2, False, backend),
              Reorder("fold_T", -2))
    return Schedule("fourstep1d", 1, stages, (axis_name,), (axis_name,))


# ---------------------------------------------------------------------------
# Registry — what the planner sweeps
# ---------------------------------------------------------------------------

CAPS = {
    "slab":       Caps(rank=2, mesh_axes=1, overlap=True, wire=True,
                       real=True),
    "slab3d":     Caps(rank=3, mesh_axes=1, overlap=True, wire=True,
                       real=True),
    "pencil":     Caps(rank=3, mesh_axes=2, overlap=True, wire=True,
                       real=True),
    "pencil_tf":  Caps(rank=3, mesh_axes=2, overlap=True, wire=True,
                       real=True),
    "pencil2d":   Caps(rank=2, mesh_axes=2, overlap=True, wire=True,
                       real=True),
    "fourstep1d": Caps(rank=1, mesh_axes=1, overlap=False, wire=True),
}

_BUILDERS = {
    "slab": slab_2d,
    "slab3d": slab_3d,
    "pencil": pencil_3d,
    "pencil_tf": pencil_tf_3d,
    "pencil2d": pencil_2d,
    "fourstep1d": fourstep_1d,
}


def annotate_topology(sched: Schedule, mesh: Mesh) -> Schedule:
    """Fill each ``AllToAll``'s ``crosses_hosts`` from ``mesh``'s
    device placement. Purely metadata — the annotated schedule runs
    identically — but it is what `exchange_topology` reports and what
    the planner's per-topology decomposition sweep keys off."""
    stages = tuple(
        dataclasses.replace(
            st, crosses_hosts=axis_crosses_processes(mesh, st.axis_name))
        if isinstance(st, AllToAll) else st
        for st in sched.stages)
    return dataclasses.replace(sched, stages=stages)


def exchange_topology(sched: Schedule) -> Tuple[dict, ...]:
    """One summary dict per ``AllToAll`` stage, in execution order:
    ``{axis_name, shards, wire_dtype, crosses_hosts}``. The
    host-crossing flags are the schedule's *wire profile* — e.g. a
    pencil whose first rotation stays on-host but whose second crosses
    DCN reads ``(False, True)``. ``wire_codec`` is the compressed-wire
    codec name when the stage encodes (wire.py), else None. See
    ``docs/multihost.md`` for how to read these when choosing a
    decomposition."""
    return tuple({"axis_name": st.axis_name, "shards": st.shards,
                  "wire_dtype": st.wire_dtype,
                  "wire_codec": st.wire_codec,
                  "crosses_hosts": st.crosses_hosts}
                 for st in sched.stages if isinstance(st, AllToAll))


def build_schedule(decomp: str, shape: Tuple[int, ...], mesh: Mesh,
                   axis_names: Tuple[str, ...], *, inverse: bool = False,
                   backend: str = "auto", wire_dtype: WireSpec = None,
                   real: bool = False) -> Schedule:
    """One entry point from (decomp, knobs) to a runnable Schedule —
    the planner's unit of sweeping. Every schedule built here comes
    back topology-annotated (``AllToAll.crosses_hosts`` filled from
    the mesh's device placement)."""
    caps = CAPS.get(decomp)
    if caps is None:
        raise ValueError(f"unknown decomposition {decomp!r}; "
                         f"known: {sorted(CAPS)}")
    if len(shape) != caps.rank:
        raise ValueError(f"{decomp} transforms rank-{caps.rank} grids, "
                         f"got shape {shape}")
    if real:
        if not caps.real:
            raise ValueError(
                f"real (r2c/c2r) plans support "
                f"{sorted(k for k, c in CAPS.items() if c.real)}, "
                f"not {decomp!r}")
        from repro.core.fft import rfft as rfft_mod
        build_r, naxes = rfft_mod.RFFT_BUILDERS[decomp]
        if naxes == 2:
            sched = build_r(shape[-1], mesh, tuple(axis_names[:2]),
                            inverse=inverse, backend=backend,
                            wire_dtype=wire_dtype)
        else:
            sched = build_r(shape[-1], mesh, axis_names[0],
                            inverse=inverse, backend=backend,
                            wire_dtype=wire_dtype)
        return annotate_topology(sched, mesh)
    build = _BUILDERS[decomp]
    if caps.mesh_axes == 2:
        sched = build(mesh, tuple(axis_names[:2]), inverse=inverse,
                      backend=backend, wire_dtype=wire_dtype)
    else:
        sched = build(mesh, axis_names[0], inverse=inverse,
                      backend=backend, wire_dtype=wire_dtype)
    return annotate_topology(sched, mesh)
