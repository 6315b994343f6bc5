"""Shared machinery for plan-cache-driven pseudo-spectral solvers:
state management, stepping, Parseval diagnostics, spectrum payloads for
the in-situ chain, and checkpoint/restart via ``ckpt/checkpoint.py``.

Subclasses provide ``_nonlinear(state)`` (the dealiased nonlinear RHS
tree) and a ``_decay_tree`` (per-leaf ``λ = -ν|k|²`` arrays); everything
else — RK4 vs integrating-factor stepping, energy sums, restart — lives
here once, which is what lets the 3-D Boussinesq system reuse the 2-D
vorticity solver's stepper verbatim.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.ckpt import checkpoint
from repro.core.fft.spectrum import radial_spectrum_k
from repro.core.solver.spectral import SpectralBasis
from repro.core.solver.stepper import exp_factors, ifrk4_step, rk4_step

STEPPERS = ("rk4", "if_rk4")


def _parseval_rows(re, im, weights, extra):
    """Float32 row partials of w·|ŝ|²(·extra), summed over the last
    (half-spectrum) axis. Elementwise products and a ``sum``, never a
    dot: a TPU matmul at default precision runs in bf16 passes."""
    with jax.named_scope("solver.parseval"):
        p = (re * re + im * im) * weights
        if extra is not None:
            p = p * extra
        return jnp.sum(p, axis=-1)


class SpectralSolverBase:
    """A time-stepping loop over a spectral state pytree.

    ``state`` leaves are (re, im) float32 arrays in the basis' spectral
    layout; subclasses initialize it (and may re-initialize freely —
    plans are cached process-wide, so a fresh solver on the same grid
    and mesh re-uses the compiled transforms)."""

    def __init__(self, basis: SpectralBasis, *, dt: float,
                 stepper: str = "if_rk4"):
        assert stepper in STEPPERS, f"stepper must be one of {STEPPERS}"
        self.basis = basis
        self.dt = float(dt)
        self.stepper = stepper
        self.t = 0.0
        self.step_count = 0
        self.state = None
        self._decay_tree = None    # subclass sets, then calls _finalize_setup
        self._e_half = None
        self._e_full = None

    def _finalize_setup(self) -> None:
        """Place the stepper constants. ``_decay_tree`` leaves arrive
        as HOST numpy; everything the stepper's eager tree algebra
        touches is placed globally-replicated so no eager op ever
        mixes a process-local array with sharded state (see
        ``SpectralBasis.replicated``)."""
        rep = self.basis.replicated
        self._decay_dev = jax.tree_util.tree_map(rep, self._decay_tree)
        if self.stepper == "if_rk4":
            self._e_half, self._e_full = exp_factors(self._decay_tree,
                                                     self.dt, place=rep)
        # ONE compiled computation per step: the four RHS stages (their
        # plan executes inline under the outer trace) plus every piece
        # of tree algebra. Eagerly-dispatched glue between plan
        # executes is not just slower — in multi-process runs the
        # per-op dispatch streams of different processes drift apart
        # and their exchange rendezvous interleave (observed deadlock
        # on the CPU backend). A single computation per step cannot
        # interleave with itself.
        self._step_fn = jax.jit(self._step_scoped)
        # the tables (weights, inv_k2) are arguments, not closed-over
        # constants the compiler would have to embed
        self._rows_fn = jax.jit(_parseval_rows, out_shardings=NamedSharding(
            self.basis.mesh, PartitionSpec()))

    def _step_scoped(self, state):
        """One step under the ``solver.step`` scope in traces."""
        with jax.named_scope("solver.step"):
            if self.stepper == "rk4":
                return rk4_step(self._rhs_full, state, self.dt)
            return ifrk4_step(self._nonlinear_scoped, state, self.dt,
                              self._e_half, self._e_full)

    def _nonlinear_scoped(self, state):
        with jax.named_scope("solver.nonlinear"):
            return self._nonlinear(state)

    # -- subclass hooks ------------------------------------------------------
    def _nonlinear(self, state):
        raise NotImplementedError

    def _rhs_full(self, state):
        n = self._nonlinear_scoped(state)
        return jax.tree_util.tree_map(
            lambda ni, lam, si: ni + lam * si, n, self._decay_dev, state)

    # -- stepping ------------------------------------------------------------
    def step(self, n: int = 1) -> None:
        assert self.state is not None, "initialize the solver state first"
        for _ in range(n):
            self.state = self._step_fn(self.state)
            self.step_count += 1
            # derived, not accumulated: t must survive a checkpoint
            # round-trip exactly (restore recomputes it from the step)
            self.t = self.step_count * self.dt

    # -- Parseval diagnostics ------------------------------------------------
    # The products and the sums within a row (at most ``hp`` terms) run
    # on the device in float32; only the vector of row partials comes
    # back. It is replicated, so every process reads the same bits from
    # its own device and finishes the same float64 sum over them in the
    # same order: identical on every process, the agreement contract
    # the in-situ monitoring path relies on.
    def _weighted_sum(self, pair, extra=None) -> float:
        """0.5·Σ w·|ŝ|²/N² (+optional extra per-mode factor) — the
        Parseval mean-square of the real field, Hermitian-corrected."""
        b = self.basis
        span = jax.profiler.TraceAnnotation
        # wait for the steps in flight first, so the sum's span times
        # the reduction and its copy alone
        with span("solver.wait_device", step=self.step_count):
            jax.block_until_ready(pair)
        with span("solver.parseval_sum", step=self.step_count) as psum:
            rows = self._rows_fn(pair[0], pair[1], b.weights, extra)
            rows = np.asarray(rows.addressable_data(0))
            psum.set_metadata(bytes=rows.nbytes)
            total = float(np.sum(rows, dtype=np.float64))
            return total * 0.5 / (b.norm * b.norm)

    def spectrum_pair(self, pair, nbins: int = 32, *, extra=None):
        """Shell-summed E(k) of one spectral pair through the basis'
        layout-matched wavenumbers (``radial_spectrum_k``)."""
        b = self.basis
        w = np.asarray(b.weights, np.float64) * (0.5 / (b.norm * b.norm))
        if extra is not None:
            w = w * np.asarray(extra, np.float64)
        re = b.gather_spectral(pair[0])
        im = b.gather_spectral(pair[1])
        w = np.broadcast_to(w, re.shape)
        centers, e = radial_spectrum_k(re, im, b.kmag, nbins, weights=w)
        return np.asarray(centers), np.asarray(e)

    # -- checkpoint / restart ------------------------------------------------
    def _ckpt_tree(self) -> Dict:
        gather = self.basis.gather_spectral
        return {"state": jax.tree_util.tree_map(gather, self.state),
                "t": np.float64(self.t),
                "step": np.int64(self.step_count)}

    def save(self, ckpt_dir, *, keep: int = 3):
        """Checkpoint the spectral state (atomic step dir + manifest)."""
        assert self.state is not None
        return checkpoint.save(ckpt_dir, self.step_count,
                               self._ckpt_tree(), keep=keep)

    def restore(self, ckpt_dir, step: Optional[int] = None) -> int:
        """Restore state from ``ckpt_dir`` (latest step by default) and
        resume; leaves go back onto the plan's output sharding, so the
        continuation is bit-identical to an uninterrupted run."""
        assert self.state is not None, \
            "build the solver (any init) before restoring into it"
        if step is None:
            step = checkpoint.latest_step(ckpt_dir)
            assert step is not None, f"no checkpoints under {ckpt_dir}"
        template = self._ckpt_tree()
        tree = checkpoint.restore(ckpt_dir, step, template)
        place = self.basis.place_spectral
        self.state = jax.tree_util.tree_map(place, tree["state"])
        self.step_count = int(tree["step"])
        # recomputed, not read back: device round-trips canonicalize
        # float64 scalars to float32, which would de-sync t from an
        # uninterrupted run at the 8th digit
        self.t = self.step_count * self.dt
        return step
