"""2-D vorticity Navier–Stokes, pseudo-spectral, integrating-factor RK4
(after spectralDNS' NS2D), written out plainly.

    ∂ω/∂t + u·∇ω = ν∇²ω,   u = (∂ψ/∂y, −∂ψ/∂x),   ∇²ψ = −ω

on [0, 2π)²: state ω̂ on the r2c half-spectrum; the nonlinear term is
−(u ∂xω + v ∂yω) formed in real space and 2/3-rule dealiased (box),
with its k = 0 bin pinned to zero. The array module and the transforms
are parameters, so the same arithmetic runs in float64 on the host
(the reference) and in float32 with bf16×3 DFTs on the device (the
precision control).
"""
from __future__ import annotations

import numpy as np
import scipy.fft as sfft

from bench.reference.chain import WORKERS, freq_index


def initial_vorticity(shape, seed: int, kpeak: int = 4,
                      amplitude: float = 1.0) -> np.ndarray:
    """The seeded smooth random field: white noise low-passed to
    |k| ≤ kpeak per axis, zero mean, scaled to max |ω| = amplitude."""
    n0, n1 = shape
    rng = np.random.default_rng(seed)
    spec = sfft.rfft2(rng.standard_normal((n0, n1)), workers=WORKERS)
    keep = ((freq_index(n0)[:, None] <= kpeak)
            & (np.arange(spec.shape[1])[None, :] <= kpeak))
    keep[0, 0] = False
    w = sfft.irfft2(spec * keep, s=(n0, n1), workers=WORKERS)
    return amplitude * w / max(np.abs(w).max(), 1e-12)


def _rfft2_f64(x):
    return sfft.rfft2(x, workers=WORKERS)


def _irfft2_f64(x, shape):
    return sfft.irfft2(x, s=shape, axes=(-2, -1), workers=WORKERS)


class NS2D:
    """IF-RK4 stepping of ω̂; ``rfft2(x)`` and ``irfft2(spec, shape)``
    act on the last two axes."""

    def __init__(self, shape, *, nu: float, dt: float, xp=np,
                 rfft2=_rfft2_f64, irfft2=_irfft2_f64, real=np.float64):
        n0, n1 = self.shape = tuple(int(n) for n in shape)
        self.xp, self.rfft2, self.irfft2 = xp, rfft2, irfft2
        k0 = np.fft.fftfreq(n0, d=1.0 / n0)[:, None]
        k1 = np.arange(n1 // 2 + 1, dtype=np.float64)[None, :]
        k2 = k0 ** 2 + k1 ** 2
        inv_k2 = np.where(k2 > 0, 1.0 / np.maximum(k2, 1e-30), 0.0)
        dealias = ((freq_index(n0)[:, None] * 3 < n0)
                   & (freq_index(n1)[: n1 // 2 + 1][None, :] * 3 < n1))
        lam = -float(nu) * k2
        put = lambda a: xp.asarray(np.asarray(a, real))
        self.k0, self.k1, self.inv_k2 = put(k0), put(k1), put(inv_k2)
        self.nlmask = put(dealias & (k2 > 0))
        self.e_half = put(np.exp(lam * (dt / 2.0)))
        self.e_full = put(np.exp(lam * dt))
        self.dt = float(dt)

    def initial(self, w0):
        """ω̂ of a real vorticity field, dealiased."""
        return self.rfft2(w0) * self.nlmask

    def nonlinear(self, w):
        xp = self.xp
        psi = w * self.inv_k2
        fields = xp.stack((1j * self.k1 * psi, -1j * self.k0 * psi,
                           1j * self.k0 * w, 1j * self.k1 * w))
        u, v, wx, wy = self.irfft2(fields, self.shape)
        return self.rfft2(-(u * wx + v * wy)) * self.nlmask

    def step(self, s):
        dt, eh, ef, n = self.dt, self.e_half, self.e_full, self.nonlinear
        k1 = n(s)
        k2 = n(eh * (s + (dt / 2.0) * k1))
        k3 = n(eh * s + (dt / 2.0) * k2)
        k4 = n(ef * s + dt * (eh * k3))
        acc = ef * k1 + 2.0 * eh * (k2 + k3) + k4
        return ef * s + (dt / 6.0) * acc
