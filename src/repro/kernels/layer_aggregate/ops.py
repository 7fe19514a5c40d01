"""Jitted wrapper: Eq. 8 aggregation for arbitrary client-stacked leaves."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.layer_aggregate import kernel as K


def aggregate_leaf(c, ww, s, lam, *, interpret=None):
    """c [N, L, ...]; ww [N, L]; s [L, ...] -> [L, ...]."""
    N, Lk = c.shape[:2]
    F = 1
    for dim in c.shape[2:]:
        F *= dim
    c2 = c.reshape(N, Lk, F)
    s2 = s.reshape(Lk, F)
    pad = (-F) % K.F_BLOCK
    if pad:
        c2 = jnp.pad(c2, ((0, 0), (0, 0), (0, pad)))
        s2 = jnp.pad(s2, ((0, 0), (0, pad)))
    out = K.aggregate_3d(c2, ww, s2, lam, interpret=interpret)
    return out[:, :F].reshape(s.shape)
