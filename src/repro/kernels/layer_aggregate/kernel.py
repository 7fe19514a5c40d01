"""Pallas TPU kernel for Eq. 8 layer-aligned aggregation.

The hot case during a 100-client round is a [N, L, F] client-stacked leaf
reduced over N per layer. Naive XLA materializes the weighted [N, L, F]
product; this kernel streams client slabs through VMEM and accumulates in a
fp32 block, one HBM read per element.

Grid: (L, F_blocks). Per step, the kernel sees one layer's client slab
c[:, l, fb] as an [N, FB] block, the weight column ww[:, l] as an [N, 1]
block, and the server row s[l, fb] as a [1, FB] block. The layer axis is a
leading, unit-block dim of every operand (``[L, N, F]``, ``[L, N, 1]``,
``[L, 1, F]``), so the two minor dims of each block are either whole or
lane-aligned — the TPU's (8, 128) tiling rule — and ``lam`` rides in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

F_BLOCK = 512


def _agg_kernel(lam_ref, c_ref, ww_ref, s_ref, out_ref):
    c = c_ref[0].astype(jnp.float32)          # [N, FB]
    ww = ww_ref[0].astype(jnp.float32)         # [N, 1]
    s = s_ref[0].astype(jnp.float32)           # [1, FB]
    lam = lam_ref[0, 0]
    num = jnp.sum(ww * c, axis=0, keepdims=True) + lam * s
    den = jnp.sum(ww) + lam
    out_ref[0] = (num / den).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def aggregate_3d(c, ww, s, lam, *, interpret: bool = None):
    """c [N, L, F] (F % F_BLOCK == 0), ww [N, L], s [L, F] -> [L, F]."""
    N, Lk, F = c.shape
    grid = (Lk, F // F_BLOCK)
    lam_arr = jnp.asarray([[lam]], jnp.float32)
    out = pl.pallas_call(
        _agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, N, F_BLOCK), lambda l, f: (l, 0, f)),
            pl.BlockSpec((1, N, 1), lambda l, f: (l, 0, 0)),
            pl.BlockSpec((1, 1, F_BLOCK), lambda l, f: (l, 0, f)),
        ],
        out_specs=pl.BlockSpec((1, 1, F_BLOCK), lambda l, f: (l, 0, f)),
        out_shape=jax.ShapeDtypeStruct((Lk, 1, F), s.dtype),
        interpret=interpret_mode(interpret),
    )(lam_arr, jnp.swapaxes(c, 0, 1), ww.T[:, :, None], s[:, None, :])
    return out[:, 0, :]
