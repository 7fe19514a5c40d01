"""Pallas TPU kernel: fused clip-scale + loss-weighted gradient blend.

TPGF Phase 3 (Eq. 4) touches every client-encoder gradient element twice in
the naive form (clip multiply, then blend) — two full HBM round-trips over
the gradient pytree. This kernel fuses them into one pass:

    out = w * (g_client * clip_scale) + (1 - w) * g_server

Layout: leaves are flattened and padded to (rows, 128) fp32/bf16 tiles;
the grid walks row-blocks, with the scalars in SMEM.

``interpret=None`` (every entry point's default) compiles the kernel on any
backend but the CPU, which runs the Pallas interpreter
(``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

LANE = 128
ROW_BLOCK = 256
SUBLANE = 8
# a small 2-D array, whole, in SMEM: 2-D so that a vmapped call (the
# batch dim leads) still has whole-array minor dims — the (8, 128) tiling
# rule's exemption
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _fuse_kernel(scalars_ref, a_ref, b_ref, out_ref):
    w = scalars_ref[0, 0]
    cs = scalars_ref[0, 1]
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    out_ref[...] = (w * (a * cs) + (1.0 - w) * b).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fuse_2d(a, b, w_client, clip_scale, *, interpret: bool = None):
    """a, b: [M, 128k] with M % ROW_BLOCK == 0 (callers pad via ops.py)."""
    M, N = a.shape
    grid = (M // ROW_BLOCK,)
    scalars = jnp.stack([jnp.float32(w_client),
                         jnp.float32(clip_scale)]).reshape(1, 2)
    return pl.pallas_call(
        _fuse_kernel,
        grid=grid,
        in_specs=[
            _SMEM,                              # [[w_client, clip_scale]]
            pl.BlockSpec((ROW_BLOCK, N), lambda i: (i, 0)),
            pl.BlockSpec((ROW_BLOCK, N), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        interpret=interpret_mode(interpret),
    )(scalars, a, b)


def _tier_sum_kernel(w_ref, x_ref, out_ref):
    t = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += w_ref[0, t] * x


@functools.partial(jax.jit, static_argnames=("interpret",))
def tier_sum_2d(x, w, *, interpret: bool = None):
    """Cross-tier accumulation ``sum_t w[t] * x[t]`` in one HBM pass.

    x: [T, M, 128k] stacked tier tiles (M % ROW_BLOCK == 0), w: [T] fp32
    normalized tier weights. The tier axis is the innermost grid dim, so
    each output row-block is revisited consecutively and accumulates in
    canonical (sorted-tier) order — the same order the jnp reference sums,
    keeping the two paths bit-comparable. Returns fp32 (callers cast)."""
    T, M, N = x.shape
    grid = (M // ROW_BLOCK, T)
    return pl.pallas_call(
        _tier_sum_kernel,
        grid=grid,
        in_specs=[
            _SMEM,                              # tier weights [1, T]
            pl.BlockSpec((1, ROW_BLOCK, N), lambda i, t: (t, i, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, N), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret_mode(interpret),
    )(jnp.asarray(w, jnp.float32).reshape(1, T), x)


def _sumsq_kernel(x_ref, out_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    # fold the row block onto one (8, 128) vreg tile: elementwise adds
    # only, so the accumulator stays a tiled VMEM block (a TPU cannot
    # store a scalar to VMEM); the final cross-lane sum runs once, outside
    partial = jnp.sum((x * x).reshape(-1, SUBLANE, x.shape[-1]), axis=0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("interpret",))
def sumsq_2d(x, *, interpret: bool = None):
    """Global sum of squares (for the clip norm), grid-carried accumulator."""
    M, N = x.shape
    grid = (M // ROW_BLOCK,)
    out = pl.pallas_call(
        _sumsq_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROW_BLOCK, N), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SUBLANE, N), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANE, N), jnp.float32),
        interpret=interpret_mode(interpret),
    )(x)
    return jnp.sum(out)
