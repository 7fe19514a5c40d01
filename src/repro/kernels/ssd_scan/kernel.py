"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid (B, nh, n_chunks) with the chunk axis innermost: TPU grids run
sequentially, so the inter-chunk recurrent state h [hd, st] lives in VMEM
scratch and is carried across chunk steps — the cross-chunk ``lax.scan`` of
the reference collapses into grid iteration (no HBM state round-trip).

Per chunk the kernel does the quadratic-in-chunk SSD math:
    s       = cumsum(dt * A)                       [cl]
    u       = x * dt                                [cl, hd]
    W       = tril(C B^T * exp(s_i - s_j))          [cl, cl]
    y       = W u + exp(s) * (C h_prev^T) + D x     [cl, hd]
    h_new   = exp(s_last) h_prev + sum_j exp(s_last - s_j) u_j (x) B_j
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, hout_ref,
                h_ref, *, cl, nc):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)             # [cl, hd]
    dt = dt_ref[0, 0].astype(jnp.float32)           # [cl, 1]... stored [cl]
    A = a_ref[0]                                     # scalar (per head)
    Bm = b_ref[0].astype(jnp.float32)                # [cl, st]
    Cm = c_ref[0].astype(jnp.float32)                # [cl, st]
    D = d_ref[0]

    dt2 = dt.reshape(cl, 1)
    dA = dt2 * A                                     # [cl, 1]
    s = jnp.cumsum(dA, axis=0)                       # [cl, 1]
    u = x * dt2                                      # [cl, hd]

    CB = Cm @ Bm.T                                   # [cl, cl]
    Lm = jnp.exp(s - s.T)                            # exp(s_i - s_j)
    tri = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    W = jnp.where(tri, CB * Lm, 0.0)
    y = W @ u                                        # intra-chunk

    h_prev = h_ref[...]                              # [hd, st]
    y = y + jnp.exp(s) * (Cm @ h_prev.T)             # inter-chunk
    y = y + D * x

    decay_end = jnp.exp(s[cl - 1] - s)               # [cl, 1]
    h_chunk = (u * decay_end).T @ Bm                 # [hd, st]
    h_ref[...] = h_prev * jnp.exp(s[cl - 1, 0]) + h_chunk

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, D=None, *, chunk: int = 128,
             interpret: bool = None):
    """x [Bt,S,nh,hd]; dt [Bt,S,nh]; A [nh]; B,C [Bt,S,st]; D [nh] or None.

    Returns (y [Bt,S,nh,hd], h_final [Bt,nh,hd,st]).
    """
    Bt, S, nh, hd = x.shape
    st = B.shape[-1]
    cl = min(chunk, S)
    assert S % cl == 0
    nc = S // cl
    if D is None:
        D = jnp.zeros((nh,), jnp.float32)
    xt = jnp.transpose(x, (0, 2, 1, 3))              # [Bt, nh, S, hd]
    dtt = jnp.transpose(dt, (0, 2, 1))               # [Bt, nh, S]
    y, h = pl.pallas_call(
        functools.partial(_ssd_kernel, cl=cl, nc=nc),
        grid=(Bt, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, cl, hd), lambda b, h_, c: (b, h_, c, 0)),
            pl.BlockSpec((1, 1, cl), lambda b, h_, c: (b, h_, c)),
            pl.BlockSpec((1,), lambda b, h_, c: (h_,)),
            pl.BlockSpec((1, cl, st), lambda b, h_, c: (b, c, 0)),
            pl.BlockSpec((1, cl, st), lambda b, h_, c: (b, c, 0)),
            pl.BlockSpec((1,), lambda b, h_, c: (h_,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cl, hd), lambda b, h_, c: (b, h_, c, 0)),
            pl.BlockSpec((1, 1, hd, st), lambda b, h_, c: (b, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, nh, S, hd), x.dtype),
            jax.ShapeDtypeStruct((Bt, nh, hd, st), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, st), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(xt, dtt, A.astype(jnp.float32), B, C, D.astype(jnp.float32))
    return jnp.transpose(y, (0, 2, 1, 3)), h
