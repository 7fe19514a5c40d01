"""Pallas TPU flash attention (causal + sliding-window, GQA-aware).

Online-softmax formulation: grid (B, H, n_q_blocks, n_kv_blocks) with the
kv-block axis innermost — TPU grids iterate sequentially, so the running
max/denominator/accumulator live in VMEM scratch carried across kv steps
(the canonical TPU flash pattern; no atomics, no HBM round-trips for the
softmax statistics).

GQA is handled in the BlockSpec index_map: query head h reads kv head
h * K // H — no materialized head repetition.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _attn_kernel(meta_ref, q_ref, k_ref, v_ref, out_ref,
                 m_ref, l_ref, acc_ref, *, bq, bk, causal, window, nk):
    i = pl.program_id(2)
    j = pl.program_id(3)
    scale = meta_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # [bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)            # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)            # [bk, hd]
    s = (q @ k.T) * scale                           # [bq, bk]

    rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask = mask & (cols <= rows)
    if window:
        mask = mask & (cols > rows - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                             # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + p @ v
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        out_ref[0, 0] = (acc_ref[...]
                         / jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         bq: int = 128, bk: int = 128,
                         interpret: bool = None):
    """q [B,H,Sq,hd]; k,v [B,K,Skv,hd] (H % K == 0). Returns [B,H,Sq,hd]."""
    B, H, Sq, hd = q.shape
    K = k.shape[1]
    Skv = k.shape[2]
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk
    meta = jnp.asarray([1.0 / math.sqrt(hd)], jnp.float32)
    kv_map = lambda b, h, i, j: (b, h * K // H, j, 0)
    return pl.pallas_call(
        functools.partial(_attn_kernel, bq=bq, bk=bk, causal=causal,
                          window=window, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(meta, q, k, v)
