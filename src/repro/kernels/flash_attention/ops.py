"""Jitted wrapper matching the model's [B, S, H, hd] attention layout."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as K


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret=None):
    """q [B,Sq,H,hd]; k,v [B,Skv,Kh,hd] -> [B,Sq,H,hd]."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = K.flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                 interpret=interpret)
    return jnp.swapaxes(out, 1, 2)
