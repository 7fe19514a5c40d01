"""Jitted wrappers: pytree-level TPGF fusion on top of the Pallas kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.tpgf_fusion import kernel as K


def _to_tiles(x):
    """Flatten to [M, LANE] padded to ROW_BLOCK rows; remember true size."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    per_block = K.ROW_BLOCK * K.LANE
    padded = ((n + per_block - 1) // per_block) * per_block
    flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(-1, K.LANE), n


def fuse_leaf(a, b, w_client, clip_scale, *, interpret=None):
    ta, n = _to_tiles(a)
    tb, _ = _to_tiles(b)
    out = K.fuse_2d(ta, tb, w_client, clip_scale, interpret=interpret)
    return out.reshape(-1)[:n].reshape(a.shape)


def tier_sum_leaf(leaves, weights, *, interpret=None):
    """``sum_t weights[t] * leaves[t]`` for one leaf shape across tiers.

    ``leaves`` are same-shape full-width (already lifted) arrays, one per
    tier in canonical order; ``weights`` the matching normalized fp32
    scalars. Tiles each leaf, stacks the tier axis, and runs the one-pass
    ``tier_sum_2d`` accumulator. Returns fp32 (``fuse_tiers`` casts)."""
    tiles, n = zip(*(_to_tiles(x) for x in leaves))
    out = K.tier_sum_2d(jnp.stack(tiles), jnp.stack(weights),
                        interpret=interpret)
    return out.reshape(-1)[:n[0]].reshape(leaves[0].shape)


def fuse_tree(g_client, g_server, w_client, *, tau: float = None,
              interpret=None):
    """Eq. 4 over a pytree. If ``tau`` is given, also computes the global-l2
    clip scale with the sumsq kernel (Phase-1 clip fused into the blend)."""
    if tau is not None:
        total = jnp.float32(0.0)
        for leaf in jax.tree.leaves(g_client):
            t, n = _to_tiles(leaf)
            total = total + K.sumsq_2d(t, interpret=interpret)
        norm = jnp.sqrt(total)
        clip_scale = jnp.minimum(1.0, tau / (norm + 1e-12))
    else:
        clip_scale = jnp.float32(1.0)
    return jax.tree.map(
        lambda a, b: fuse_leaf(a, b, w_client, clip_scale,
                               interpret=interpret),
        g_client, g_server)
