"""Three-Phase Gradient Fusion (TPGF) — paper §II-B / Algorithm 2.

Phase 1 (client): local head loss, phi_i update grad, clipped encoder grad.
Phase 2 (server): suffix loss, server param grads, g_z returned to client,
                  client backprop of g_z through the encoder (one shared
                  ``jax.vjp`` of the prefix — exactly Algorithm 2 line 13).
Phase 3 (client): loss-weighted fusion (Eq. 3/4) of the two encoder grads.

Everything returns *gradients*; the optimizer application lives in
``repro.optim`` so the same step works under SGD/AdamW and under pjit.
The client-side work (prefix forward, both prefix backward passes, local
head, clip, fusion) runs under the device name scope ``tpgf.client`` and
the server suffix's value-and-grad under ``tpgf.server`` (metadata only;
the profiler attributes device time by them).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import supernet as SN
from repro.models import model as M


class TPGFOut(NamedTuple):
    grads: Dict[str, Any]        # full-params-aligned gradient tree
    loss_client: jnp.ndarray
    loss_server: jnp.ndarray
    w_client: jnp.ndarray
    aux: jnp.ndarray             # MoE router load-balance loss


def tpgf_weight(loss_client, loss_server, d_i: int, d_s: int,
                eps: float = 1e-8, variant: str = "full"):
    """Eq. (3): depth-aware x inverse-loss reliability weighting.

    ``variant`` implements the paper's Fig. 6 ablation:
      full     — both factors (the paper's rule)
      no_loss  — depth factor only
      no_depth — loss factor only
      equal    — neither (naive 0.5/0.5 fusion)
    """
    depth = d_i / (d_i + d_s)
    ic = 1.0 / (loss_client + eps)
    is_ = 1.0 / (loss_server + eps)
    loss_term = ic / (ic + is_)
    if variant == "full":
        return depth * loss_term
    if variant == "no_loss":
        return depth + 0.0 * loss_term          # depth fraction alone
    if variant == "no_depth":
        return loss_term                         # reliability alone
    if variant == "equal":
        return 0.5 + 0.0 * loss_term             # naive average
    raise ValueError(variant)


def fused_loss(loss_client, loss_server, d_i: int, d_s: int,
               eps: float = 1e-8, variant: str = "full"):
    """The same fusion rule applied to losses (used by Eq. 6 aggregation).

    ``variant`` must match the ``cfg.tpgf_variant`` the gradients were
    fused under, or the recorded Eq. 6 weights disagree with the update
    actually applied (the Fig. 6 ablation bug)."""
    w = tpgf_weight(loss_client, loss_server, d_i, d_s, eps, variant)
    return w * loss_client + (1.0 - w) * loss_server


def _fault_degrade(server_available, w_c, g_server_params, g_client,
                   g_client_local):
    """Fault-tolerant degrade shared by both TPGF entry points (paper
    §II-C): where the server is unreachable this step, the fusion weight
    collapses to 1, the encoder takes its local-only (Phase-1) gradient,
    and the server branch gets zero gradient. ``server_available=None``
    means the caller never degrades — everything passes through."""
    if server_available is None:
        return w_c, g_server_params, g_client
    w_c = jnp.where(server_available, w_c, 1.0)
    g_server_params = jax.tree.map(
        lambda g: jnp.where(server_available, g, jnp.zeros_like(g)),
        g_server_params)
    g_client = jax.tree.map(
        lambda fused, loc: jnp.where(server_available, fused, loc),
        g_client, g_client_local)
    return w_c, g_server_params, g_client


def clip_by_global_l2(tree, tau: float):
    """Paper's Phase-1 encoder-gradient clip (tau = 0.5)."""
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
             for x in jax.tree.leaves(tree))
    norm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, tau / (norm + 1e-12))
    return jax.tree.map(lambda x: (x * scale).astype(x.dtype), tree), norm


def fuse_gradients(g_client, g_server, w_client, *, use_pallas: bool = False):
    """Eq. (4): per-leaf fused encoder gradient."""
    w_c = w_client.astype(jnp.float32)
    if use_pallas:
        from repro.kernels.tpgf_fusion.ops import fuse_tree
        return fuse_tree(g_client, g_server, w_c)
    return jax.tree.map(
        lambda a, b: (w_c * a.astype(jnp.float32)
                      + (1.0 - w_c) * b.astype(jnp.float32)).astype(a.dtype),
        g_client, g_server)


def tpgf_grads(cfg: ModelConfig, params, batch, d, *,
               server_available=None) -> TPGFOut:
    """One TPGF iteration's gradients for all parameter groups.

    ``server_available``: optional bool scalar. When False this degrades to
    the fault-tolerant Phase-1-only update (paper §II-C): encoder+phi_i get
    local gradients, server params get zero.

    ``d`` may be a jax scalar: the runtime-depth form delegates to
    :func:`tpgf_grads_split` over full-``L`` views (masked scans), then
    row-selects the two stack gradients back into one tree — the active
    rows carry exactly the static path's values and the inactive rows are
    exactly zero.
    """
    if not M.static_depth(d):
        client_p, server_p, local_p = SN.split_params(cfg, params, None)
        out = tpgf_grads_split(cfg, cfg, client_p, server_p, local_p,
                               batch, d, server_available=server_available)
        grads = SN.merge_params(cfg, out.g_client, out.g_server,
                                out.g_local, d)
        return TPGFOut(grads, out.loss_client, out.loss_server,
                       out.w_client, out.aux)
    d_s = cfg.split_stack_len - d
    client_p, server_p, local_p = SN.split_params(cfg, params, d)

    # ---- shared prefix forward with a single vjp (Algorithm 2, line 13)
    def prefix_fn(cp):
        full = SN.merge_params(cfg, cp, server_p, local_p)
        return M.prefix_apply(cfg, full, batch, d)

    def local_fn(lp, z_):
        full = SN.merge_params(cfg, client_p, server_p, lp)
        return M.local_loss(cfg, full, z_, batch)

    def server_fn(sp, z_):
        full = SN.merge_params(cfg, client_p, sp, local_p)
        return M.server_loss(cfg, full, z_, batch, d)

    with jax.named_scope("tpgf.client"):
        # ---- shared prefix forward with a single vjp (Alg. 2, line 13)
        (z, aux_prefix), vjp_prefix = jax.vjp(prefix_fn, client_p)
        # ---- Phase 1: local supervision
        loss_client, (g_local, gz_client) = jax.value_and_grad(
            local_fn, argnums=(0, 1))(local_p, z)

    # ---- Phase 2: server supervision
    with jax.named_scope("tpgf.server"):
        loss_server, (g_server_params, gz_server) = jax.value_and_grad(
            server_fn, argnums=(0, 1))(server_p, z)

    with jax.named_scope("tpgf.client"):
        # client backprop of each branch's dL/dz through the encoder
        (g_client_local,) = vjp_prefix((gz_client,
                                        jnp.zeros_like(aux_prefix)))
        (g_client_server,) = vjp_prefix((gz_server,
                                         jnp.zeros_like(aux_prefix)))
        # ---- Phase 3: clip + loss-weighted fusion (Eqs. 3-4)
        g_client_local, _ = clip_by_global_l2(g_client_local, cfg.tpgf_clip)
        w_c = tpgf_weight(loss_client, loss_server, d, d_s, cfg.tpgf_eps,
                          variant=cfg.tpgf_variant)
        g_client = fuse_gradients(g_client_local, g_client_server, w_c,
                                  use_pallas=cfg.use_pallas)
        w_c, g_server_params, g_client = _fault_degrade(
            server_available, w_c, g_server_params, g_client,
            g_client_local)

    grads = SN.merge_params(cfg, g_client, g_server_params, g_local)
    return TPGFOut(grads, loss_client, loss_server, w_c, aux_prefix)


class TPGFSplitOut(NamedTuple):
    g_client: Dict[str, Any]     # sliced-client-aligned gradient tree
    g_server: Dict[str, Any]     # server-view gradient tree
    g_local: Dict[str, Any]      # phi_i gradient tree
    loss_client: jnp.ndarray
    loss_server: jnp.ndarray
    w_client: jnp.ndarray
    aux: jnp.ndarray


def tpgf_grads_split(cfg: ModelConfig, wcfg: ModelConfig, client_p, server_p,
                     local_p, batch, d, *,
                     server_available=None) -> TPGFSplitOut:
    """TPGF over an already-split (and possibly width-sliced) subnetwork.

    ``client_p`` is the ``split_params(cfg, params, d, width)`` client view
    and ``wcfg`` the matching ``supernet.width_cfg`` — the client forward
    runs entirely on the slice, so a narrow client never materializes (or
    pays FLOPs for) the pruned coordinates. The returned ``g_client`` is
    aligned with the slice; the caller scatters it back into the shared
    supernet with ``supernet.scatter_width`` / ``widen_width`` so
    aggregation stays mask-aware. Phases and the fault-tolerant degrade
    mirror :func:`tpgf_grads` exactly.

    When ``d`` is a jax scalar, both views must hold all ``L`` stack rows
    (``split_params(cfg, params, None, width)``): the forwards run the
    masked scans, inactive rows get exactly zero gradient, and one jit
    program serves every depth.
    """
    d_s = cfg.split_stack_len - d
    length = None if M.static_depth(d) else d

    def prefix_fn(cp):
        return M.client_apply(wcfg, cp, batch, length=length)

    # the local head is width-oblivious — it reads the full-d_model
    # smashed data
    def local_fn(lp, z_):
        return M.local_loss(cfg, lp, z_, batch)

    def server_fn(sp, z_):
        return M.server_split_loss(cfg, sp, z_, batch, length=length)

    with jax.named_scope("tpgf.client"):
        # ---- shared prefix forward with a single vjp (Alg. 2, line 13)
        (z, aux_prefix), vjp_prefix = jax.vjp(prefix_fn, client_p)
        # ---- Phase 1: local supervision
        loss_client, (g_local, gz_client) = jax.value_and_grad(
            local_fn, argnums=(0, 1))(local_p, z)

    # ---- Phase 2: server supervision (full-width suffix)
    with jax.named_scope("tpgf.server"):
        loss_server, (g_server_params, gz_server) = jax.value_and_grad(
            server_fn, argnums=(0, 1))(server_p, z)

    with jax.named_scope("tpgf.client"):
        # client backprop of each branch's dL/dz through the encoder slice
        (g_client_local,) = vjp_prefix((gz_client,
                                        jnp.zeros_like(aux_prefix)))
        (g_client_server,) = vjp_prefix((gz_server,
                                         jnp.zeros_like(aux_prefix)))
        # ---- Phase 3: clip + loss-weighted fusion (Eqs. 3-4)
        g_client_local, _ = clip_by_global_l2(g_client_local, cfg.tpgf_clip)
        w_c = tpgf_weight(loss_client, loss_server, d, d_s, cfg.tpgf_eps,
                          variant=cfg.tpgf_variant)
        g_client = fuse_gradients(g_client_local, g_client_server, w_c,
                                  use_pallas=cfg.use_pallas)
        w_c, g_server_params, g_client = _fault_degrade(
            server_available, w_c, g_server_params, g_client,
            g_client_local)
    return TPGFSplitOut(g_client, g_server_params, g_local,
                        loss_client, loss_server, w_c, aux_prefix)


# ------------------------------------------------------- cross-tier fusion

class TierUpdate(NamedTuple):
    """One width tier's contribution to :func:`fuse_tiers`.

    width  — host float in (0, 1]: the tier's width slice (1.0 = full);
    weight — fp32 scalar (device scalar fine): the tier's mass — Eq. 6-style
             summed inverse fused losses of its live clients. Weight 0 means
             the tier trained nobody this round and must fuse as a no-op;
    tree   — the tier's update tree living on its width slice (plan leaves
             hold only the kept channel prefix), or already full-width.
    """
    width: float
    weight: Any
    tree: Any


def fuse_tiers(cfg: ModelConfig, tiers, *, base=None,
               use_pallas: bool = False):
    """Cross-tier TPGF: ONE full-width update from per-tier width slices.

    Lift -> per-coordinate fuse -> single update: each tier's tree is
    zero-extended to full-width coordinates (``supernet.widen_width``, the
    ``widen(slice(t)) == mask(t)`` identity), then fused with
    per-coordinate denominators reusing ``aggregation.width_coord_masks``
    — the same membership law as Eq. (8)'s width-aware aggregation — so a
    coordinate pruned in some tiers is fused only over the tiers that
    actually trained it:

        fused[f] = sum_t ( w_t * m_t[f] / sum_u w_u * m_u[f] ) * x_t[f]

    The normalizer divides BEFORE the multiply: a coordinate held by
    exactly one tier gets that tier's value exactly (``w/w == 1.0`` in
    IEEE) and a zero-weight tier contributes an exact ``+/-0.0`` — the
    property suite in ``tests/test_tpgf_cross_tier.py`` pins both. Tiers
    are canonically sorted by width before accumulating, so the result is
    invariant (bit for bit) to the caller's tier ordering; equal-width
    tiers keep their given order (two-term float adds commute exactly).

    ``base=None`` fuses gradient-like trees: coordinates no tier holds
    come out zero. With ``base`` (delta mode, used for the shared server
    branch and its optimizer moments) the result is
    ``base + sum_t hw_t * (x_t - base)`` and un-held coordinates fall back
    to ``base`` through a where-guard, so an all-zero-weight cohort is a
    bit-exact no-op — the frozen-server invariant under fusion.

    ``use_pallas`` routes the full-width (scalar-weight) accumulation
    through the ``tpgf_fusion.tier_sum`` kernel; the per-coordinate slice
    path stays in jnp (the postscale is memory-bound either way).
    """
    from repro.core import aggregation as AGG

    if not tiers:
        raise ValueError("fuse_tiers needs at least one tier")
    tiers = sorted(tiers, key=lambda t: float(t.width))
    widths = [float(t.width) for t in tiers]
    wts = [jnp.asarray(t.weight, jnp.float32) for t in tiers]
    lifted = [SN.widen_width(cfg, t.tree, t.width) for t in tiers]

    tot = wts[0]
    for wt in wts[1:]:
        tot = tot + wt
    safe_tot = jnp.where(tot > 0, tot, 1.0)
    coord = any(wi < 1.0 for wi in widths)
    plan = SN.width_plan(cfg, 1.0)
    masks = AGG.width_coord_masks(cfg, widths) if coord else {}
    wvec = jnp.stack(wts)

    flat0, treedef = jax.tree_util.tree_flatten_with_path(lifted[0])
    flats = [jax.tree_util.tree_flatten_with_path(t)[0] for t in lifted]
    base_leaves = ([None] * len(flat0) if base is None
                   else jax.tree.leaves(base))
    out = []
    for i, (path, x0) in enumerate(flat0):
        name = SN._leaf_name(path)
        xs = [flat[i][1].astype(jnp.float32) for flat in flats]
        b = base_leaves[i]
        bf = None if b is None else b.astype(jnp.float32)
        if coord and name in masks:
            ax, F = plan[name]
            axis = x0.ndim + ax
            den = jnp.einsum("t,tf->f", wvec, masks[name])        # [F]
            sden = jnp.where(den > 0, den, 1.0)
            shape = [1] * x0.ndim
            shape[axis] = F
            held = (den > 0).reshape(shape)
            acc = None
            for wt, mt, xf in zip(wts, masks[name], xs):
                hw = (wt * mt / sden).reshape(shape)
                term = hw * (xf if bf is None else xf - bf)
                acc = term if acc is None else acc + term
        else:
            held = tot > 0
            hws = [jnp.where(held, wt / safe_tot, 0.0) for wt in wts]
            terms = xs if bf is None else [xf - bf for xf in xs]
            if use_pallas:
                from repro.kernels.tpgf_fusion.ops import tier_sum_leaf
                acc = tier_sum_leaf(terms, hws)
            else:
                acc = None
                for hw, term in zip(hws, terms):
                    acc = hw * term if acc is None else acc + hw * term
        if bf is None:
            fused = jnp.where(held, acc, jnp.zeros((), jnp.float32))
        else:
            fused = jnp.where(held, bf + acc, bf)
        out.append(fused.astype(x0.dtype if b is None else b.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def local_only_grads(cfg: ModelConfig, params, batch, d: int):
    """Pure fallback-mode step (server unreachable) — Algorithm 3 else-branch.

    Returns (grads, loss_client): encoder + local head trained from the
    client classifier alone; server parameters receive zero gradient.
    """
    client_p, server_p, local_p = SN.split_params(cfg, params, d)

    def loss_fn(cp, lp):
        full = SN.merge_params(cfg, cp, server_p, lp)
        z, _ = M.prefix_apply(cfg, full, batch, d)
        return M.local_loss(cfg, full, z, batch)

    loss, (g_client, g_local) = jax.value_and_grad(
        loss_fn, argnums=(0, 1))(client_p, local_p)
    g_client, _ = clip_by_global_l2(g_client, cfg.tpgf_clip)
    zeros_server = jax.tree.map(jnp.zeros_like, server_p)
    grads = SN.merge_params(cfg, g_client, zeros_server, g_local)
    return grads, loss
