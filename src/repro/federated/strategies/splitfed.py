"""SplitFed baselines (SFL + dynamic-split DFL) as engine strategies.

SplitFedV1-faithful: the server keeps a PER-CLIENT server-side copy trained
on that client's smashed stream; copies are FedAvg'd by the fed server at
round end. Client gradients come only from the server branch (no local
classifier); a stalled client (server unreachable) gets zero update.

  sfl — one rigid mid-stack split point for every client; clients whose
        Eq.1 capacity is below it cannot participate.
  dfl — resource-aware depths like ssfl (Samikwa et al.) but
        server-grad-only training and depth-weighted FedAvg.

Execution follows the bucketed device-resident kernel contract
(``federated.bucketing``): one scanned kernel per (width, bucket) runs all
local steps with on-device batch gather — depth is a RUNTIME scalar
(masked scan over the full stack, ``model.run_stack``), so dfl's
heterogeneous depth tiers share one compiled program. Padded slots ride
with ``avail=False`` (zero update, frozen moments) and are excluded from
the round-end FedAvg over server copies.

Client-side optimizer state is per-round (clients re-download their
subnetwork), but the *server* moments persist across rounds in
``TrainState.opt_state["server"]``: each cohort broadcasts the shared
moments onto its per-client server copies and the post-round mean is folded
back — the moment-space analogue of SplitFed's FedAvg over server copies.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import aggregation as AGG
from repro.core import supernet as SN
from repro.federated import bucketing as BK
from repro.federated import metrics as MET
from repro.federated.strategies import base
from repro.federated.strategies.base import (CohortResult, RoundContext,
                                             Strategy, register_strategy)
from repro.launch.sharding import P, slot_pspec
from repro.models import model as M
from repro.optim import apply_updates


def _cohort_specs(axes, d, client_stack, server_stack,
                  images, labels, idx, avail, valid, srv_state):
    """shard_map layout: client/server stacks and masks shard their slot
    axis; the runtime depth scalar and flat dataset replicate.
    ``srv_state`` mixes per-slot moment stacks (sharded) with shared
    bookkeeping scalars (replicated) — the split mirrors
    ``optim.map_moments``."""
    slot = slot_pspec(0, axes)
    sdef = jax.tree_util.tree_structure(server_stack)
    srv_spec = {k: (jax.tree.map(lambda _: slot, v)
                    if jax.tree_util.tree_structure(v) == sdef else
                    jax.tree.map(lambda _: P(), v))
                for k, v in srv_state.items()} \
        if isinstance(srv_state, dict) else P()
    in_specs = (P(), slot, slot, P(), P(), slot_pspec(1, axes),
                slot, slot, srv_spec)
    out_specs = (slot, slot, srv_spec, slot)
    return in_specs, out_specs


@BK.register_kernel(n_static=4, specs=_cohort_specs)
def cohort_kernel(cfg: ModelConfig, opt, steps: int, width: float, d,
                  client_stack, server_stack,
                  images, labels, idx, avail, valid, srv_state,
                  axis_name=None):
    """All ``steps`` server-grad-only steps for one padded cohort bucket
    sharing runtime depth ``d`` and width tier ``width``, as a single
    compiled scan.

    ``d`` is a RUNTIME jax scalar: both stacks hold all ``L`` split-stack
    rows per slot, the client/server forwards are the masked prefix/suffix
    scans (``model.run_stack``, bit-exact vs the static slices), and
    ``supernet.depth_freeze`` reverts every optimizer touch of an
    out-of-window row — one compiled program per (width, bucket) covers
    every depth tier. The ephemeral client-stack optimizer state
    initializes inside the kernel; ``srv_state`` is the persistent FULL
    server moments broadcast onto the [Nc]-stacked copies (rows ``< d``
    ride along frozen). ``avail`` is False on padded slots (they can
    never step), ``valid`` marks real clients. ``axis_name`` is bound to
    the fleet mesh axes under the shard-mapped variant, so the freeze gate
    sees every shard's slots. ``width`` is STATIC — the compile key is
    (width, bucket); at ``width < 1`` the client stack is the
    ``supernet.slice_width`` view and the forward runs on the slice.
    """

    wcfg = SN.width_cfg(cfg, width)
    anyav = BK.freeze_gate(avail, valid, axis_name)

    def one(cp, sp, b, av):
        def loss_fn(cp_, sp_):
            z, _ = M.client_apply(wcfg, cp_, b, length=d)
            return M.server_split_loss(cfg, sp_, z, b, length=d)

        loss, (gc, gs) = jax.value_and_grad(loss_fn, argnums=(0, 1))(cp, sp)
        zero = lambda t: jax.tree.map(
            lambda g: jnp.where(av, g, jnp.zeros_like(g)), t)
        return zero(gc), zero(gs), loss

    def step(carry, idx_t):
        cstack, sstack, eph_state, s_state = carry
        BK.guard_gather(idx_t, images.shape[0])   # sanitize-mode OOB check
        batch = {"images": images[idx_t], "label": labels[idx_t]}
        gc, gs, loss = jax.vmap(one, in_axes=(0, 0, 0, 0))(
            cstack, sstack, batch, avail)
        eph_updates, eph_state = opt.update(gc, eph_state, cstack)
        srv_updates, new_s_state = opt.update(gs, s_state, sstack)
        # a stalled client gets a bit-exact zero update on BOTH sides: its
        # zeroed gradient must not turn into a momentum-decay or
        # weight-decay step, and its carried server moments stay frozen (so
        # they don't contaminate the round-end mean); shared bookkeeping
        # (step counter) advances only if anyone is live
        row = lambda x: avail.reshape((-1,) + (1,) * (x.ndim - 1))
        zero_stalled = lambda tree: jax.tree.map(
            lambda u: jnp.where(row(u), u, jnp.zeros_like(u)), tree)
        eph_updates = zero_stalled(eph_updates)
        srv_updates = zero_stalled(srv_updates)
        new_c = apply_updates(cstack, eph_updates)
        new_s = apply_updates(sstack, srv_updates)
        # runtime-depth row freeze: out-of-window rows of every per-slot
        # stack (params AND server moments) must be bit-exact no-ops so
        # the host's d=0 opt-state round trip and the fold accumulators
        # stay on the legacy contract
        new_c = SN.depth_freeze(cfg, new_c, cstack, d, keep="prefix",
                                axis=1)
        new_s = SN.depth_freeze(cfg, new_s, sstack, d, keep="suffix",
                                axis=1)
        new_s_state = _gate_server_state(new_s_state, s_state, sstack,
                                         avail, anyav)
        s_state = SN.depth_freeze(cfg, new_s_state, s_state, d,
                                  keep="suffix", axis=1)
        return ((new_c, new_s, eph_state, s_state), loss)

    eph_state = opt.init(client_stack)
    carry = (client_stack, server_stack, eph_state, srv_state)
    (cstack, sstack, _, srv_state), loss = jax.lax.scan(step, carry, idx)
    return cstack, sstack, srv_state, loss[-1]


def _gate_server_state(new, old, params_stack, avail, anyav):
    """Per-client freeze of stacked server moments: keep the updated entry
    only for live clients; bookkeeping scalars advance iff any real client
    is live. Mirrors the optimizer-state contract (``optim.map_moments``)."""
    if not isinstance(new, dict):
        return new
    row = lambda x: avail.reshape((-1,) + (1,) * (x.ndim - 1))
    pdef = jax.tree_util.tree_structure(params_stack)
    out = {}
    for k, v in new.items():
        if jax.tree_util.tree_structure(v) == pdef:
            out[k] = jax.tree.map(lambda a, b: jnp.where(row(a), a, b),
                                  v, old[k])
        else:
            out[k] = jax.tree.map(lambda a, b: jnp.where(anyav, a, b),
                                  v, old[k])
    return out


class SplitFedBase(Strategy):
    """Shared SFL/DFL round logic; subclasses pick split + weighting."""

    def client_weights(self, depths, mask):
        """[N] aggregation weights over the full fleet; ``mask`` marks the
        clients that trained this round (weights must be 0 elsewhere)."""
        raise NotImplementedError

    def init_round(self, engine, ctx: RoundContext) -> Dict[str, Any]:
        cfg, state = engine.cfg, engine.state
        sname = SN.split_stack_name(cfg)
        ws = base.fleet_workspace(engine)
        # accumulators for FedAvg over per-client server copies
        ws.update({"num_stack": jax.tree.map(
                       lambda x: jnp.zeros_like(x, jnp.float32),
                       state.params[sname]),
                   "den_rows": np.zeros(cfg.split_stack_len),
                   "num_other": {},
                   "den_other": 0})
        return ws

    def cohort_step(self, engine, ctx, ws, d, ids) -> CohortResult:
        """Split the depth-``d`` cohort into same-width sub-cohorts (the
        width is a static kernel arg — compile key (width, bucket)) and
        CHAIN them through the shared server moments: each group's
        per-client server copies start from the previous group's
        fed-averaged moments. Depth rides the kernel as a runtime scalar
        over full-``L`` views, so re-tiered fleets reuse the same
        compiled programs."""
        cfg, state = engine.cfg, engine.state
        sname = SN.split_stack_name(cfg)
        client_p, server_p, _ = SN.split_params(cfg, state.params, None)
        srv_template, srv_full, srv_slice = base.cohort_server_opt(
            engine, cfg, sname, 0)
        folds, losses, csum = [], None, 0
        from repro.federated.strategies.ssfl import SuperSFL
        for w, gids in SuperSFL._width_groups(engine, ids):
            group_p = client_p if w >= 1.0 else \
                SN.split_params(cfg, state.params, None, w)[0]
            sstack, valid, srv_slice, losses = self._run_subcohort(
                engine, ctx, ws, d, gids, group_p, server_p,
                srv_slice, width=w)
            folds.append((sstack, valid, len(gids)))
            csum += len(gids) * base.split_param_counts(
                cfg, state.params, d, w)[0]
        state.opt_state["server"] = base.merge_server_opt(
            srv_full, srv_slice, srv_template, sname, 0)
        cparams = csum // max(len(ids), 1)
        sparams = base.split_param_counts(cfg, state.params, d)[1]
        return CohortResult(cparams, sparams, payload=folds, losses=losses)

    def _run_subcohort(self, engine, ctx, ws, d, ids, client_p, server_p,
                       srv_slice, width: float = 1.0):
        """One bucketed kernel call for a same-width group: broadcast the
        full server view/moments onto per-client copies, run all local
        steps, fed-average the moments back (rows ``< d`` are restored
        from the chained input — the kernel froze them, and a
        mean-of-identical-copies is not guaranteed bit-exact). ``client_p``
        must already be the width-``width`` slice when ``width < 1``.
        Returns ``(sstack, valid, srv_slice, losses)`` so callers can
        chain groups through the shared moments."""
        cfg, state = engine.cfg, engine.state
        n = state.n_clients
        bucket = engine.bucket_for(len(ids))
        pids = jnp.asarray(BK.pad_ids(np.asarray(ids), bucket, n))
        valid = jnp.asarray(np.arange(bucket) < len(ids))
        avail = jnp.asarray(BK.pad_rows(
            np.asarray(ctx.avail[ids], bool), bucket, fill=False))
        idx = jnp.asarray(BK.pad_slot_axis(
            ctx.sample_indices(ids, engine.local_steps, engine.batch_size),
            bucket, axis=1))
        bcast = lambda t: jax.tree.map(
            lambda x: jnp.broadcast_to(x, (bucket,) + x.shape), t)
        cstack, sstack = bcast(client_p), bcast(server_p)
        srv_state = base.broadcast_server_opt(srv_slice, server_p, bucket)
        dd = engine.device_data
        kernel = engine.launch_kernel(cohort_kernel, bucket, width=width,
                                      real=len(ids))
        cstack, sstack, srv_state, loss = kernel(
            cfg, engine.optimizer, engine.local_steps, width,
            jnp.int32(d), cstack, sstack, dd.images, dd.labels, idx,
            avail, valid, srv_state)
        srv_mean = base.mean_server_opt(srv_state, server_p, valid=valid)
        srv_slice = SN.depth_freeze(cfg, srv_mean, srv_slice, d,
                                    keep="suffix")
        base.scatter_client_rows(cfg, ws, pids, cstack, d, width)
        base.record_cohort(ws, pids, loss)
        return sstack, valid, srv_slice, loss

    def fold_server(self, engine, ws, d, ids, res) -> None:
        """Fold each sub-cohort's server copies into the FedAvg
        accumulators (padded bucket slots are masked out of every sum).
        The payload stacks are full-``L`` (runtime-depth kernel); only the
        trained suffix rows [d:] accumulate — rows < d are frozen
        broadcast copies."""
        sname = SN.split_stack_name(engine.cfg)
        for sstack, valid, count in res.payload:
            msum = lambda x: jnp.sum(
                jnp.where(valid.reshape((-1,) + (1,) * (x.ndim - 1)),
                          x.astype(jnp.float32), 0.0), axis=0)
            ws["num_stack"] = jax.tree.map(
                lambda acc, s: acc.at[d:].add(msum(s)[d:]),
                ws["num_stack"], sstack[sname])
            ws["den_rows"][d:] += count
            for k, v in sstack.items():
                if k == sname:
                    continue
                add = jax.tree.map(msum, v)
                ws["num_other"][k] = add if k not in ws["num_other"] \
                    else jax.tree.map(lambda a, b: a + b,
                                      ws["num_other"][k], add)
            ws["den_other"] += count

    def aggregate(self, engine, ws):
        cfg, state = engine.cfg, engine.state
        sname = SN.split_stack_name(cfg)
        # FedAvg the per-client server copies into the server view
        den_rows = ws["den_rows"]
        den = jnp.asarray(np.maximum(den_rows, 1e-9))
        server_view: Dict[str, Any] = {sname: jax.tree.map(
            lambda n, g: jnp.where(
                (den_rows > 0).reshape((-1,) + (1,) * (n.ndim - 1)),
                n / den.reshape((-1,) + (1,) * (n.ndim - 1)),
                g.astype(jnp.float32)).astype(g.dtype),
            ws["num_stack"], state.params[sname])}
        for k, v in ws["num_other"].items():
            server_view[k] = jax.tree.map(
                lambda n, g: (n / max(ws["den_other"], 1)).astype(g.dtype),
                v, state.params[k])
        widths = getattr(state.fleet, "widths", None)
        return self._finish_aggregation(
            engine, ws, server_view,
            lambda g, s, dep, l, m: AGG.aggregate_weighted(
                cfg, g, s, dep, self.client_weights(dep, m), mask=m,
                widths=widths))

    def comm_cost(self, engine, d, available, ids=None):
        # SplitFed ships BOTH client- and server-side nets through the fed
        # server each round; a stalled client moves no useful bytes
        pbytes = MET.tree_bytes(engine.state.params)
        total = 2 * pbytes + 2 * engine.smashed_bytes(d) * engine.local_steps
        return (total if available else 0, 2 + 2 * engine.local_steps)


@register_strategy("sfl")
class SplitFed(SplitFedBase):

    def fixed_depth(self, cfg):
        # SplitFed's rigid split: one fixed point (mid-stack) for everyone
        return max(cfg.split_stack_len // 2, 1)

    def client_weights(self, depths, mask):
        mask = np.asarray(mask, np.float32)
        return jnp.asarray(mask / mask.sum())


@register_strategy("dfl")
class DynamicSplitFed(SplitFedBase):

    def client_weights(self, depths, mask):
        w = depths.astype(np.float32) * np.asarray(mask, np.float32)
        return jnp.asarray(w / w.sum())
