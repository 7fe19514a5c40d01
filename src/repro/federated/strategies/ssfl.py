"""SuperSFL — the paper's method, as an engine strategy.

Resource-aware depths (Eq. 1), TPGF gradient fusion (Alg. 2),
fault-tolerant fallback (Alg. 3), Eq. 6/8 client-server aggregation.
ONE shared main-server model per round, updated with each cohort's pooled
gradient (Alg. 2 line 11).

Execution is device-resident and bounded-compile: ``cohort_kernel`` runs
ALL local steps for a padded cohort bucket under one ``jax.lax.scan``,
gathering batches on device from the flat dataset by index
(``data.synthetic.DeviceData``), so one compiled program per
(width, bucket, batch size, steps) covers every cohort shape the fleet can
produce — depth is a RUNTIME argument (masked scan over the full layer
stack, see ``model.run_stack``), so per-round depth re-tuning never
recompiles. Padded slots are masked out of the pooled server gradient,
carry ``avail=False`` (they can never unfreeze the server), and their
outputs are dropped at the sentinel-id scatter (see
``federated.bucketing``).

Optimizer state is split the same way the parameters are: the client /
local-head groups are re-initialized per cohort (clients re-download their
subnetwork every round, so momentum has nothing to carry), while the shared
server branch's moments persist across rounds in
``TrainState.opt_state["server"]`` and stream through cohorts in cohort
order — the moment-space mirror of Alg. 2's pooled sequential server
update. See ``strategies.base.server_opt_state``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import aggregation as AGG
from repro.core import supernet as SN
from repro.core import tpgf as T
from repro.federated import bucketing as BK
from repro.federated import tracing
from repro.federated.strategies import base
from repro.federated.strategies.base import (CohortResult, RoundContext,
                                             Strategy, register_strategy)
from repro.launch.sharding import P, slot_pspec
from repro.optim import apply_updates


def _cohort_specs(axes, d, client_stack, local_stack, server_p,
                  images, labels, idx, avail, valid, srv_state):
    """shard_map layout: slot-leading stacks and masks shard over the
    fleet axes, the runtime depth scalar, the shared server tree / moments
    and the flat dataset replicate; outputs mirror the inputs (per-slot
    losses stay sharded)."""
    slot = slot_pspec(0, axes)
    in_specs = (P(), slot, slot, P(), P(), P(), slot_pspec(1, axes),
                slot, slot, P())
    out_specs = (slot, slot, P(), P(), slot, slot)
    return in_specs, out_specs


@BK.register_kernel(n_static=4, specs=_cohort_specs)
def cohort_kernel(cfg: ModelConfig, opt, steps: int, width: float, d,
                  client_stack, local_stack, server_p,
                  images, labels, idx, avail, valid, srv_state,
                  axis_name=None):
    """All ``steps`` TPGF local steps for one padded cohort bucket of
    runtime depth ``d`` and width tier ``width``, as one compiled scan.

    ``d`` is a RUNTIME jax scalar, not a static key: client_stack and
    server_p both hold all ``L`` split-stack rows, the masked scans in
    ``model.run_stack`` apply only the in-window layers (prefix rows
    ``< d`` client-side, suffix rows ``>= d`` server-side, bit-exact vs
    the static slice), and ``supernet.depth_freeze`` reverts every
    optimizer touch of an out-of-window row — so ONE compiled program per
    (width, bucket, batch shape) serves every depth tier the fleet can
    produce. Client moment rows ``>= d`` stay exactly zero on their own
    (zero grads into zero-initialized ephemeral moments); the param rows
    still freeze because decoupled weight decay would move them.

    client_stack/local_stack: [Nc, ...] stacked client/local param trees
    (Nc = bucket size, or bucket/shards under shard_map); at ``width < 1``
    the client stack is the ``supernet.slice_width`` view (full-``L``
    rows, sliced channels) so the pruned coordinates are never
    materialized. server_p: shared server tree (always full-width — the
    smashed data is full ``d_model``). images/labels: the flat
    device-resident dataset; idx: [steps, Nc, B] flat sample indices
    (batches are gathered on device each step). avail: [Nc] bool, server
    reachable (False on padded slots). valid: [Nc] bool, real-client
    slots. ``opt`` is a ``repro.optim.Optimizer``; the ephemeral
    client/local state is initialized inside the kernel, ``srv_state`` is
    the cross-round FULL shared server branch state and threads through
    the scan (rows ``< d`` ride along frozen). ``axis_name`` is the fleet
    mesh axes when the kernel runs shard-mapped (cross-slot reductions
    then span every shard; see ``federated.bucketing``). ``width`` is
    STATIC — the compile key is (width, bucket).
    """

    wcfg = SN.width_cfg(cfg, width)

    # a padded slot can never unfreeze the server; avail is already forced
    # False there, but guard with valid too so the invariant cannot depend
    # on the caller's padding discipline
    anyav = BK.freeze_gate(avail, valid, axis_name)

    def step(carry, idx_t):
        cstack, lstack, srv_p, eph_state, s_state = carry
        with jax.named_scope("cohort_kernel.gather"):
            BK.guard_gather(idx_t, images.shape[0])   # sanitize-mode check
            batch = {"images": images[idx_t], "label": labels[idx_t]}

        def one(cp, lp, b, av):
            # closes over the CARRY's server params: each local step sees
            # the pooled server update of the previous step (Alg. 2)
            out = T.tpgf_grads_split(cfg, wcfg, cp, srv_p, lp, b, d,
                                     server_available=av)
            return (out.g_client, out.g_server, out.g_local,
                    out.loss_client, out.loss_server)

        gc, gs, gl, l_c, l_s = jax.vmap(one, in_axes=(0, 0, 0, 0))(
            cstack, lstack, batch, avail)
        with jax.named_scope("cohort_kernel.update"):
            # SuperSFL (Alg. 2 line 11): ONE shared main-server model,
            # updated with the cohort's pooled gradient as the smashed
            # batches stream in. Padded slots contribute zero to the pool
            # and are excluded from the denominator; under shard_map the
            # mean spans every shard.
            gs_mean = BK.masked_slot_mean(gs, valid, axis_name)
            eph_groups = {"client": cstack, "local": lstack}
            eph_updates, eph_state = opt.update({"client": gc, "local": gl},
                                                eph_state, eph_groups)
            srv_updates, new_s_state = opt.update(gs_mean, s_state, srv_p)
            new = apply_updates(eph_groups, eph_updates)
            new_server = apply_updates(srv_p, srv_updates)
            # runtime-depth row freeze: out-of-window stack rows must be a
            # bit-exact no-op so the host's d=0 opt-state round trip and
            # the aggregation's zero-pad contract both hold
            new_client = SN.depth_freeze(cfg, new["client"], cstack, d,
                                         keep="prefix", axis=1)
            new_server = SN.depth_freeze(cfg, new_server, srv_p, d,
                                         keep="suffix")
            new_s_state = SN.depth_freeze(cfg, new_s_state, s_state, d,
                                          keep="suffix")
            # fault-tolerance invariant (tpgf "frozen server"): a cohort
            # that never reached the server must be a bit-exact server
            # no-op — carried moments would otherwise still step the
            # params (momentum decay) and advance
            freeze = lambda n_, o: jax.tree.map(
                lambda a, b_: jnp.where(anyav, a, b_), n_, o)
            new_server = freeze(new_server, srv_p)
            s_state = freeze(new_s_state, s_state)
        return ((new_client, new["local"], new_server, eph_state,
                 s_state), (l_c, l_s))

    eph_state = opt.init({"client": client_stack, "local": local_stack})
    carry = (client_stack, local_stack, server_p, eph_state, srv_state)
    (cstack, lstack, server_p, _, srv_state), (l_c, l_s) = jax.lax.scan(
        step, carry, idx)
    return cstack, lstack, server_p, srv_state, l_c[-1], l_s[-1]


@register_strategy("ssfl")
class SuperSFL(Strategy):

    def init_round(self, engine, ctx: RoundContext) -> Dict[str, Any]:
        sname = SN.split_stack_name(engine.cfg)
        params = engine.state.params
        ws = base.fleet_workspace(engine)
        # running server view: full-L split stack + non-stack server leaves
        ws["server_view"] = {sname: jax.tree.map(lambda x: x,
                                                 params[sname])}
        return ws

    @staticmethod
    def _width_groups(engine, ids):
        """Order-preserving same-width sub-cohorts: jit kernels need one
        static width per call, so a width-heterogeneous cohort becomes
        several kernel launches chained through the shared server branch
        (exactly how hasfl chains same-batch groups). A homogeneous
        full-width fleet yields the single group ``[(1.0, ids)]`` — the
        legacy call sequence, bit-exact."""
        widths = getattr(engine.state.fleet, "widths", None)
        ids = np.asarray(ids)
        if widths is None:
            return [(1.0, ids)]
        groups: Dict[float, list] = {}
        for i in ids:
            groups.setdefault(float(widths[i]), []).append(int(i))
        return [(w, np.asarray(g)) for w, g in sorted(groups.items())]

    def cohort_step(self, engine, ctx, ws, d, ids) -> CohortResult:
        cfg, state = engine.cfg, engine.state
        sname = SN.split_stack_name(cfg)
        with tracing.span("strategy.split"):
            # runtime depth: full-L views into the one compiled kernel per
            # (width, bucket); the kernel masks/freezes rows by the traced d
            client_p, server_p, _ = SN.split_params(cfg, state.params, None)
            # the shared server branch's moments persist across rounds:
            # hand the kernel the WHOLE state (d=0 slice = full copy) — it
            # freezes moment rows < d in-kernel, so the d=0 merge below is
            # bit-equal to the legacy depth-sliced round trip
            srv_template, srv_full, srv_state = base.cohort_server_opt(
                engine, cfg, sname, 0)
        losses = None
        csum = 0
        groups = self._width_groups(engine, ids)
        fused = len(groups) > 1 and engine.cross_tier == "fused"
        tiers, tier_states, live = [], [], []
        base_server, base_state = server_p, srv_state
        for w, gids in groups:
            if w >= 1.0:
                group_p = client_p
            else:
                with tracing.span("strategy.split"):
                    group_p = SN.split_params(cfg, state.params, None, w)[0]
            # fused: every tier starts from the SAME server snapshot;
            # chained (legacy / comparator): from the previous tier's
            src = (base_server, base_state) if fused \
                else (server_p, srv_state)
            server_p, srv_state, losses, mass = self._run_subcohort(
                engine, ctx, ws, d, gids, group_p, src[0], src[1],
                width=w)
            if fused:
                tiers.append(T.TierUpdate(1.0, mass, server_p))
                tier_states.append(srv_state)
                live.append(bool(ctx.avail[gids].any()))
            csum += len(gids) * base.split_param_counts(
                cfg, state.params, d, w)[0]
        if fused:
            # ONE cross-tier TPGF update: the server branch is full-width
            # (the smashed data is full d_model), so each tier enters at
            # width 1.0 with its Eq. 6-style mass — summed inverse fused
            # losses of its live clients — and delta-mode fuse_tiers
            # keeps an all-frozen cohort a bit-exact server no-op
            with tracing.span("strategy.fuse_tiers"):
                server_p = T.fuse_tiers(cfg, tiers, base=base_server,
                                        use_pallas=cfg.use_pallas)
                srv_state = self._fuse_server_state(
                    cfg, base_state, tier_states,
                    [t.weight for t in tiers], live, base_server)
        with tracing.span("strategy.merge_server_opt"):
            state.opt_state["server"] = base.merge_server_opt(
                srv_full, srv_state, srv_template, sname, 0)
        cparams = csum // max(len(ids), 1)
        sparams = base.split_param_counts(cfg, state.params, d)[1]
        return CohortResult(cparams, sparams, payload=server_p,
                            losses=losses)

    @staticmethod
    def _fuse_server_state(cfg, base_state, tier_states, masses, live,
                           server_tpl):
        """Cross-tier fusion of the shared server optimizer state.

        Moment entries (dicts mirroring the server branch tree, the
        ``optim.map_moments`` criterion) fuse in delta mode with the same
        tier masses as the parameters, so moments and params move under
        one law. Bookkeeping entries (AdamW's ``t``) are not averageable:
        every live tier stepped the same count from the same base, so the
        first live tier's value is taken — and the base's when the whole
        cohort was frozen, keeping the no-op bit-exact. ``live`` comes
        from the host-side availability draw (no device sync)."""
        if not isinstance(base_state, dict):
            return base_state                      # stateless (sgd)
        pdef = jax.tree_util.tree_structure(server_tpl)
        first_live = next((i for i, lv in enumerate(live) if lv), None)
        out = {}
        for k, bv in base_state.items():
            if jax.tree_util.tree_structure(bv) == pdef:
                out[k] = T.fuse_tiers(
                    cfg, [T.TierUpdate(1.0, m, ts[k])
                          for m, ts in zip(masses, tier_states)], base=bv)
            else:
                out[k] = bv if first_live is None \
                    else tier_states[first_live][k]
        return out

    def _run_subcohort(self, engine, ctx, ws, d, ids, client_p, server_p,
                       srv_state, batch_size: int = None,
                       width: float = 1.0):
        """All local steps for ``ids`` in ONE bucketed kernel call:
        ephemeral client/local optimizer state, threaded server params +
        moments, on-device batch gather. ``client_p`` must already be the
        width-``width`` slice when ``width < 1``. Returns the updated
        ``(server_p, srv_state, losses, mass)`` so callers can chain
        sub-cohorts (HASFL's same-depth batch groups, width tiers)
        through the shared branch — ``mass`` is the group's Eq. 6-style
        tier weight for cross-tier fusion: summed inverse fused losses
        over the slots that actually reached the server (an all-frozen
        group has mass exactly 0, so ``fuse_tiers`` no-ops it)."""
        cfg, state = engine.cfg, engine.state
        bs = engine.batch_size if batch_size is None else batch_size
        n = state.n_clients
        bucket = engine.bucket_for(len(ids))
        with tracing.span("strategy.stage", width=float(width),
                          bucket=bucket):
            pids = jnp.asarray(BK.pad_ids(np.asarray(ids), bucket, n))
            valid = jnp.asarray(np.arange(bucket) < len(ids))
            avail = jnp.asarray(BK.pad_rows(
                np.asarray(ctx.avail[ids], bool), bucket, fill=False))
            idx = jnp.asarray(BK.pad_slot_axis(
                ctx.sample_indices(ids, engine.local_steps, bs), bucket,
                axis=1))
            cstack = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (bucket,) + x.shape), client_p)
            lstack = base.gather_rows(state.local_heads, pids)
            dd = engine.device_data
            d_arr = jnp.int32(d)
        kernel = engine.launch_kernel(cohort_kernel, bucket, width=width,
                                      real=len(ids))
        cstack, lstack, server_p, srv_state, l_c, l_s = kernel(
            cfg, engine.optimizer, engine.local_steps, width,
            d_arr, cstack, lstack, server_p, dd.images, dd.labels,
            idx, avail, valid, srv_state)
        with tracing.span("strategy.publish"):
            # heads + client trees scatter back (padded slots drop at the
            # sentinel ids), per-slot losses stay on device
            state.local_heads = base.scatter_rows(state.local_heads, pids,
                                                  lstack)
            base.scatter_client_rows(cfg, ws, pids, cstack, d, width)
            losses = jnp.where(
                avail,
                T.fused_loss(l_c, l_s, d, cfg.split_stack_len - d,
                             cfg.tpgf_eps, cfg.tpgf_variant),
                l_c)
            base.record_cohort(ws, pids, losses)
            # Eq. 6-style tier mass for cross-tier fusion: inverse fused
            # loss, where-guarded over the slots that reached the server
            # (padded and unreachable slots contribute exactly 0 — FL002
            # contract)
            mass = jnp.sum(jnp.where(valid & avail,
                                     1.0 / (losses + cfg.tpgf_eps), 0.0))
        return server_p, srv_state, losses, mass

    def fold_server(self, engine, ws, d, ids, res) -> None:
        # the cohort's payload stack is full-L (runtime-depth kernel);
        # rows < d rode along frozen, so only the trained suffix folds in
        sname = SN.split_stack_name(engine.cfg)
        server_p, sv = res.payload, ws["server_view"]
        sv[sname] = jax.tree.map(
            lambda full, nd: jnp.concatenate([full[:d], nd[d:]], axis=0),
            sv[sname], server_p[sname])
        for k, v in server_p.items():
            if k != sname:
                sv[k] = v

    def aggregate(self, engine, ws):
        # Eq. 6 weights (depth x inverse fused loss) + Eq. 8 averaging;
        # per-coordinate width denominators kick in only when some client
        # trained a width-sliced tier (homogeneous fleets: legacy path)
        widths = getattr(engine.state.fleet, "widths", None)
        return self._finish_aggregation(
            engine, ws, ws["server_view"],
            lambda g, s, dep, l, m: AGG.aggregate(engine.cfg, g, s, dep, l,
                                                  mask=m, widths=widths)[0])

    def comm_cost(self, engine, d, available, ids=None):
        # only the client subnetwork crosses the network (paper §III-C);
        # ssfl fallback mode skips the smashed-activation traffic. The
        # smashed data is full d_model at every width tier, so only the
        # parameter download scales with width.
        per_step = 2 * engine.smashed_bytes(d) if available else 0
        msgs = 2 + 2 * engine.local_steps
        widths = getattr(engine.state.fleet, "widths", None)
        hetero = widths is not None and bool(
            (np.asarray(widths) < 1.0).any())
        if ids is not None and hetero:
            pbytes = np.array(
                [SN.client_param_bytes(engine.cfg, engine.state.params, d,
                                       float(widths[i]))
                 for i in np.asarray(ids)], np.int64)
            return (2 * pbytes + engine.local_steps * per_step,
                    np.full(len(pbytes), msgs, np.int64))
        pbytes = SN.client_param_bytes(engine.cfg, engine.state.params, d)
        return 2 * pbytes + engine.local_steps * per_step, msgs
