"""HASFL-style heterogeneity-aware batch/split co-tuning (Lin et al.).

SuperSFL's training loop, with the fleet re-tuned EVERY round: instead of
fixing each client at its Eq. 1 memory-capacity depth with one global batch
size, the strategy jointly picks a (split depth, batch size) pair per
client from the device model's compute/communication cost estimates
(``repro.core.allocation.co_tune``), so fast devices grow their batches
while stragglers shed depth/batch instead of stalling the synchronous
round barrier.

The solver runs in ``init_round`` — the per-round analogue of
``prepare_fleet`` (it needs the live parameter tree for per-depth parameter
counts, which the construction-time hook does not see). Depths are written
back into ``fleet.depths`` (never above ``fleet.capacity``, so every
assignment stays feasible), and ``cohort_step`` splits each same-depth
cohort into same-batch sub-cohorts: jit kernels need one batch shape per
call, so heterogeneity *within* a cohort becomes several kernel launches
chained through the shared server branch — each group continues from the
previous group's server params and optimizer moments (Alg. 2 line 11's
pooled sequential update, at sub-cohort granularity).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import allocation as AL
from repro.core import supernet as SN
from repro.federated.strategies import base
from repro.federated.strategies.base import (CohortResult, RoundContext,
                                             register_strategy)
from repro.federated.strategies.ssfl import SuperSFL


@register_strategy("hasfl")
class HASFL(SuperSFL):
    """Per-round joint depth/batch co-tuning on the SuperSFL round."""

    def __init__(self, batch_choices=(4, 8, 16, 32),
                 time_budget_factor: float = 1.0, width_tiers=None):
        self.batch_choices = tuple(batch_choices)
        self.time_budget_factor = time_budget_factor
        # optional supernet width ladder, e.g. (0.5, 0.75, 1.0): co_tune
        # then emits a per-client width tier beside (depth, batch) and the
        # tiers land in fleet.widths. None keeps the depth/batch-only
        # solve (and the legacy goldens) untouched.
        self.width_tiers = None if width_tiers is None \
            else tuple(sorted(width_tiers))
        self._dm = None
        self._bs: np.ndarray = None        # [N] per-client batch size

    # ------------------------------------------------------- fleet tuning
    def prepare_fleet(self, cfg, fleet, device_model=None) -> None:
        """Record the device model; the actual (depth, batch) solve runs in
        ``init_round`` each round, where the parameter tree is available."""
        self._dm = device_model

    def retune(self, engine) -> None:
        """Re-solve every client's (split depth, batch size) from the cost
        model. Idempotent while profiles are static; profile drift or a
        changed device model is picked up the next round."""
        cfg, fleet = engine.cfg, engine.state.fleet
        dm = self._dm or engine.accountant.dm
        params = engine.state.params
        sname = SN.split_stack_name(cfg)
        per_layer = sum(int(x.size) // x.shape[0]
                        for x in jax.tree.leaves(params[sname]))
        input_side = sum(int(x.size) for x in jax.tree.leaves(
            SN.split_params(cfg, params, 0)[0]))
        counts = np.array([input_side + d * per_layer
                           for d in range(cfg.split_stack_len + 1)])
        tps = engine.tokens_per_sample()
        tuned = AL.co_tune(
            fleet.capacity,
            [p.mem_gb for p in fleet.profiles],
            [p.lat_ms for p in fleet.profiles],
            counts, tps, tps * cfg.d_model * 4,
            batch_choices=self.batch_choices,
            base_batch=engine.batch_size,
            time_budget_factor=self.time_budget_factor,
            gflops_per_mem=dm.client_gflops_per_mem,
            bandwidth_mb_s=dm.bandwidth_mb_s,
            width_tiers=self.width_tiers)
        if self.width_tiers is not None:
            depths, self._bs, fleet.widths = tuned
        else:
            depths, self._bs = tuned
        fleet.depths = depths
        fleet.feasible = fleet.depths <= fleet.capacity

    # ------------------------------------------------------- round phases
    def init_round(self, engine, ctx: RoundContext) -> Dict[str, Any]:
        self.retune(engine)
        return super().init_round(engine, ctx)

    def cohort_step(self, engine, ctx, ws, d, ids) -> CohortResult:
        """Split the depth-``d`` cohort into same-batch sub-cohorts (jit
        kernels need one batch shape per call) and CHAIN them through the
        shared server branch: each group starts from the previous group's
        server params and moments, so no sub-cohort's server compute is
        overwritten. The engine folds the final result once. Each sub-group
        is itself bucketed and depth rides the kernel as a RUNTIME scalar,
        so the compile key is (width, bucket, batch choice) — independent
        of how re-tuning reshuffles the fleet's depths — and
        under ``Engine(mesh=...)`` each group rides the shared ssfl
        kernel's shard_map variant (sub-group buckets round up to whole
        slots per shard like any other cohort)."""
        cfg, state = engine.cfg, engine.state
        sname = SN.split_stack_name(cfg)
        # runtime depth: full-L views + full opt state (d=0), exactly as
        # in SuperSFL.cohort_step — re-tuned depths reuse the same
        # compiled (width, bucket, batch) kernels
        client_p, server_p, _ = SN.split_params(cfg, state.params, None)
        srv_template, srv_full, srv_state = base.cohort_server_opt(
            engine, cfg, sname, 0)
        widths = getattr(state.fleet, "widths", None)
        groups: Dict[tuple, list] = {}
        for i in np.asarray(ids):
            w = 1.0 if widths is None else float(widths[i])
            groups.setdefault((int(self._bs[i]), w), []).append(int(i))
        wkeys = sorted({w for _, w in groups})
        if len(wkeys) > 1 and engine.cross_tier == "fused":
            # cross-tier TPGF at width-tier granularity: batch groups
            # WITHIN a tier still chain (same slice, Alg. 2's sequential
            # pooled update), but every tier starts from the same server
            # snapshot and the per-tier results fuse into ONE update —
            # the tier mass is the sum of its batch groups' masses
            from repro.core import tpgf as T
            base_server, base_state = server_p, srv_state
            tiers, tier_states, live = [], [], []
            for w in wkeys:
                group_p = client_p if w >= 1.0 else \
                    SN.split_params(cfg, state.params, None, w)[0]
                t_server, t_state = base_server, base_state
                mass, any_live = jnp.float32(0.0), False
                for (b, w2), gids in sorted(groups.items()):
                    if w2 != w:
                        continue
                    t_server, t_state, _, m = self._run_subcohort(
                        engine, ctx, ws, d, np.asarray(gids), group_p,
                        t_server, t_state, batch_size=b, width=w)
                    mass = mass + m
                    any_live = any_live or bool(ctx.avail[gids].any())
                tiers.append(T.TierUpdate(1.0, mass, t_server))
                tier_states.append(t_state)
                live.append(any_live)
            server_p = T.fuse_tiers(cfg, tiers, base=base_server,
                                    use_pallas=cfg.use_pallas)
            srv_state = self._fuse_server_state(
                cfg, base_state, tier_states,
                [t.weight for t in tiers], live, base_server)
        else:
            for (b, w), gids in sorted(groups.items()):
                group_p = client_p if w >= 1.0 else \
                    SN.split_params(cfg, state.params, None, w)[0]
                server_p, srv_state, _, _ = self._run_subcohort(
                    engine, ctx, ws, d, np.asarray(gids), group_p,
                    server_p, srv_state, batch_size=b, width=w)
        state.opt_state["server"] = base.merge_server_opt(
            srv_full, srv_state, srv_template, sname, 0)
        cparams, sparams = base.split_param_counts(cfg, state.params, d)
        mean_b = float(np.mean([self._bs[i] for i in np.asarray(ids)]))
        return CohortResult(cparams, sparams, payload=server_p,
                            tokens_per_batch=int(
                                mean_b * engine.tokens_per_sample()))

    # -------------------------------------------------------- accounting
    def comm_cost(self, engine, d, available, ids=None):
        """ssfl's cost with the smashed traffic scaled to each client's
        *tuned* batch size: with ``ids`` the engine gets exact per-client
        pricing (arrays aligned with ``ids``); without, the fleet-wide mean
        for this depth keeps legacy callers working."""
        pbytes = SN.client_param_bytes(engine.cfg, engine.state.params, d)
        per_tok = (engine.tokens_per_sample() * engine.cfg.d_model
                   * jnp.dtype(engine.cfg.dtype).itemsize)
        msgs = 2 + 2 * engine.local_steps
        if ids is not None and self._bs is not None:
            bs = self._bs[np.asarray(ids)].astype(np.float64)
            per_step = 2 * (bs * per_tok).astype(np.int64) if available \
                else np.zeros(len(bs), np.int64)
            widths = getattr(engine.state.fleet, "widths", None)
            if widths is not None and bool((np.asarray(widths) < 1.0).any()):
                # width-tiered download: each client ships only its slice
                pbytes = np.array(
                    [SN.client_param_bytes(engine.cfg, engine.state.params,
                                           d, float(widths[i]))
                     for i in np.asarray(ids)], np.int64)
            return (2 * pbytes + engine.local_steps * per_step,
                    np.full(len(bs), msgs, np.int64))
        mean_b = None
        if self._bs is not None:
            mask = engine.state.fleet.depths == d
            if mask.any():
                mean_b = float(self._bs[mask].mean())
        if mean_b is None:   # before the first round: engine default
            mean_b = float(engine.batch_size)
        per_step = 2 * int(mean_b * per_tok) if available else 0
        return 2 * pbytes + engine.local_steps * per_step, msgs
