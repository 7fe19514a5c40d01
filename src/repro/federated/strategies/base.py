"""The Strategy protocol: what a federated method must supply.

The ``Engine`` owns everything method-independent — arrival/availability
draws, client sampling, staleness tracking, batch RNG, cohorting, the
metrics ``Accountant``, history and eval. A ``Strategy`` supplies only the
method-specific pieces:

  init_round   — allocate the per-round workspace (server views, FedAvg
                 accumulators, loss buffers)
  cohort_step  — run ``local_steps`` updates for one same-depth cohort,
                 recording client trees / losses into the workspace
  fold_server  — fold a cohort's server-side result into the running
                 server view / accumulators
  aggregate    — produce the next global params + the round's loss scalar
  comm_cost    — per-client bytes and message count for the round

so the accounting that the seed trainer duplicated three times lives in
exactly one place (``Engine._account_cohort``).

Strategies register with ``@register_strategy("name")`` and are resolved by
``get_strategy(name)``; anything matching the protocol can be passed to the
engine directly, so new scenarios (unstable participation, co-tuned splits)
are a new module, not a new copy of the trainer. ``docs/strategies.md``
walks through the protocol hook by hook with ``unstable`` as the worked
example.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import supernet as SN
from repro.core.fault import ArrivalProcess
from repro.optim import map_moments


@dataclasses.dataclass
class RoundContext:
    """Engine-drawn randomness + bookkeeping for one round.

    avail        — [N] bool, server reachable this round (drawn from the
                   engine's availability :class:`ArrivalProcess`)
    participants — [N] bool, client showed up: the intersection of the
                   ``sample_frac`` draw and the participation arrival
                   process (all-True when neither is configured)
    batch_fn     — ids -> stacked batch; accepts an optional ``batch_size``
                   keyword for strategies that co-tune per-client batches.
                   Legacy host path — draws from the same stream as
                   ``sample_indices``, so a strategy must use one or the
                   other, not both
    sample_indices — (ids, steps, batch_size) -> [steps, len(ids), B] int32
                   flat-dataset indices for the device-resident path: the
                   kernel gathers batches on device from
                   ``engine.device_data`` (see ``data.synthetic.DeviceData``)
    staleness    — [N] int, rounds each client has been absent since it
                   last participated (0 for a client seen last round and
                   for everyone in round 0); engine-owned, used by
                   staleness-weighted aggregation
    """
    avail: np.ndarray
    participants: np.ndarray
    batch_fn: Callable[..., Any]
    sample_indices: Callable[..., np.ndarray] = None
    staleness: np.ndarray = None


@dataclasses.dataclass
class CohortResult:
    """What ``cohort_step`` hands back for accounting + server folding."""
    client_params: int           # per-client trainable param count
    server_params: int           # server-side param count (0 => no server)
    payload: Any = None          # strategy-private, consumed by fold_server
    tokens_per_batch: int = None  # effective per-step tokens when a strategy
    #                               tunes batch sizes (None => engine default)
    losses: Any = None           # [bucket] device array, per-slot final-step
    #                               losses (never host-synced by the engine)


class Strategy:
    """Base: shared hooks with no-op defaults. Subclasses implement the
    four round phases; ``name`` is set by ``@register_strategy``."""

    name: str = "?"

    # ---------------------------------------------------- fleet construction
    def fixed_depth(self, cfg) -> int | None:
        """A rigid split point for every client, or None for Eq.1 depths."""
        return None

    def prepare_fleet(self, cfg, fleet, device_model=None) -> None:
        """Post-allocation fleet adjustment (e.g. FedAvg trains the full
        model locally; HASFL records the device model for co-tuning)."""

    def participation_process(self, cfg, n_clients: int,
                              seed: int) -> Optional[ArrivalProcess]:
        """An :class:`ArrivalProcess` governing which clients show up each
        round, or None for always-on participation. The engine prefers an
        explicitly passed ``participation=`` process over this default."""
        return None

    # ------------------------------------------------------------- cohorting
    def cohorts(self, engine, ctx: RoundContext) -> Dict[int, np.ndarray]:
        """Feasible same-depth cohorts, restricted to sampled participants."""
        out: Dict[int, np.ndarray] = {}
        for d, ids in engine.state.fleet.cohorts().items():
            ids = ids[ctx.participants[ids]]
            if len(ids):
                out[d] = ids
        return out

    # ---------------------------------------------------------- round phases
    def init_round(self, engine, ctx: RoundContext) -> Dict[str, Any]:
        raise NotImplementedError

    def cohort_step(self, engine, ctx: RoundContext, ws: Dict[str, Any],
                    d: int, ids: np.ndarray) -> CohortResult:
        raise NotImplementedError

    def fold_server(self, engine, ws: Dict[str, Any], d: int,
                    ids: np.ndarray, res: CohortResult) -> None:
        pass

    def aggregate(self, engine, ws: Dict[str, Any]) -> Tuple[Any, float]:
        """-> (new global params, round loss scalar)."""
        raise NotImplementedError

    def _finish_aggregation(self, engine, ws: Dict[str, Any],
                            server_view: Dict[str, Any],
                            agg_fn: Callable) -> Tuple[Any, float]:
        """Shared aggregation tail over the device-resident workspace:
        merge this round's server view into the globals and delegate the
        weighting to ``agg_fn(globals, stacked, depths, losses, mask)``,
        where ``stacked`` is the full-fleet ``ws["client_stack"]`` buffer
        and ``mask`` the ``ws["trained"]`` validity mask (clients that did
        not train keep zero weight; their rows are never read). This is the
        ONE host sync of the round's training outputs: the trained mask and
        per-client losses come back together, everything else stays on
        device. The participating ids land in ``ws["participated"]`` so
        host-side scenario bookkeeping can still line up per-client data.
        Returns (new params, mean participant loss)."""
        state = engine.state
        mask, losses = jax.device_get((ws["trained"], ws["losses"]))
        if not mask.any():   # e.g. every sampled client infeasible this round
            return state.params, float("nan")
        ws["participated"] = np.where(mask)[0]
        globals_with_server = dict(state.params)
        globals_with_server.update(server_view)
        new_params = agg_fn(globals_with_server, ws["client_stack"],
                            state.fleet.depths, ws["losses"], mask)
        return new_params, float(np.mean(losses[mask]))

    # ------------------------------------------------------------ accounting
    def comm_cost(self, engine, d: int, available: bool) -> Tuple[int, int]:
        """-> (total bytes on the wire this round, messages) per client."""
        raise NotImplementedError


# --------------------------------------------- device-resident fleet buffers
#
# One round's training outputs live in full-fleet stacked device buffers:
# ``client_stack`` (input-side leaves [N, ...], split-stack leaves
# [N, L_full, ...] zero-padded beyond each client's depth — exactly the
# ``core.aggregation`` stacked format), ``losses`` [N] f32 and ``trained``
# [N] bool. Cohort kernels gather their slots, train, and scatter results
# back through the helpers below; aggregation consumes the buffers directly
# with the validity mask, so nothing is sliced to host between cohorts.
# Padded slots carry the out-of-range sentinel id (``bucketing.pad_ids``):
# their scatters are dropped by jax's out-of-bounds rule, so no masking is
# needed at the buffer boundary.

def fleet_workspace(engine) -> Dict[str, Any]:
    """Fresh per-round stacked buffers for ``engine``'s fleet. With a
    fleet mesh, buffers place client-axis-sharded (the same
    ``fleet_pspecs`` layout as the stacked local heads), so the sharded
    kernels' scatters and the mask-aware aggregation reductions stay on
    their shards until the one host sync in ``_finish_aggregation``."""
    n = engine.state.n_clients
    template = SN.split_params(engine.cfg, engine.state.params,
                               engine.cfg.split_stack_len)[0]
    shapes = {"client_stack": jax.tree.map(
                  lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype),
                  template),
              "losses": jax.ShapeDtypeStruct((n,), jnp.float32),
              "trained": jax.ShapeDtypeStruct((n,), jnp.bool_)}
    if engine.mesh is None:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    # build each zeros buffer directly on its client-axis shards — the
    # shape templates cost nothing, so no single-device materialize +
    # re-place round trip
    from repro.launch import sharding as SH
    shardings = SH.named(engine.mesh, SH.fleet_pspecs(shapes, engine.mesh))
    return jax.tree.map(
        lambda s, sh: jnp.zeros(s.shape, s.dtype, device=sh),
        shapes, shardings)


def scatter_rows(buf_tree, ids, rows_tree):
    """Write per-slot rows into a stacked [N, ...] buffer tree.
    ``ids`` is the sentinel-padded [bucket] id vector; padded slots drop."""
    return jax.tree.map(lambda b, r: b.at[ids].set(r.astype(b.dtype)),
                        buf_tree, rows_tree)


def gather_rows(buf_tree, ids):
    """Per-slot rows out of a stacked buffer tree; padded (sentinel) slots
    clamp to the last client's row — placeholder data their kernel slot
    trains on but never publishes."""
    return jax.tree.map(lambda b: b[ids], buf_tree)


def scatter_client_rows(cfg, ws: Dict[str, Any], ids, cstack, d: int,
                        width: float = 1.0):
    """Scatter a cohort's trained client trees (split-stack rows [:d]) into
    ``ws["client_stack"]``, zero-padding rows [d:] to the full stack depth
    (they are masked by presence at aggregation). A runtime-depth cohort
    hands back FULL-``L`` stacks whose rows [d:] were frozen at their
    broadcast (non-zero) values, so the depth window is sliced out first —
    the zero-pad invariant the aggregation denominators rely on. A
    width-sliced cohort's stack is zero-embedded back to full width
    (``supernet.widen_width``) — the pruned coordinates are excluded from
    the aggregation denominators by the per-coordinate width masks, so the
    zeros never dilute anything."""
    sname = SN.split_stack_name(cfg)
    Lfull = cfg.split_stack_len

    def pad(x):
        x = x[:, :d]   # identity for a depth-sliced stack
        return jnp.pad(x, [(0, 0), (0, Lfull - d)]
                       + [(0, 0)] * (x.ndim - 2))

    buf = ws["client_stack"]
    out = dict(buf)
    for k, v in cstack.items():
        if k == sname:
            if width < 1.0:
                v = SN.widen_width(cfg, v, width)
            rows = jax.tree.map(pad, v)
        else:
            rows = v
        out[k] = scatter_rows(buf[k], ids, rows)
    ws["client_stack"] = out


def split_param_counts(cfg, params, d: int, width: float = 1.0):
    """(client, server) parameter counts of the depth-``d`` width-``w``
    split, from shapes (``supernet.split_sizes``) — no device work. The
    runtime-depth cohort path hands full-``L`` views to the kernels, so
    per-cohort accounting can no longer just count the view's leaves."""
    return SN.split_sizes(cfg, params, d, width)[:2]


def record_cohort(ws: Dict[str, Any], ids, losses):
    """Mark a cohort's slots trained and scatter their per-slot losses
    (device arrays in, device arrays out — no host sync)."""
    ws["losses"] = ws["losses"].at[ids].set(losses.astype(jnp.float32))
    ws["trained"] = ws["trained"].at[ids].set(True)


# ----------------------------------------------- persistent server opt state
#
# The shared server branch's optimizer state lives in
# ``TrainState.opt_state["server"]``, shaped over the FULL server branch
# (the d=0 view: whole split stack + non-stack server leaves) so it is
# independent of which cohort depths exist in a given round. The
# runtime-depth kernels take the WHOLE state (``cohort_server_opt`` at
# ``d=0`` — a value-preserving full slice) and freeze moment stack rows
# ``< d`` in-kernel (``supernet.depth_freeze``), so the d=0
# ``merge_server_opt`` write-back is bit-equal to the legacy rows-``[d:]``
# slice/merge round trip. ``repro.optim.map_moments`` keeps all of this
# optimizer-agnostic.

def server_opt_state(engine, template) -> Any:
    """The persistent full-server-branch optimizer state, lazily
    initialized (and re-initialized if the stored state does not match the
    current optimizer/model — e.g. after switching optimizers between a
    save and a restore). The shape validation runs once per (engine,
    optimizer) and after every ``Engine.restore``, not on every cohort;
    adopt external state through ``Engine.restore`` so it is re-checked."""
    cur = engine.state.opt_state.get("server")
    opt_id = id(engine.optimizer)
    if cur is not None and getattr(engine, "_server_opt_ok", None) == opt_id:
        return cur
    want = jax.eval_shape(engine.optimizer.init, template)
    if cur is None or not _state_like(cur, want):
        cur = engine.optimizer.init(template)
        engine.state.opt_state["server"] = cur
    engine._server_opt_ok = opt_id
    return cur


def cohort_server_opt(engine, cfg, sname: str, d: int):
    """The cohort-step prologue every split strategy shares: fetch the
    persistent full-branch state and slice this cohort's depth-``d`` view.
    Returns ``(srv_template, srv_full, srv_state)``; after stepping, hand
    ``srv_state`` back through :func:`merge_server_opt`."""
    srv_template = SN.split_params(cfg, engine.state.params, 0)[1]
    srv_full = server_opt_state(engine, srv_template)
    return (srv_template, srv_full,
            slice_server_opt(srv_full, srv_template, sname, d))


def _state_like(state, shaped) -> bool:
    if jax.tree_util.tree_structure(state) != \
            jax.tree_util.tree_structure(shaped):
        return False
    return all(tuple(np.shape(a)) == tuple(b.shape)
               for a, b in zip(jax.tree.leaves(state),
                               jax.tree.leaves(shaped)))


def slice_server_opt(state, template, sname: str, d: int):
    """Project the depth-``d`` cohort's server slice out of the full-branch
    state: moment stack rows ``[d:]``, non-stack moments and bookkeeping
    whole. ``template`` is the full server params tree (structure probe)."""
    def sl(tree):
        out = {k: v for k, v in tree.items() if k != sname}
        out[sname] = jax.tree.map(lambda x: x[d:], tree[sname])
        return out
    return map_moments(sl, state, template)


def merge_server_opt(full, cohort, template, sname: str, d: int):
    """Write a cohort's post-update server slice back into the full-branch
    state. Stack moment rows ``[d:]`` are replaced; non-stack moments and
    bookkeeping (step counters) take the cohort's values — last cohort
    wins, mirroring the server-view fold."""
    if not isinstance(full, dict):
        return full
    pdef = jax.tree_util.tree_structure(template)
    out = {}
    for k, v in full.items():
        cv = cohort[k]
        if jax.tree_util.tree_structure(v) == pdef:
            merged = {kk: vv for kk, vv in cv.items() if kk != sname}
            merged[sname] = jax.tree.map(
                lambda f, c: jnp.concatenate([f[:d], c], axis=0),
                v[sname], cv[sname])
            out[k] = merged
        else:
            out[k] = cv
    return out


def broadcast_server_opt(state, template, n: int):
    """Stack a server opt-state slice along a new leading client axis
    (SplitFed trains per-client server copies; each starts the round from
    the shared fed-averaged moments)."""
    return map_moments(
        lambda t: jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape), t),
        state, template)


def mean_server_opt(state, template, valid=None):
    """Collapse per-client server moments back to the shared state by
    averaging over the leading client axis (the moment-space analogue of
    SplitFed's round-end FedAvg over server copies). ``valid`` ([Nc] bool)
    excludes padded bucket slots from the mean — a padded slot's frozen
    broadcast copy must not dilute the live clients' moments."""
    if valid is None:
        mean = lambda x: jnp.mean(x.astype(jnp.float32), axis=0)  # fleetlint: disable=FL002 — valid=None contract: caller vouches every row is live
    else:
        nv = jnp.sum(valid).astype(jnp.float32)

        def mean(x):
            row = valid.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.sum(jnp.where(row, x.astype(jnp.float32), 0.0),
                           axis=0) / nv
    return map_moments(
        lambda t: jax.tree.map(lambda x: mean(x).astype(x.dtype), t),
        state, template)


# ----------------------------------------------------------------- registry

_REGISTRY: Dict[str, Type[Strategy]] = {}


def register_strategy(name: str):
    def deco(cls: Type[Strategy]) -> Type[Strategy]:
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def get_strategy(name: str) -> Strategy:
    if name not in _REGISTRY:
        raise KeyError(f"unknown strategy {name!r}; "
                       f"available: {available_strategies()}")
    return _REGISTRY[name]()


def available_strategies():
    return sorted(_REGISTRY)
