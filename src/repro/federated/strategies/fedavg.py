"""Classic FedAvg as an engine strategy: full model trained locally,
data-size-weighted full-model sync. No split, no server compute.

The FedOpt family (Reddi et al., Adaptive Federated Optimization) rides on
the same fold: the round's data-weighted average is treated as a
pseudo-gradient ``theta_old - theta_avg`` and folded through a pluggable
*server* optimizer whose moments persist across rounds (and checkpoints)
in the same ``TrainState.opt_state["server"]`` slot the split strategies
use. ``fedavgm`` is the heavy-ball member (Hsu et al.); ``fedadam`` and
``fedyogi`` are the adaptive members (``repro.optim.fedadam`` /
``fedyogi`` — Adam / Yogi second moments without bias correction, tau
= 1e-3). All three resume bit-identically from a checkpoint.

Execution follows the bucketed device-resident kernel contract: one
scanned kernel per bucket runs all local steps with on-device batch
gather; padded slots train throwaway copies that the size-weighted
aggregation zeroes out.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.federated import bucketing as BK
from repro.federated import metrics as MET
from repro.federated.strategies import base
from repro.federated.strategies.base import (CohortResult, RoundContext,
                                             Strategy, register_strategy)
from repro.launch.sharding import P, slot_pspec
from repro.models import model as M
from repro.optim import (Optimizer, apply_updates, fedadam, fedyogi,
                         sgd_momentum)


def _step_specs(axes, params_stack, images, labels, idx):
    """shard_map layout: slots are fully independent in FedAvg, so only
    the param stack and index slot axes shard; no cross-shard collectives
    are needed at all."""
    slot = slot_pspec(0, axes)
    return ((slot, P(), P(), slot_pspec(1, axes)), (slot, slot))


@BK.register_kernel(n_static=3, specs=_step_specs)
def step_kernel(cfg: ModelConfig, opt, steps: int, params_stack,
                images, labels, idx, axis_name=None):
    """All ``steps`` full-model local steps for one padded bucket, scanned,
    with on-device batch gather. Slots are independent (classic FedAvg), so
    padded slots simply train a throwaway copy that aggregation ignores —
    and the shard-mapped variant (``axis_name`` bound) needs no
    collectives."""

    def one(p, b):
        return jax.value_and_grad(lambda pp: M.full_loss(cfg, pp, b))(p)

    def step(carry, idx_t):
        pstack, opt_state = carry
        BK.guard_gather(idx_t, images.shape[0])   # sanitize-mode OOB check
        batch = {"images": images[idx_t], "label": labels[idx_t]}
        losses, grads = jax.vmap(one)(pstack, batch)
        updates, opt_state = opt.update(grads, opt_state, pstack)
        return (apply_updates(pstack, updates), opt_state), losses

    carry = (params_stack, opt.init(params_stack))
    (pstack, _), losses = jax.lax.scan(step, carry, idx)
    return pstack, losses[-1]


@register_strategy("fedavg")
class FedAvg(Strategy):
    """server_momentum=0 and server_opt=None is exact FedAvg (the server
    fold is skipped entirely, not applied with a unit step — float-identical
    to the plain average). ``fedavgm`` registers heavy-ball momentum at the
    0.9 default; ``fedadam`` / ``fedyogi`` register the adaptive FedOpt
    members. Any ``repro.optim.Optimizer`` can be passed as ``server_opt``
    — it receives the pseudo-gradient ``theta_old - theta_avg`` once per
    round and its state persists in ``opt_state["server"]``."""

    def __init__(self, server_momentum: float = 0.0,
                 server_opt: Optimizer = None):
        assert not (server_momentum and server_opt is not None), \
            "pass either server_momentum or an explicit server_opt"
        self.server_momentum = server_momentum
        # pseudo-gradient step: mu <- beta*mu + (old - avg); p <- p - mu
        self._server_opt = server_opt if server_opt is not None else (
            sgd_momentum(1.0, server_momentum) if server_momentum else None)

    def prepare_fleet(self, cfg, fleet, device_model=None) -> None:
        fleet.depths[:] = cfg.split_stack_len   # full model local

    def cohorts(self, engine, ctx: RoundContext):
        """One cohort of every available sampled client (all-full-depth);
        if nobody is reachable the round degrades to everyone-local."""
        ids = np.where(ctx.avail & ctx.participants)[0]
        if len(ids) == 0:   # _draw_participants guarantees >= 1 sampled
            ids = np.where(ctx.participants)[0]
        if len(ids) == 0:   # an arrival process may leave nobody at all
            return {}
        return {engine.cfg.split_stack_len: ids}

    def init_round(self, engine, ctx: RoundContext) -> Dict[str, Any]:
        return {"ids": None, "pstack": None, "valid": None, "losses": None}

    def cohort_step(self, engine, ctx, ws, d, ids) -> CohortResult:
        state = engine.state
        bucket = engine.bucket_for(len(ids))
        idx = jnp.asarray(BK.pad_slot_axis(
            ctx.sample_indices(ids, engine.local_steps, engine.batch_size),
            bucket, axis=1))
        pstack = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (bucket,) + x.shape),
            state.params)
        dd = engine.device_data
        kernel = engine.launch_kernel(step_kernel, bucket, real=len(ids))
        pstack, losses = kernel(engine.cfg, engine.optimizer,
                                engine.local_steps, pstack,
                                dd.images, dd.labels, idx)
        ws["ids"], ws["pstack"], ws["losses"] = ids, pstack, losses
        ws["valid"] = np.arange(bucket) < len(ids)
        nparams = sum(int(x.size) for x in jax.tree.leaves(state.params))
        return CohortResult(nparams, 0, losses=losses)

    def aggregate(self, engine, ws):
        ids, pstack = ws["ids"], ws["pstack"]
        if ids is None:   # nobody arrived this round (participation process)
            return engine.state.params, float("nan")
        # data-size weights over real slots; padded slots weigh 0, so their
        # throwaway contents never reach the average
        sizes = np.zeros(len(ws["valid"]), np.float32)
        sizes[:len(ids)] = [len(engine.data["clients"][i].labels)
                            for i in ids]
        w = sizes / sizes.sum()
        avg = jax.tree.map(
            lambda s: jnp.einsum("n,n...->...", jnp.asarray(w),
                                 s.astype(jnp.float32)).astype(s.dtype),
            pstack)
        loss = float(np.mean(np.asarray(ws["losses"])[ws["valid"]]))
        if self._server_opt is None:
            return avg, loss
        return self._server_fold(engine, avg), loss

    def _server_fold(self, engine, avg):
        """FedOpt: fold the round average through the persistent server
        optimizer — heavy-ball (FedAvgM), Adam (FedAdam) or Yogi (FedYogi)
        — lazily (re)initialized when absent or shape-mismatched, e.g.
        after a restore from a different run. Validation runs once per
        (engine, optimizer) and after every ``Engine.restore`` — the same
        ``_server_opt_ok`` discipline as ``base.server_opt_state``."""
        params = engine.state.params
        cur = engine.state.opt_state.get("server")
        opt_id = id(self._server_opt)
        if cur is None or getattr(engine, "_server_opt_ok",
                                  None) != opt_id:
            want = jax.eval_shape(self._server_opt.init, params)
            if cur is None or not base._state_like(cur, want):
                cur = self._server_opt.init(params)
            engine._server_opt_ok = opt_id
        delta = jax.tree.map(
            lambda old, new: (old.astype(jnp.float32)
                              - new.astype(jnp.float32)), params, avg)
        updates, cur = self._server_opt.update(delta, cur, params)
        engine.state.opt_state["server"] = cur
        return apply_updates(params, updates)

    def comm_cost(self, engine, d, available, ids=None):
        return 2 * MET.tree_bytes(engine.state.params), 2


@register_strategy("fedavgm")
class FedAvgM(FedAvg):
    """FedAvg + 0.9 server momentum (Hsu et al., 2019)."""

    def __init__(self, server_momentum: float = 0.9):
        super().__init__(server_momentum=server_momentum)


@register_strategy("fedadam")
class FedAdam(FedAvg):
    """FedAvg + server-side Adam on the round pseudo-gradient (Reddi et
    al., 2021). ``server_lr`` is eta_s; the 1e-3 tau bounds adaptivity."""

    def __init__(self, server_lr: float = 0.1, b1: float = 0.9,
                 b2: float = 0.99, eps: float = 1e-3):
        super().__init__(server_opt=fedadam(server_lr, b1=b1, b2=b2,
                                            eps=eps))


@register_strategy("fedyogi")
class FedYogi(FedAvg):
    """FedAvg + server-side Yogi (Reddi et al., 2021): Adam's first
    moment, Yogi's additive second-moment rule — slower variance decay
    under the sparse, bursty pseudo-gradients of partial participation."""

    def __init__(self, server_lr: float = 0.1, b1: float = 0.9,
                 b2: float = 0.99, eps: float = 1e-3):
        super().__init__(server_opt=fedyogi(server_lr, b1=b1, b2=b2,
                                            eps=eps))
