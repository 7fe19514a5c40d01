"""The federated engine: ONE round loop for every strategy.

``Engine.run_round`` owns everything method-independent — arrival /
availability draws, per-round client sampling (``sample_frac``), staleness
tracking, batch RNG ordering, cohorting, the metrics ``Accountant``,
history and eval — and delegates the method-specific phases (cohort update,
server fold, aggregation, per-client communication cost) to a ``Strategy``
resolved from the registry. Adding a scenario means registering a strategy,
not copy-pasting a trainer.

Device residency / bounded compile
----------------------------------
One round is a small, fixed set of compiled programs regardless of fleet
composition: cohorts run in padded size buckets (``federated.bucketing``),
all local steps of a cohort execute as one scanned kernel that gathers its
batches on device from the flat dataset (``engine.device_data``), and the
round's training outputs accumulate in full-fleet stacked device buffers
(``strategies.base.fleet_workspace``) that aggregation consumes directly
with a validity mask. Host floats materialize once per round — the
trained-mask/loss sync in ``Strategy._finish_aggregation`` — plus the pure
cost-model arithmetic in ``_account_cohort``, which never touches device
data.

Multi-device fleet execution
----------------------------
Pass ``mesh=`` (e.g. ``repro.launch.mesh.make_fleet_mesh()``) and the
client axis stops being storage-only sharding: stacked state and workspace
buffers place with ``launch.sharding.fleet_pspecs``, bucket sizes round up
to a multiple of the mesh's data extent (every shard owns whole slots —
padding is a numerical no-op by the padded-slot contract), and each cohort
kernel dispatches to its ``shard_map`` variant
(``bucketing.FleetKernel.sharded``), whose cross-slot reductions ``psum``
over the fleet axis. A 1-device mesh (or a bucket the mesh cannot split
evenly, e.g. an explicit ladder entry) falls back to the replicated
kernel — same numbers, no shard_map.

Construction is either direct::

    Engine(cfg, n_clients=16, strategy="ssfl", lr=0.25)

or builder-style::

    engine = (Engine.builder(cfg)
              .clients(16, availability=0.9)
              .strategy("ssfl")
              .optimizer("sgd", lr=0.25)
              .data(alpha=0.5, noise=0.7)
              .build())

RNG-stream contract
-------------------
Every source of randomness is a separate stream with a fixed offset from
the construction ``seed``, so adding a knob never perturbs the others:

  seed          — global params (jax PRNG), fleet profiles, the synthetic
                  data, and the batch-sampling stream (``TrainState.rng``,
                  drawn in cohort order by ``batch_fn``)
  seed + 1      — per-client local heads phi_i (one jax sub-key each)
  seed + 7      — server availability (``avail_model``, an
                  :class:`~repro.core.fault.ArrivalProcess`)
  seed + 13     — per-round client sampling (``sample_frac``); a
                  ``sample_frac=1.0`` run never touches this stream, so it
                  is bit-identical to a run without the knob
  seed + 21     — client participation (the strategy-supplied or
                  explicitly passed ``participation`` arrival process)

``Engine.save`` persists the position of every stream (plus the Markov
on/off state) in the checkpoint manifest; ``Engine.restore`` rewinds them,
so a resumed run is bit-identical to an uninterrupted one.
"""
from __future__ import annotations

import functools
import inspect
from typing import Dict, List, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.fault import ArrivalProcess, AvailabilityModel
from repro.federated import bucketing as BK
from repro.federated import metrics as MET
from repro.federated import tracing
from repro.federated.simulator import make_fleet
from repro.federated.state import TrainState, init_train_state
from repro.federated.strategies import RoundContext, Strategy, get_strategy
from repro.models import model as M
from repro.optim import Optimizer, get_optimizer

# per-round kernel counters, counted by Engine.launch_kernel
_ROUND_COUNTS = ("kernel_launches", "kernel_slots", "kernel_real_slots")


class Engine:
    def __init__(self, cfg: ModelConfig, n_clients: int,
                 strategy: Union[str, Strategy] = "ssfl", *,
                 seed: int = 0, lr: float = None, local_steps: int = 2,
                 batch_size: int = 16,
                 availability: Union[float, ArrivalProcess] = 1.0,
                 participation: ArrivalProcess = None,
                 sample_frac: float = 1.0,
                 optimizer: Union[str, Optimizer] = "sgd",
                 data=None, device_model: MET.DeviceModel = None,
                 alpha: float = 0.5, noise: float = 0.35,
                 bucketing="ladder", mesh=None, sanitize: bool = False,
                 width_tiers=None, cross_tier: str = "fused"):
        assert 0.0 < sample_frac <= 1.0
        self.cfg = cfg
        # cross-tier TPGF: with >1 width tier in a cohort, "fused" (the
        # paper path) runs every tier from the same server snapshot and
        # fuses the per-tier updates into ONE with tpgf.fuse_tiers;
        # "chained" keeps the pre-fusion sequential chaining (each tier
        # continues from the previous tier's server branch) as the
        # per-tier comparator the benchmarks sweep against. Homogeneous
        # fleets never branch — one width group is the legacy call.
        if cross_tier not in ("fused", "chained"):
            raise ValueError(
                f"cross_tier={cross_tier!r}: expected 'fused' or 'chained'")
        self.cross_tier = cross_tier
        # sanitize=True swaps every bucket kernel for its checkify-
        # instrumented variant (NaN/inf + OOB-gather checks, per-slot
        # attribution via SlotSanitizerError). Debug mode: it adds a host
        # sync per kernel call, so the one-host-sync contract — and the
        # round-path goldens — only hold with the default False.
        self.sanitize = bool(sanitize)
        self.strategy = (get_strategy(strategy)
                         if isinstance(strategy, str) else strategy)
        # cohort-size bucket ladder: "ladder" (default powers of two),
        # "exact" (no padding — one compile per distinct cohort size; the
        # benchmark's pre-refactor reference mode), or an explicit sequence
        if bucketing == "ladder":
            self.bucket_ladder = None
        elif bucketing == "exact":
            self.bucket_ladder = ()
        elif isinstance(bucketing, (tuple, list)) and all(
                isinstance(b, int) and b > 0 for b in bucketing):
            self.bucket_ladder = tuple(bucketing)
        else:
            raise ValueError(
                f"bucketing={bucketing!r}: expected 'ladder', 'exact', or "
                "a sequence of positive ints (an explicit bucket ladder)")
        self.mesh = mesh
        # lr is baked into name-resolved optimizers (default 0.05); a
        # pre-built Optimizer instance has its rate inside its closures, so
        # engine.lr stays None there unless the caller states it — it never
        # silently disagrees with the update rule
        if isinstance(optimizer, str):
            lr = 0.05 if lr is None else lr
            self.optimizer = get_optimizer(optimizer, lr)
        else:
            self.optimizer = optimizer
        self.lr, self.local_steps = lr, local_steps
        self.batch_size, self.sample_frac = batch_size, sample_frac
        self.accountant = MET.Accountant(device_model)
        fleet = make_fleet(cfg, n_clients, seed=seed,
                           fixed_depth=self.strategy.fixed_depth(cfg))
        if width_tiers is not None:
            # supernet width ladder: snap each client's memory budget to a
            # tier (core.allocation.allocate_widths); strategies group
            # same-width sub-cohorts and kernels key on (width, bucket) —
            # depth rides as a runtime array. Default None keeps
            # fleet.widths all-ones — the bit-exact legacy path.
            from repro.core import allocation as AL
            fleet.widths = AL.allocate_widths(
                [p.mem_gb for p in fleet.profiles], width_tiers)
        self.width_tiers = None if width_tiers is None \
            else tuple(sorted(float(t) for t in width_tiers))
        self._call_prepare_fleet(cfg, fleet)
        self.avail_model: ArrivalProcess = (
            availability if isinstance(availability, ArrivalProcess)
            else AvailabilityModel(availability, seed=seed + 7))
        # sampling stream is separate from the batch stream so that
        # sample_frac=1.0 runs are bit-identical to never drawing at all
        self._sample_rng = np.random.default_rng(seed + 13)
        self.participation: ArrivalProcess = (
            participation
            or self.strategy.participation_process(cfg, n_clients,
                                                   seed + 21))
        from repro.data.synthetic import make_federated_data
        self.data = data or make_federated_data(
            n_clients, n_classes=cfg.n_classes or 10,
            image_size=cfg.image_size, alpha=alpha, seed=seed, noise=noise)
        self.state: TrainState = init_train_state(cfg, n_clients, seed=seed,
                                                  fleet=fleet)
        if mesh is not None:
            from repro.launch import sharding as SH
            self.state.local_heads = SH.shard_fleet(self.state.local_heads,
                                                    mesh)
        self._staleness = np.zeros(n_clients, np.int64)
        self._server_updates = 0    # rounds in which any client had a server
        self.history: List[Dict] = []
        self._counts = dict.fromkeys(_ROUND_COUNTS, 0)

    def _call_prepare_fleet(self, cfg, fleet):
        """Pass ``device_model`` only to hooks that accept it, so strategies
        written against the original ``prepare_fleet(cfg, fleet)`` protocol
        keep working unchanged."""
        sig = inspect.signature(self.strategy.prepare_fleet)
        params = sig.parameters.values()
        if "device_model" in sig.parameters or any(
                p.kind == p.VAR_KEYWORD for p in params):
            self.strategy.prepare_fleet(cfg, fleet,
                                        device_model=self.accountant.dm)
        else:
            self.strategy.prepare_fleet(cfg, fleet)

    @classmethod
    def builder(cls, cfg: ModelConfig) -> "EngineBuilder":
        return EngineBuilder(cfg)

    # ----------------------------------------------------- device residency
    @property
    def device_data(self):
        """The flat device-resident dataset view (built on first use).
        With a fleet mesh the pixels replicate across its devices ONCE
        here — otherwise every sharded kernel call would re-broadcast the
        dataset at the shard_map boundary."""
        from repro.data.synthetic import as_device_data
        dd = as_device_data(self.data)
        if self.mesh is not None and \
                getattr(dd, "_fleet_mesh", None) is not self.mesh:
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(self.mesh, PartitionSpec())
            dd.images = jax.device_put(dd.images, rep)
            dd.labels = jax.device_put(dd.labels, rep)
            dd._fleet_mesh = self.mesh
        return dd

    def bucket_for(self, n: int) -> int:
        """Cohort-size bucket under this engine's ladder, rounded up to a
        multiple of the fleet-mesh data extent so every shard owns whole
        slots (``fleet_shards`` is 1 without a mesh — no change)."""
        from repro.federated.bucketing import bucket_size
        return bucket_size(n, self.bucket_ladder,
                           multiple_of=self.fleet_shards)

    @property
    def fleet_shards(self) -> int:
        """Number of shards the bucket-slot/client axis splits into: the
        product of the mesh's data-axis sizes (1 without a mesh)."""
        if self.mesh is None:
            return 1
        from repro.launch.sharding import fleet_extent
        return fleet_extent(self.mesh)

    def kernel_fn(self, kernel, bucket: int):
        """The callable to run one bucketed cohort with: the kernel's
        per-mesh ``shard_map`` variant when a multi-device fleet mesh is
        configured and the bucket splits into whole slots per shard, else
        the replicated jit (identical semantics, one device).

        With ``sanitize=True`` the checkify-instrumented variant runs
        instead (always replicated — see ``FleetKernel.sanitized``): each
        call unpacks ``(err, out)`` and raises ``SlotSanitizerError`` with
        the offending bucket slots if any float/index check tripped."""
        from repro.federated.bucketing import FleetKernel, sanitize_failure
        if self.sanitize and isinstance(kernel, FleetKernel):
            fn = kernel.sanitized()
            name = getattr(kernel, "__name__", "kernel")

            def run(*args):
                err, out = fn(*args)
                sanitize_failure(err, out, bucket, kernel=name)
                return out

            return run
        shards = self.fleet_shards
        if (shards > 1 and isinstance(kernel, FleetKernel)
                and bucket % shards == 0):
            return kernel.sharded(self.mesh)
        return kernel

    # ------------------------------------------------------------- one round
    def run_round(self) -> Dict:
        """One federated round. Returns (and appends to ``history``) the
        round record: ``round``, ``loss``, the kernel counters of the
        round (``kernel_launches``, ``kernel_slots``,
        ``kernel_real_slots``, ``kernel_compiles``; host integers, no
        device sync) and the accountant's running summary. Every phase
        runs under a host span (``tracing.span``; docs/architecture.md,
        "Tracing")."""
        state, strat = self.state, self.strategy
        rnd = state.round_idx + 1
        compiles0 = BK.kernel_compiles()
        self._counts = dict.fromkeys(_ROUND_COUNTS, 0)
        with tracing.span("engine.round", round=rnd):
            with tracing.span("engine.draw"):
                avail = self.avail_model.draw(state.fleet.n_clients)
                ctx = RoundContext(avail=avail,
                                   participants=self._draw_participants(),
                                   batch_fn=self._stack_batches,
                                   sample_indices=self._sample_indices,
                                   staleness=self._staleness.copy())
            with tracing.span("strategy.init_round"):
                ws = strat.init_round(self, ctx)
            stats = MET.RoundStats()
            server_busy_s = 0.0
            head_trained = False
            for d, ids in strat.cohorts(self, ctx).items():
                with tracing.span("engine.cohort", round=rnd, depth=int(d),
                                  clients=len(ids)):
                    with tracing.span("strategy.cohort_step"):
                        res = strat.cohort_step(self, ctx, ws, d, ids)
                    with tracing.span("strategy.fold_server"):
                        strat.fold_server(self, ws, d, ids, res)
                    with tracing.span("engine.account"):
                        server_busy_s += self._account_cohort(stats, ctx, d,
                                                              ids, res)
                # the global head learns when a cohort reaches the server
                # — or trains the full model locally (serverless
                # strategies)
                if res.server_params == 0 or bool(ctx.avail[ids].any()):
                    head_trained = True
            stats.round_time_s += server_busy_s
            stats.energy_j += self.accountant.dm.server_power_w \
                * server_busy_s
            with tracing.span("strategy.aggregate"):
                state.params, loss = strat.aggregate(self, ws)
        trained = ctx.participants & state.fleet.feasible
        self._staleness = np.where(trained, 0, self._staleness + 1)
        if head_trained:
            self._server_updates += 1
        state.round_idx += 1
        self.accountant.log_round(stats)
        rec = {"round": state.round_idx, "loss": loss, **self._counts,
               "kernel_compiles": BK.kernel_compiles() - compiles0,
               **self.accountant.summary()}
        self.history.append(rec)
        return rec

    def launch_kernel(self, kernel, bucket: int, *, width: float = 1.0,
                      real: int = None):
        """The :meth:`kernel_fn` callable for one cohort launch of
        ``real`` clients (default: a full bucket) at width tier
        ``width``: counted in the round record and run under the
        ``cohort_kernel`` span, which covers the dispatch and nothing
        else."""
        run = self.kernel_fn(kernel, bucket)
        real = bucket if real is None else int(real)

        def call(*args):
            counts = self._counts
            counts["kernel_launches"] += 1
            counts["kernel_slots"] += bucket
            counts["kernel_real_slots"] += real
            with tracing.span("cohort_kernel", width=float(width),
                              bucket=bucket, real=real):
                return run(*args)

        return call

    def _draw_participants(self) -> np.ndarray:
        """sample_frac subset ∩ the participation arrival process (when one
        is configured); all-True when neither knob is active."""
        n = self.state.fleet.n_clients
        if self.sample_frac >= 1.0:
            mask = np.ones(n, bool)
        else:
            k = max(1, int(round(self.sample_frac * n)))
            mask = np.zeros(n, bool)
            mask[self._sample_rng.choice(n, size=k, replace=False)] = True
        if self.participation is not None:
            mask &= self.participation.draw(n)
        return mask

    def _stack_batches(self, ids, batch_size: int = None):
        """Legacy host path: ids -> stacked batch; co-tuning strategies pass
        their per-cohort ``batch_size``, everyone else gets the engine
        default. Batches are drawn from ``state.rng`` in call order (the
        batch-stream contract). The built-in strategies use
        :meth:`_sample_indices` + on-device gather instead; this hook stays
        for strategies written against the PR-1 protocol."""
        bs = self.batch_size if batch_size is None else batch_size
        batches = [self.data["clients"][i].sample_batch(bs, self.state.rng)
                   for i in ids]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)

    def _sample_indices(self, ids, steps: int, batch_size: int = None):
        """Device-resident path: [steps, len(ids), B] flat-dataset indices,
        drawn from ``state.rng`` in the same order ``_stack_batches`` would
        have (the batch-stream contract — both paths consume identical
        draws, so they are interchangeable per cohort, never mixed within
        one)."""
        bs = self.batch_size if batch_size is None else batch_size
        return self.device_data.sample_indices(ids, steps, bs, self.state.rng)

    def _account_cohort(self, stats: MET.RoundStats, ctx: RoundContext,
                        d: int, ids, res) -> float:
        """Method-independent cost model over one cohort; returns the
        server busy-time contribution (0 for serverless strategies). Pure
        host arithmetic over profile scalars — device arrays are never
        synced here."""
        dm = self.accountant.dm
        # co-tuning strategies report their cohort's effective batch tokens
        n_tok = res.tokens_per_batch or self.tokens_per_batch()
        cflops = MET.dense_train_flops(res.client_params, n_tok) \
            * self.local_steps
        per_id = self._comm_cost_takes_ids()
        if per_id:
            # ids-aware hook: exact per-client arrays (HASFL prices each
            # client at its own tuned batch size)
            cost = {av: self.strategy.comm_cost(self, d, av, ids=ids)
                    for av in (True, False)}
        else:
            # legacy hook: comm_cost depends only on (d, available)
            cost = {av: self.strategy.comm_cost(self, d, av)
                    for av in (True, False)}
        def pick(v, j):
            a = np.asarray(v).reshape(-1)   # per-id array or a shared scalar
            return int(a[j]) if a.size > 1 else int(a[0])

        for j, i in enumerate(ids):
            prof = self.state.fleet.profiles[i]
            nbytes, nmsg = cost[bool(ctx.avail[i])]
            if per_id:
                nbytes, nmsg = pick(nbytes, j), pick(nmsg, j)
            t = cflops / dm.client_speed(prof.mem_gb) + dm.comm_time_s(
                nbytes, prof.lat_ms, nmsg)
            stats.comm_bytes += nbytes
            stats.client_flops += cflops
            stats.round_time_s = max(stats.round_time_s, t)
            stats.energy_j += dm.client_power_w * t
            stats.n_messages += nmsg
        sflops = MET.dense_train_flops(res.server_params, n_tok) \
            * self.local_steps * len(ids)
        stats.server_flops += sflops
        return sflops / (dm.server_gflops * 1e9)

    def _comm_cost_takes_ids(self) -> bool:
        """Back-compat signature probe, cached per strategy instance: the
        extended hook is ``comm_cost(engine, d, available, ids=None)`` and
        returns per-id arrays when ids are passed; strategies written
        against the PR-1 three-argument protocol keep working unchanged."""
        cached = getattr(self, "_comm_ids_ok", None)
        if cached is not None:
            return cached
        sig = inspect.signature(self.strategy.comm_cost)
        self._comm_ids_ok = "ids" in sig.parameters or any(
            p.kind == p.VAR_KEYWORD for p in sig.parameters.values())
        return self._comm_ids_ok

    # -------------------------------------------------------------- utilities
    def tokens_per_batch(self) -> int:
        return self.batch_size * self.tokens_per_sample()

    def tokens_per_sample(self) -> int:
        cfg = self.cfg
        if cfg.family == "vit":
            return (cfg.image_size // cfg.patch_size) ** 2
        return 128

    def smashed_bytes(self, d: int) -> int:
        # activations cross the wire in the model's compute dtype
        itemsize = jnp.dtype(self.cfg.dtype).itemsize
        return self.tokens_per_batch() * self.cfg.d_model * itemsize

    def evaluate(self, max_batches: int = 8, *, head: str = "auto") -> float:
        """Test accuracy of the current global model.

        head="global" — the server-side classifier (paper's main metric).
        head="local"  — fault-tolerant client-side ensemble: each client
                        runs its depth-d_i prefix + its phi_i head, logits
                        are averaged (paper §II-C inference; what a fleet
                        that never reached the server can actually serve).
        head="auto"   — "global" once any round has trained the global
                        head (a cohort reached the server, or a serverless
                        strategy trained the full model locally), else
                        "local" (the Table III 0%-availability row).
        """
        if head not in ("auto", "global", "local"):
            raise ValueError(head)
        if head == "auto":
            head = "global" if self._server_updates > 0 else "local"
        cfg = self.cfg
        test = self.data["test"]
        bs = 64
        correct = total = 0
        for i in range(0, min(len(test.labels), max_batches * bs), bs):
            batch = {"images": jnp.asarray(test.images[i:i + bs]),
                     "label": jnp.asarray(test.labels[i:i + bs])}
            if head == "global":
                logits = predict(cfg, self.state.params, batch)
            else:
                logits = self._local_ensemble_logits(batch)
            pred = np.asarray(jnp.argmax(logits, -1))
            correct += int((pred == test.labels[i:i + bs]).sum())
            total += len(pred)
        return correct / max(total, 1)

    def _local_ensemble_logits(self, batch):
        """Mean of per-client fault-tolerant head logits, each computed at
        the client's own split depth with its own phi_i. Degrades to the
        global head when no client is feasible (nobody ever trained)."""
        fleet = self.state.fleet
        acc = None
        n = 0
        for i in range(fleet.n_clients):
            if not fleet.feasible[i]:
                continue
            params = {**self.state.params, **self.state.head_for(i)}
            logits = local_predict(self.cfg, params, batch,
                                   int(fleet.depths[i]))
            acc = logits if acc is None else acc + logits
            n += 1
        if acc is None:
            return predict(self.cfg, self.state.params, batch)
        return acc / n

    def train(self, n_rounds: int, *, eval_every: int = 5,
              target_accuracy: float = None, verbose: bool = False):
        for r in range(n_rounds):
            rec = self.run_round()
            if (r + 1) % eval_every == 0 or r == n_rounds - 1:
                rec["accuracy"] = self.evaluate()
                if verbose:
                    print(f"[{self.strategy.name}] round {rec['round']} "
                          f"loss={rec['loss']:.3f} acc={rec['accuracy']:.3f}")
                if target_accuracy and rec["accuracy"] >= target_accuracy:
                    return rec
        return self.history[-1]

    # ------------------------------------------------------------ checkpoint
    def save(self, path: str, *, meta: Dict = None):
        """``TrainState.save`` plus the engine's own stream positions
        (availability / sampling / participation RNGs, staleness counters),
        so :meth:`restore` resumes bit-identically. Strategy-owned
        cross-round state — kernel server moments, FedOpt server moments,
        the buffered-async update buffer — rides along automatically
        because it lives in ``TrainState.opt_state`` slots. The metrics
        ledger and history are NOT persisted — a restored engine accounts
        from zero."""
        meta = dict(meta or {})
        streams = {"avail": self.avail_model.get_state(),
                   "sample": self._sample_rng.bit_generator.state,
                   "staleness": self._staleness.tolist(),
                   "server_updates": self._server_updates,
                   # width tiers ride the stream manifest because fleet
                   # profiles are reconstructed from the seed, not
                   # persisted — a strategy (hasfl retune) may have moved
                   # them since construction
                   "widths": np.asarray(self.state.fleet.widths,
                                        np.float64).tolist()}
        if self.participation is not None:
            streams["participation"] = self.participation.get_state()
        meta["engine_streams"] = streams
        self.state.save(path, meta=meta)

    def restore(self, path: str) -> "Engine":
        """Inverse of :meth:`save`; the engine must have been constructed
        with the same (cfg, n_clients, strategy, optimizer) shape."""
        self.state.restore(path)
        if self.mesh is not None:
            # TrainState.restore rebuilds arrays on the default device;
            # re-apply the client-axis placement the constructor set up
            from repro.launch import sharding as SH
            self.state.local_heads = SH.shard_fleet(self.state.local_heads,
                                                    self.mesh)
        # adopted opt_state must be re-validated by its owners: the kernel
        # server moments and the fedavg-family FedOpt fold (both cache in
        # _server_opt_ok), async_buffered's flush moments (_fedopt_ok),
        # and its update buffer (_buffer_ok)
        self._server_opt_ok = None
        self._fedopt_ok = None
        self._buffer_ok = None
        streams = self.state.last_restore_meta.get("engine_streams")
        if streams:
            self.avail_model.set_state(streams["avail"])
            self._sample_rng.bit_generator.state = streams["sample"]
            self._staleness = np.asarray(streams["staleness"], np.int64)
            self._server_updates = int(streams.get("server_updates", 0))
            if "widths" in streams:
                self.state.fleet.widths = np.asarray(streams["widths"],
                                                     np.float64)
            if self.participation is not None \
                    and "participation" in streams:
                self.participation.set_state(streams["participation"])
        return self


class EngineBuilder:
    """Fluent construction for the common quickstart path."""

    def __init__(self, cfg: ModelConfig):
        self._cfg = cfg
        self._kw: Dict = {"n_clients": 8}

    def clients(self, n: int, *,
                availability: Union[float, ArrivalProcess] = 1.0,
                sample_frac: float = 1.0,
                participation: ArrivalProcess = None) -> "EngineBuilder":
        self._kw.update(n_clients=n, availability=availability,
                        sample_frac=sample_frac, participation=participation)
        return self

    def strategy(self, name: Union[str, Strategy]) -> "EngineBuilder":
        self._kw["strategy"] = name
        return self

    def optimizer(self, name: Union[str, Optimizer], *, lr: float = None,
                  **opt_kw) -> "EngineBuilder":
        if isinstance(name, str):
            lr = 0.05 if lr is None else lr
            self._kw.update(optimizer=get_optimizer(name, lr, **opt_kw),
                            lr=lr)
        else:
            # a pre-built Optimizer already has its rate baked in; only
            # record lr when the caller states it, so engine.lr never
            # silently disagrees with the update rule
            self._kw["optimizer"] = name
            if lr is not None:
                self._kw["lr"] = lr
        return self

    def data(self, *, alpha: float = 0.5, noise: float = 0.35,
             dataset=None) -> "EngineBuilder":
        self._kw.update(alpha=alpha, noise=noise, data=dataset)
        return self

    def rounds(self, *, local_steps: int = 2, batch_size: int = 16,
               seed: int = 0) -> "EngineBuilder":
        self._kw.update(local_steps=local_steps, batch_size=batch_size,
                        seed=seed)
        return self

    def device_model(self, dm: MET.DeviceModel) -> "EngineBuilder":
        self._kw["device_model"] = dm
        return self

    def execution(self, *, bucketing="ladder", mesh=None,
                  sanitize: bool = False,
                  width_tiers=None,
                  cross_tier: str = "fused") -> "EngineBuilder":
        """Bucket ladder ("ladder" | "exact" | explicit tuple), optional
        mesh for client-axis sharding, the checkify sanitizer mode
        (debug: per-slot NaN/OOB attribution, extra host syncs), an
        optional supernet width ladder (e.g. ``(0.5, 1.0)``) that maps
        client memory budgets to width tiers, and the cross-tier TPGF
        mode ("fused" = one update per mixed-width cohort via
        ``tpgf.fuse_tiers``; "chained" = per-tier sequential chaining)."""
        self._kw.update(bucketing=bucketing, mesh=mesh, sanitize=sanitize,
                        width_tiers=width_tiers, cross_tier=cross_tier)
        return self

    def build(self) -> Engine:
        kw = dict(self._kw)   # builder stays reusable (seed sweeps etc.)
        return Engine(self._cfg, kw.pop("n_clients"), **kw)


@functools.partial(jax.jit, static_argnames=("cfg",))
def predict(cfg: ModelConfig, params, batch):
    Lfull = cfg.split_stack_len
    z, _ = M.prefix_apply(cfg, params, batch, Lfull)
    logits, _ = M.suffix_apply(cfg, params, z, batch, Lfull)
    return logits


@functools.partial(jax.jit, static_argnames=("cfg", "d"))
def local_predict(cfg: ModelConfig, params, batch, d: int):
    """Client-side inference: depth-``d`` prefix + the phi head in
    ``params`` (callers overlay a client's phi_i on the global tree)."""
    z, _ = M.prefix_apply(cfg, params, batch, d)
    return M.local_logits(cfg, params, z)
