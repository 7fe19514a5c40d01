"""Chip benchmark of the SuperSFL fleet engine: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run, in order:

1. turns JAX's persistent compilation cache on (``JAX_COMPILATION_CACHE_DIR``
   when set, else ``<checkout>/.jax_cache``) and refuses any device that
   is not a TPU listed in ``bench/peaks.json``, or fewer chips than the
   cell asks for: exit 3, no result line (exit 2 where the program under
   test, ``src/repro``, is not in the checkout);
2. builds the cell's ``Engine`` through ``Engine.builder``, checks that
   the fleet it allocates from the traffic's ``fleet_seed`` (each client's
   depth and width) is the one the traffic states (exit 4, no result
   line, where it is not), and hands it the
   benchmark's inputs for ``--seed`` (``bench/inputs.py``, with the
   configuration's family, ``bench/families/``): weights, dataset, server
   availability and batch streams;
3. runs three rounds through ``Engine.run_round`` — the window's own call —
   recording each round's loss and the change of every parameter leaf
   after rounds 1 and 3, then further whole rounds until one compiles
   nothing. That ends ``setup_s``;
4. runs whole rounds back to back until ``--seconds`` have passed, each
   ending in ``block_until_ready`` on the global parameters
   (``--trace 1``: under the profiler, with host spans around the
   strategy's calls, and for at most ``TRACE_SECONDS``);
5. reads the device's peak memory, frees the engine, runs the plain
   reference (``bench/references/``) over the same three rounds from the
   same inputs, and compares (``bench/compare.py``);
6. prints one JSON line: with ``--trace 0`` the cell's end-to-end metrics,
   with ``--trace 1`` its per-layer metrics (``bench/metrics/``).
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import compare, inputs, spec  # noqa: E402

TRACE_DIR = os.path.join(ROOT, "bench", ".trace")
MAX_WARM_ROUNDS = 4       # extra warm-up rounds allowed after the first 3
CHECK_ROUNDS = 3
# A traced window ends at the first round end after this many seconds: on
# a TPU v5e the profiler takes about 22 s per ViT-Base round to write its
# trace out, and a traced run has to end within 360 s.
TRACE_SECONDS = 12.0


class NoChip(Exception):
    """No accelerator the benchmark can measure on."""


class FleetMismatch(Exception):
    """The engine allocated another fleet than the traffic mix states."""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(*a):
    print(*a, flush=True)


def check_devices(chips: int):
    """The cell's devices; raises NoChip off a TPU of the peak table."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    spec.peaks(devs[0].device_kind)
    return devs[:chips]


# ------------------------------------------------------------ compile count

class CompileCounter:
    """Counts XLA backend compilations (kernels and eager ops alike)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if "backend_compile" in event:
            self.n += 1


# ------------------------------------------------------- spans and counters

class Recorder:
    """Harness-side counters and host spans around the program's calls.

    Counters (every run): each cohort-kernel launch's (bucket, valid-slot
    mask, steps) through a wrapper on ``engine.kernel_fn``, and
    the server-availability draws. Spans (``--trace 1`` only), as
    ``jax.profiler.TraceAnnotation``: ``strategy.cohort_step``,
    ``strategy.fuse_tiers``, ``strategy.aggregate``, ``cohort_kernel``
    (the kernel call's host side), ``bench.round`` and ``bench.window``.
    """

    def __init__(self, engine, spans: bool):
        import jax
        self.launches = []
        self.avail = []
        self.spans = spans
        self._ann = jax.profiler.TraceAnnotation
        kernel_fn = engine.kernel_fn

        def recording_kernel_fn(kernel, bucket):
            run = kernel_fn(kernel, bucket)

            def call(*args):
                # (cfg, opt, steps, width, d, cstack, lstack, server_p,
                #  images, labels, idx, avail, valid, srv_state)
                self.launches.append((int(bucket), args[12], int(args[2])))
                with self.span("cohort_kernel"):
                    return run(*args)

            return call

        engine.kernel_fn = recording_kernel_fn
        draw = engine.avail_model.draw

        def recording_draw(n):
            a = draw(n)
            self.avail.append(a.copy())
            return a

        engine.avail_model.draw = recording_draw
        if spans:
            strat = engine.strategy
            for hook in ("cohort_step", "aggregate"):
                setattr(strat, hook, self._wrap(getattr(strat, hook),
                                                "strategy." + hook))
            from repro.core import tpgf
            self._fuse = tpgf.fuse_tiers
            tpgf.fuse_tiers = self._wrap(tpgf.fuse_tiers,
                                         "strategy.fuse_tiers")

    def span(self, name):
        return self._ann(name) if self.spans else contextlib.nullcontext()

    def _wrap(self, fn, name):
        def wrapped(*a, **k):
            with self._ann(name):
                return fn(*a, **k)
        return wrapped

    def close(self):
        if self.spans:
            from repro.core import tpgf
            tpgf.fuse_tiers = self._fuse

    def slots(self, since: int = 0):
        """(bucket slots, real slots, real sample-steps per batch row) of
        the launches from index ``since`` on."""
        import jax
        rows = self.launches[since:]
        valid = jax.device_get([r[1] for r in rows])
        slots = sum(r[0] for r in rows)
        real = sum(int(v.sum()) for v in valid)
        steps = sum(int(v.sum()) * r[2] for v, r in zip(valid, rows))
        return slots, real, steps


# ------------------------------------------------------------------ build

def build(cell: spec.Cell, seed: int, aggregation_precision="stated"):
    """The cell's engine with the benchmark's inputs for ``seed``, at the
    matmul precisions the configuration states (``aggregation_precision``
    None leaves the aggregation at the model's). Returns (engine, dataset,
    the traffic's fleet)."""
    import jax
    import numpy as np
    from repro.configs.base import ModelConfig
    from repro.core.fault import AvailabilityModel
    from repro.federated import Engine

    m, t, family = cell.model, cell.traffic, cell.family()
    if cell.precision != "default":
        jax.config.update("jax_default_matmul_precision", cell.precision)
    data = inputs.make_dataset(family, m, t, seed)
    fleet = inputs.make_fleet(t)
    engine = (Engine.builder(ModelConfig(**m))
              .clients(t["n_clients"], availability=AvailabilityModel(
                  t["availability"], seed=inputs.avail_seed(seed)))
              .strategy(t["strategy"])
              .optimizer(t["optimizer"], lr=cell.cell["lr"])
              .rounds(local_steps=t["local_steps"],
                      batch_size=t["batch_size"], seed=t["fleet_seed"])
              .data(dataset=data.engine_data())
              .execution(width_tiers=tuple(t["width_tiers"]))
              .build())
    f = engine.state.fleet
    if not (np.array_equal(f.depths, fleet.depths)
            and np.array_equal(f.widths, fleet.widths) and f.feasible.all()):
        raise FleetMismatch(
            f"the engine allocated depths {f.depths.tolist()} widths "
            f"{np.asarray(f.widths).tolist()} feasible "
            f"{f.feasible.tolist()}; the traffic states depths "
            f"{fleet.depths.tolist()} widths {fleet.widths.tolist()}")
    params, heads = inputs.make_weights(
        family.weight_shapes(m, t["n_clients"]), seed)
    _same_layout(engine.state.params, params, "params")
    _same_layout(engine.state.local_heads, heads, "local_heads")
    engine.state.params, engine.state.local_heads = params, heads
    engine.state.rng = np.random.default_rng(inputs.batch_seed(seed))
    if aggregation_precision == "stated":
        aggregation_precision = cell.aggregation_precision
    if aggregation_precision is not None:
        aggregate = engine.strategy.aggregate

        def at_stated_precision(*a, **k):
            with jax.default_matmul_precision(aggregation_precision):
                return aggregate(*a, **k)

        engine.strategy.aggregate = at_stated_precision
    jax.block_until_ready((params, heads))
    return engine, data, fleet


def _same_layout(have, want, what):
    import jax
    sd = lambda t: jax.tree.map(lambda x: (x.shape, str(x.dtype)), t)
    if jax.tree.structure(have) != jax.tree.structure(want) \
            or sd(have) != sd(want):
        raise RuntimeError(f"the benchmark's {what} do not match the "
                           f"engine's layout")


def leaf_names(tree):
    """'params/layers/attn/wq'-style names of a tree's leaves."""
    import jax
    return ["/".join(str(getattr(k, "key", k)) for k in p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _norm_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                            - y.astype(jnp.float32))))
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return norms


def state_tree(engine):
    return {"params": engine.state.params,
            "local_heads": engine.state.local_heads}


# ---------------------------------------------------------------- rounds

def one_round(engine):
    import jax
    rec = engine.run_round()
    jax.block_until_ready(engine.state.params)
    return rec


def check_rounds(engine):
    """Rounds 1..3 through the window's own call, with the readings the
    comparison needs: losses and per-leaf change norms after 1 and 3."""
    import jax
    norms = _norm_fn()
    s0 = state_tree(engine)
    names = leaf_names(s0)
    out = {"losses": []}
    for r in range(1, CHECK_ROUNDS + 1):
        rec = one_round(engine)
        out["losses"].append(float(rec["loss"]))
        if r in (1, CHECK_ROUNDS):
            vals = jax.device_get(norms(state_tree(engine), s0))
            out["d1" if r == 1 else "d3"] = dict(zip(names, map(float,
                                                                 vals)))
    return out


def reference_readings(cell: spec.Cell, seed: int, data, fleet, *,
                       dtype="float32", precision="highest"):
    """The plain reference's readings for the same three rounds."""
    import numpy as np
    ref = cell.reference()
    m, t = cell.model, cell.traffic
    params, heads = inputs.make_weights(
        cell.family().weight_shapes(m, t["n_clients"]), seed)
    avail = inputs.availability(seed, t["n_clients"], CHECK_ROUNDS,
                                t["availability"])
    rng = np.random.default_rng(inputs.batch_seed(seed))
    out = {"losses": []}
    s0 = {"global": ref.to_layers(params, m["n_layers"]), "heads": heads}

    def on_round(r, loss, state):
        out["losses"].append(loss)
        if r in (1, CHECK_ROUNDS):
            out["d1" if r == 1 else "d3"] = ref.leaf_norms(
                ref.diff_state(state, s0))

    ref.run(m, t, cell.cell["lr"], params, heads, data, fleet, avail, rng,
            CHECK_ROUNDS, dtype=dtype, precision=precision,
            on_round=on_round)
    return out, avail


# ----------------------------------------------------------------- trace

def reduce_trace(trace_dir):
    from bench import trace_reduce
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return trace_reduce.reduce(files[-1])


def per_layer_metrics(cell, ctx):
    out = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load(args.workload)
    try:
        from repro.launch import compile_cache
    except ModuleNotFoundError as e:
        print(f"bench: the program under test is not in this checkout "
              f"({e}); it is imported from src/", file=sys.stderr,
              flush=True)
        return 2
    cache = compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = check_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    dev = devices[0]
    peak = spec.peaks(dev.device_kind)
    counter = CompileCounter()
    t, m = cell.traffic, cell.model
    log(f"bench: cell {cell.name} config {cell.config['name']} seed "
        f"{args.seed} lr {cell.cell['lr']} device {dev.device_kind} x"
        f"{len(devices)} compile cache {cache}")
    try:
        engine, data, fleet = build(cell, args.seed)
    except FleetMismatch as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 4
    rec = Recorder(engine, spans=bool(args.trace))
    groups = [(d, w, ids) for d, gs in fleet.cohorts() for w, ids in gs]
    buckets = [(d, w, len(ids), engine.bucket_for(len(ids)))
               for d, w, ids in groups]
    log("bench: fleet groups (depth, width, clients, bucket) "
        f"{buckets}; largest bucket {max(b[3] for b in buckets)}")

    prog = check_rounds(engine)
    warm = 0
    while True:
        before = counter.n
        one_round(engine)
        warm += 1
        if counter.n == before or warm >= MAX_WARM_ROUNDS:
            break
    setup_s = time.perf_counter() - _T0
    log(f"bench: set-up {setup_s:.3f} s, {CHECK_ROUNDS + warm} rounds, "
        f"{counter.n} backend compiles, last warm-up round compiled "
        f"{counter.n - before}; round losses {prog['losses']}")

    compiles0, kernels0 = counter.n, _kernel_compiles()
    launch0, avail0 = len(rec.launches), len(rec.avail)
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    rounds, losses = 0, []
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace \
        else args.seconds
    w0 = time.perf_counter()
    with rec.span("bench.window"):
        while True:
            with rec.span("bench.round"):
                losses.append(float(one_round(engine)["loss"]))
            rounds += 1
            if time.perf_counter() - w0 >= seconds:
                break
    window_s = time.perf_counter() - w0
    if args.trace:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"bench: profiler stopped in {time.perf_counter() - t_stop:.3f}"
            " s")
    rec.close()
    in_window = counter.n - compiles0
    kernel_in_window = _kernel_compiles() - kernels0
    log(f"bench: window {window_s:.3f} s, {rounds} rounds, "
        f"kernel_compiles() in window {kernel_in_window}, backend "
        f"compiles in window {in_window}; last loss {losses[-1]!r}")
    slots, real, sample_rows = rec.slots(launch0)
    samples = sample_rows * t["batch_size"]
    mem_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    work = [cell.family().round_work(m, t, groups, a)
            for a in rec.avail[avail0:]]
    failed = sum(1 for x in losses if not math.isfinite(x))

    drawn = rec.avail[:CHECK_ROUNDS]
    del engine, rec
    gc.collect()
    r0 = time.perf_counter()
    ref, avail_ref = reference_readings(cell, args.seed, data, fleet)
    ref_s = time.perf_counter() - r0
    values = compare.readings(prog, ref)
    ok, checks = compare.judge(values, cell.cell["limits"])
    if not all((a == b).all() for a, b in zip(drawn, avail_ref)):
        ok = False
        log("bench: the program drew other server availability than the "
            "benchmark's stream")
    log(f"bench: reference {ref_s:.3f} s; losses program {prog['losses']} "
        f"reference {ref['losses']}; worst leaves {values['leaves']}; "
        f"left out {values['left_out']}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if args.trace:
        t_red = time.perf_counter()
        try:
            red = reduce_trace(TRACE_DIR)
        except ValueError as e:          # a trace the reduction cannot read
            log(f"bench: trace not reduced: {e}")
            red = None
        log(f"bench: trace reduced in {time.perf_counter() - t_red:.3f} s")
        ctx = types.SimpleNamespace(
            trace=red, window_s=red.window_s if red else window_s,
            rounds=rounds, flops=sum(w[0] for w in work),
            bytes=sum(w[1] for w in work), peak=peak, chips=len(devices),
            memory_peak_bytes=mem_peak, slots=slots, real_slots=real)
        metrics = per_layer_metrics(cell, ctx)
        breakdown = None
        if red is not None:
            log(f"bench: trace window {red.window_s:.6f} s, busy "
                f"{red.busy_s:.6f} s, {red.rounds} rounds, "
                f"{red.executions} executions, {red.dispatches} "
                f"dispatches, kernel executions {red.kernel_executions} "
                f"({red.kernel_s} s), other {red.other_s} s")
            device.update(busy_s=red.busy_s, window_s=red.window_s)
            breakdown = {"device_ops": red.top_ops(10),
                         "idle_gaps": red.top_gaps(10)}
    else:
        metrics = {}
        for mm in cell.end_to_end:
            if mm["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            elif mm["name"] == "samples_per_s":
                metrics["samples_per_s"] = {"value": samples / window_s,
                                            "unit": mm["unit"]}
        breakdown = None
    result = {"correct": bool(ok),
              "attempted": rounds, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if in_window:
        log(f"bench: {in_window} compiles inside the window")
    result["checks"] = checks
    for n, c in checks.items():
        print(f"check {n} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    log(f"bench: run took {time.perf_counter() - _T0:.3f} s")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _kernel_compiles():
    from repro.federated import bucketing
    return bucketing.kernel_compiles()


if __name__ == "__main__":
    sys.exit(main())
