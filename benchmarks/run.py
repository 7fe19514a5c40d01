"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Paper mapping:
  table1_*    — Table I   (rounds / comm MB / modeled time to target)
  fig3_*      — Fig. 3    (accuracy per round)
  table2_*    — Table II  (power / energy / CO2 model)
  fig6_*      — Fig. 6    (TPGF fusion-rule ablation)
  table3_*    — Table III (server-gradient availability sweep)
  kernel_*    — Pallas kernel microbenches (CPU-interpret vs jnp oracle)
  roofline_*  — §Roofline summary per (arch x shape) from results/dryrun.jsonl
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

ROWS = []


def emit(name, us, derived):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}")


def bench_table1_fig3():
    """Rounds/comm/time to target accuracy, + accuracy curves (Fig. 3)."""
    from benchmarks.common import make_trainer, run_until, Timer
    target = 0.82   # above the rigid-split baseline's plateau (see
    # EXPERIMENTS.md §Paper-validation — the paper's rounds-to-target gap
    # appears at targets the baselines struggle to reach)
    results = {}
    for method in ("ssfl", "dfl", "sfl"):
        tr = make_trainer(method, n_clients=48, seed=0, local_steps=4,
                          lr=0.2, batch_size=16)
        with Timer() as t:
            curve, hit = run_until(tr, max_rounds=30, target=target)
        s = tr.accountant.summary()
        results[method] = (curve, hit, s)
        emit(f"table1_{method}_rounds_to_{int(target*100)}",
             t.dt * 1e6, hit if hit else f">{30}")
        emit(f"table1_{method}_comm_mb", t.dt * 1e6, round(s["comm_mb"], 1))
        emit(f"table1_{method}_modeled_time_s", t.dt * 1e6, s["time_s"])
        emit(f"table2_{method}_avg_power_w", t.dt * 1e6, s["avg_power_w"])
        emit(f"table2_{method}_co2_g", t.dt * 1e6, s["co2_g"])
        final_acc = curve[-1][1]
        emit(f"table2_{method}_power_per_acc",
             t.dt * 1e6,
             round(s["avg_power_w"] / max(final_acc * 100, 1e-6), 3))
        for r, acc in curve:
            emit(f"fig3_{method}_round{r:02d}_acc", 0.0, round(acc, 4))
    if results["ssfl"][1] and results["sfl"][1]:
        emit("table1_speedup_rounds_ssfl_vs_sfl", 0.0,
             round(results["sfl"][1] / results["ssfl"][1], 2))
        emit("table1_comm_reduction_ssfl_vs_sfl", 0.0,
             round(results["sfl"][2]["comm_mb"]
                   / max(results["ssfl"][2]["comm_mb"], 1e-9), 2))
    return results


def bench_fig6_ablation():
    from benchmarks.common import make_trainer, run_until, sim_config
    for variant in ("full", "no_loss", "no_depth", "equal"):
        cfg = sim_config(tpgf_variant=variant)
        tr = make_trainer("ssfl", cfg=cfg, n_clients=12, seed=1, noise=0.85,
                          availability=0.8)
        curve, _ = run_until(tr, max_rounds=20, eval_every=4)
        emit(f"fig6_tpgf_{variant}_final_acc", 0.0, round(curve[-1][1], 4))


def bench_scenario_sampling():
    """Engine-native scenario knob: per-round client sampling (the first
    knob the strategy-registry engine adds over the seed trainer)."""
    from benchmarks.common import make_engine
    for frac in (1.0, 0.5):
        eng = make_engine("ssfl", n_clients=8, seed=5, sample_frac=frac,
                          local_steps=2, batch_size=16)
        for _ in range(3):
            rec = eng.run_round()
        emit(f"scenario_sample_frac_{int(frac*100):03d}_comm_mb", 0.0,
             round(rec["comm_mb"], 2))
        emit(f"scenario_sample_frac_{int(frac*100):03d}_loss", 0.0,
             round(rec["loss"], 4))


def bench_table3_availability():
    from benchmarks.common import make_trainer, run_until
    for frac in (1.0, 0.7, 0.5, 0.2, 0.0):
        tr = make_trainer("ssfl", availability=frac, n_clients=12, seed=2,
                          noise=0.45)
        curve, _ = run_until(tr, max_rounds=24, eval_every=4)
        emit(f"table3_avail_{int(frac*100):03d}_final_acc", 0.0,
             round(curve[-1][1], 4))


def bench_engine():
    """Device-resident round-path throughput (PR 3 tentpole): rounds/sec
    and compiles-per-5-round-run at N in {8, 32, 64} clients under
    per-round cohort churn (sample_frac=0.8), fused execution (bucket
    ladder + scanned local steps + on-device batch gather) vs the
    ``bucketing="exact"`` reference that re-specializes per distinct cohort
    size like the pre-refactor engine did. Emits ``engine_*`` rows and
    writes BENCH_engine.json so the perf trajectory is tracked from this
    PR onward. (The true pre-refactor path also staged batches through the
    host each step, so the reference is a conservative floor — measured
    pre-refactor hasfl@64 was 0.099 rounds/s on the same harness.)"""
    import time
    from benchmarks.common import sim_config
    from repro.federated import Engine
    from repro.federated import bucketing as BK

    # test-scale model (matches the parity/bucketing test config): the
    # engine bench measures ROUND-PATH overhead — dispatch, recompiles,
    # host syncs — which the full sim_config model would drown in matmul
    # time on 1 CPU core
    cfg = sim_config(n_layers=4, d_model=48, head_dim=12, d_ff=96,
                     n_classes=6)
    results = {}
    for method in ("ssfl", "hasfl"):
        for n in (8, 32, 64):
            row = {}
            for mode, bucketing in (("reference", "exact"),
                                    ("fused", "ladder")):
                eng = Engine(cfg, n, method, seed=0, lr=0.2, local_steps=2,
                             batch_size=8, sample_frac=0.8,
                             bucketing=bucketing)
                eng.run_round()   # warm the round path
                c0 = BK.kernel_compiles()
                t0 = time.perf_counter()
                for _ in range(5):
                    eng.run_round()
                dt = time.perf_counter() - t0
                row[mode] = {"rounds_per_s": round(5 / dt, 3),
                             "compiles_5rounds": BK.kernel_compiles() - c0}
                emit(f"engine_{method}_n{n:02d}_{mode}_rounds_per_s",
                     dt / 5 * 1e6, row[mode]["rounds_per_s"])
                emit(f"engine_{method}_n{n:02d}_{mode}_compiles5", 0.0,
                     row[mode]["compiles_5rounds"])
            row["speedup_fused_vs_reference"] = round(
                row["fused"]["rounds_per_s"]
                / max(row["reference"]["rounds_per_s"], 1e-9), 2)
            emit(f"engine_{method}_n{n:02d}_speedup", 0.0,
                 row["speedup_fused_vs_reference"])
            results[f"{method}_n{n}"] = row
    payload = {
        "setting": "sim_config reduced to n_layers=4/d_model=48/d_ff=96, "
                   "seed=0, lr=0.2, local_steps=2, batch_size=8, "
                   "sample_frac=0.8, 5 timed rounds after 1 warmup",
        "note": "reference = bucketing='exact' (one compile per distinct "
                "cohort size, like the pre-refactor engine); fused = "
                "default bucket ladder. Both use scanned steps + device "
                "batch gather, so the ratio under-states the win over the "
                "true pre-refactor host-staged path.",
        "results": results,
    }
    path = os.path.join(ROOT, "BENCH_engine.json")
    if os.path.exists(path):   # keep bench_engine_sharded's section
        prev = json.load(open(path))
        if "sharded_8dev" in prev:
            payload["sharded_8dev"] = prev["sharded_8dev"]
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return results


def bench_engine_sharded():
    """Multi-device fleet execution (PR 4 tentpole): rounds/sec of the
    shard_map'd bucket kernels vs the replicated path at N in {32, 64},
    measured on a forced 8-device host in a subprocess (the device-count
    flag must never touch this process — same discipline as the
    tier-1 conftest guard). Emits ``engine_sharded_*`` rows and merges a
    ``sharded_8dev`` section into BENCH_engine.json."""
    import subprocess

    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks",
                                      "sharded_worker.py")],
        capture_output=True, text=True, env=env, timeout=3600)
    if r.returncode != 0:
        # keep the row stream one-record-per-line: full stderr to our own
        # stderr, a flattened tail in the derived field
        print(r.stderr, file=sys.stderr)
        emit("engine_sharded_worker_failed", 0.0,
             r.stderr[-200:].replace("\n", " ").replace(",", ";"))
        return None
    results = json.loads(r.stdout.strip().splitlines()[-1])
    for name, row in results.items():
        for mode in ("replicated", "sharded"):
            emit(f"engine_sharded_{name}_{mode}_rounds_per_s",
                 1e6 / max(row[mode]["rounds_per_s"], 1e-9),
                 row[mode]["rounds_per_s"])
        emit(f"engine_sharded_{name}_ratio", 0.0,
             row["ratio_sharded_vs_replicated"])
        if "kernel_ratio_sharded_vs_replicated" in row:
            emit(f"engine_sharded_{name}_kernel_ratio", 0.0,
                 row["kernel_ratio_sharded_vs_replicated"])
    path = os.path.join(ROOT, "BENCH_engine.json")
    payload = json.load(open(path)) if os.path.exists(path) else {}
    payload["sharded_8dev"] = {
        "setting": "same reduced sim_config as `results`, best of 3 "
                   "passes x 3 timed rounds after 1 warmup, XLA_FLAGS="
                   "--xla_force_host_platform_device_count=8, fleet mesh "
                   "= 1-D ('data',) over all 8 forced devices",
        "note": "replicated = same 8-device process, kernels compute on "
                "one device; sharded = shard_map over the fleet axis "
                "(bucket slots split 8 ways, psum'd pooled means). Forced "
                "host devices SHARE the physical cores, so the ratio "
                "measures partition/dispatch overhead, not multi-chip "
                "speedup: the single-device baseline already gets full "
                "XLA intra-op parallelism over the slot-batched matmuls, "
                "while the sharded path pays 8 serialized executables + "
                "collectives + eager multi-device glue per round. "
                "kernel_s_per_round / kernel_ratio isolate the "
                "cohort-kernel phase from that glue; both the end-to-end "
                "and kernel ratios swing with container CPU contention "
                "(passes are interleaved so both modes see the same "
                "load). On real multi-chip hosts the sharded path is the "
                "one that scales with device count.",
        "results": results,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return results


def bench_async():
    """Buffered-async aggregation study (PR 5 tentpole): synchronous
    staleness-weighted folding (``unstable``, Wei et al.) vs FedBuff-style
    buffered folding (``async_buffered``) under gamma x Markov operating
    points, with the FedOpt server-optimizer family on the buffered side.
    The operating points follow Han et al.'s heterogeneous-data convergence
    analysis: what matters is the *stationary participation fraction* and
    the *outage correlation length*, so the sweep pins one flaky-but-mostly-
    up chain and one mostly-down chain rather than more gamma points.
    Emits ``async_*`` rows and writes BENCH_async.json + BENCH_async.md
    (the markdown comparison table). Schema in docs/benchmarks.md."""
    import time

    import numpy as np

    from benchmarks.common import sim_config
    from repro.federated import Engine
    from repro.federated.strategies.async_buffered import BufferedAsync
    from repro.federated.strategies.unstable import UnstableParticipation

    cfg = sim_config(n_layers=4, d_model=48, head_dim=12, d_ff=96,
                     n_classes=6)
    GAMMAS = (0.5, 2.0)
    # Markov operating points: stationary on-fraction 2/3 with ~5-round
    # mean outages (flaky) vs 1/3 with ~7-round outages (mostly_down)
    MARKOV = (("flaky", dict(p_up=0.4, p_down=0.2, straggle_p=0.1)),
              ("mostly_down", dict(p_up=0.15, p_down=0.3, straggle_p=0.1)))
    SERVER_OPTS = (("sgd", 1.0), ("fedadam", 0.03), ("fedyogi", 0.03))
    N_CLIENTS, ROUNDS = 8, 8

    def run_one(tag, strat):
        eng = Engine(cfg, N_CLIENTS, strat, seed=0, lr=0.2, local_steps=2,
                     batch_size=8)
        t0 = time.perf_counter()
        losses = [eng.run_round()["loss"] for _ in range(ROUNDS)]
        dt = time.perf_counter() - t0
        finite = [l for l in losses if l == l]   # drop empty-round NaNs
        # "flushes" = global updates actually applied: buffer flushes for
        # async_buffered; for unstable, the rounds that folded (a round
        # with zero participants leaves the globals untouched)
        row = {"final_acc": round(eng.evaluate(max_batches=4), 4),
               "mean_loss": round(float(np.mean(finite)), 4) if finite
               else None,
               "rounds_per_s": round(ROUNDS / dt, 3),
               "flushes": getattr(strat, "flushes", len(finite))}
        emit(f"async_{tag}_final_acc", dt / ROUNDS * 1e6, row["final_acc"])
        emit(f"async_{tag}_flushes", 0.0, row["flushes"])
        return row

    results = {}
    for mk_name, mk in MARKOV:
        for gamma in GAMMAS:
            key = f"{mk_name}_gamma{gamma}"
            grp = {}
            grp["unstable"] = run_one(
                f"{key}_unstable",
                UnstableParticipation(gamma=gamma, **mk))
            for so, slr in SERVER_OPTS:
                grp[f"async_buffered_{so}"] = run_one(
                    f"{key}_buffered_{so}",
                    BufferedAsync(capacity=4, gamma=gamma, server_opt=so,
                                  server_lr=slr, **mk))
            results[key] = grp
    payload = {
        "setting": "sim_config reduced to n_layers=4/d_model=48/d_ff=96, "
                   f"n_clients={N_CLIENTS}, seed=0, lr=0.2, local_steps=2, "
                   f"batch_size=8, {ROUNDS} rounds, eval on 4x64 test "
                   "samples; async_buffered: capacity=4, policy='count', "
                   "server_lr 1.0 (sgd) / 0.03 (fedadam, fedyogi)",
        "note": "unstable folds every round (staleness-discounted Eq.6 "
                "weights); async_buffered defers cohort deltas into the "
                "capacity-4 server buffer and only moves the globals on "
                "flush, through the named server optimizer. gamma drives "
                "both the per-client discount and the flush-time entry "
                "discount. Markov points: flaky = pi_on 2/3, mean outage "
                "5 rounds; mostly_down = pi_on 1/3, mean outage ~6.7 "
                "rounds (plus 10% deadline stragglers each).",
        "results": results,
    }
    with open(os.path.join(ROOT, "BENCH_async.json"), "w") as f:
        json.dump(payload, f, indent=1)
    _write_async_md(results, payload)
    return results


def _write_async_md(results, payload):
    """BENCH_async.md: one markdown table per Markov operating point,
    strategies as rows, gamma sweep as column groups."""
    variants = ("unstable", "async_buffered_sgd", "async_buffered_fedadam",
                "async_buffered_fedyogi")
    gammas, points = [], []
    for key in results:
        mk, g = key.rsplit("_gamma", 1)
        if mk not in points:
            points.append(mk)
        if g not in gammas:
            gammas.append(g)
    lines = ["# Buffered-async aggregation study (`bench_async`)", "",
             payload["setting"], "", payload["note"], ""]
    for mk in points:
        lines += [f"## Markov operating point: `{mk}`", ""]
        head = "| strategy | " + " | ".join(
            f"acc (γ={g}) | loss (γ={g}) | flushes (γ={g})" for g in gammas
        ) + " |"
        lines += [head,
                  "|" + "---|" * (1 + 3 * len(gammas))]
        for v in variants:
            cells = []
            for g in gammas:
                row = results[f"{mk}_gamma{g}"][v]
                cells += [f"{row['final_acc']:.3f}",
                          f"{row['mean_loss']}", f"{row['flushes']}"]
            lines.append("| `" + v + "` | " + " | ".join(cells) + " |")
        lines.append("")
    with open(os.path.join(ROOT, "BENCH_async.md"), "w") as f:
        f.write("\n".join(lines))


def bench_supernet(rounds: int = 6):
    """Elastic width-sliceable supernet study (PR 7 tentpole): final
    accuracy, accuracy-per-byte AND convergence curves across width tiers
    x strategies. Each (strategy, tier) cell trains ``rounds`` rounds with
    the fleet pinned to that width tier (single-tier ladder); the
    ``ladder`` cell lets ``core.allocation`` map client memory budgets
    onto the (0.5, 1.0) ladder, so narrow devices download the sliced
    prefix while the wide ones keep the full supernet. ``acc_per_byte`` =
    final accuracy / cumulative fleet communication — the paper's
    accuracy-per-resource lens with bytes as the resource. The per-round
    eval trace becomes a convergence curve per cell: rounds-to-target and
    bytes-to-target (Table-1's "resource to reach X%" lens). A second
    sweep (PR 10 tentpole) runs mixed-tier cohorts at N in {64, 256}
    under ``cross_tier="fused"`` (one TPGF update per cohort) vs
    ``"chained"`` (per-tier sequential folds) and records the same
    convergence lens for each — the ``cross_tier`` section of the JSON.
    Emits ``supernet_*`` rows and writes BENCH_supernet.json (schema in
    docs/benchmarks.md)."""
    import numpy as np

    from benchmarks.common import sim_config
    from repro.core import supernet as SN
    from repro.federated import Engine

    cfg = sim_config(n_layers=4, d_model=48, head_dim=12, d_ff=96,
                     n_classes=6)
    TIERS = (0.5, 1.0)
    TARGETS = (0.2, 0.3)   # accuracy thresholds for the convergence lens
    results = {}
    convergence = {}
    for method in ("ssfl", "hasfl"):
        for tier in TIERS + ("ladder",):
            ladder = TIERS if tier == "ladder" else (tier,)
            eng = Engine(cfg, 8, method, seed=0, lr=0.2, local_steps=2,
                         batch_size=8, width_tiers=ladder)
            curve = []   # [round, eval_acc, cumulative comm_mb]
            for r in range(rounds):
                eng.run_round()
                curve.append([r + 1,
                              round(eng.evaluate(max_batches=4), 4),
                              round(eng.accountant.summary()["comm_mb"],
                                    3)])
            acc = curve[-1][1]
            s = eng.accountant.summary()
            widths = np.asarray(eng.state.fleet.widths, float)
            dl = float(np.mean(
                [SN.client_param_bytes(cfg, eng.state.params, int(d),
                                       float(w))
                 for d, w in zip(eng.state.fleet.depths, widths)]))
            comm_bytes = max(s["comm_mb"] * 2**20, 1e-9)
            key = f"{method}_w{tier}"
            row = {"strategy": method,
                   "width_tier": tier if tier == "ladder" else float(tier),
                   "mean_width": round(float(widths.mean()), 3),
                   "final_acc": round(acc, 4),
                   "comm_mb": s["comm_mb"],
                   "mean_client_download_bytes": int(dl),
                   "acc_per_byte": float(f"{acc / comm_bytes:.3e}"),
                   "acc_per_gb": round(acc * 2**30 / comm_bytes, 3)}
            results[key] = row
            targets = {}
            for tgt in TARGETS:
                hit = next((p for p in curve if p[1] >= tgt), None)
                targets[f"{tgt:g}"] = {
                    "rounds_to_target": None if hit is None else hit[0],
                    "mb_to_target": None if hit is None else hit[2]}
            convergence[key] = {"strategy": method,
                                "width_tier": row["width_tier"],
                                "curve": curve, "targets": targets}
            emit(f"supernet_{key}_final_acc", 0.0, row["final_acc"])
            emit(f"supernet_{key}_comm_mb", 0.0, row["comm_mb"])
            emit(f"supernet_{key}_acc_per_gb", 0.0, row["acc_per_gb"])
            r2t = targets[f"{TARGETS[0]:g}"]["rounds_to_target"]
            emit(f"supernet_{key}_rounds_to_{TARGETS[0]:g}", 0.0,
                 "n/a" if r2t is None else r2t)
    # ---- cross-tier fusion sweep: mixed-width cohorts, fused vs chained.
    # Same model/seed/ladder as the cells above; the knob is the only
    # difference, so the convergence gap is attributable to the fusion law.
    COHORTS = (64, 256)
    cross_cells = {}
    for n in COHORTS:
        for mode in ("fused", "chained"):
            eng = Engine(cfg, n, "ssfl", seed=0, lr=0.2, local_steps=2,
                         batch_size=8, width_tiers=TIERS, cross_tier=mode)
            widths = np.asarray(eng.state.fleet.widths, float)
            curve = []
            for r in range(rounds):
                eng.run_round()
                curve.append([r + 1,
                              round(eng.evaluate(max_batches=4), 4),
                              round(eng.accountant.summary()["comm_mb"],
                                    3)])
            targets = {}
            for tgt in TARGETS:
                hit = next((p for p in curve if p[1] >= tgt), None)
                targets[f"{tgt:g}"] = {
                    "rounds_to_target": None if hit is None else hit[0],
                    "mb_to_target": None if hit is None else hit[2]}
            key = f"ssfl_n{n}_{mode}"
            cross_cells[key] = {
                "strategy": "ssfl", "n_clients": n, "cross_tier": mode,
                "mean_width": round(float(widths.mean()), 3),
                "final_acc": curve[-1][1],
                "comm_mb": eng.accountant.summary()["comm_mb"],
                "curve": curve, "targets": targets}
            emit(f"supernet_{key}_final_acc", 0.0, curve[-1][1])
            r2t = targets[f"{TARGETS[0]:g}"]["rounds_to_target"]
            emit(f"supernet_{key}_rounds_to_{TARGETS[0]:g}", 0.0,
                 "n/a" if r2t is None else r2t)
    payload = {
        "setting": "sim_config reduced to n_layers=4/d_model=48/d_ff=96, "
                   f"n_clients=8, seed=0, lr=0.2, local_steps=2, "
                   f"batch_size=8, {rounds} rounds, eval on 4x64 test "
                   "samples; width tiers pinned via single-tier ladders, "
                   "'ladder' = allocation over (0.5, 1.0)",
        "note": "acc_per_byte = final_acc / cumulative fleet comm bytes "
                "(acc_per_gb is the same number scaled by 2^30 for "
                "readability). Width slices only the client prefix "
                "download — the smashed stream stays full d_model — so "
                "the byte saving grows with split depth and local steps.",
        "results": results,
        "convergence": {
            "note": "curve = [round, eval_acc, cumulative comm_mb] per "
                    "round; targets map an accuracy threshold to the "
                    "first round (and the fleet bytes spent by then) "
                    "that reaches it — null when never reached within "
                    "the budget.",
            "targets": [float(t) for t in TARGETS],
            "cells": convergence,
        },
        "cross_tier": {
            "note": "mixed-width (0.5, 1.0) cohorts at fleet size "
                    "n_clients: cross_tier='fused' lifts each tier's TPGF "
                    "output to full width and fuses ONE update with "
                    "per-coordinate denominators; 'chained' folds the "
                    "tiers sequentially (per-tier aggregation). curve / "
                    "targets use the same convergence lens as above.",
            "cohorts": list(COHORTS),
            "targets": [float(t) for t in TARGETS],
            "cells": cross_cells,
        },
    }
    with open(os.path.join(ROOT, "BENCH_supernet.json"), "w") as f:
        json.dump(payload, f, indent=1)
    return results


def bench_kernels():
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import time_call
    rng = np.random.default_rng(0)

    from repro.kernels.tpgf_fusion import ops as FO, ref as FR
    a = jnp.asarray(rng.normal(size=(1024, 1024)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(1024, 1024)), jnp.float32)
    us_ref = time_call(lambda: FR.fuse(a, b, 0.3, 0.9))
    got = FO.fuse_leaf(a, b, 0.3, 0.9)
    err = float(jnp.max(jnp.abs(got - FR.fuse(a, b, 0.3, 0.9))))
    emit("kernel_tpgf_fusion_ref_jnp", us_ref, f"interp_maxerr={err:.1e}")

    from repro.kernels.layer_aggregate import ops as AO, ref as AR
    c = jnp.asarray(rng.normal(size=(16, 6, 4096)), jnp.float32)
    ww = jnp.asarray(rng.uniform(size=(16, 6)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(6, 4096)), jnp.float32)
    us_ref = time_call(lambda: AR.aggregate(c, ww, s, 0.01))
    err = float(jnp.max(jnp.abs(AO.aggregate_leaf(c, ww, s, 0.01)
                                - AR.aggregate(c, ww, s, 0.01))))
    emit("kernel_layer_aggregate_ref_jnp", us_ref, f"interp_maxerr={err:.1e}")

    from repro.kernels.flash_attention import ops as O, ref as R
    q = jnp.asarray(rng.normal(size=(1, 512, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 512, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 512, 2, 64)), jnp.float32)
    us_ref = time_call(lambda: R.flash_attention_ref(q, k, v, causal=True))
    err = float(jnp.max(jnp.abs(O.flash_attention(q, k, v, causal=True)
                                - R.flash_attention_ref(q, k, v, causal=True))))
    emit("kernel_flash_attention_ref_jnp", us_ref, f"interp_maxerr={err:.1e}")

    from repro.kernels.ssd_scan import ops as SO, ref as SR
    x = jnp.asarray(rng.normal(size=(1, 512, 4, 32)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (1, 512, 4)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2, (4,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(1, 512, 16)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(1, 512, 16)), jnp.float32)
    us_ref = time_call(lambda: SR.ssd_ref(x, dt, A, B, C, chunk=128)[0])
    yk, _ = SO.ssd_scan(x, dt, A, B, C, chunk=128)
    yr, _ = SR.ssd_ref(x, dt, A, B, C, chunk=128)
    err = float(jnp.max(jnp.abs(yk - yr)))
    emit("kernel_ssd_scan_ref_jnp", us_ref, f"interp_maxerr={err:.1e}")


def bench_roofline():
    path = os.path.join(ROOT, "results", "dryrun.jsonl")
    if not os.path.exists(path):
        emit("roofline_missing", 0.0, "run python -m repro.launch.dryrun")
        return
    best = {}
    for line in open(path):
        r = json.loads(line)
        if "dominant" not in r:
            continue
        best[(r["arch"], r["shape"], r["mesh"])] = r
    for (arch, shape, mesh), r in sorted(best.items()):
        if mesh != "16x16":
            continue
        t = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        emit(f"roofline_{arch}_{shape}", t * 1e6,
             f"dom={r['dominant']};useful={r['useful_flops_ratio']:.2f}")


ALL_BENCHES = ("bench_table1_fig3", "bench_fig6_ablation",
               "bench_table3_availability", "bench_scenario_sampling",
               "bench_engine", "bench_engine_sharded", "bench_async",
               "bench_supernet", "bench_kernels", "bench_roofline")


def main(argv=None) -> None:
    """Run every bench, or just the ones named on the command line
    (``python benchmarks/run.py bench_engine bench_engine_sharded``).
    ``--rounds N`` shortens the benches that take a round budget
    (``bench_supernet``) — the CI smoke runs ``bench_supernet --rounds 2``."""
    import inspect
    names = list(argv if argv is not None else sys.argv[1:])
    rounds = None
    if "--rounds" in names:
        i = names.index("--rounds")
        rounds = int(names[i + 1])
        del names[i:i + 2]
    names = names or list(ALL_BENCHES)
    unknown = [n for n in names if n not in ALL_BENCHES]
    if unknown:
        raise SystemExit(f"unknown bench(es) {unknown}; "
                         f"available: {list(ALL_BENCHES)}")
    from repro.launch import compile_cache
    compile_cache.enable()
    for name in names:
        fn = globals()[name]
        kw = {"rounds": rounds} if rounds is not None and \
            "rounds" in inspect.signature(fn).parameters else {}
        fn(**kw)
    print(f"# {len(ROWS)} rows", file=sys.stderr)


if __name__ == "__main__":
    main()
