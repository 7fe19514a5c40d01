"""Chip smoke: drive the SuperSFL fleet engine's main path once on a TPU.

The paper's ``ssfl`` strategy trains an 8-client, two-width-tier fleet of
``vit16_cifar`` at its full ViT-Base width (12 layers, d_model 768, d_ff
3072, 64 patch tokens, float32) through the entry points a user calls —
``Engine.builder(...).build()``, ``run_round``, ``evaluate`` — from random
weights and synthetic data made from one seed. Everything runs in this one
process.

    python chip_smoke.py            one chip: two phases
        1. 3 rounds + ``evaluate`` with the jnp TPGF fusion;
        2. the same fleet from the same seed with ``use_pallas=True``: its
           round losses must match phase 1 within ``LOSS_TOL`` and its
           compiled cohort kernel must hold the Pallas kernel
           (``tpu_custom_call``), i.e. ``fuse_2d`` ran compiled.
    python chip_smoke.py --chips 4  the sharded fleet path only: the same
        fleet on a 4-device fleet mesh against the replicated engine, both
        at fp32 matmul precision — losses within ``LOSS_TOL``,
        ``fleet_shards == 4``, and the stacked local heads split over four
        distinct devices.

Earlier lines are smoke output (device, config, per-round loss, compile
count and seconds, peak device bytes, accuracy), not benchmark metrics.
The last line is one JSON object, ``{"ok": true, "device": {...}}``. When
JAX finds no TPU, or any check or phase fails, the script exits non-zero
and does not print it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import base  # noqa: E402
from repro.federated import Engine, bucketing  # noqa: E402
from repro.federated.strategies.ssfl import cohort_kernel  # noqa: E402

CONFIG = "vit16_cifar"
N_CLIENTS = 8
WIDTH_TIERS = (0.5, 1.0)   # seed 0 puts clients 3 (w0.5) and 6 (w1.0) in
                           # one depth cohort: both width kernels and the
                           # fused cross-tier update run every round
AVAILABILITY = 0.9
LOCAL_STEPS = 2
# 16, not 32: the masked 12-row scans keep every layer's activations for
# the backward pass, so the 2-client full-width cohort kernel needs
# 16.7 GiB of temporaries at batch 32 (v5e compile, over the 15.75 GiB the
# chip gives a program) and 9.4 GiB at 16. No fleet size from seed 0 keeps
# a mixed-width cohort with one client per width group, so fewer clients
# would not fit batch 32 either.
BATCH_SIZE = 16
SEED = 0
ROUNDS = 3
# fp32 round losses: the two phases differ only in how Eq. 4's
# w * a + (1 - w) * b is evaluated (Pallas vs XLA fusion: FMA contraction,
# ulp-level), and sharded vs replicated (both at fp32 matmul precision)
# only in the order of fp32 sums; three rounds of SGD keep that below
# 1e-4, the engine's own sharded-parity tolerance, while a real divergence
# moves a round loss by 1e-2 or more
LOSS_TOL = 1e-4


def build_engine(cfg, *, mesh=None) -> Engine:
    return (Engine.builder(cfg)
            .clients(N_CLIENTS, availability=AVAILABILITY)
            .strategy("ssfl")
            .optimizer("sgd")
            .rounds(local_steps=LOCAL_STEPS, batch_size=BATCH_SIZE,
                    seed=SEED)
            .execution(width_tiers=WIDTH_TIERS, mesh=mesh)
            .build())


def _record_cohort_calls(engine, calls):
    """Keep the abstract arguments of every cohort-kernel call, so the
    program that ran can be lowered and compiled again afterwards."""
    kernel_fn = engine.kernel_fn

    def recording(kernel, bucket):
        run = kernel_fn(kernel, bucket)

        def call(*args):
            statics, arrays = args[:kernel.n_static], args[kernel.n_static:]
            calls.append(statics + tuple(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding),
                arrays)))
            return run(*args)

        return call

    engine.kernel_fn = recording


def _peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def run_fleet(cfg, *, mesh=None, rounds: int = ROUNDS, label: str = "",
              calls=None):
    """Build the fleet, run ``rounds`` rounds and ``evaluate``; print what
    is worth seeing and return the numbers the checks need. The engine is
    dropped before returning, so the next phase has the device to itself."""
    engine = build_engine(cfg, mesh=mesh)
    if calls is not None:
        _record_cohort_calls(engine, calls)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(engine.state.params))
    print(f"[{label}] config={cfg.name} n_layers={cfg.n_layers} "
          f"d_model={cfg.d_model} d_ff={cfg.d_ff} dtype={cfg.dtype} "
          f"params={n_params} use_pallas={cfg.use_pallas} "
          f"clients={N_CLIENTS} widths={engine.state.fleet.widths.tolist()} "
          f"depths={engine.state.fleet.depths.tolist()} "
          f"fleet_shards={engine.fleet_shards}", flush=True)
    losses, seconds = [], []
    compiles0 = bucketing.kernel_compiles()
    for _ in range(rounds):
        t0 = time.perf_counter()
        rec = engine.run_round()
        jax.block_until_ready(engine.state.params)
        seconds.append(time.perf_counter() - t0)
        losses.append(rec["loss"])
        print(f"[{label}] round {rec['round']} loss={rec['loss']!r} "
              f"seconds={seconds[-1]:.2f}", flush=True)
    acc = engine.evaluate()
    compiles = bucketing.kernel_compiles() - compiles0
    later = seconds[1:] or [float("nan")]
    print(f"[{label}] kernel_compiles={compiles} "
          f"first_round_s={seconds[0]:.2f} "
          f"later_rounds_mean_s={float(np.mean(later)):.2f} "
          f"accuracy={acc!r}", flush=True)
    heads = jax.tree.leaves(engine.state.local_heads)
    out = {"losses": losses, "accuracy": acc, "compiles": compiles,
           "fleet_shards": engine.fleet_shards,
           "head_devices": [len({s.device for s in h.addressable_shards})
                            for h in heads],
           "head_rows": [(h.sharding.shard_shape(h.shape)[0], h.shape[0])
                         for h in heads]}
    del engine, heads
    gc.collect()
    for d in (mesh.devices.flat if mesh is not None else jax.devices()[:1]):
        print(f"[{label}] device {d.id} peak_bytes_in_use="
              f"{_peak_bytes(d)}", flush=True)
    return out


def check_losses(name, got, want, tol=LOSS_TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise AssertionError(f"{name}: non-finite round loss {got} / {want}")
    diff = np.abs(got - want)
    print(f"[check] {name}: max |diff|={float(diff.max())!r} (tol {tol})",
          flush=True)
    if (diff > tol).any():
        raise AssertionError(f"{name}: round losses {got} vs {want} "
                             f"differ by {diff} > {tol}")


def one_chip(cfg):
    ref = run_fleet(cfg, label="jnp")
    calls = []
    pal = run_fleet(cfg.replace(use_pallas=True), label="pallas",
                    calls=calls)
    check_losses("use_pallas=True vs False", pal["losses"], ref["losses"])
    # the Pallas phase's cohort kernel, compiled again from the recorded
    # shapes (the persistent cache serves it): the Mosaic custom call
    # proves fuse_2d ran compiled, not in the interpreter
    hlo = cohort_kernel.lower(*calls[-1]).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("the use_pallas cohort kernel holds no "
                             "tpu_custom_call: the Pallas fusion did not "
                             "compile for the chip")
    print("[check] pallas cohort kernel HLO holds tpu_custom_call",
          flush=True)


def four_chips(cfg):
    from repro.launch.mesh import make_fleet_mesh
    # A TPU runs an fp32 matmul at default precision as one bf16 pass, and
    # the sharded engine (bucket 4, one slot per chip) and the replicated
    # one (buckets 1 and 2) are differently shaped programs. At "highest"
    # the matmuls round like fp32, so a difference past fp32 summation
    # order is the sharding's, not the matmul unit's.
    with jax.default_matmul_precision("highest"):
        shd = run_fleet(cfg, mesh=make_fleet_mesh(4), label="sharded")
        rep = run_fleet(cfg, label="replicated")
    check_losses("sharded vs replicated", shd["losses"], rep["losses"])
    if shd["fleet_shards"] != 4:
        raise AssertionError(f"fleet_shards={shd['fleet_shards']}, want 4")
    if set(shd["head_devices"]) != {4} or any(
            rows * 4 != n for rows, n in shd["head_rows"]):
        raise AssertionError(
            f"local heads not split over 4 devices: devices per leaf "
            f"{shd['head_devices']}, (rows per shard, rows) "
            f"{shd['head_rows']}")
    print("[check] fleet_shards=4; local heads split over 4 devices",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    print(f"[setup] device={dev.device_kind} count={len(devices)} "
          f"compile_cache={compile_cache.enable()}", flush=True)
    cfg = base.get_config(CONFIG)
    (four_chips if args.chips == 4 else one_chip)(cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
