"""Readings that the limits of ``bench/workloads/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] \
        [--program-default-seeds 1,2,3] [--out file.json]

Not part of a benchmark run. In one process on the chip, at the cell's own
size, for each seed:

* ``program``: the timed path's first three rounds (``run.check_rounds``)
  against the float32 ``highest`` reference — the lower readings;
* ``control`` (``--control-seeds``): the reference computed one precision
  step below what the configuration states (``spec.Cell.control``), put in
  the program's place — the upper readings;
* ``half_batch`` (``--fault-seeds``): the program with half of every batch
  left out (each batch's second half repeats its first, so every mean is
  over the first half) — a planted fault that must read as not correct;
* ``program_default`` (``--program-default-seeds``): the program with its
  aggregation left at the model's matmul precision instead of the one the
  configuration states — the program's own lower-precision path.

A round that returns its state unchanged reads 1 on ``grad1`` and
``change3`` by their definition and needs no run. Prints one JSON line
per reading and writes them all to ``--out``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import compare, run, spec  # noqa: E402


def half_batch(engine):
    """Plant the half-batch fault: the second half of every drawn batch
    repeats the first. The batch stream is consumed as before."""
    draw = engine._sample_indices

    def halved(ids, steps, batch_size=None):
        idx = draw(ids, steps, batch_size)
        h = idx.shape[-1] // 2
        idx[..., h:2 * h] = idx[..., :h]
        return idx

    engine._sample_indices = halved


def program_readings(cell, seed, fault=None, **build):
    engine, data, fleet = run.build(cell, seed, **build)
    if fault is not None:
        fault(engine)
    out = run.check_rounds(engine)
    del engine
    gc.collect()
    return out, data, fleet


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--program-default-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = lambda s: [int(x) for x in s.split(",") if x]
    cell = spec.load(args.workload)
    from repro.launch import compile_cache
    compile_cache.enable()
    run.check_devices(cell.chips)
    rows = []

    def emit(kind, seed, prog, ref, seconds):
        v = compare.readings(prog, ref)
        row = {"kind": kind, "seed": seed, "seconds": seconds,
               **{n: v[n] for n in compare.NAMES}, "leaves": v["leaves"],
               "losses": prog["losses"], "ref_losses": ref["losses"]}
        rows.append(row)
        print(json.dumps(row), flush=True)

    every = sorted(set(seeds(args.seeds) + seeds(args.control_seeds)
                       + seeds(args.fault_seeds)
                       + seeds(args.program_default_seeds)))
    for seed in every:
        t0 = time.perf_counter()
        prog, data, fleet = program_readings(cell, seed)
        t1 = time.perf_counter()
        ref, _ = run.reference_readings(cell, seed, data, fleet)
        t2 = time.perf_counter()
        if seed in seeds(args.seeds):
            emit("program", seed, prog, ref, {"program": t1 - t0,
                                              "reference": t2 - t1})
        if seed in seeds(args.control_seeds):
            dtype, precision = cell.control
            ctl, _ = run.reference_readings(cell, seed, data, fleet,
                                            dtype=dtype, precision=precision)
            emit("control", seed, ctl, ref,
                 {"control": time.perf_counter() - t2})
        if seed in seeds(args.fault_seeds):
            t3 = time.perf_counter()
            bad, _, _ = program_readings(cell, seed, fault=half_batch)
            emit("half_batch", seed, bad, ref,
                 {"program": time.perf_counter() - t3})
        if seed in seeds(args.program_default_seeds):
            t4 = time.perf_counter()
            low, _, _ = program_readings(cell, seed,
                                         aggregation_precision=None)
            emit("program_default", seed, low, ref,
                 {"program": time.perf_counter() - t4})
    summary = {}
    for kind in ("program", "control", "half_batch", "program_default"):
        ks = [r for r in rows if r["kind"] == kind]
        if ks:
            pick = max if kind == "program" else min
            summary[kind] = {n: pick(r[n] for r in ks)
                             for n in compare.NAMES}
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"cell": cell.name, "rows": rows,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
