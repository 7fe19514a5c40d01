"""CPU checks of the chip benchmark's yardstick.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Covers the trace reduction (on a small trace written out by hand), the
required-FLOP count against a hand count, that every file the benchmark
names loads, that a configuration's family module is found by name, that
``bench/run.py`` refuses a machine without a TPU, that the plain reference
computes what the program computes at a tiny size, and that the comparison
fails the lower-precision control and the planted faults at the limits the
cells use.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import compare, inputs, run, spec  # noqa: E402
from bench.families import vit  # noqa: E402

CELLS = [w["name"] for w in
         json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


# ------------------------------------------------------------ trace reduce

def _reduce_text(name, tmp_path):
    from jax.profiler import ProfileData

    from bench import trace_reduce
    with open(os.path.join(BENCH, "tests", "data", name)) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    return trace_reduce.reduce(str(path))


def test_trace_reduction_by_hand(tmp_path):
    """A 100 us window written out by hand: host spans and nested
    dispatch pairs as the TPU's trace records them, three program
    executions, four ops (one nested in the kernel's while loop)."""
    r = _reduce_text("synthetic_trace.txtpb", tmp_path)
    assert r.window_s == pytest.approx(100e-6)
    assert r.busy_s == pytest.approx(38e-6)          # 30 + 4 + 4 us
    assert (r.executions, r.dispatches, r.rounds) == (3, 3, 1)
    assert r.kernel_executions == 1
    assert r.kernel_s == pytest.approx(30e-6)
    assert r.other_s == pytest.approx(8e-6)
    assert r.idle_share == pytest.approx(0.62)
    assert r.ops == pytest.approx({"cohort_kernel/while.1": 30e-6,
                                   "cohort_kernel/fusion.2": 10e-6,
                                   "jit_scatter/scatter.3": 4e-6,
                                   "jit_add/add.4": 4e-6})
    assert r.gaps == pytest.approx({"strategy.cohort_step": 15e-6,
                                    "bench.round": 35e-6,
                                    "strategy.aggregate": 12e-6})


def test_union_and_clip():
    from bench import trace_reduce as T
    assert T._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
    assert T._clip([(0, 4), (5, 9), (10, 12)], 2, 10) == [(2, 4), (5, 9)]


# ------------------------------------------------------------------ flops

TINY = {"n_layers": 4, "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
        "head_dim": 4, "d_ff": 16, "n_classes": 3, "image_size": 4,
        "patch_size": 2}


def test_required_flops_hand_count():
    # T = (4/2)^2 = 4 tokens; a layer at full width, per sample:
    # qkv 2*4*8*24 = 1536, scores + weighted sum 2*2*4*4*8 = 512,
    # out 2*4*8*8 = 512, mlp 2*2*4*8*16 = 2048 -> 4608
    assert vit.layer_fwd_flops(TINY, 1.0) == 4608
    # width 0.5: 1 head (a = 4), 8 hidden units:
    # 2*4*8*12 + 2*2*4*4*4 + 2*4*4*8 + 2*2*4*8*8 = 768+256+256+1024
    assert vit.layer_fwd_flops(TINY, 0.5) == 2304
    emb = 2 * 4 * 12 * 8          # 4 tokens x (2*2*3) x 8
    head = 2 * 8 * 3
    assert vit.embed_flops(TINY) == emb
    # depth 1, width 0.5, server reachable:
    # prefix fwd (emb + 2304) + local head 3*head + prefix bwd twice
    # (emb + 2*2304 each) + suffix 3 * 3 * 4608 + global head 3*head
    want = (emb + 2304) + 3 * head + 2 * (emb + 2 * 2304) \
        + 3 * 3 * 4608 + 3 * head
    assert vit.sample_step_flops(TINY, 1, 0.5, True) == want
    # unreachable: no suffix, no global head, one prefix backward
    assert vit.sample_step_flops(TINY, 1, 0.5, False) == \
        (emb + 2304) + 3 * head + (emb + 2 * 2304)
    traffic = {"batch_size": 2, "local_steps": 3}
    f, b, s = vit.round_work(TINY, traffic, [(1, 0.5, [0, 1])],
                               [True, False])
    assert s == 2 * 3 * 2
    assert f == 2 * 3 * (want + vit.sample_step_flops(TINY, 1, 0.5,
                                                         False))
    assert b > 0


def _digest(arrays):
    import hashlib

    import numpy as np
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# Cell 1's inputs and required work at one seed, as the harness made them
# before the model-specific parts moved into bench/families/vit.py: the
# move changes no reading of the cell.
PINNED_SEED = 2 ** 33 + 5
PINNED_WEIGHTS = \
    "518877a658d695a1ab58c0b5c18723235d7dd674c938bfe3fb8865dcc97eff78"
PINNED_DATASET = \
    "6b8e9ae507a2f6e8c771b1b807c9fa0e08926ed360c20c18339354bcb51dddfc"
PINNED_WORK = {
    "all_reached": ([True] * 8, (40313328500736.0, 144871234560.0, 1024)),
    "two_missed": ([True, False, True, True, False, True, True, True],
                   (34315797528576.0, 144871234560.0, 1024)),
}


def test_cell1_weights_are_the_parents():
    import jax
    cell = spec.load(CELLS[0])
    params, heads = inputs.make_weights(
        cell.family().weight_shapes(cell.model, cell.traffic["n_clients"]),
        PINNED_SEED)
    assert _digest(jax.tree.leaves((params, heads))) == PINNED_WEIGHTS


def test_cell1_dataset_is_the_parents():
    cell = spec.load(CELLS[0])
    d = inputs.make_dataset(cell.family(), cell.model, cell.traffic,
                            PINNED_SEED)
    assert _digest([d.images, d.labels, d.offsets, d.sizes]) == \
        PINNED_DATASET


@pytest.mark.parametrize("case", sorted(PINNED_WORK))
def test_cell1_round_work_is_the_parents(case):
    import numpy as np
    avail, want = PINNED_WORK[case]
    cell = spec.load(CELLS[0])
    fleet = inputs.make_fleet(cell.traffic)
    groups = [(d, w, ids) for d, gs in fleet.cohorts() for w, ids in gs]
    assert cell.family().round_work(cell.model, cell.traffic, groups,
                                    np.array(avail)) == want


TINY_WIDTHS = {
    "heads4": dict(d_model=48, n_heads=4, n_kv_heads=4, head_dim=12,
                   d_ff=96),
    # ViT-Small's 6 heads: tiers 0.25 and 0.75 keep round(1.5) = 2 and
    # round(4.5) = 4 heads
    "heads6": dict(d_model=48, n_heads=6, n_kv_heads=6, head_dim=8,
                   d_ff=96),
}


def _model(name):
    """A configuration's model by its name in BENCHMARK.json, or the tiny
    model at one of ``TINY_WIDTHS``."""
    if name in TINY_WIDTHS:
        return dict(spec.load(CELLS[0]).model, n_layers=6, image_size=16,
                    **TINY_WIDTHS[name])
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, cfg["file"])) as f:
        return json.load(f)["model"]


MODELS = sorted(c["name"] for c in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["configs"]) + ["heads6"]


@pytest.mark.parametrize("width", [0.25, 0.5, 0.75, 1.0])
@pytest.mark.parametrize("name", MODELS)
def test_kept_is_what_the_program_slices(name, width):
    """The FLOP count and the reference keep the heads and hidden units
    that the program's width slice keeps; at 6 heads, tiers 0.25 and 0.75
    round 1.5 and 4.5 heads to 2 and 4."""
    from repro.configs.base import ModelConfig
    from repro.core.supernet import width_cfg
    model = _model(name)
    sliced = width_cfg(ModelConfig(**model), width)
    assert vit.kept(model, width) == (sliced.n_heads, sliced.d_ff)


TOY_FAMILY = '''
def weight_shapes(model, n_clients):
    d = model["d"]
    return ({"w": ((d, d), 0.02), "b": ((d,), "zeros")},
            {"h": ((n_clients, d), "ones")})


def make_samples(model, traffic, rng):
    n = traffic["samples"]
    return rng.normal(size=(n, model["d"])), rng.integers(0, 2, n)


def kept(model, width):
    return max(1, round(width * model["d"]))


def round_work(model, traffic, groups, avail):
    n = sum(len(ids) for _, _, ids in groups)
    return 2.0 * n, 4.0 * n, n * traffic["batch_size"]
'''


def test_a_family_comes_as_new_files(tmp_path):
    """A configuration of another family needs no edit of the harness: its
    family module, named by ``model.family``, is found in bench/families
    beside its configuration, traffic and cell files."""
    import numpy as np
    for d in ("configs", "families", "traffic", "workloads"):
        (tmp_path / "bench" / d).mkdir(parents=True)
    (tmp_path / "bench" / "families" / "toy.py").write_text(TOY_FAMILY)
    files = {
        "configs/toy-cfg.json": {"name": "toy-cfg", "reference": "toy",
                                 "model": {"family": "toy", "d": 3}},
        "traffic/toy-mix.json": {"n_clients": 2, "samples": 16,
                                 "batch_size": 4, "dirichlet_alpha": 0.5,
                                 "fleet": {"depths": [1, 1],
                                           "widths": [0.5, 1.0]}},
        "workloads/toy-cell.json": {"lr": 0.1, "limits": {}},
    }
    for name, body in files.items():
        (tmp_path / "bench" / name).write_text(json.dumps(body))
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy-cfg",
                     "file": "bench/configs/toy-cfg.json"}],
        "workloads": [{"name": "toy-cell", "config": "toy-cfg",
                       "traffic": "toy-mix", "chips": 1}],
        "end_to_end": real["end_to_end"], "per_layer": []}))
    cell = spec.load("toy-cell", root=str(tmp_path))
    family = cell.family()
    assert family.__file__ == str(tmp_path / "bench" / "families" /
                                  "toy.py")
    params, heads = inputs.make_weights(family.weight_shapes(cell.model, 2),
                                        5)
    assert params["w"].shape == (3, 3) and float(params["b"].sum()) == 0
    assert heads["h"].shape == (2, 3)
    data = inputs.make_dataset(family, cell.model, cell.traffic, 5)
    assert data.images.shape == (16, 3) and data.sizes.sum() == 16
    fleet = inputs.make_fleet(cell.traffic)
    groups = [(d, w, ids) for d, gs in fleet.cohorts() for w, ids in gs]
    assert family.round_work(cell.model, cell.traffic, groups,
                             np.ones(2, bool)) == (4.0, 8.0, 8)
    assert family.kept(cell.model, 0.5) == 2


# ------------------------------------------------------------- the files

@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load(name):
    from repro.configs.base import ModelConfig
    cell = spec.load(name)
    ModelConfig(**cell.model)
    assert cell.cell["lr"] > 0
    assert set(cell.cell["limits"]) == set(compare.NAMES)
    assert cell.reference().run
    assert callable(cell.family().round_work)
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    fleet = inputs.make_fleet(cell.traffic)
    assert len(fleet.depths) == cell.traffic["n_clients"]
    assert set(fleet.widths) <= set(cell.traffic["width_tiers"])
    assert 1 <= fleet.depths.min() and \
        fleet.depths.max() < cell.model["n_layers"]
    assert {m["name"] for m in cell.end_to_end} == {"samples_per_s",
                                                    "setup_s"}


def test_peak_table_refuses_an_unknown_device():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")


def test_seed_streams_take_large_seeds():
    big = 2 ** 31 + 12345
    assert inputs.stream_seed(big, 1) != inputs.stream_seed(big + 1, 1)
    assert inputs.jax_key(big).shape == (2,)


def test_run_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


# ------------------------------------------- reference, control and faults

# the fleet the program allocates for the tiny model from fleet_seed 3
TINY_FLEET = {"depths": [2, 5, 5, 5, 3, 5],
              "widths": [0.25, 0.25, 1.0, 0.75, 0.25, 0.5]}


def tiny_cell(limits_from=CELLS[0], heads="heads4", **traffic):
    model = dict(spec.load(limits_from).model)
    model.update(name="tiny", n_layers=6, image_size=16,
                 **TINY_WIDTHS[heads])
    t = dict(spec.load(limits_from).traffic)
    t.update(n_clients=6, local_steps=2, batch_size=4, samples=512,
             fleet_seed=3, fleet=TINY_FLEET)
    t.update(traffic)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return spec.Cell(
        name="tiny", chips=1,
        config={"name": "tiny", "model": model,
                "reference": "vit_supersfl",
                "aggregation_precision": "highest"},
        traffic=t,
        cell={"lr": 0.05, "limits": spec.load(limits_from).cell["limits"]},
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


@pytest.fixture(scope="module", params=sorted(TINY_WIDTHS))
def tiny(request):
    cell = tiny_cell(heads=request.param)
    seed = 2 ** 32 + 7
    engine, data, fleet = run.build(cell, seed)
    prog = run.check_rounds(engine)
    ref, _ = run.reference_readings(cell, seed, data, fleet)
    return cell, seed, data, fleet, prog, ref


def test_tiny_fleet_has_cohorts_of_several_depths_and_widths(tiny):
    cell, _, _, fleet, _, _ = tiny
    cohorts = fleet.cohorts()
    assert len(cohorts) > 1
    assert any(len(groups) > 1 for _, groups in cohorts)


def test_reference_computes_what_the_program_computes(tiny):
    """float32 on the CPU: the program and the reference agree far inside
    the cells' limits (the gaps left are float32 rounding)."""
    _, _, _, _, prog, ref = tiny
    v = compare.readings(prog, ref)
    assert v["loss"] < 1e-5
    assert v["grad1"] < 2e-3 and v["change3"] < 2e-3
    assert v["left_out"] == ["params/local_head", "params/local_head_bias"]


@pytest.mark.parametrize("name", CELLS)
def test_lower_precision_control_is_not_correct(tiny, name):
    cell, seed, data, fleet, _, ref = tiny
    target = spec.load(name)
    dtype, precision = target.control
    ctl, _ = run.reference_readings(cell, seed, data, fleet, dtype=dtype,
                                    precision=precision)
    ok, checks = compare.judge(compare.readings(ctl, ref),
                               target.cell["limits"])
    assert not ok, checks


def _main_with(cell, monkeypatch, capsys, plant=None):
    import jax
    monkeypatch.setattr(spec, "load", lambda name: cell)
    monkeypatch.setattr(run, "check_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(spec, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 2 ** 34})
    if plant is not None:
        build = run.build

        def planted(*a, **k):
            engine, data, fleet = build(*a, **k)
            plant(engine)
            return engine, data, fleet

        monkeypatch.setattr(run, "build", planted)
    assert run.main(["--workload", "tiny", "--seed", "11", "--seconds",
                     "0.5", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _unchanged(engine):
    """A round that returns its state unchanged."""
    round_ = engine.run_round

    def still():
        params, heads = engine.state.params, engine.state.local_heads
        rec = round_()
        engine.state.params, engine.state.local_heads = params, heads
        return rec

    engine.run_round = still


def _half_batch(engine):
    from bench import calibrate
    calibrate.half_batch(engine)


@pytest.mark.parametrize("plant,want", [(None, True), (_unchanged, False),
                                        (_half_batch, False)],
                         ids=["sound", "state_unchanged", "half_batch"])
def test_a_run_fails_each_planted_fault(plant, want, monkeypatch, capsys):
    out = _main_with(tiny_cell(), monkeypatch, capsys, plant)
    assert out["correct"] is want, out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"samples_per_s", "setup_s"}


def test_a_run_refuses_a_fleet_the_engine_does_not_allocate(monkeypatch,
                                                            capsys):
    import jax
    cell = tiny_cell(fleet=dict(TINY_FLEET,
                                depths=[3] + TINY_FLEET["depths"][1:]))
    monkeypatch.setattr(spec, "load", lambda name: cell)
    monkeypatch.setattr(run, "check_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(spec, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 2 ** 34})
    assert run.main(["--workload", "tiny", "--seed", "11", "--seconds",
                     "0.5", "--trace", "0"]) == 4
    assert '"correct"' not in capsys.readouterr().out


def test_control_is_the_step_below_the_stated_precision():
    cell = tiny_cell()
    for stated, control in [("highest", ("float32", "high")),
                            ("high", ("float32", "default")),
                            ("default", ("bfloat16", "default"))]:
        cell.config["matmul_precision"] = stated
        assert cell.control == control


def test_the_aggregation_runs_at_the_stated_precision(monkeypatch):
    import jax
    from repro.core import aggregation
    seen = []
    inner = aggregation.aggregate

    def spy(*a, **k):
        seen.append(jax.config.jax_default_matmul_precision)
        return inner(*a, **k)

    monkeypatch.setattr(aggregation, "aggregate", spy)
    engine, _, _ = run.build(tiny_cell(), 5)
    run.one_round(engine)
    assert seen == ["highest"]
