"""The fleet engine's own tracing: host spans, device name scopes, and the
round record's kernel counters (docs/architecture.md, "Tracing").

One tiny ``ssfl`` engine with two depths and two width tiers (one
mixed-width cohort, so the cross-tier fusion runs) is traced on the CPU
for two rounds; a second engine of the same seed runs the same rounds
untraced, and the two must agree bit for bit.
"""
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs import base
from repro.federated import Engine, bucketing as BK
from repro.federated.strategies.ssfl import cohort_kernel

CFG = base.get_reduced("vit16_cifar").replace(
    n_layers=4, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
    image_size=16, n_classes=6)
# seed 2 allocates depth 1 (one client at width 0.5) and depth 3 (two at
# 0.5, three at 1.0): two cohorts, three launches in buckets 1, 2 and 4
SEED = 2
ROUNDS = 2

SPANS = ("engine.round", "engine.draw", "strategy.init_round",
         "engine.cohort", "strategy.cohort_step", "strategy.split",
         "strategy.stage", "cohort_kernel", "strategy.publish",
         "strategy.fuse_tiers", "strategy.merge_server_opt",
         "strategy.fold_server", "engine.account", "strategy.aggregate")
SCOPES = ("tpgf.client", "tpgf.server", "cohort_kernel.update",
          "cohort_kernel.gather")


def _engine():
    return Engine(CFG, 6, "ssfl", seed=SEED, lr=0.3, local_steps=2,
                  batch_size=4, width_tiers=(0.5, 1.0))


def _groups(engine):
    """[(depth, width, clients)] in launch order."""
    f = engine.state.fleet
    return [(d, float(w), int(sum(f.widths[ids] == w)))
            for d, ids in f.cohorts().items()
            for w in sorted(set(f.widths[ids].tolist()))]


def _state(engine):
    return jax.device_get({"params": engine.state.params,
                           "heads": engine.state.local_heads})


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(engine, records, host spans, launched args) of ROUNDS traced
    rounds after one warm-up round."""
    from jax.profiler import ProfileData
    eng = _engine()
    launched = []
    kernel_fn = eng.kernel_fn

    def capture(kernel, bucket):
        run = kernel_fn(kernel, bucket)

        def call(*args):
            launched.append(args)
            return run(*args)

        return call

    eng.kernel_fn = capture
    compiles0 = BK.kernel_compiles()
    warm = eng.run_round()
    warm["compiled"] = BK.kernel_compiles() - compiles0
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    recs = [eng.run_round() for _ in range(ROUNDS)]
    jax.block_until_ready(eng.state.params)
    jax.profiler.stop_trace()
    path = glob.glob(str(out / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    line = next(l for l in host.lines
                if any(e.name == "engine.round" for e in l.events))
    spans = [(e.start_ns, e.end_ns, e.name, dict(e.stats))
             for e in line.events if e.name in SPANS]
    return eng, [warm] + recs, spans, launched


@pytest.fixture(scope="module")
def untraced():
    eng = _engine()
    recs = [eng.run_round() for _ in range(1 + ROUNDS)]
    return eng, recs


def test_every_span_is_recorded(traced):
    _, _, spans, _ = traced
    assert set(SPANS) == {n for _, _, n, _ in spans}


def test_spans_nest_round_cohort_step_kernel(traced):
    _, _, spans, _ = traced

    def within(inner, outer):
        return [o for o in spans if o[2] == outer
                and o[0] <= inner[0] and inner[1] <= o[1]]

    kernels = [s for s in spans if s[2] == "cohort_kernel"]
    assert kernels
    for k in kernels:
        (step,) = within(k, "strategy.cohort_step")
        (cohort,) = within(step, "engine.cohort")
        (rnd,) = within(cohort, "engine.round")
        assert cohort[3]["round"] == rnd[3]["round"]


def test_span_metadata(traced):
    eng, _, spans, _ = traced
    rounds = [s[3]["round"] for s in spans if s[2] == "engine.round"]
    assert rounds == [2, 3]
    cohorts = [(s[3]["depth"], s[3]["clients"]) for s in spans
               if s[2] == "engine.cohort"]
    f = eng.state.fleet
    assert cohorts == [(d, len(ids)) for d, ids in f.cohorts().items()] \
        * ROUNDS
    launches = [(s[3]["width"], s[3]["bucket"], s[3]["real"])
                for s in spans if s[2] == "cohort_kernel"]
    want = [(w, eng.bucket_for(n), n) for _, w, n in _groups(eng)]
    assert launches == want * ROUNDS
    stages = [(s[3]["width"], s[3]["bucket"]) for s in spans
              if s[2] == "strategy.stage"]
    assert stages == [(w, b) for w, b, _ in want] * ROUNDS


def test_span_count_per_round_follows_the_formula(traced):
    """4 per round (round, draw, init_round, aggregate); 6 per cohort
    (cohort, cohort_step, its split prologue, merge_server_opt,
    fold_server, account); 3 per width group (stage, kernel, publish);
    1 per narrow group (its width slice, strategy.split); 1 per
    mixed-width cohort (fuse_tiers) — PERF.md section 3."""
    eng, _, spans, _ = traced
    groups = _groups(eng)
    cohorts = {d for d, _, _ in groups}
    narrow = sum(1 for _, w, _ in groups if w < 1.0)
    mixed = sum(1 for d in cohorts
                if len({w for dd, w, _ in groups if dd == d}) > 1)
    want = 4 + 6 * len(cohorts) + 3 * len(groups) + narrow + mixed
    assert (len(cohorts), len(groups), narrow, mixed) == (2, 3, 2, 1)
    assert len(spans) == want * ROUNDS


def test_round_counters(traced):
    eng, recs, _, _ = traced
    groups = _groups(eng)
    slots = sum(eng.bucket_for(n) for _, _, n in groups)
    real = sum(n for _, _, n in groups)
    assert (slots, real) == (7, 6)
    for rec in recs:
        assert rec["kernel_launches"] == len(groups)
        assert rec["kernel_slots"] == slots
        assert rec["kernel_real_slots"] == real
    assert recs[0]["kernel_compiles"] == recs[0]["compiled"]
    assert [r["kernel_compiles"] for r in recs[1:]] == [0] * ROUNDS


def test_profiler_changes_no_bit(traced, untraced):
    a, arecs, _, _ = traced
    b, brecs = untraced
    assert [r["loss"] for r in arecs] == [r["loss"] for r in brecs]
    sa, sb = _state(a), _state(b)
    assert jax.tree.structure(sa) == jax.tree.structure(sb)
    for x, y in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        np.testing.assert_array_equal(x, y)


def test_kernel_is_named_and_carries_its_scopes(traced):
    _, _, _, launched = traced
    args = launched[-1]
    lowered = cohort_kernel.lower(*args)
    assert lowered.as_text().splitlines()[0].startswith(
        "module @jit_cohort_kernel")
    hlo = lowered.compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in SCOPES:
        assert any(scope in n for n in op_names), scope
