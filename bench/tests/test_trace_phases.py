"""CPU checks of ``bench/trace_phases.py`` and ``bench/xplane_meta.py`` on
a trace written out by hand (``data/program_spans_trace.txtpb``): idle by
layer, the cohort kernel's self time by name scope, and that the
program's own spans leave every reading of ``bench/trace_reduce.py``
where it was.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec, trace_phases, trace_reduce, xplane_meta  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data", "program_spans_trace.txtpb")
CELL = "vitb-tiers4-n8-steps32"
NEW = ("engine.idle_ms_per_round", "strategy.idle_ms_per_round",
       "cohort_kernel.client_ms_per_round",
       "cohort_kernel.server_ms_per_round",
       "cohort_kernel.update_ms_per_round")


def _text(program_spans=True, kernel="jit_cohort_kernel"):
    with open(DATA) as f:
        lines = f.read().splitlines()
    if not program_spans:
        lines = [ln for ln in lines if not ln.endswith("# program")]
    return "\n".join(lines).replace("jit_cohort_kernel(", kernel + "(")


def _write(tmp_path, text, name="t.xplane.pb"):
    from jax.profiler import ProfileData
    path = tmp_path / name
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def _ctx(path, rounds=1):
    red = trace_reduce.reduce(path)
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
            "hbm_bytes": 1 << 30}
    return types.SimpleNamespace(
        trace=red, window_s=red.window_s, rounds=rounds, flops=2e6,
        bytes=1e5, peak=peak, chips=1, memory_peak_bytes=1 << 28, slots=8,
        real_slots=7)


def test_idle_by_layer(tmp_path):
    ph = trace_phases.reduce(_write(tmp_path, _text()))
    assert ph.idle == pytest.approx({"engine": 48e-6, "strategy": 74e-6,
                                     "harness": 3e-6})
    assert ph.idle_spans == pytest.approx({
        "engine.draw": 10e-6, "strategy.split": 13e-6,
        "cohort_kernel": 14e-6, "strategy.cohort_step": 22e-6,
        "engine.account": 38e-6, "strategy.aggregate": 25e-6,
        "bench.round": 3e-6})
    # every idle second is charged to one layer
    red = trace_reduce.reduce(_write(tmp_path, _text(), "r.xplane.pb"))
    assert sum(ph.idle.values()) == pytest.approx(
        red.window_s - red.busy_s)


def test_kernel_self_time_by_scope(tmp_path):
    ph = trace_phases.reduce(_write(tmp_path, _text()))
    assert ph.kernel_executions == 1
    assert ph.scopes == pytest.approx({"client": 20e-6, "server": 15e-6,
                                       "update": 10e-6, "gather": 2e-6,
                                       "other": 13e-6})
    red = trace_reduce.reduce(_write(tmp_path, _text(), "r.xplane.pb"))
    assert sum(ph.scopes.values()) == pytest.approx(red.kernel_s)


def test_self_time_subtracts_nested_ops_and_scopes_are_inherited():
    """A loop with no scope of its own takes the one scope its body
    holds; an unscoped op inside it takes the loop's; a loop holding two
    scopes, and what sits directly in it, keep none."""
    ev = [(0, 100, "step"), (10, 30, "a"), (40, 90, "layers"),
          (50, 60, "b"), (60, 70, "copy"), (95, 99, "c")]
    own = {"a": "update", "b": "client", "c": None, "copy": None,
           "layers": None, "step": None}
    got = {n: (t, sc) for _, _, n, t, sc in
           trace_phases.scoped_self_times(ev, own.get)}
    assert got == {"step": (100 - 20 - 50 - 4, None), "a": (20, "update"),
                   "layers": (30, "client"), "b": (10, "client"),
                   "copy": (10, "client"), "c": (4, None)}


@pytest.mark.parametrize("path,want", [
    ("jit(f)/while/body/vmap(tpgf.client)/jvp(tpgf.client)/dot", "client"),
    ("jit(f)/vmap(tpgf.client)/transpose(jvp(tpgf.server))/dot", "server"),
    ("jit(cohort_kernel)/while/body/cohort_kernel.update/add", "update"),
    ("jit(cohort_kernel)/while/body/cohort_kernel.gather/gather", "gather"),
    ("jit(cohort_kernel)/while", None),
    ("jit(f)/tpgf.clients/dot", None),
    ("jit(cohort_kernel)/while/body/closed_call/vmap(transpose(jvp("
     "tpgf.client)))/while/body/closed_call/reshape;reshape:", "client"),
    ("jit(f)/while/copy;jit(f)/while/tpgf.server/dot:", "server"),
    ("jit(f)/tpgf.server/add;jit(f)/cohort_kernel.update/add:", "server"),
    ("", None),
    (None, None),
])
def test_innermost_scope_of_a_path(path, want):
    assert trace_phases.scope(path) == want


def test_metadata_stats_are_read(tmp_path):
    meta = xplane_meta.op_metadata(_write(tmp_path, _text()))
    assert set(meta) == {"/device:TPU:0"}
    ops = meta["/device:TPU:0"]
    assert ops["fusion.3"]["tf_op"].endswith("vmap(tpgf.server)/jvp()/"
                                            "dot_general")
    assert ops["copy.8"] == {}
    host = xplane_meta.op_metadata(_write(tmp_path, _text(), "h.xplane.pb"),
                                   "/host:")
    assert "bench.window" in host["/host:CPU"]


def test_a_program_without_spans_or_scopes_reads_nothing(tmp_path):
    """The parent of this change: no engine.* spans, a kernel compiled as
    jit__unknown. Both breakdowns are left out, never 0."""
    ph = trace_phases.reduce(_write(
        tmp_path, _text(program_spans=False, kernel="jit__unknown")))
    assert ph.idle is None and ph.scopes is None
    assert sum(ph.idle_spans.values()) == pytest.approx(125e-6)


def test_program_spans_leave_the_eight_readings_as_they_were(tmp_path):
    """The accepted per-layer metrics read the same on the trace with the
    program's spans as on the same trace without them."""
    cell = spec.load(CELL)
    old = [m["name"] for m in cell.per_layer if m["name"] not in NEW]
    assert len(old) == 8
    with_spans = _ctx(_write(tmp_path, _text(), "a.xplane.pb"))
    without = _ctx(_write(tmp_path, _text(program_spans=False),
                          "b.xplane.pb"))
    a = {n: cell.reader(n).read(with_spans) for n in old}
    b = {n: cell.reader(n).read(without) for n in old}
    assert a == b
    assert all(v is not None for v in a.values())
    assert with_spans.trace.kernel_s == pytest.approx(60e-6)


def test_new_readers_read_the_latest_trace(tmp_path, monkeypatch):
    profile = tmp_path / "plugins" / "profile" / "1"
    profile.mkdir(parents=True)
    path = _write(profile, _text(), "host.xplane.pb")
    monkeypatch.setattr(trace_phases, "TRACE_DIR", str(tmp_path))
    cell = spec.load(CELL)
    ctx = _ctx(path, rounds=2)
    got = {n: cell.reader(n).read(ctx) for n in NEW}
    assert got == pytest.approx({
        "engine.idle_ms_per_round": 48e-3 / 2,
        "strategy.idle_ms_per_round": 74e-3 / 2,
        "cohort_kernel.client_ms_per_round": 20e-3 / 2,
        "cohort_kernel.server_ms_per_round": 15e-3 / 2,
        "cohort_kernel.update_ms_per_round": 10e-3 / 2})
    assert ctx.phases.kernel_executions == 1     # reduced once, kept
    untraced = types.SimpleNamespace(trace=None, rounds=2)
    assert all(cell.reader(n).read(untraced) is None for n in NEW)


def test_new_metrics_are_declared_for_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]    # every cell traces
    assert CELL in cells
    for n in NEW:
        m = per_layer[n]
        assert (m["unit"], m["better"], m["source"], m["moves"],
                m["workloads"]) == ("ms", "lower", "device_trace",
                                    "samples_per_s", cells)
        assert os.path.exists(os.path.join(BENCH, "metrics", n + ".py"))
