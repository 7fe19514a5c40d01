"""Child process for tests/test_multidevice.py (not collected by pytest).

The parent spawns this under ``XLA_FLAGS=--xla_force_host_platform_device_
count=8`` (the test_dryrun_small.py pattern, so the flag never leaks into
the tier-1 process). Commands:

  parity <mesh_n> <method> [...]  — 2-round sharded-vs-replicated parity
  widthparity                     — the same parity for one width-
                                    heterogeneous cohort (width_tiers
                                    ladder, 8-device mesh)
  invariants                      — frozen-server + bit-identical resume
                                    under the sharded path
  compiles                        — O(widths x buckets) compile count and
                                    warm-cache stability under churn
  sanitize                        — Engine(sanitize=True) smoke on the
                                    forced-8-device mesh: 2 healthy rounds
                                    match the replicated engine, and an
                                    injected NaN still raises with slot
                                    attribution
  crosstier                       — cross-tier FUSED mixed-width cohorts
                                    (the ``cross_tier="fused"`` default):
                                    sharded==replicated 2-round parity,
                                    plus frozen-server and adamw-resume
                                    bit-identical under fusion

Each command prints ``<COMMAND>_OK`` lines the parent asserts on.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np


def _cfg():
    from repro.configs import base
    return base.get_reduced("vit16_cifar").replace(
        n_layers=3, d_model=24, n_heads=2, n_kv_heads=2, head_dim=12,
        d_ff=48, image_size=16, n_classes=6)


def _mesh(n):
    import jax
    from jax.sharding import Mesh
    assert len(jax.devices()) >= n, (len(jax.devices()), n)
    return Mesh(np.asarray(jax.devices()[:n]), ("data",))


def _engines(method, mesh, **kw):
    """(replicated, sharded) engine pair on identical seeds/knobs."""
    from repro.federated import Engine
    kw.setdefault("seed", 0)
    kw.setdefault("lr", 0.3)
    kw.setdefault("local_steps", 2)
    kw.setdefault("batch_size", 4)
    n = kw.pop("n_clients", 13)
    return (Engine(_cfg(), n, method, **kw),
            Engine(_cfg(), n, method, mesh=mesh, **kw))


def parity(mesh_n, *methods):
    """Per-seed 2-round parity of the sharded engine against the
    replicated one: losses, cost accounting, final params and local heads
    (fp32 tolerance — the shard-mapped pooled means psum partial sums, so
    reduction order differs). 13 clients deliberately do NOT divide the
    mesh: buckets pad to whole slots per shard, head storage falls back to
    replication, and parity must still hold."""
    import jax
    mesh = _mesh(int(mesh_n))
    for method in methods:
        rep, shd = _engines(method, mesh, availability=0.7, sample_frac=0.8)
        assert shd.fleet_shards == int(mesh_n)
        for _ in range(2):
            a, b = rep.run_round(), shd.run_round()
            nan = np.isnan(a["loss"]) and np.isnan(b["loss"])
            assert nan or abs(a["loss"] - b["loss"]) < 1e-4, (method, a, b)
            assert a["comm_mb"] == b["comm_mb"], (method, a, b)
        for name, ta, tb in (("params", rep.state.params, shd.state.params),
                             ("heads", rep.state.local_heads,
                              shd.state.local_heads)):
            for x, y in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
                np.testing.assert_allclose(
                    np.asarray(x), np.asarray(y), atol=1e-5, rtol=1e-5,
                    err_msg=f"{method}/{name}")
        print("PARITY_OK", method)


def widthparity():
    """Sharded == replicated for a width-HETEROGENEOUS cohort: the ladder
    splits the fleet into (depth, width) sub-cohorts, each riding the
    shared kernel's shard_map variant; losses, accounting and final state
    must match the replicated engine at fp32 tolerance."""
    import jax
    mesh = _mesh(8)
    rep, shd = _engines("ssfl", mesh, availability=0.7, sample_frac=0.8,
                        width_tiers=(0.5, 1.0))
    widths = rep.state.fleet.widths
    assert (widths < 1.0).any() and (widths >= 1.0).any(), widths
    np.testing.assert_array_equal(widths, shd.state.fleet.widths)
    for _ in range(2):
        a, b = rep.run_round(), shd.run_round()
        assert abs(a["loss"] - b["loss"]) < 1e-4, (a, b)
        assert a["comm_mb"] == b["comm_mb"], (a, b)
    for name, ta, tb in (("params", rep.state.params, shd.state.params),
                         ("heads", rep.state.local_heads,
                          shd.state.local_heads)):
        for x, y in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), atol=1e-5, rtol=1e-5,
                err_msg=name)
    print("WIDTHPARITY_OK ssfl")


def invariants():
    """The SPMD-fragile invariants, bit-exact under the sharded path."""
    import jax
    from repro.core.fault import AvailabilityModel
    mesh = _mesh(8)

    # frozen server: an unreachable round must be a bit-exact server no-op
    # even with carried adamw moments psum'd across shards
    _, eng = _engines("ssfl", mesh, optimizer="adamw", lr=0.05,
                      n_clients=8)
    eng.run_round()   # builds nonzero server moments
    eng.avail_model = AvailabilityModel(0.0)
    head = np.asarray(eng.state.params["head"]).copy()
    t = int(np.asarray(eng.state.opt_state["server"]["t"]))
    opt_leaves = [np.asarray(x).copy()
                  for x in jax.tree.leaves(eng.state.opt_state)]
    eng.run_round()
    np.testing.assert_array_equal(head, np.asarray(eng.state.params["head"]))
    assert int(np.asarray(eng.state.opt_state["server"]["t"])) == t
    for a, b in zip(opt_leaves, jax.tree.leaves(eng.state.opt_state)):
        np.testing.assert_array_equal(a, np.asarray(b))
    print("INVARIANTS_OK frozen_server")

    # resume: 2 uninterrupted sharded rounds == 1 round + save + fresh
    # sharded engine + restore + 1 round, bit for bit
    import tempfile
    mk = lambda: _engines("ssfl", mesh, optimizer="adamw", lr=0.01,
                          availability=0.7, sample_frac=0.8, n_clients=8)[1]
    a = mk()
    a.run_round()
    a.run_round()
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "ck")
        b = mk()
        b.run_round()
        b.save(path)
        c = mk()
        c.restore(path)
        assert c.state.round_idx == 1
        # restore must re-apply the client-axis placement (fleet_pspecs)
        head = jax.tree.leaves(c.state.local_heads)[0]
        from jax.sharding import PartitionSpec as P
        from repro.launch.sharding import fleet_axes
        # PartitionSpec normalizes a one-axis tuple to the bare name
        assert head.sharding.spec[0] == P(fleet_axes(mesh))[0], \
            head.sharding
        c.run_round()
    for x, y in zip(jax.tree.leaves((a.state.params, a.state.local_heads,
                                     a.state.opt_state)),
                    jax.tree.leaves((c.state.params, c.state.local_heads,
                                     c.state.opt_state))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    print("INVARIANTS_OK resume")


def compiles():
    """Bounded compile under the sharded path: the compile count of a
    churning run stays O(widths x buckets) (strictly fewer programs than
    distinct cohort shapes) and the warm cache absorbs rounds 4-6."""
    from repro.federated import Engine, bucketing as BK
    mesh = _mesh(8)
    eng = Engine(_cfg(), 16, "ssfl", seed=0, lr=0.3, local_steps=2,
                 batch_size=4, sample_frac=0.6, mesh=mesh)
    shapes = set()          # what an unbucketed path would specialize on
    keys = set()            # (depth, bucket) the sharded path compiles
    strat, orig = eng.strategy, type(eng.strategy).cohorts

    def spy(self, engine, ctx):
        out = orig(self, engine, ctx)
        for d, ids in out.items():
            shapes.add((d, len(ids)))
            keys.add((d, engine.bucket_for(len(ids))))
        return out

    strat.cohorts = spy.__get__(strat)
    before = BK.kernel_compiles()
    for _ in range(3):
        eng.run_round()
    fresh = BK.kernel_compiles() - before
    assert len(shapes) > len(keys), shapes
    assert fresh <= len(keys), (fresh, keys)
    warm = BK.kernel_compiles()
    for _ in range(3):
        eng.run_round()
    assert BK.kernel_compiles() == warm
    print("COMPILES_OK", fresh, len(shapes), len(keys))


def crosstier():
    """Cross-tier TPGF fusion under the sharded path. A mixed-width
    cohort runs every tier's kernel from the same server snapshot and
    ``tpgf.fuse_tiers`` folds them into ONE update; the per-tier masses
    are global (psum'd) sums, so the fused trees come out replicated and
    sharded == replicated must hold at fp32 tolerance — while the
    SPMD-fragile invariants (frozen server, resume) stay bit-exact."""
    import jax
    from repro.core.fault import AvailabilityModel
    mesh = _mesh(8)

    # 2-round parity for a mixed-width FUSED cohort (the engine default)
    rep, shd = _engines("ssfl", mesh, availability=0.7, sample_frac=0.8,
                        width_tiers=(0.5, 1.0))
    assert rep.cross_tier == "fused" and shd.cross_tier == "fused"
    widths = rep.state.fleet.widths
    assert (widths < 1.0).any() and (widths >= 1.0).any(), widths
    for _ in range(2):
        a, b = rep.run_round(), shd.run_round()
        assert abs(a["loss"] - b["loss"]) < 1e-4, (a, b)
        assert a["comm_mb"] == b["comm_mb"], (a, b)
    for name, ta, tb in (("params", rep.state.params, shd.state.params),
                         ("heads", rep.state.local_heads,
                          shd.state.local_heads)):
        for x, y in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), atol=1e-5, rtol=1e-5,
                err_msg=name)
    print("CROSSTIER_OK parity")

    # frozen server: an all-unreachable round must stay a bit-exact
    # server no-op under fusion — every tier's mass is exactly 0, the
    # delta-mode where-guard returns the base trees, and the bookkeeping
    # (adamw t) falls back to the carried value
    _, eng = _engines("ssfl", mesh, optimizer="adamw", lr=0.05,
                      n_clients=8, width_tiers=(0.5, 1.0))
    w8 = eng.state.fleet.widths
    assert (w8 < 1.0).any() and (w8 >= 1.0).any(), w8
    eng.run_round()   # builds nonzero server moments through the fuse
    eng.avail_model = AvailabilityModel(0.0)
    head = np.asarray(eng.state.params["head"]).copy()
    t = int(np.asarray(eng.state.opt_state["server"]["t"]))
    opt_leaves = [np.asarray(x).copy()
                  for x in jax.tree.leaves(eng.state.opt_state)]
    eng.run_round()
    np.testing.assert_array_equal(head, np.asarray(eng.state.params["head"]))
    assert int(np.asarray(eng.state.opt_state["server"]["t"])) == t
    for a, b in zip(opt_leaves, jax.tree.leaves(eng.state.opt_state)):
        np.testing.assert_array_equal(a, np.asarray(b))
    print("CROSSTIER_OK frozen_server")

    # resume: 2 uninterrupted fused rounds == 1 + save + restore + 1,
    # bit for bit (the fused update is deterministic given the streams)
    import tempfile
    mk = lambda: _engines("ssfl", mesh, optimizer="adamw", lr=0.01,
                          availability=0.7, sample_frac=0.8, n_clients=8,
                          width_tiers=(0.5, 1.0))[1]
    a = mk()
    a.run_round()
    a.run_round()
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "ck")
        b = mk()
        b.run_round()
        b.save(path)
        c = mk()
        c.restore(path)
        assert c.state.round_idx == 1
        c.run_round()
    for x, y in zip(jax.tree.leaves((a.state.params, a.state.local_heads,
                                     a.state.opt_state)),
                    jax.tree.leaves((c.state.params, c.state.local_heads,
                                     c.state.opt_state))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    print("CROSSTIER_OK resume")


def sanitize():
    """Sanitizer mode under a fleet mesh: the checkified variant always
    runs replicated (see ``FleetKernel.sanitized``), so a mesh engine with
    ``sanitize=True`` must still complete healthy rounds at replicated
    parity — and still trip on an injected NaN."""
    from repro.federated import Engine
    from repro.federated.bucketing import SlotSanitizerError
    mesh = _mesh(8)
    rep, shd = _engines("ssfl", mesh, availability=0.7, n_clients=8,
                        sanitize=True)
    rep.sanitize = False   # plain replicated reference, same seed/knobs
    for _ in range(2):
        a, b = rep.run_round(), shd.run_round()
        assert abs(a["loss"] - b["loss"]) < 1e-5, (a, b)
    print("SANITIZE_OK healthy_mesh_rounds")

    eng = Engine(_cfg(), 8, "ssfl", seed=0, lr=0.3, local_steps=1,
                 batch_size=4, mesh=mesh, sanitize=True)
    eng.data["clients"][3].images[:] = float("nan")
    try:
        eng.run_round()
        raise AssertionError("poisoned round did not raise")
    except SlotSanitizerError as e:
        assert e.slots, e
    print("SANITIZE_OK nan_caught_under_mesh")


if __name__ == "__main__":
    cmd, args = sys.argv[1], sys.argv[2:]
    {"parity": parity, "widthparity": widthparity,
     "invariants": invariants, "compiles": compiles,
     "sanitize": sanitize, "crosstier": crosstier}[cmd](*args)
