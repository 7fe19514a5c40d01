"""Multi-device fleet execution (PR 4 tentpole): the bucket kernels run
through ``jax.shard_map`` over the fleet/client axis and must be
numerically equivalent to the replicated path — per-seed 2-round parity
for every strategy, bit-exact frozen-server / resume invariants, and the
bounded-compile property, all on a *forced* 8-device host.

Subprocess pattern from test_dryrun_small.py: each test spawns
``tests/_multidevice_child.py`` with the device-count flag set in the
child's environment only, so it never leaks into this process (see
conftest.py). In-process tests cover the single-device / non-dividing
fallbacks, which need no mesh."""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
CHILD = os.path.join(os.path.dirname(__file__), "_multidevice_child.py")


def _run(*args, devices=8):
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, CHILD] + [str(a) for a in args],
                       capture_output=True, text=True, cwd=ROOT, env=env,
                       timeout=900)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    return r.stdout


class TestShardedParity:
    """Sharded == replicated, per seed, for every registered strategy
    (grouped into a few children to amortize jax startup)."""

    @pytest.mark.parametrize("group", [("ssfl", "hasfl"), ("sfl", "dfl"),
                                       ("fedavg", "fedavgm", "unstable")],
                             ids=lambda g: "+".join(g))
    def test_two_round_parity_8dev(self, group):
        out = _run("parity", 8, *group)
        for method in group:
            assert f"PARITY_OK {method}" in out, out

    def test_mesh_that_does_not_divide_the_fleet(self):
        """3 shards, 13 clients: buckets pad to whole slots per shard,
        head storage falls back to replication, parity still holds."""
        out = _run("parity", 3, "ssfl")
        assert "PARITY_OK ssfl" in out, out

    def test_width_heterogeneous_cohort_parity_8dev(self):
        """A width-laddered fleet ((0.5, 1.0) tiers) splits cohorts into
        (depth, width) sub-groups — sharded must still equal replicated."""
        out = _run("widthparity")
        assert "WIDTHPARITY_OK ssfl" in out, out


class TestShardedInvariants:
    def test_frozen_server_and_resume_bit_exact(self):
        out = _run("invariants")
        assert "INVARIANTS_OK frozen_server" in out, out
        assert "INVARIANTS_OK resume" in out, out

    def test_cross_tier_fused_cohort(self):
        """Cross-tier TPGF fusion (the ``cross_tier="fused"`` default) on
        the forced-8-device mesh: mixed-width sharded == replicated
        2-round parity, and the frozen-server / adamw-resume invariants
        stay bit-exact when the server update is the fused one."""
        out = _run("crosstier")
        assert "CROSSTIER_OK parity" in out, out
        assert "CROSSTIER_OK frozen_server" in out, out
        assert "CROSSTIER_OK resume" in out, out


class TestShardedCompileCount:
    def test_compiles_o_depths_x_buckets(self):
        out = _run("compiles")
        assert "COMPILES_OK" in out, out


class TestFallbacks:
    """No multi-device host needed: the sharded dispatch must degrade
    cleanly to the replicated kernels."""

    def _engine(self, **kw):
        from repro.configs import base
        from repro.federated import Engine
        cfg = base.get_reduced("vit16_cifar").replace(
            n_layers=3, d_model=24, n_heads=2, n_kv_heads=2, head_dim=12,
            d_ff=48, image_size=16, n_classes=6)
        kw.setdefault("seed", 0)
        kw.setdefault("lr", 0.3)
        kw.setdefault("local_steps", 1)
        kw.setdefault("batch_size", 4)
        return Engine(cfg, kw.pop("n_clients", 4), "ssfl", **kw)

    def test_single_device_fleet_mesh_runs_replicated(self):
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.federated.bucketing import FleetKernel
        from repro.federated.strategies.ssfl import cohort_kernel
        from repro.launch.mesh import make_fleet_mesh
        from repro.launch.sharding import fleet_axes
        eng = self._engine(mesh=make_fleet_mesh(1))
        assert eng.fleet_shards == 1
        assert isinstance(cohort_kernel, FleetKernel)
        # extent-1 mesh: the dispatch hands back the replicated kernel
        assert eng.kernel_fn(cohort_kernel, 8) is cohort_kernel
        assert np.isfinite(eng.run_round()["loss"])
        head = jax.tree.leaves(eng.state.local_heads)[0]
        # PartitionSpec normalizes a one-axis tuple to the bare name
        assert head.sharding.spec[0] == P(fleet_axes(eng.mesh))[0]

    def test_non_dividing_bucket_falls_back(self):
        """An explicit ladder whose entry resists the shard rounding can
        never reach shard_map: kernel_fn hands back the replicated jit."""
        from repro.federated.strategies.ssfl import cohort_kernel
        from repro.launch.mesh import make_abstract_mesh
        eng = self._engine()
        eng.mesh = make_abstract_mesh((8,), ("data",))
        assert eng.fleet_shards == 8
        assert eng.kernel_fn(cohort_kernel, 12) is cohort_kernel
        # dividing buckets would dispatch to a per-mesh sharded variant
        assert eng.bucket_for(3) == 8

    def test_bucket_rounds_to_whole_slots_per_shard(self):
        from repro.federated.bucketing import bucket_size
        assert bucket_size(5, multiple_of=8) == 8
        assert bucket_size(9, multiple_of=8) == 16
        assert bucket_size(17, multiple_of=8) == 32   # ladder entry 32
        assert bucket_size(5, (), multiple_of=8) == 8   # exact ladder
        assert bucket_size(9, (3, 9), multiple_of=3) == 9
        assert bucket_size(4, (3, 9), multiple_of=8) == 16
