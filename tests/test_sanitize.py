"""Checkify sanitizer mode (``Engine(sanitize=True)``): injected NaNs are
caught and attributed to the offending bucket slot, out-of-bounds batch
gathers trip the ``guard_gather`` user check, healthy sanitized rounds are
bit-exact with the normal path, and ``sanitize=False`` keeps the
seed-golden parity untouched. The forced-8-device mesh smoke runs through
the ``_multidevice_child.py`` subprocess pattern so the device-count flag
never leaks into this process."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import base
from repro.federated import Engine
from repro.federated.bucketing import (SlotSanitizerError, kernel_compiles)

ROOT = os.path.join(os.path.dirname(__file__), "..")
CHILD = os.path.join(os.path.dirname(__file__), "_multidevice_child.py")

# the seed-golden setting from test_engine_api.py (2 rounds, ssfl): the
# seed trainer's losses (commit 11d6a28, re-run under jax/jaxlib 0.9.0 on
# the CPU backend)
GOLDEN_SSFL = [1.7321600294675696, 1.64927921416254]


def _cfg():
    return base.get_reduced("vit16_cifar").replace(
        n_layers=4, d_model=48, n_heads=4, n_kv_heads=4, head_dim=12,
        d_ff=96, image_size=16, n_classes=6)


def _engine(method="ssfl", **kw):
    kw.setdefault("seed", 0)
    kw.setdefault("lr", 0.3)
    kw.setdefault("local_steps", 2)
    kw.setdefault("batch_size", 8)
    return Engine(_cfg(), kw.pop("n_clients", 5), method, **kw)


class TestNaNAttribution:
    def test_injected_nan_is_caught_with_the_offending_slot(self):
        # fedavg runs ONE cohort of all clients at availability 1.0, so
        # bucket slot i holds client i: poisoning client 3's shard must
        # come back as exactly slot 3.
        eng = _engine("fedavg", sanitize=True)
        eng.data["clients"][3].images[:] = np.nan
        with pytest.raises(SlotSanitizerError) as exc:
            eng.run_round()
        assert exc.value.slots == (3,)
        assert "nan" in str(exc.value).lower()
        assert "step_kernel" in str(exc.value)

    def test_split_strategy_reports_a_slot_too(self):
        eng = _engine("ssfl", n_clients=4, local_steps=1, batch_size=4,
                      sanitize=True)
        eng.data["clients"][2].images[:] = np.nan
        with pytest.raises(SlotSanitizerError) as exc:
            eng.run_round()
        assert exc.value.slots   # depth-grouped cohorts: slot != client id
        assert "cohort_kernel" in str(exc.value)

    def test_unsanitized_run_propagates_silently(self):
        # the hazard the sanitizer exists for: same poison, default mode,
        # the round completes and the NaN just drifts into the loss
        eng = _engine("ssfl", n_clients=4, local_steps=1, batch_size=4)
        eng.data["clients"][2].images[:] = np.nan
        assert np.isnan(eng.run_round()["loss"])


class TestOOBGather:
    def test_oob_batch_index_trips_guard_gather(self):
        eng = _engine("ssfl", n_clients=4, local_steps=1, batch_size=4,
                      sanitize=True)
        orig = eng._sample_indices

        def poisoned(ids, steps, batch_size=None):
            out = orig(ids, steps, batch_size)
            out[0, 0, 0] = 10_000_000   # way past the flat dataset
            return out

        eng._sample_indices = poisoned
        with pytest.raises(SlotSanitizerError, match="out of bounds"):
            eng.run_round()

    def test_in_bounds_padded_slots_do_not_trip(self):
        # 3 of 4 clients in a 4-slot bucket: pad_rows fills the pad slot's
        # sample indices with 0 — in range, so the guard must stay quiet
        eng = _engine("ssfl", n_clients=3, local_steps=1, batch_size=4,
                      sanitize=True)
        assert np.isfinite(eng.run_round()["loss"])


class TestParity:
    def test_sanitize_false_matches_seed_goldens(self):
        eng = _engine("ssfl", availability=0.7, sanitize=False)
        for want in GOLDEN_SSFL:
            assert abs(eng.run_round()["loss"] - want) < 1e-5

    def test_sanitize_false_is_bitwise_the_default_engine(self):
        import jax
        a, b = _engine("ssfl"), _engine("ssfl", sanitize=False)
        for _ in range(2):
            ra, rb = a.run_round(), b.run_round()
            assert ra["loss"] == rb["loss"]
        for x, y in zip(jax.tree.leaves(a.state.params),
                        jax.tree.leaves(b.state.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_healthy_sanitized_rounds_match_bit_exact(self):
        # checkify only *observes* — it adds no arithmetic — but under jax
        # 0.9 its error plumbing changes how the CPU backend fuses the
        # kernel, which reorders a few fp32 sums: the losses agree to a few
        # ulp, not bit for bit, so sanitize=True stays a drop-in for debug
        a, b = _engine("ssfl"), _engine("ssfl", sanitize=True)
        for _ in range(2):
            ra, rb = a.run_round()["loss"], b.run_round()["loss"]
            assert rb == pytest.approx(ra, rel=1e-6, abs=0)


class TestAccounting:
    def test_sanitized_variant_counts_as_compiles(self):
        before = kernel_compiles()
        eng = _engine("fedavg", n_clients=4, local_steps=1, batch_size=4,
                      sanitize=True)
        eng.run_round()
        fresh = kernel_compiles() - before
        assert fresh >= 1
        warm = kernel_compiles()
        eng.run_round()   # same (depth, bucket): cache must absorb it
        assert kernel_compiles() == warm


class TestMeshSmoke:
    def test_sanitize_on_forced_8_device_mesh(self):
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, CHILD, "sanitize"],
                           capture_output=True, text=True, cwd=ROOT,
                           env=env, timeout=900)
        assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
        assert "SANITIZE_OK healthy_mesh_rounds" in r.stdout
        assert "SANITIZE_OK nan_caught_under_mesh" in r.stdout
