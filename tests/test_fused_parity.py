"""Parity pin for the device-resident round path (PR 3 tentpole).

The bucketed / scanned / device-gather execution refactor must be a
numerical no-op: these golden 2-round records were produced by the
PRE-refactor engine (commit 735bb12 — host-looped batches, one jit per
cohort size, per-client tree lists) on this exact setting, and the
refactored path must reproduce them within 1e-5. Together with the seed
goldens in ``test_engine_api.py`` (a different availability/fleet setting)
this pins every layer the refactor touched: batch-RNG order, kernel math,
masked pooled-gradient means, and masked aggregation.
"""
import numpy as np
import pytest

from repro.configs import base
from repro.federated import Engine

# Pre-refactor engine records (commit 735bb12, re-run under jax/jaxlib
# 0.9.0 on the CPU backend): vit16_cifar reduced to n_layers=4/d_model=48/
# n_heads=4/head_dim=12/d_ff=96/image_size=16/n_classes=6, n_clients=6,
# seed=0, lr=0.3, local_steps=2, batch_size=8, availability=0.8.
PRE_REFACTOR_GOLDEN = {
    "ssfl": [{"loss": 1.7477719177146558, "comm_mb": 2.54, "time_s": 1.16},
             {"loss": 1.7392217234149328, "comm_mb": 5.17, "time_s": 2.31}],
    "sfl": [{"loss": 1.7640077114105224, "comm_mb": 2.08, "time_s": 1.04},
            {"loss": 1.7273731708526612, "comm_mb": 4.86, "time_s": 2.08}],
    "fedavg": [{"loss": 1.7428882122039795, "comm_mb": 2.4, "time_s": 0.45},
               {"loss": 1.7359342575073242, "comm_mb": 5.41, "time_s": 0.9}],
}


def _cfg():
    return base.get_reduced("vit16_cifar").replace(
        n_layers=4, d_model=48, n_heads=4, n_kv_heads=4, head_dim=12,
        d_ff=96, image_size=16, n_classes=6)


@pytest.mark.parametrize("method", sorted(PRE_REFACTOR_GOLDEN))
def test_two_round_records_match_pre_refactor_engine(method):
    eng = Engine(_cfg(), 6, method, seed=0, lr=0.3, local_steps=2,
                 batch_size=8, availability=0.8)
    for want in PRE_REFACTOR_GOLDEN[method]:
        rec = eng.run_round()
        for k, v in want.items():
            assert rec[k] == pytest.approx(v, abs=1e-5), (method, k)


def test_exact_and_ladder_bucketing_agree():
    """Padding a cohort up to its bucket must be a numerical no-op: the
    same run under exact-size kernels (no padded slots) and under the
    default ladder (padded slots masked everywhere) produces the same
    model."""
    import jax
    mk = lambda b: Engine(_cfg(), 5, "ssfl", seed=0, lr=0.3, local_steps=2,
                          batch_size=8, availability=0.7, bucketing=b)
    a, b = mk("exact"), mk("ladder")
    for _ in range(2):
        ra, rb = a.run_round(), b.run_round()
        assert rb["loss"] == pytest.approx(ra["loss"], abs=1e-5)
    for x, y in zip(jax.tree.leaves(a.state.params),
                    jax.tree.leaves(b.state.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5)
