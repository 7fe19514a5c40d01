"""``chip_smoke.py`` on the CPU: its fleet phases at the reduced
``vit16_cifar`` config (finite losses, the ``use_pallas`` phases agree),
and its entry point refusing to report without a TPU."""
import importlib.util
import os

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleet_phases_agree_at_reduced_width(smoke):
    import numpy as np

    from repro.configs import base
    cfg = base.get_reduced("vit16_cifar")
    ref = smoke.run_fleet(cfg, rounds=2, label="jnp")
    calls = []
    pal = smoke.run_fleet(cfg.replace(use_pallas=True), rounds=2,
                          label="pallas", calls=calls)
    assert np.isfinite(ref["losses"]).all() and len(ref["losses"]) == 2
    smoke.check_losses("use_pallas", pal["losses"], ref["losses"])
    assert pal["fleet_shards"] == 1 and pal["compiles"] >= 1
    # the recorded cohort-kernel shapes lower again to the program that
    # ran (on the CPU the fusion is interpreted: no Mosaic custom call)
    assert calls and calls[-1][0].use_pallas
    text = smoke.cohort_kernel.lower(*calls[-1]).as_text()
    assert "tpu_custom_call" not in text


def test_check_losses_rejects_divergence_and_nan(smoke):
    smoke.check_losses("same", [2.0, 1.9], [2.0, 1.9 + 0.5e-4])
    with pytest.raises(AssertionError, match="differ"):
        smoke.check_losses("apart", [2.0, 1.9], [2.0, 1.8])
    with pytest.raises(AssertionError, match="non-finite"):
        smoke.check_losses("nan", [float("nan")], [1.0])


def test_main_refuses_the_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs 1 TPU" in out.err
