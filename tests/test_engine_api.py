"""Strategy/Engine API tests: registry round-trip, numerical parity of the
single-code-path engine against the seed ``FederatedTrainer`` records, the
new scenario knobs (``sample_frac``, pluggable optimizer), and TrainState
checkpointing."""
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import base
from repro.federated import (Engine, FederatedTrainer, available_strategies,
                             get_strategy)
from repro.federated.strategies.base import Strategy

METHODS = ("ssfl", "sfl", "dfl", "fedavg")

# Golden 2-round records produced by the pre-refactor seed trainer
# (commit 11d6a28, re-run under jax/jaxlib 0.9.0 on the CPU backend) on
# this exact setting: vit16_cifar reduced to
# n_layers=4/d_model=48/n_heads=4/head_dim=12/d_ff=96/image_size=16/
# n_classes=6, n_clients=5, seed=0, lr=0.3, local_steps=2, batch_size=8,
# availability=0.7. The engine must reproduce them within 1e-5.
SEED_GOLDEN = {
    "ssfl": [{"loss": 1.7321600294675696, "comm_mb": 2.56, "time_s": 1.16},
             {"loss": 1.64927921416254, "comm_mb": 5.02, "time_s": 2.33}],
    "sfl": [{"loss": 1.7432544946670532, "comm_mb": 2.08, "time_s": 1.17},
            {"loss": 1.7295214176177978, "comm_mb": 3.47, "time_s": 2.34}],
    "dfl": [{"loss": 1.7432544946670532, "comm_mb": 2.08, "time_s": 1.17},
            {"loss": 1.7295230627059937, "comm_mb": 3.47, "time_s": 2.34}],
    "fedavg": [{"loss": 1.6961787939071655, "comm_mb": 1.8, "time_s": 0.41},
               {"loss": 1.6326570510864258, "comm_mb": 3.01, "time_s": 0.83}],
}


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


class TestRegistry:
    @pytest.mark.parametrize("name", METHODS)
    def test_round_trip(self, name):
        strat = get_strategy(name)
        assert isinstance(strat, Strategy)
        assert strat.name == name

    def test_all_builtins_listed(self):
        assert set(METHODS) <= set(available_strategies())

    def test_unknown_raises(self):
        with pytest.raises(KeyError, match="unknown strategy"):
            get_strategy("no-such-method")


class TestSeedParity:
    @pytest.mark.parametrize("method", METHODS)
    def test_two_round_records_match_seed(self, method):
        """The seed-shim constructor path must reproduce the seed trainer's
        per-round (loss, comm_mb, time_s) on a fixed seed."""
        tr = FederatedTrainer(_cfg(), n_clients=5, method=method, seed=0,
                              lr=0.3, local_steps=2, batch_size=8,
                              availability=0.7)
        for want in SEED_GOLDEN[method]:
            rec = tr.run_round()
            for k, v in want.items():
                assert rec[k] == pytest.approx(v, abs=1e-5), (method, k)


class TestScenarioKnobs:
    def test_sample_frac_draws_subset(self):
        eng = _engine(n_clients=8, sample_frac=0.5)
        mask = eng._draw_participants()
        assert mask.sum() == 4
        # full participation consumes no sampling randomness
        full = _engine(n_clients=8)
        assert full._draw_participants().all()

    def test_sample_frac_round_trains_only_sampled(self):
        eng = _engine(n_clients=8, sample_frac=0.5)
        # local heads are ONE stacked tree with a leading [N] client axis
        before = np.asarray(jax.tree.leaves(eng.state.local_heads)[0]).copy()
        rec = eng.run_round()
        assert np.isfinite(rec["loss"])
        after = np.asarray(jax.tree.leaves(eng.state.local_heads)[0])
        changed = [not np.allclose(before[i], after[i])
                   for i in range(eng.state.n_clients)]
        # exactly the sampled half trained their phi_i
        assert 0 < sum(changed) <= 4

    def test_sample_frac_cheaper_than_full(self):
        full = _engine(n_clients=8).run_round()
        half = _engine(n_clients=8, sample_frac=0.5).run_round()
        assert half["comm_mb"] < full["comm_mb"]

    @pytest.mark.parametrize("opt", ["sgd_momentum", "adamw"])
    def test_optimizer_hook(self, opt):
        eng = _engine(n_clients=4, optimizer=opt, local_steps=2, lr=0.05)
        rec = eng.run_round()
        assert np.isfinite(rec["loss"])

    def test_builder(self):
        eng = (Engine.builder(_cfg())
               .clients(4, availability=0.9, sample_frac=1.0)
               .strategy("ssfl")
               .optimizer("sgd", lr=0.3)
               .rounds(local_steps=1, batch_size=8, seed=1)
               .build())
        assert np.isfinite(eng.run_round()["loss"])


class TestTrainState:
    def test_is_pytree(self):
        eng = _engine(n_clients=3)
        leaves = jax.tree.leaves(eng.state)
        assert len(leaves) > 0
        doubled = jax.tree.map(lambda x: x * 2, eng.state)
        assert doubled.round_idx == eng.state.round_idx

    def test_checkpoint_round_trip(self):
        eng = _engine(n_clients=3, local_steps=1)
        eng.run_round()
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "state")
            eng.state.save(path)
            other = _engine(n_clients=3, local_steps=1, seed=4)
            other.state.restore(path)
        assert other.state.round_idx == 1
        for a, b in zip(jax.tree.leaves(eng.state.params),
                        jax.tree.leaves(other.state.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def test_restores_pre_stacking_checkpoint(self):
        """PR-2-era checkpoints stored local_heads as one subtree per
        client index; restore must detect the layout and stack it."""
        from repro.checkpoint import save_checkpoint
        eng = _engine(n_clients=3, local_steps=1)
        eng.run_round()
        legacy_heads = {str(i): eng.state.head_for(i) for i in range(3)}
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "legacy")
            save_checkpoint(path, {"params": eng.state.params,
                                   "local_heads": legacy_heads,
                                   "opt_state": eng.state.opt_state},
                            step=1, meta={})
            other = _engine(n_clients=3, local_steps=1, seed=4)
            other.state.restore(path)
        for a, b in zip(jax.tree.leaves(eng.state.local_heads),
                        jax.tree.leaves(other.state.local_heads)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))


class TestLegacyCheckpointFormats:
    def test_engine_restore_from_legacy_per_index_checkpoint(self):
        """End-to-end regression for the stacked-head manifest migration:
        an ACTUAL legacy-format checkpoint on disk (``local_heads/<i>/...``
        subtrees, 11 clients so multi-digit index keys are exercised) must
        restore through ``Engine.restore`` and continue bit-identically to
        the uninterrupted run."""
        from repro.checkpoint import load_checkpoint, save_checkpoint
        mk = lambda: _engine(n_clients=11, local_steps=1, optimizer="adamw",
                             lr=0.01, availability=0.7)
        a = mk()
        a.run_round()
        a.run_round()
        with tempfile.TemporaryDirectory() as tmp:
            b = mk()
            b.run_round()
            b.save(os.path.join(tmp, "modern"))
            # rewrite the modern stacked checkpoint in the PR-2 layout:
            # one local_heads subtree per client index
            tree, manifest = load_checkpoint(os.path.join(tmp, "modern"))
            tree["local_heads"] = {
                str(i): jax.tree.map(lambda x, i=i: x[i],
                                     tree["local_heads"])
                for i in range(11)}
            save_checkpoint(os.path.join(tmp, "legacy"), tree,
                            step=manifest["step"], meta=manifest["meta"])
            c = mk()
            c.restore(os.path.join(tmp, "legacy"))
            assert c.state.round_idx == 1
            c.run_round()
        for x, y in zip(jax.tree.leaves((a.state.params,
                                         a.state.local_heads)),
                        jax.tree.leaves((c.state.params,
                                         c.state.local_heads))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestCommCostSignatureProbe:
    def test_new_hook_accepts_ids(self):
        eng = _engine("ssfl", n_clients=3)
        assert eng._comm_cost_takes_ids() is True

    def test_legacy_three_arg_hook_still_works(self):
        """A strategy written against the PR-1 protocol — no ``ids``
        parameter — must run end-to-end through the probed fallback."""
        from repro.federated.strategies.ssfl import SuperSFL

        class LegacyCost(SuperSFL):
            def comm_cost(self, engine, d, available):
                return (1000, 4) if available else (0, 4)

        eng = Engine(_cfg(), 3, LegacyCost(), seed=0, lr=0.3,
                     local_steps=1, batch_size=8, availability=1.0)
        assert eng._comm_cost_takes_ids() is False
        rec = eng.run_round()
        assert np.isfinite(rec["loss"])
        assert sum(r.comm_bytes for r in eng.accountant.rounds) == 3 * 1000
        assert sum(r.n_messages for r in eng.accountant.rounds) == 3 * 4

    def test_hasfl_per_id_pricing_matches_hand_computed(self):
        """3-client example, tuned batches pinned to (4, 8, 16): the
        ids-aware hook must price each client's smashed traffic at its OWN
        batch, the legacy call at the cohort mean."""
        from repro.core import supernet as SN
        eng = _engine("hasfl", n_clients=3, local_steps=2)
        strat = eng.strategy
        strat._bs = np.array([4, 8, 16])
        eng.state.fleet.depths[:] = 2
        d = 2
        pbytes = SN.client_param_bytes(eng.cfg, eng.state.params, d)
        per_tok = eng.tokens_per_sample() * eng.cfg.d_model * 4
        ids = np.array([0, 2])
        nbytes, nmsg = strat.comm_cost(eng, d, True, ids=ids)
        want = [2 * pbytes + eng.local_steps * 2 * b * per_tok
                for b in (4, 16)]
        np.testing.assert_array_equal(nbytes, want)
        np.testing.assert_array_equal(nmsg, [2 + 2 * eng.local_steps] * 2)
        # unavailable: only the parameter sync moves
        nbytes, _ = strat.comm_cost(eng, d, False, ids=ids)
        np.testing.assert_array_equal(nbytes, [2 * pbytes] * 2)
        # legacy (no ids) call: fleet-mean batch for this depth = 28/3
        scalar_bytes, msgs = strat.comm_cost(eng, d, True)
        assert scalar_bytes == 2 * pbytes + eng.local_steps * 2 * int(
            (28 / 3) * per_tok)
        assert msgs == 2 + 2 * eng.local_steps


class TestShapeAccounting:
    """The per-cohort accounting sizes subnetworks from shapes cached by
    (depth, width): a heterogeneous-width ``ssfl`` fleet's round stats
    equal those of the same run priced by slicing the live parameters,
    and from round 2 on the accounting sizes nothing new."""

    @staticmethod
    def _eager_sizes(cfg, params, d, width=1.0):
        from repro.core import supernet as SN
        client, server, local = SN.split_params(cfg, params, d, width)
        count = lambda t: sum(int(x.size) for x in jax.tree.leaves(t))
        nbytes = sum(int(x.size) * x.dtype.itemsize
                     for x in jax.tree.leaves(client)
                     + jax.tree.leaves(local))
        return count(client), count(server), nbytes

    def _run(self, rounds):
        eng = _engine("ssfl", n_clients=5, width_tiers=(0.5, 1.0),
                      availability=0.7)
        assert len(set(eng.state.fleet.widths.tolist())) > 1
        recs = [eng.run_round() for _ in range(rounds)]
        return eng, recs

    def test_round_stats_match_eager_sizes(self, monkeypatch):
        from repro.core import supernet as SN
        eng, recs = self._run(3)
        assert [r["size_cache_misses"] for r in recs[1:]] == [0, 0]
        with monkeypatch.context() as m:
            m.setattr(SN, "split_sizes", self._eager_sizes)
            ref, ref_recs = self._run(3)
        for got, want in zip(eng.accountant.rounds, ref.accountant.rounds):
            assert got.comm_bytes == want.comm_bytes > 0
            assert got.client_flops == want.client_flops > 0
            assert got.server_flops == want.server_flops
            assert got.n_messages == want.n_messages
        assert [r["loss"] for r in recs] == [r["loss"] for r in ref_recs]
        assert [r["comm_mb"] for r in recs] == \
            [r["comm_mb"] for r in ref_recs]
