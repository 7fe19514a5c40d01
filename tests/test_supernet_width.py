"""Elastic width-sliceable supernet — the slice-parity contract (PR 7).

Pins the four width views of ``repro.core.supernet`` and their algebra:

  * slice-then-forward == forward-then-mask (allclose: the two traces
    reduce matmuls in different orders, so bit-exactness is NOT the
    contract here — everything structural is);
  * ``widen(slice(t)) == mask(t)`` and the scatter identity
    ``scatter(t, slice(t)) == t``, both BIT-exact (pure copy/zero ops);
  * scatter-back touches ONLY the kept coordinates;
  * width=1.0 is the identity everywhere (the legacy bit-exact path);
  * heterogeneous-width training state survives save/restore
    bit-identically (widths ride the engine stream metadata).

Property tests need hypothesis (dev extras); they skip clean without it,
the deterministic classes below always run.
"""
import functools
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.core import supernet as SN
from repro.federated import Engine
from repro.models import model as M


def _cfg(**kw):
    d = dict(n_layers=3, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
             d_ff=64, image_size=16, n_classes=6)
    d.update(kw)
    return base.get_reduced("vit16_cifar").replace(**d)


CFG = _cfg()
WIDTHS = (0.25, 0.5, 0.75)


def _params(seed: int):
    return M.init_params(CFG, jax.random.PRNGKey(seed))


def _batch(seed: int, n: int = 2):
    rng = np.random.default_rng(seed)
    return {"images": jnp.asarray(
                rng.normal(size=(n, CFG.image_size, CFG.image_size, 3)),
                jnp.float32),
            "label": jnp.asarray(rng.integers(0, CFG.n_classes, n),
                                 jnp.int32)}


# one compiled forward per width cfg; cfg is frozen/hashable == static key
_fwd = jax.jit(M.client_apply, static_argnums=0)


def _plan_masks(cfg, tree, width):
    """(path, leaf, kept?) triples: kept is the bool prefix mask for plan
    leaves, None for full-width leaves."""
    plan = SN.width_plan(cfg, width)
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = SN._leaf_name(path)
        if name in plan:
            ax, keep = plan[name]
            axis = leaf.ndim + ax
            kept = np.arange(leaf.shape[axis]) < keep
            yield path, leaf, (axis, kept)
        else:
            yield path, leaf, None


def _engine(method, **kw):
    kw.setdefault("seed", 0)
    kw.setdefault("lr", 0.3)
    kw.setdefault("local_steps", 2)
    kw.setdefault("batch_size", 4)
    cfg = kw.pop("cfg", None) or _cfg()
    return Engine(cfg, kw.pop("n_clients", 6), method, **kw)


# ------------------------------------------------------------- properties
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    S = settings(max_examples=200, deadline=None)

    class TestSliceParityProperties:
        """The ISSUE's three properties, >=200 random examples each."""

        @S
        @given(width=st.sampled_from(WIDTHS),
               d=st.integers(1, CFG.split_stack_len - 1),
               pseed=st.integers(0, 3), bseed=st.integers(0, 10**6))
        def test_slice_forward_equals_mask_forward(self, width, d, pseed,
                                                   bseed):
            """Forwarding the width-w SLICE equals forwarding the full
            client view with the pruned coordinates ZEROED: pruned head /
            hidden outputs are killed by the zeroed wo / w_down rows, so
            the two computations agree up to matmul reduction order."""
            params, batch = _params(pseed), _batch(bseed)
            full_c = SN.split_params(CFG, params, d)[0]
            sliced_c = SN.split_params(CFG, params, d, width)[0]
            z_sliced, _ = _fwd(SN.width_cfg(CFG, width), sliced_c, batch)
            z_masked, _ = _fwd(CFG, SN.mask_width(CFG, full_c, width),
                               batch)
            np.testing.assert_allclose(np.asarray(z_sliced),
                                       np.asarray(z_masked),
                                       rtol=1e-4, atol=1e-4)

        @S
        @given(width=st.sampled_from(WIDTHS),
               d=st.integers(1, CFG.split_stack_len - 1),
               pseed=st.integers(0, 3))
        def test_roundtrip_bit_exact(self, width, d, pseed):
            """widen(slice(t)) == mask(t) and scatter(t, slice(t)) == t,
            bit for bit; and depth split/merge round-trips the whole
            supernet bit-exact with the width axis in play."""
            params = _params(pseed)
            client = SN.split_params(CFG, params, d)[0]
            sliced = SN.slice_width(CFG, client, width)
            widened = SN.widen_width(CFG, sliced, width)
            masked = SN.mask_width(CFG, client, width)
            for a, b in zip(jax.tree.leaves(widened),
                            jax.tree.leaves(masked)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            rt = SN.scatter_width(CFG, client, sliced, width)
            for a, b in zip(jax.tree.leaves(rt), jax.tree.leaves(client)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            cw, server, local = SN.split_params(CFG, params, d, width)
            full_c = SN.scatter_width(CFG, client, cw, width)
            merged = SN.merge_params(CFG, full_c, server, local)
            assert set(merged) == set(params)
            for a, b in zip(jax.tree.leaves(merged),
                            jax.tree.leaves(params)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        @S
        @given(width=st.sampled_from(WIDTHS),
               d=st.integers(1, CFG.split_stack_len - 1),
               sa=st.integers(0, 10**6), sb=st.integers(0, 10**6))
        def test_scatter_touches_only_kept_coords(self, width, d, sa, sb):
            """Scattering a width-w sliced update into the shared supernet
            writes the kept prefix and NOTHING else: pruned coordinates
            keep the host tree's values bit-exact (the gradient
            scatter-back contract for mask-aware aggregation)."""
            host = SN.split_params(CFG, _params(0), d)[0]
            ra, rb = np.random.default_rng(sa), np.random.default_rng(sb)
            host = jax.tree.map(
                lambda x: jnp.asarray(ra.normal(size=x.shape), x.dtype),
                host)
            update_full = jax.tree.map(
                lambda x: jnp.asarray(rb.normal(size=x.shape), x.dtype),
                host)
            update = SN.slice_width(CFG, update_full, width)
            out = SN.scatter_width(CFG, host, update, width)
            got = jax.tree_util.tree_flatten_with_path(out)[0]
            want_new = jax.tree_util.tree_flatten_with_path(update_full)[0]
            for (g, w_, (path, h, kept)) in zip(
                    got, want_new, _plan_masks(CFG, host, width)):
                g, w_ = np.asarray(g[1]), np.asarray(w_[1])
                h = np.asarray(h)
                if kept is None:    # fully-held leaf: replaced whole
                    np.testing.assert_array_equal(g, w_)
                    continue
                axis, mask = kept
                keep_idx = tuple(
                    mask if i == axis else slice(None)
                    for i in range(g.ndim))
                drop_idx = tuple(
                    ~mask if i == axis else slice(None)
                    for i in range(g.ndim))
                np.testing.assert_array_equal(g[keep_idx], w_[keep_idx])
                np.testing.assert_array_equal(g[drop_idx], h[drop_idx])
else:   # pragma: no cover - hypothesis in [dev] extras, absent on tier-1
    class TestSliceParityProperties:
        def test_slice_parity_properties(self):
            pytest.skip("hypothesis not installed")


# ------------------------------------------------- width=1.0 is identity

class TestFullWidthIdentity:
    def test_width_cfg_identity(self):
        assert SN.width_cfg(CFG, 1.0) is CFG

    def test_views_identity(self):
        client = SN.split_params(CFG, _params(0), 2)[0]
        assert SN.slice_width(CFG, client, 1.0) is client
        assert SN.mask_width(CFG, client, 1.0) is client
        assert SN.widen_width(CFG, client, 1.0) is client
        assert SN.scatter_width(CFG, client, client, 1.0) is client

    def test_split_params_default_matches_full_width(self):
        params = _params(0)
        for a, b in zip(
                jax.tree.leaves(SN.split_params(CFG, params, 2)),
                jax.tree.leaves(SN.split_params(CFG, params, 2, 1.0))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gqa_groups_stay_whole(self):
        """Kept query heads must never read a pruned KV head: n_heads
        slices by whole GQA groups at every tier."""
        for w in (0.2, 0.25, 0.4, 0.5, 0.6, 0.75, 0.9):
            wcfg = SN.width_cfg(CFG, w)
            group = CFG.n_heads // CFG.n_kv_heads
            assert wcfg.n_heads == group * wcfg.n_kv_heads
            assert wcfg.head_dim == CFG.resolved_head_dim
            assert 1 <= wcfg.n_kv_heads <= CFG.n_kv_heads
            assert 1 <= wcfg.d_ff <= CFG.d_ff

    def test_client_param_bytes_monotone_in_width(self):
        params = _params(0)
        sizes = [SN.client_param_bytes(CFG, params, 2, w)
                 for w in (0.25, 0.5, 0.75, 1.0)]
        assert sizes == sorted(sizes)
        assert sizes[0] < sizes[-1]


# ----------------------------------------------- shape-cached split sizes

_SIZE_CFGS = {"vit16_cifar": CFG,
              "whisper_small": base.get_reduced("whisper_small")}


@functools.lru_cache(maxsize=None)
def _family_params(family: str, seed: int):
    return M.init_params(_SIZE_CFGS[family], jax.random.PRNGKey(seed))


def _eager_sizes(cfg, params, d, width):
    """The split's sizes summed over eagerly sliced leaves: (client count,
    server count, client + local bytes)."""
    client, server, local = SN.split_params(cfg, params, d, width)
    count = lambda t: sum(int(x.size) for x in jax.tree.leaves(t))
    nbytes = sum(int(x.size) * x.dtype.itemsize
                 for x in jax.tree.leaves(client) + jax.tree.leaves(local))
    return count(client), count(server), nbytes


class TestSplitSizes:
    """``client_param_bytes`` and ``split_param_counts`` come from shapes
    through a cache keyed by (cfg, shape signature, d, width): the same
    integers as slicing the live parameters, from abstract parameters
    too, and no new miss for new arrays of the same shapes."""

    @pytest.mark.parametrize("width", (0.25, 0.5, 1.0))
    @pytest.mark.parametrize("d", (1, 2))
    @pytest.mark.parametrize("family", sorted(_SIZE_CFGS))
    def test_cached_equals_eager(self, family, d, width):
        from repro.federated.strategies import base as SB
        cfg, params = _SIZE_CFGS[family], _family_params(family, 0)
        ccount, scount, nbytes = _eager_sizes(cfg, params, d, width)
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        misses0 = SN.size_cache_misses()
        assert SN.client_param_bytes(cfg, abstract, d, width) == nbytes
        assert SN.size_cache_misses() - misses0 <= 1
        misses1 = SN.size_cache_misses()
        assert SN.client_param_bytes(cfg, params, d, width) == nbytes
        assert SB.split_param_counts(cfg, abstract, d, width) == (
            ccount, scount)
        fresh = _family_params(family, 1)
        assert SN.client_param_bytes(cfg, fresh, d, width) == nbytes
        assert SB.split_param_counts(cfg, fresh, d, width) == (
            ccount, scount)
        assert SN.size_cache_misses() == misses1


# ------------------------------------------- engine-level width behavior

class TestWidthEngine:
    def test_full_width_ladder_is_bit_exact_noop(self):
        """width_tiers=(1.0,) routes through the width-grouping machinery
        but must land bit-identical to the legacy no-ladder engine."""
        a = _engine("ssfl")
        b = _engine("ssfl", width_tiers=(1.0,))
        for _ in range(2):
            a.run_round()
            b.run_round()
        for x, y in zip(jax.tree.leaves(a.state.params),
                        jax.tree.leaves(b.state.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    @pytest.mark.parametrize("method", ["ssfl", "sfl", "dfl"])
    def test_heterogeneous_width_round_runs(self, method):
        eng = _engine(method, width_tiers=(0.5, 1.0))
        widths = eng.state.fleet.widths
        assert set(np.unique(widths)) <= {0.5, 1.0}
        assert (widths < 1.0).any(), "ladder produced no narrow client"
        rec = eng.run_round()
        assert np.isfinite(rec["loss"])

    def test_hasfl_co_tunes_widths(self):
        from repro.federated.strategies.hasfl import HASFL
        eng = _engine(HASFL(width_tiers=(0.5, 1.0)))
        eng.run_round()
        widths = eng.state.fleet.widths
        assert set(np.unique(widths)) <= {0.5, 1.0}
        assert np.isfinite(eng.run_round()["loss"])

    def test_width_resume_bit_identical(self):
        """2 uninterrupted heterogeneous-width rounds == 1 round + save +
        fresh engine + restore + 1 round, bit for bit — and the width
        tiers themselves survive the checkpoint (they ride the engine
        stream metadata; fleet profiles are reconstructed from the seed,
        the widths must NOT be)."""
        mk = lambda: _engine("ssfl", optimizer="adamw", lr=0.01,
                             availability=0.7, sample_frac=0.8,
                             width_tiers=(0.5, 1.0))
        a = mk()
        assert (a.state.fleet.widths < 1.0).any()
        a.run_round()
        a.run_round()
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "ck")
            b = mk()
            b.run_round()
            b.save(path)
            c = mk()
            # sabotage the reconstructed widths: restore must overwrite
            c.state.fleet.widths = np.ones_like(c.state.fleet.widths)
            c.restore(path)
            np.testing.assert_array_equal(c.state.fleet.widths,
                                          b.state.fleet.widths)
            assert c.state.round_idx == 1
            c.run_round()
        for x, y in zip(jax.tree.leaves(a.state.params),
                        jax.tree.leaves(c.state.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a.state.local_heads),
                        jax.tree.leaves(c.state.local_heads)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
