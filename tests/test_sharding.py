"""Sharding-rule validation with an abstract 16x16 / 2x16x16 mesh:
every PartitionSpec axis must divide its dimension for EVERY assigned
architecture (this is what makes the dry-run lower)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import base
from repro.launch import sharding as SH
from repro.launch import steps as ST
from repro.launch.mesh import make_abstract_mesh

MESH_1POD = make_abstract_mesh((16, 16), ("data", "model"))
MESH_2POD = make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(shapes_tree, specs_tree, mesh, where):
    flat_s, _ = jax.tree_util.tree_flatten_with_path(shapes_tree)
    flat_p = jax.tree_util.tree_leaves(
        specs_tree, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_s) == len(flat_p)
    for (path, leaf), spec in zip(flat_s, flat_p):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert leaf.shape[dim] % size == 0, (
                f"{where}: {jax.tree_util.keystr(path)} dim{dim}="
                f"{leaf.shape[dim]} not divisible by {axes}={size}")


@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD],
                         ids=["16x16", "2x16x16"])
@pytest.mark.parametrize("arch", base.ARCH_IDS)
def test_param_specs_divisible(arch, mesh):
    cfg = base.get_config(arch)
    shapes = ST.params_specs(cfg)
    specs = SH.param_pspecs(cfg, shapes, mesh)
    _check_divisible(shapes, specs, mesh, arch)


@pytest.mark.parametrize("arch", ["gemma_2b", "hymba_1_5b", "whisper_small",
                                  "mamba2_2_7b", "grok_1_314b"])
@pytest.mark.parametrize("shape_name", ["decode_32k", "long_500k"])
def test_cache_specs_divisible(arch, shape_name):
    if base.skip_reason(arch, shape_name):
        pytest.skip("by design")
    cfg = base.get_config(arch)
    shape = base.INPUT_SHAPES[shape_name]
    cshapes = ST.cache_specs(cfg, shape)
    specs = SH.cache_pspecs(cfg, cshapes, MESH_1POD)
    _check_divisible(cshapes, specs, MESH_1POD, f"{arch}/{shape_name}")


def test_tricky_head_fallbacks():
    """whisper 12H & hymba 25H don't divide 16, but the flattened H*hd
    projections do — heads must never produce an invalid spec."""
    for arch in ("whisper_small", "hymba_1_5b", "gemma_2b"):
        cfg = base.get_config(arch)
        shapes = ST.params_specs(cfg)
        specs = SH.param_pspecs(cfg, shapes, MESH_1POD)
        _check_divisible(shapes, specs, MESH_1POD, arch)


def test_seq_cache_variant():
    cfg = base.get_config("internlm2_1_8b").replace(decode_cache_shard="seq")
    shape = base.INPUT_SHAPES["decode_32k"]
    specs = SH.cache_pspecs(cfg, ST.cache_specs(cfg, shape), MESH_1POD)
    assert specs["k"][2] == "model"          # W sharded over tensor axis
    assert specs["k"][3] is None and specs["k"][4] is None


def test_vocab_padding_sharding():
    for arch in base.ARCH_IDS:
        cfg = base.get_config(arch)
        assert cfg.padded_vocab % 16 == 0


class TestFleetAxis:
    """Client-axis sharding for the federated engine's stacked structures."""

    def test_fleet_pspecs_shard_when_divisible(self):
        tree = {"local_head": jax.ShapeDtypeStruct((32, 48, 6), np.float32),
                "local_head_bias": jax.ShapeDtypeStruct((32, 6), np.float32)}
        specs = SH.fleet_pspecs(tree, MESH_1POD)
        assert specs["local_head"] == P(("data",), None, None)
        assert specs["local_head_bias"] == P(("data",), None)

    def test_fleet_pspecs_replicate_small_fleets(self):
        tree = {"local_head": jax.ShapeDtypeStruct((6, 48, 6), np.float32)}
        specs = SH.fleet_pspecs(tree, MESH_1POD)   # 6 % 16 != 0
        assert specs["local_head"] == P(None, None, None)

    def test_fleet_pspecs_scalar_leaves_replicate_rank0(self):
        """0-d leaves must get the rank-0 spec P() — a P(None) would be
        longer than the leaf's rank and NamedSharding rejects it."""
        tree = {"counter": jax.ShapeDtypeStruct((), np.int32),
                "stacked": jax.ShapeDtypeStruct((32, 3), np.float32)}
        specs = SH.fleet_pspecs(tree, MESH_1POD)
        assert specs["counter"] == P()
        assert specs["stacked"] == P(("data",), None)

    def test_engine_accepts_mesh(self):
        """End-to-end on a 1-device fleet mesh: heads are placed with the
        client-axis sharding and a round still runs."""
        from jax.sharding import Mesh
        from repro.configs import base as B
        from repro.federated import Engine
        cfg = B.get_reduced("vit16_cifar").replace(
            n_layers=4, d_model=48, n_heads=4, n_kv_heads=4, head_dim=12,
            d_ff=96, image_size=16, n_classes=6)
        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
        eng = Engine(cfg, 4, "ssfl", seed=0, lr=0.3, local_steps=1,
                     batch_size=4, mesh=mesh)
        head = jax.tree.leaves(eng.state.local_heads)[0]
        # PartitionSpec normalizes a one-axis tuple to the bare name, so
        # compare against the fleet axes put through the same constructor
        assert head.sharding.spec[0] == P(SH.fleet_axes(mesh))[0]
        assert np.isfinite(eng.run_round()["loss"])


# ------------------------------------------------------------- properties
#
# Hypothesis guard scoped to the class (tests/test_core.py's importorskip
# pattern would skip this whole module, which must keep running without
# hypothesis).
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    class TestFleetPspecsProperty:
        """For random leaf shapes and mesh sizes, every spec
        ``fleet_pspecs`` returns must be divisibility-valid, never longer
        than the leaf's rank, and scalar/0-d leaves must replicate."""

        @settings(max_examples=50, deadline=None)
        @given(shapes=st.lists(st.lists(st.integers(1, 24), min_size=0,
                                        max_size=3),
                               min_size=1, max_size=6),
               data=st.sampled_from([1, 2, 3, 4, 8, 16]),
               pod=st.sampled_from([None, 2]))
        def test_specs_valid(self, shapes, data, pod):
            from repro.launch.mesh import make_abstract_mesh
            if pod is None:
                mesh = make_abstract_mesh((data, 2), ("data", "model"))
                extent = data
            else:
                mesh = make_abstract_mesh((pod, data, 2),
                                          ("pod", "data", "model"))
                extent = pod * data
            tree = {f"leaf{i}": jax.ShapeDtypeStruct(tuple(s), np.float32)
                    for i, s in enumerate(shapes)}
            specs = SH.fleet_pspecs(tree, mesh)
            for i, shape in enumerate(shapes):
                spec = specs[f"leaf{i}"]
                assert len(spec) <= len(shape), (shape, spec)
                if not shape:
                    assert spec == P()
                    continue
                if shape[0] % extent == 0:
                    assert spec[0] == P(SH.fleet_axes(mesh))[0]
                else:
                    assert spec[0] is None
                assert all(ax is None for ax in tuple(spec)[1:])
else:   # pragma: no cover - hypothesis in [dev] extras, absent on tier-1
    class TestFleetPspecsProperty:
        def test_specs_valid(self):
            pytest.skip("hypothesis not installed")
