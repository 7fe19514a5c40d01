"""The engine-path Pallas kernels, and the cohort kernel the chip smoke runs,
compile for a described TPU v5e chip at ViT-Base shapes.

Nothing runs: ``jax.experimental.topologies`` describes a ``v5e:2x2`` host
that is not attached, and each test lowers and compiles for its first chip
with ``interpret=False`` — the compiler refuses what the chip would refuse
(tiling, memory spaces, a program that does not fit HBM). The topology is
described inside a module-scoped fixture (only the worker given this file
loads the TPU library) and the persistent compilation cache is off around
the compiles, since such entries cannot be read back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# ViT-Base MLP stack [12, 768, 3072] as ``tpgf_fusion.ops._to_tiles`` lays
# it out: [rows, 128] with rows a multiple of ROW_BLOCK
MLP_STACK = (12, 768, 3072)
TILE_ROWS = 12 * 768 * 3072 // 128
HBM_BYTES = int(15.75 * 2 ** 30)   # what a v5e chip gives one program


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", ["fuse_2d", "tier_sum_2d", "sumsq_2d",
                                    "aggregate_3d"])
def test_engine_path_kernel_compiles(kernel, dtype, one_chip,
                                     no_persistent_cache):
    from repro.kernels.layer_aggregate import kernel as AK
    from repro.kernels.tpgf_fusion import kernel as TK

    def S(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    tiles = S((TILE_ROWS, TK.LANE))
    f32 = "float32"
    if kernel == "fuse_2d":
        _compile(lambda a, b, w, c: TK.fuse_2d(a, b, w, c, interpret=False),
                 tiles, tiles, S((), f32), S((), f32))
    elif kernel == "tier_sum_2d":
        _compile(lambda x, w: TK.tier_sum_2d(x, w, interpret=False),
                 S((2, TILE_ROWS, TK.LANE)), S((2,), f32))
    elif kernel == "sumsq_2d":
        _compile(lambda x: TK.sumsq_2d(x, interpret=False), tiles)
    else:
        L, F = MLP_STACK[0], MLP_STACK[1] * MLP_STACK[2]
        _compile(lambda c, w, s: AK.aggregate_3d(c, w, s, 0.01,
                                                 interpret=False),
                 S((8, L, F)), S((8, L), f32), S((L, F)))


def test_smoke_cohort_kernel_fits_one_chip(one_chip, no_persistent_cache,
                                           monkeypatch, request):
    """The largest cohort-kernel program ``chip_smoke.py`` runs — full
    ``vit16_cifar`` width, a 2-client bucket, its batch size, the Pallas
    TPGF fusion compiled inside — fits one chip's HBM together with the
    8-client fleet workspace the engine keeps beside it."""
    import numpy as np

    from repro.configs import base
    from repro.core import supernet as SN
    from repro.federated.strategies.ssfl import cohort_kernel
    from repro.kernels.tpgf_fusion import kernel as TK
    from repro.models import model as M
    from repro.optim import get_optimizer

    # this process's backend is the CPU, where the kernels would choose
    # the interpreter: compile them, as a TPU backend would
    monkeypatch.setattr(TK, "interpret_mode",
                        lambda interpret=None: False)
    # jit caches the traces made under the patch (keyed on interpret=None,
    # not on the backend): drop them, or a later CPU test in this process
    # that hits the same kernel shapes would get the compiled mode
    request.addfinalizer(jax.clear_caches)
    bucket, steps, batch, n_clients = 2, 2, 16, 8
    cfg = base.get_config("vit16_cifar").replace(use_pallas=True)
    params = jax.eval_shape(lambda: M.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    client_p, server_p, _ = jax.eval_shape(
        lambda p: SN.split_params(cfg, p, None), params)
    local_p = jax.eval_shape(lambda p: SN.split_params(cfg, p, 1)[2], params)

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def stacked(tree):
        return jax.tree.map(lambda x: S((bucket,) + x.shape, x.dtype), tree)

    n_img = 4096
    args = (S((), jnp.int32), stacked(client_p), stacked(local_p),
            jax.tree.map(lambda x: S(x.shape, x.dtype), server_p),
            S((n_img, cfg.image_size, cfg.image_size, 3), jnp.float32),
            S((n_img,), jnp.int32), S((steps, bucket, batch), jnp.int32),
            S((bucket,), jnp.bool_), S((bucket,), jnp.bool_), ())
    compiled = cohort_kernel.lower(cfg, get_optimizer("sgd", 0.05), steps,
                                   1.0, *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    program = (ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    full_client = jax.eval_shape(
        lambda p: SN.split_params(cfg, p, cfg.split_stack_len)[0], params)
    workspace = n_clients * sum(int(np.prod(x.shape)) * x.dtype.itemsize
                                for x in jax.tree.leaves(full_client))
    assert program + workspace < HBM_BYTES, (program, workspace)
