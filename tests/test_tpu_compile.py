"""Every Pallas kernel of the dithered backward, compiled for a TPU chip.

The CPU host has no TPU, but the TPU compiler is installed and compiles for
a chip that is described and not attached (a ``v5e:2x2`` topology). Each
case lowers a kernel with ``interpret=False`` at the mamba2-370m
``L.ssm.in`` widths (4096 tokens x 1024 -> 4384, zero-padded to 4480 as
``repro.kernels.ops`` pads it), compiles it through Mosaic, and checks that
the executable holds the kernel as a ``tpu_custom_call``. What interpret
mode accepts and Mosaic refuses (block shapes off the (8, 128) tiling,
reshapes across lanes, VMEM over budget) fails here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.bsp_matmul.bsp_matmul import bsp_matmul, bsp_matmul_int8
from repro.kernels.levels.levels import (CHUNK, levels_compact_blocked,
                                         levels_expand_blocked)
from repro.kernels.nsd_quant.nsd_quant import nsd_quantize_blocked
from repro.kernels.pack.pack import bitmap_pack_blocked, bitmap_unpack_blocked

T, D_MODEL, D_IN_PROJ = 4096, 1024, 4384  # mamba2-370m in_proj, 4096 tokens
D_INNER = 2048  # out_proj input width
BLOCK = 128
NP = D_IN_PROJ + (-D_IN_PROJ) % BLOCK  # 4480: the kernels' padded width


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _cases():
    """name -> (fn, [(shape, dtype), ...]) at the in_proj widths."""
    tiles = (T // BLOCK, NP // BLOCK)
    return {
        "nsd_quantize_blocked": (
            lambda x, nu, d: nsd_quantize_blocked(
                x, nu, d, bm=BLOCK, bn=BLOCK, interpret=False),
            [((T, NP), jnp.float32), ((T, NP), jnp.float32),
             ((), jnp.float32)]),
        "bitmap_pack_blocked": (
            lambda k: bitmap_pack_blocked(k, bm=BLOCK, bn=BLOCK,
                                          interpret=False),
            [((T, NP), jnp.int8)]),
        "bitmap_unpack_blocked": (
            lambda b: bitmap_unpack_blocked(b, bm=BLOCK, bn=BLOCK,
                                            interpret=False),
            [((T, NP // 8), jnp.uint8)]),
        # dx = g~ @ w^T: (T, NP) x (NP, d_model)
        "bsp_matmul": (
            lambda k, d, b, m: bsp_matmul(k, d, b, m, interpret=False),
            [((T, NP), jnp.int8), ((), jnp.float32),
             ((NP, D_MODEL), jnp.float32), (tiles, jnp.int32)]),
        "bsp_matmul_int8": (
            lambda k, b, s, m: bsp_matmul_int8(k, b, s, m, interpret=False),
            [((T, NP), jnp.int8), ((NP, D_MODEL), jnp.int8),
             ((), jnp.float32), (tiles, jnp.int32)]),
        # one wire chunk per column: the in_proj gradient as 256-element
        # chunks, T * NP / 256 columns
        "levels_compact_blocked": (
            lambda kt: levels_compact_blocked(kt, interpret=False),
            [((CHUNK, T * NP // CHUNK), jnp.int8)]),
        "levels_expand_blocked": (
            lambda lv, m: levels_expand_blocked(lv, m, interpret=False),
            [((CHUNK, T * NP // CHUNK), jnp.int8),
             ((CHUNK, T * NP // CHUNK), jnp.int8)]),
    }


def _compile(fn, specs, sharding):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = _cases()[name]
    hlo = _compile(fn, specs, one_chip).as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo, name


@pytest.mark.parametrize("k_in,n_out", [(D_MODEL, D_IN_PROJ),
                                        (D_INNER, D_MODEL)],
                         ids=["ssm_in", "ssm_out"])
def test_dithered_backward_compiles_for_v5e(one_chip, k_in, n_out):
    """The whole kernel-variant backward of one projection (fused NSD,
    bitmap pack, both tile-skipping int8 matmuls) as the training step
    holds it: every stage a Mosaic call, none left to interpret mode."""
    def bwd(g, x, w, key):
        return ops.dithered_backward_matmuls(g, x, w, key, 2.0,
                                             interpret=False)

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compiled = _compile(bwd, [((T, n_out), jnp.bfloat16),
                              ((T, k_in), jnp.bfloat16),
                              ((k_in, n_out), jnp.bfloat16),
                              (key.shape, key.dtype)], one_chip)
    hlo = compiled.as_text()
    # nsd + pack + the two int8 matmuls
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 4, \
        hlo.count('custom_call_target="tpu_custom_call"')
