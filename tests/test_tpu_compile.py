"""Compile every fused-engine Pallas kernel for a described TPU v5e chip.

Interpret mode (every other kernel test) runs the kernel bodies in
Python and cannot see what the chip's compiler refuses: blocks not
aligned to the (8, 128) tiling, vector loads at dynamic lane offsets,
primitives Mosaic does not lower, more VMEM than a kernel may use. Here
each kernel is compiled, not run, for one chip of a ``v5e:2x2``
topology, at the shapes the fused engine hands it in the on-chip smoke
(``chip_smoke.py``): the paper's 30-worker testbed fleet of MLP workers,
and a 4-worker fleet of the published-width dense worker
(``configs/smollm_360m.dfl_model_spec``, 4 layers, vocabulary 6144); and
at the largest fleet the README advertises on one device, the W=2048
edge-list ring, where ``gossip_edges`` and ``robust_gossip`` keep all
2048 rows of a column tile in VMEM. The dense mixes (``gossip_mix_rows``
on the fused engine's [W, P] rows, and ``gossip_mix_2d``, which stages
all W neighbour blocks at once) unroll over W, so they are compiled only
for the fleets that mix densely.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports this file. The persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back
without one).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.smollm_360m import dfl_model_spec
from repro.core import compression
from repro.core.modelspec import get_adapter
from repro.kernels.gossip_edges import gossip_edges
from repro.kernels.gossip_mix import gossip_mix_2d, gossip_mix_rows
from repro.kernels.quantize_block import dequantize_block_2d, quantize_block_2d
from repro.kernels.robust_gossip import robust_gossip
from repro.kernels.sparsify_block import sparsify_block_2d

# fleet -> (W, model spec, max degree of a round topology); the engine
# pads each round to power-of-two buckets of W * degree directed edges
# (E_max) and of the degree (D_max, the robust neighbour table)
FLEETS = {
    "testbed": (30, "mlp", 29),       # benchmarks/run.py --full
    "published": (4, dfl_model_spec(layers=4, vocab=6144, seq=64), 3),
    "ring2048": (2048, "mlp", 2),     # benchmarks/run.py sparse_gossip
}
KERNELS = ("gossip_mix_rows", "gossip_mix_2d", "gossip_edges",
           "quantize_block_2d", "dequantize_block_2d", "sparsify_block_2d",
           "robust_gossip_trimmed", "robust_gossip_median")
DENSE = ("gossip_mix_rows", "gossip_mix_2d")
CASES = [(k, f) for f in FLEETS for k in KERNELS
         if not (k in DENSE and f == "ring2048")]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no describer
        jax.config.update("jax_enable_compilation_cache", cache_was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def _cases(one_chip, fleet):
    """kernel name -> (function, argument shapes on the described chip),
    each as the fused scan calls it (the codecs vmapped over worker rows,
    the mix vmapped over receiving workers)."""
    W, spec, degree = FLEETS[fleet]
    p = get_adapter(spec).param_count
    rows, cols = compression.flat_tile_shape(p)
    e_max = 1 << (W * degree - 1).bit_length()
    d_max = 1 << (degree - 1).bit_length()
    i32 = jnp.int32

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    return {
        "gossip_mix_rows": (gossip_mix_rows, (sds((W, p)), sds((W, W)))),
        "gossip_mix_2d": (
            lambda x2, mix: jax.vmap(
                lambda xi, wi: gossip_mix_2d(xi, x2, wi))(x2, mix),
            (sds((W, rows, cols)), sds((W, W)))),
        "gossip_edges": (
            gossip_edges,
            (sds((W, p)), sds((e_max,), i32), sds((e_max,), i32),
             sds((e_max,)))),
        "quantize_block_2d": (
            jax.vmap(quantize_block_2d), (sds((W, rows, cols)),)),
        "dequantize_block_2d": (
            jax.vmap(dequantize_block_2d),
            (sds((W, rows, cols), jnp.int8),
             sds((W, -(-rows // 8), 1)))),
        "sparsify_block_2d": (
            jax.vmap(sparsify_block_2d),
            (sds((W, rows, cols)), sds((W, rows, cols)), sds((W,)))),
        "robust_gossip_trimmed": (
            lambda x, t, nbr, deg: robust_gossip(x, t, nbr, deg, b=1.0,
                                                 mode="trimmed"),
            (sds((W, p)), sds((W, p)), sds((W, d_max), i32),
             sds((W,), i32))),
        "robust_gossip_median": (
            lambda x, t, nbr, deg: robust_gossip(x, t, nbr, deg, b=0.0,
                                                 mode="median"),
            (sds((W, p)), sds((W, p)), sds((W, d_max), i32),
             sds((W,), i32))),
    }


@pytest.mark.parametrize("kernel,fleet", CASES)
def test_kernel_compiles_for_v5e(one_chip, kernel, fleet):
    fn, args = _cases(one_chip, fleet)[kernel]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
