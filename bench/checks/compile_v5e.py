"""Compile a cell's round program for a described TPU v5e, without a chip
(or, with ``--chip``, for the chip this process holds).

Builds the abstract arguments of ``core.fused._scan_segment`` for one
segment of the cell (its worker count, sequence, batch, tau bucket and
gossip form, at the configuration's widths), lowers and compiles the
program for one chip of a described ``v5e:2x2`` topology and prints
``memory_analysis()``: what the chip's compiler refuses here costs no
chip time. Run with ``JAX_PLATFORMS=cpu``; one compile at a time (it
takes minutes and several GB of host memory at published widths).

    JAX_PLATFORMS=cpu python3 bench/checks/compile_v5e.py --workload <cell>
    python3 bench/checks/compile_v5e.py --workload <cell> --chip
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parent)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--chip", action="store_true",
                    help="compile for the attached chip, not a described one")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.core import fused, modelspec

    jax.config.update("jax_enable_compilation_cache", False)
    cell = run.load_cell(args.workload)
    p = cell.traffic
    spec = run.model_spec(cell)
    adapter = modelspec.get_adapter(spec)
    w, seq, b = p["workers"], p.get("seq"), p["batch"]
    adaptive = p["strategy"] == "fedhp"
    k = 1 if adaptive else min(p["rounds"], fused.MAX_FUSE_ROUNDS)
    cap = 1 << (p["tau_max"] - 1).bit_length()
    sparse = p["gossip"] == "sparse"
    n_test = max(p["num_samples"] // 6, 256)
    n_eval = min(p["eval_rows"], n_test)
    if args.chip:
        one = SingleDeviceSharding(jax.devices()[0])
    else:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    feat = (seq,) if seq else (32,)
    xdt = jnp.int32 if seq else jnp.float32
    stacked = jax.tree.map(lambda l: sds((1, w) + l.shape, l.dtype),
                           adapter.template)
    e_max = 8 if not sparse else max(8, 1 << (2 * w - 1).bit_length())
    args_ = (
        stacked, sds((1, w, 1), jnp.float32),
        sds((1, k, w, cap, b) + feat, xdt), sds((1, k, w, cap, b), jnp.int32),
        sds((1, w, 256) + feat, xdt), sds((1, w, 256), jnp.int32),
        sds((1, w, 32) + feat, xdt), sds((1, w, 32), jnp.int32),
        sds((k, w), jnp.int32), sds((k,), jnp.float32),
        sds((k, 1, 1) if sparse else (k, w, w), jnp.float32),
        sds((k, e_max), jnp.int32), sds((k, e_max), jnp.int32),
        sds((k, e_max), jnp.float32), sds((k,), jnp.float32),
        sds((k, w), jnp.float32), sds((k, w), jnp.float32),
        sds((k, w), jnp.bool_), sds((k, w), jnp.float32),
        sds((k,), jnp.int32), sds((k, w, 1), jnp.int32),
        sds((k, w), jnp.int32), sds((w,), jnp.bool_),
        sds((), jnp.float32), sds((2,), jnp.uint32), sds((), jnp.float32),
        sds((n_eval,) + feat, xdt), sds((n_eval,), jnp.int32))
    t = time.perf_counter()
    compiled = fused._scan_segment.lower(
        *args_, adapter=adapter, tau_cap=cap, measure=adaptive,
        needs_cross=False, interpret=False, kind="none", k=0, ef=True,
        sparse=sparse, lcodec=None, robust="none", rb=0.0,
        attack="").compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"{cell.name}: {k} round(s) a segment, tau bucket {cap}, "
          f"W={w}, P={adapter.param_count:,}; compiled for "
          f"{'the chip' if args.chip else 'a described v5e'} in {time.perf_counter() - t:.1f} s; "
          f"tpu_custom_call x{text.count('tpu_custom_call')}")
    print(f"memory_analysis: {mem}")
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"arguments + outputs + temporaries - aliased: {peak:,} bytes "
          f"({peak / 2**30:.2f} GiB)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
