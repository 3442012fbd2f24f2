"""Compile a cell's round program for a described TPU v5e, without a chip
(or, with ``--chip``, for the chip this process holds).

Builds the abstract arguments of ``core.fused._scan_segment`` for one
segment of the cell (its worker count, sequence, batch, tau bucket and
gossip form, at the configuration's widths), lowers and compiles the
program for one chip of a described ``v5e:2x2`` topology and prints
``memory_analysis()``: what the chip's compiler refuses here costs no
chip time. A cell whose traffic sets ``mesh_chips`` compiles
``_scan_segment_sharded`` instead, over a mesh of that many described
chips, with the fleet's rows split over them as ``run_dfl_fused`` splits
them; ``memory_analysis()`` is then per chip. Run with
``JAX_PLATFORMS=cpu``; one compile at a time (it takes minutes and
several GB of host memory at published widths).

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
        devices = jax.devices()
    else:
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    one = SingleDeviceSharding(devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    feat = (seq,) if seq else (32,)
    xdt = jnp.int32 if seq else jnp.float32
    t = time.perf_counter()
    if p["mesh_chips"]:
        compiled = sharded_program(devices[:p["mesh_chips"]], adapter, w, k,
                                   cap, b, feat, xdt, n_eval, adaptive)
    else:
        compiled = one_chip_program(sds, adapter, w, k, cap, b, feat, xdt,
                                    n_eval, adaptive, sparse)
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


def one_chip_program(sds, adapter, w, k, cap, b, feat, xdt, n_eval,
                     adaptive, sparse):
    import jax
    import jax.numpy as jnp

    from repro.core import fused
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
    return fused._scan_segment.lower(
        *args_, adapter=adapter, tau_cap=cap, measure=adaptive,
        needs_cross=False, interpret=False, kind="none", k=0, ef=True,
        sparse=sparse, lcodec=None, robust="none", rb=0.0,
        attack="").compile()


def sharded_program(devices, adapter, w, k, cap, b, feat, xdt, n_eval,
                    adaptive):
    """``_scan_segment_sharded`` over a 1-D mesh of ``devices``, with the
    ring's edge tables built by the engine's own ``_sharded_edge_tables``
    (the ring is the only base topology a sharded cell has)."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import fused, topology
    from repro.runtime import shardexec

    mesh = jax.sharding.Mesh(np.array(devices), ("workers",))
    plan = shardexec.WorkerShardPlan(mesh, w)
    wp = plan.w_pad
    src, dst, wt = topology.directed_edges(topology.ring_edges(w),
                                           np.full(w, 1 / 3))
    e_max = max(8, 1 << (len(src) - 1).bit_length())
    pad = lambda a: np.pad(a, (0, e_max - len(a)))  # noqa: E731
    seg = SimpleNamespace(esrc=np.stack([pad(src)] * k),
                          edst=np.stack([pad(dst)] * k),
                          ewt=np.stack([pad(wt)] * k))
    offsets, esl, _, _ = fused._sharded_edge_tables(seg, plan)

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    rows = ("workers",)
    stacked = jax.tree.map(
        lambda l: sds((wp,) + l.shape, l.dtype, *rows), adapter.template)
    tab = (None, None, "workers", None)
    args_ = (
        stacked, sds((wp, 1), jnp.float32, *rows),
        sds((k, wp, cap, b) + feat, xdt, None, "workers"),
        sds((k, wp, cap, b), jnp.int32, None, "workers"),
        sds((w, 256) + feat, xdt), sds((w, 256), jnp.int32),
        sds((w, 32) + feat, xdt), sds((w, 32), jnp.int32),
        sds((k, wp), jnp.int32, None, "workers"), sds((k,), jnp.float32),
        sds(esl.shape, jnp.int32, *tab), sds(esl.shape, jnp.int32, *tab),
        sds(esl.shape, jnp.float32, *tab), sds((k,), jnp.float32),
        sds((k, wp), jnp.float32, None, "workers"),
        sds((k, wp), jnp.float32, None, "workers"),
        sds((k, wp), jnp.bool_, None, "workers"),
        sds((k, wp), jnp.float32, None, "workers"),
        sds((k,), jnp.int32), sds((2,), jnp.uint32), sds((), jnp.float32),
        sds((n_eval,) + feat, xdt), sds((n_eval,), jnp.int32))
    print(f"mesh of {len(devices)} chips, {plan.rows} rows a chip; ring "
          f"offsets {offsets}, edge tables {esl.shape}")
    return fused._scan_segment_sharded.lower(
        *args_, adapter=adapter, tau_cap=cap, measure=adaptive, kind="none",
        k=0, ef=True, mesh=mesh, axes=plan.axes, offsets=offsets,
        n_shards=plan.n_shards).compile()


if __name__ == "__main__":
    sys.exit(main())
