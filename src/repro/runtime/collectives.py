"""Gossip collectives: the paper's P2P model exchange (Eq. 5) as TPU-native
`shard_map` + `lax.ppermute`.

The round topology A^h is edge-colored into matchings
(``topology.matching_decomposition``); each matching is ONE
collective-permute over the worker axes (an involution), so a sparse
topology costs (#matchings) x |params| wire bytes instead of the
2(N-1)/N x |params| of an all-reduce — the paper's adaptive-topology knob
becomes a measurable collective-bytes term in the roofline.

Also provides the fused consensus-distance measurement (Alg. 1 line 9) in
the same data pass, and compressed gossip (beyond-paper;
DeepSqueeze/ChocoSGD-style) sharing ``core/compression``'s codecs —
int8 + error feedback, x̂-tracked top-k, shared-mask rand-k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import compression
from repro.core import topology as topo


def _shard_map(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def matchings_as_pairs(adj: np.ndarray) -> list[list[tuple[int, int]]]:
    """Topology -> list of ppermute pair-lists (each an involution, with
    identity pairs for unmatched workers so every destination is written)."""
    n = adj.shape[0]
    matchings = topo.matching_decomposition(adj)
    perms = topo.matchings_to_perms(matchings, n)          # [M, N]
    out = []
    for row in perms:
        out.append([(int(i), int(row[i])) for i in range(n)])
    return out


def matching_weight_tables(adj: np.ndarray, mix: np.ndarray) -> np.ndarray:
    """[M, N] per-worker mixing weight for its partner in matching m
    (0 where unmatched — identity pairs contribute w*(x-x)=0 anyway, but a
    zero weight also guards non-involution edge cases)."""
    n = adj.shape[0]
    matchings = topo.matching_decomposition(adj)
    w = np.zeros((len(matchings), n), np.float32)
    for m, match in enumerate(matchings):
        for (i, j) in match:
            w[m, i] = mix[i, j]
            w[m, j] = mix[j, i]
    return w


def gossip_fn(mesh: Mesh, worker_axes: tuple[str, ...],
              pairs: list[list[tuple[int, int]]],
              weight_table: np.ndarray, param_specs,
              *, measure_distances: bool = False):
    """Build a jit-able gossip(params) -> mixed (or (mixed, dists [M]))."""
    wt = jnp.asarray(weight_table)                        # [M, N]
    tp_axes = tuple(a for a in mesh.axis_names if a not in worker_axes)

    def body(x):
        me = jax.lax.axis_index(worker_axes)
        acc = x
        dists = []
        for m, perm in enumerate(pairs):
            y = jax.tree.map(
                lambda l: jax.lax.ppermute(l, axis_name=worker_axes,
                                           perm=perm), x)
            w_m = wt[m, me]
            acc = jax.tree.map(
                lambda a, yy, xx: a + (w_m * (yy.astype(jnp.float32)
                                              - xx.astype(jnp.float32))
                                       ).astype(a.dtype),
                acc, y, x)
            if measure_distances:
                d2 = sum(jnp.sum(jnp.square(yy.astype(jnp.float32)
                                            - xx.astype(jnp.float32)))
                         for yy, xx in zip(jax.tree.leaves(y),
                                           jax.tree.leaves(x)))
                # partial over the within-worker (TP/FSDP) shards -> full
                if tp_axes:
                    d2 = jax.lax.psum(d2, tp_axes)
                dists.append(jnp.sqrt(d2))
        if measure_distances:
            return acc, jnp.stack(dists) if dists else jnp.zeros((0,))
        return acc

    out_specs = (param_specs, P(None)) if measure_distances else param_specs
    return _shard_map(body, mesh, (param_specs,), out_specs)


def gossip_compressed_fn(mesh: Mesh, worker_axes: tuple[str, ...],
                         pairs: list[list[tuple[int, int]]],
                         weight_table: np.ndarray, param_specs,
                         *, mode: str = "int8", seed: int = 0,
                         gamma: float = 0.25):
    """Compressed gossip with the core codecs (beyond-paper).

    The updates are the ones ``core/compression.py`` defines (and the
    core engines implement), applied per leaf shard:

    - ``mode="int8"``: each worker sends the int8 round trip of
      z = x + e instead of x, the residual e <- z - dequant(quant(z))
      carries to the next round, and quantization uses the shared wire
      format — the flattened leaf shard laid out per ``flat_tile_shape``
      with one f32 scale per (8, 1024) tile, exactly what
      ``kernels/quantize_block.py`` produces. Wire bytes per matching
      drop ~4x (f32), plus the scale side-channel.
    - ``mode="topk:<k>"``: ChocoSGD x̂-tracking — the err buffer holds
      the public copy, the wire carries the top-k innovation (k resolved
      per leaf shard), and the mix runs damped (``gamma``) on the
      advanced copies.
    - ``mode="randk:<k>"``: the shared seeded mask (``seed``, the
      caller-supplied per-round ``step`` and the leaf index pick the
      draw — identical on every worker, so sender and receiver agree
      without shipping indices) ships k coordinates exactly; no state
      evolves.

    Returns gossip(params, err, step) -> (mixed, new_err) — ``step`` is
    a traced i32 round counter the caller advances every call (a reused
    rand-k mask would freeze the un-drawn coordinates forever; int8 and
    top-k ignore it). For topk pass the initial params as the initial
    ``err`` (``compression.state_init``).
    """
    codec = compression.parse_mode(mode)
    if codec.kind == "none":
        raise ValueError("use gossip_fn for uncompressed exchange")
    wt = jnp.asarray(weight_table)
    skey = compression.sparsify_base_key(seed)

    def sparse_payload(leaf, e, idx, step):
        """(payload ŷ or innovation q, new state) for one leaf shard."""
        zf = leaf.astype(jnp.float32).reshape(-1)
        kk = codec.resolve_k(zf.size)
        if codec.kind == "topk":
            q = compression.sparsify_rows((zf - e.reshape(-1))[None],
                                          "topk", kk)[0]
            xhat = e.reshape(-1) + q
            return xhat.reshape(leaf.shape), xhat.reshape(leaf.shape)
        kst = jax.random.fold_in(skey, idx)
        y = compression.sparsify_rows(zf[None], "randk", kk, key=kst,
                                      step=step)[0]
        return y.reshape(leaf.shape), e

    def body(x, err, step):
        me = jax.lax.axis_index(worker_axes)

        if codec.kind == "int8":
            def q8(leaf, e):
                z = leaf.astype(jnp.float32) + e
                n = int(np.prod(z.shape))
                q, scale = compression.quantize_flat(z.reshape(-1))
                deq = compression.dequantize_flat(q, scale,
                                                  n).reshape(leaf.shape)
                return q, scale, z - deq, deq

            packed = jax.tree.map(
                q8, x, err, is_leaf=lambda l: isinstance(l, jnp.ndarray))
            qs = jax.tree.map(lambda t: t[0], packed,
                              is_leaf=lambda t: isinstance(t, tuple))
            scales = jax.tree.map(lambda t: t[1], packed,
                                  is_leaf=lambda t: isinstance(t, tuple))
            new_err = jax.tree.map(lambda t: t[2], packed,
                                   is_leaf=lambda t: isinstance(t, tuple))
            deq_self = jax.tree.map(lambda t: t[3], packed,
                                    is_leaf=lambda t: isinstance(t, tuple))

            acc = x
            for m, perm in enumerate(pairs):
                pq = jax.tree.map(
                    lambda l: jax.lax.ppermute(l, worker_axes, perm=perm),
                    qs)
                ps = jax.tree.map(
                    lambda l: jax.lax.ppermute(l, worker_axes, perm=perm),
                    scales)
                w_m = wt[m, me]

                def mix(a, qn, sn, ds):
                    yn = compression.dequantize_flat(
                        qn, sn, int(np.prod(a.shape))).reshape(a.shape)
                    return a + (w_m * (yn - ds)).astype(a.dtype)

                acc = jax.tree.map(mix, acc, pq, ps, deq_self)
            return acc, new_err

        # sparse codecs: the masked payload rides ppermute dense (the
        # simulated wire cost is codec.wire_bits); mixing matches the
        # core compensated update on ŷ (rand-k) / x̂ (top-k, damped)
        xl, treedef = jax.tree.flatten(x)
        el = jax.tree.leaves(err)
        ys, news = [], []
        for idx, (leaf, e) in enumerate(zip(xl, el)):
            y, ne = sparse_payload(leaf, e, idx, step)
            ys.append(y)
            news.append(ne)
        ys = jax.tree.unflatten(treedef, ys)
        new_err = jax.tree.unflatten(treedef, news)
        g = gamma if codec.kind == "topk" else 1.0
        acc = x
        for m, perm in enumerate(pairs):
            yn = jax.tree.map(
                lambda l: jax.lax.ppermute(l, worker_axes, perm=perm), ys)
            w_m = wt[m, me]
            acc = jax.tree.map(
                lambda a, ynn, ysf: a + (g * w_m * (
                    ynn.astype(jnp.float32) - ysf.astype(jnp.float32))
                    ).astype(a.dtype),
                acc, yn, ys)
        return acc, new_err

    return _shard_map(body, mesh, (param_specs, param_specs, P()),
                      (param_specs, param_specs))


def worker_shard_extent(mesh: Mesh, worker_axes: tuple[str, ...]) -> int:
    """Number of row-shards the worker dim splits into over ``worker_axes``."""
    n = 1
    for a in worker_axes:
        n *= mesh.shape[a]
    return n


def edge_shard_tables(src, dst, w, num_workers: int, n_shards: int, *,
                      offsets: tuple[int, ...] | None = None,
                      width: int | None = None):
    """Group a directed edge list by shard-offset delta, host-side.

    Edges are grouped by ``delta = shard(dst) - shard(src) mod n_shards``
    (contiguous row sharding, ``rows = W / n_shards`` per shard); within
    a group they are bucketed by destination shard and zero-weight padded
    to a common width so every shard runs the same static shapes (padding
    rows land on local row 0 with weight 0 and add exactly nothing).

    Returns ``(offsets, sl, dl, wl)``: the sorted tuple of distinct
    deltas, and ``[D, n_shards, width]`` tables of local source rows,
    local destination rows and edge weights. Pass ``offsets``/``width``
    to force a shape shared across rounds (the fused scan stacks one
    table per round); a delta outside the forced ``offsets`` raises.
    """
    if num_workers % n_shards != 0:
        raise ValueError(f"W={num_workers} not divisible by "
                         f"worker-shard extent {n_shards}")
    rows = num_workers // n_shards
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float32)
    deltas = (dst // rows - src // rows) % n_shards
    present = sorted(set(deltas.tolist()))
    if offsets is None:
        offsets = tuple(int(d) for d in present)
    else:
        extra = set(present) - set(offsets)
        if extra:
            raise ValueError(f"edge deltas {sorted(extra)} not in the "
                             f"forced offsets {offsets}")
    need = 1
    for delta in offsets:
        sel = deltas == delta
        if sel.any():
            need = max(need, int(np.bincount(dst[sel] // rows,
                                             minlength=n_shards).max()))
    if width is None:
        width = need
    elif width < need:
        raise ValueError(f"forced width {width} < required {need}")
    sl = np.zeros((len(offsets), n_shards, width), np.int32)
    dl = np.zeros((len(offsets), n_shards, width), np.int32)
    wl = np.zeros((len(offsets), n_shards, width), np.float32)
    for gi, delta in enumerate(offsets):
        sel = deltas == delta
        es, ed, ew = src[sel], dst[sel], w[sel]
        dshard = ed // rows
        for k in range(n_shards):
            m = dshard == k
            c = int(m.sum())
            sl[gi, k, :c] = es[m] % rows
            dl[gi, k, :c] = ed[m] % rows
            wl[gi, k, :c] = ew[m]
    return offsets, sl, dl, wl


def routed_mix_delta(v, sl, dl, wl, offsets: tuple[int, ...],
                     worker_axes: tuple[str, ...], n_shards: int):
    """The per-shard slice of ``compression.edge_mix_delta``: one
    ``lax.ppermute`` of the local ``[rows, P]`` block per distinct shard
    offset, then a local ``segment_sum`` over the group's edge table.
    Runs inside ``shard_map``; ``sl/dl/wl`` are the LOCAL ``[D, 1, width]``
    slices of :func:`edge_shard_tables` output."""
    acc = jnp.zeros(v.shape, jnp.float32)
    rows = v.shape[0]
    for gi, delta in enumerate(offsets):
        if delta == 0:
            recv = v
        else:
            perm = [(s, (s + delta) % n_shards) for s in range(n_shards)]
            recv = jax.lax.ppermute(v, worker_axes, perm=perm)
        contrib = wl[gi, 0][:, None] * (recv[sl[gi, 0]] - v[dl[gi, 0]])
        acc = acc + jax.ops.segment_sum(contrib, dl[gi, 0],
                                        num_segments=rows)
    return acc


def _edge_table_specs(worker_axes):
    spec = P(None, worker_axes, None)           # [D, n_shards, width]
    return (spec, spec, spec)


def gossip_edges_sharded_fn(mesh: Mesh, worker_axes: tuple[str, ...],
                            src: np.ndarray, dst: np.ndarray,
                            w: np.ndarray, num_workers: int):
    """Sparse edge-list gossip over a worker-sharded [W, P] stack.

    The dense path above pays one ppermute per *matching* (O(degree) of
    them). Here the directed edge list (``topology.directed_edges``) is
    grouped host-side by shard offset delta = shard(dst) - shard(src)
    mod n_shards (``edge_shard_tables``); each distinct delta costs
    exactly ONE ppermute of the local [W/n_shards, P] block, and every
    edge in the group lands via a per-shard segment_sum on local row
    indices — so wire cost scales with the number of distinct shard
    offsets the topology touches, not E.

    Returns a jit-able f(x: [W, P]) -> mixed [W, P] with
    y_i = x_i + sum_{e: dst_e=i} w_e (x_{src_e} - x_i); x is sharded
    P(worker_axes, None). Requires W divisible by the worker-axes extent.
    """
    n_shards = worker_shard_extent(mesh, worker_axes)
    offsets, sl, dl, wl = edge_shard_tables(src, dst, w, num_workers,
                                            n_shards)
    tables = (jnp.asarray(sl), jnp.asarray(dl), jnp.asarray(wl))

    def body(x, sl, dl, wl):
        xf = x.astype(jnp.float32)
        delta = routed_mix_delta(xf, sl, dl, wl, offsets, worker_axes,
                                 n_shards)
        return (xf + delta).astype(x.dtype)

    spec = P(worker_axes, None)
    mapped = _shard_map(body, mesh, (spec,) + _edge_table_specs(worker_axes),
                        spec)
    return lambda x: mapped(x, *tables)


def gossip_edges_compressed_sharded_fn(mesh: Mesh,
                                       worker_axes: tuple[str, ...],
                                       src: np.ndarray, dst: np.ndarray,
                                       w: np.ndarray, num_workers: int, *,
                                       kind: str = "int8", k: int = 0,
                                       error_feedback: bool = True,
                                       seed: int = 0, gamma: float = 1.0):
    """Compressed edge-list gossip over a worker-sharded [W, P] stack.

    Every codec payload is row-local — int8 quantizes per row on the
    shared wire layout, top-k thresholds per row, rand-k recomputes the
    one shared mask from ``(seed, step)`` on every shard — so each shard
    compresses its own rows and only the mixing delta crosses shards,
    via the same ppermute-by-offset routing as
    :func:`gossip_edges_sharded_fn`. The payload/state/update formulas
    are ``compression.compressed_gossip_ref`` itself with the routed
    delta injected (``mix_delta_fn``), so the sharded trajectory matches
    the single-device engines to summation-order tolerance.

    Returns f(x [W, P], err [W, P], step) -> (mixed, new_err); ``err``
    follows ``compression.state_init`` / ``carries_state`` semantics
    (top-k+EF tracks x̂, int8 the residual, rand-k nothing).
    """
    codec = compression.parse_mode(kind) if ":" in kind else None
    if codec is not None:
        kind, k = codec.kind, 0     # resolved below against P
    n_shards = worker_shard_extent(mesh, worker_axes)
    offsets, sl, dl, wl = edge_shard_tables(src, dst, w, num_workers,
                                            n_shards)
    tables = (jnp.asarray(sl), jnp.asarray(dl), jnp.asarray(wl))
    skey = compression.sparsify_base_key(seed)

    def body(x, err, step, sl, dl, wl):
        kk = codec.resolve_k(x.shape[1]) if codec is not None else k
        route = lambda v: routed_mix_delta(v, sl, dl, wl, offsets,   # noqa: E731
                                           worker_axes, n_shards)
        return compression.compressed_gossip_ref(
            x.astype(jnp.float32), err, None,
            error_feedback=error_feedback, kind=kind, k=kk, key=skey,
            step=step, gamma=gamma, use_kernel=False, mix_delta_fn=route)

    spec = P(worker_axes, None)
    mapped = _shard_map(
        body, mesh,
        (spec, spec, P()) + _edge_table_specs(worker_axes), (spec, spec))
    return lambda x, err, step: mapped(x, err, step, *tables)


def ring_allreduce_mean_fn(mesh: Mesh, worker_axes: tuple[str, ...],
                           param_specs):
    """Dense baseline: full model averaging over all workers (what a
    PS/all-reduce system does) — for collective-bytes comparisons."""
    def body(x):
        return jax.tree.map(
            lambda l: (jax.lax.pmean(l.astype(jnp.float32), worker_axes)
                       ).astype(l.dtype), x)

    return _shard_map(body, mesh, (param_specs,), param_specs)
