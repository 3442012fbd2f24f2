"""Sparse edge-list gossip: edge-op parity, kernel parity, and the
sparse-vs-dense engine differential.

The edge-list path (``cfg.gossip="sparse"``) must be a drop-in for the
dense [W, W] mixing matrix: the host control plane (cluster RNG, plans,
clock) is shared code so host-replayed fields match bit-exactly, and the
device trajectories differ only by summation order (segment_sum / the
gather-mix-scatter kernel vs tensordot) — within 1e-5, 2e-3 compressed.
Edge mixing weights are computed from degrees with the same float ops as
the dense matrices' off-diagonals, so there is no weight drift to hide
behind.
"""
from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedHPConfig
from repro.core import topology as topo
from repro.core.experiment import run_algorithm
from repro.kernels import ref as kref
from repro.kernels.gossip_edges import gossip_edges, pad_edges
from repro.simulation.cluster import ChurnEvent, ChurnSchedule

CFG = FedHPConfig(num_workers=8, rounds=10, tau_init=5, tau_max=20,
                  lr=0.1, batch_size=32, seed=3)
SPARSE = replace(CFG, gossip="sparse")

SCHED = ChurnSchedule((
    ChurnEvent(2, "leave", 1),
    ChurnEvent(3, "crash", 6),
    ChurnEvent(4, "straggle", 2, factor=5.0, duration=3),
    ChurnEvent(6, "join", 1),
))

EXACT = ("round", "round_time", "waiting_time", "mean_tau", "num_links",
         "cumulative_time")
DEVICE_TOL = {"accuracy": 1e-6, "loss": 1e-4, "consensus": 1e-4}
COMPRESSED_TOL = {"accuracy": 1e-6, "loss": 1e-4, "consensus": 2e-3}


# ---------------------------------------------------------------------------
# edge-list ops vs their dense twins
# ---------------------------------------------------------------------------

def _random_adj(rng, n, p=0.4):
    a = (rng.random((n, n)) < p).astype(np.int8)
    a = np.maximum(a, a.T)
    np.fill_diagonal(a, 0)
    return a


def test_edges_adjacency_roundtrip():
    rng = np.random.default_rng(0)
    for _ in range(50):
        n = int(rng.integers(2, 20))
        adj = _random_adj(rng, n)
        e = topo.edges_from_adj(adj)
        np.testing.assert_array_equal(topo.adj_from_edges(e, n), adj)
        assert e.shape == (adj.sum() // 2, 2)
        assert (e[:, 0] < e[:, 1]).all()


def test_ring_edges_matches_ring_topology():
    for n in (2, 3, 5, 16):
        np.testing.assert_array_equal(
            topo.adj_from_edges(topo.ring_edges(n), n), topo.ring_topology(n))


def test_degrees_from_edges():
    rng = np.random.default_rng(1)
    for _ in range(20):
        n = int(rng.integers(2, 16))
        adj = _random_adj(rng, n)
        e = topo.edges_from_adj(adj)
        np.testing.assert_array_equal(topo.degrees_from_edges(e, n),
                                      adj.sum(axis=1))


def test_edge_weights_match_dense_offdiagonals():
    """The per-edge weights must be BIT-identical to the dense mixing
    matrices' off-diagonal entries (same float expressions), so the only
    sparse-vs-dense divergence anywhere is summation order."""
    rng = np.random.default_rng(2)
    for mixing, mixfn in (("uniform", topo.mixing_matrix_uniform),
                          ("metropolis", topo.mixing_matrix_metropolis)):
        for _ in range(20):
            n = int(rng.integers(2, 16))
            adj = _random_adj(rng, n)
            if adj.sum() == 0:
                continue
            e = topo.edges_from_adj(adj)
            w = topo.edge_mixing_weights(e, n, mixing)
            dense = mixfn(adj)
            np.testing.assert_array_equal(w, dense[e[:, 0], e[:, 1]],
                                          err_msg=mixing)


def test_mask_edges_matches_masked_adjacency():
    rng = np.random.default_rng(3)
    for _ in range(20):
        n = int(rng.integers(3, 16))
        adj = _random_adj(rng, n)
        alive = rng.random(n) > 0.3
        masked = adj.copy()
        masked[~alive, :] = 0
        masked[:, ~alive] = 0
        e = topo.edges_from_adj(adj)
        kept = topo.mask_edges(e, alive)
        np.testing.assert_array_equal(kept, topo.edges_from_adj(masked))


def test_connected_components_edges_matches_dense():
    rng = np.random.default_rng(4)
    for _ in range(50):
        n = int(rng.integers(2, 18))
        adj = _random_adj(rng, n, p=0.15)
        e = topo.edges_from_adj(adj)
        nodes = None
        if rng.random() < 0.5:
            alive = rng.random(n) > 0.3
            if not alive.any():
                alive[0] = True
            nodes = np.nonzero(alive)[0]
        want = topo.connected_components(adj, nodes)
        got = topo.connected_components_edges(e, n, nodes)
        assert len(got) == len(want)
        for ga, wa in zip(got, want):
            np.testing.assert_array_equal(np.sort(ga), np.sort(wa))
        assert topo.is_connected_edges(e, n) == topo.is_connected(adj)


def test_directed_edges_doubles_and_preserves_weights():
    adj = _random_adj(np.random.default_rng(5), 10)
    e = topo.edges_from_adj(adj)
    w = topo.edge_mixing_weights(e, 10, "metropolis")
    src, dst, ww = topo.directed_edges(e, w)
    assert src.shape == dst.shape == ww.shape == (2 * len(e),)
    # every undirected edge appears once per direction, same weight
    pairs = {(int(s), int(d)): float(x) for s, d, x in zip(src, dst, ww)}
    for (i, j), wij in zip(e, w):
        # directed_edges casts to the device dtype (f32)
        assert pairs[(i, j)] == np.float32(wij)
        assert pairs[(j, i)] == np.float32(wij)


# ---------------------------------------------------------------------------
# kernel vs jnp oracle vs dense matrix
# ---------------------------------------------------------------------------

def test_gossip_edges_ref_matches_dense_mix():
    """y = x + sum_e w_e (x_src - x_dst) over both edge orientations is
    exactly W @ x for the row-stochastic dense mixing matrix."""
    rng = np.random.default_rng(6)
    for mixing, mixfn in (("uniform", topo.mixing_matrix_uniform),
                          ("metropolis", topo.mixing_matrix_metropolis)):
        n = 12
        adj = _random_adj(rng, n)
        e = topo.edges_from_adj(adj)
        w = topo.edge_mixing_weights(e, n, mixing)
        src, dst, ww = topo.directed_edges(e, w)
        x = rng.standard_normal((n, 33)).astype(np.float32)
        y = np.asarray(kref.gossip_edges_ref(
            jnp.asarray(x), jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(ww)))
        want = mixfn(adj).astype(np.float32) @ x
        np.testing.assert_allclose(y, want, atol=1e-5, err_msg=mixing)


@pytest.mark.parametrize("shape", [(8, 256), (8, 16), (30, 700), (2, 5)])
def test_gossip_edges_kernel_matches_ref(shape):
    """Pallas gather-mix-scatter (interpret mode on CPU) vs the
    segment_sum oracle, across row/col padding regimes."""
    rng = np.random.default_rng(7)
    n, p = shape
    adj = _random_adj(rng, n, p=0.5)
    e = topo.edges_from_adj(adj)
    w = topo.edge_mixing_weights(e, n, "metropolis")
    src, dst, ww = topo.directed_edges(e, w)
    src, dst, ww = pad_edges(src, dst, ww)
    x = rng.standard_normal((n, p)).astype(np.float32)
    y = np.asarray(gossip_edges(jnp.asarray(x), jnp.asarray(src),
                                jnp.asarray(dst), jnp.asarray(ww),
                                interpret=True))
    want = np.asarray(kref.gossip_edges_ref(
        jnp.asarray(x), jnp.asarray(src), jnp.asarray(dst),
        jnp.asarray(ww)))
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_gossip_edges_kernel_zero_weight_edges_are_noops():
    """All-zero weights (padding rows / no-comm rounds in the fused scan)
    must return x EXACTLY — bit-identical, not just close."""
    rng = np.random.default_rng(8)
    x = rng.standard_normal((8, 64)).astype(np.float32)
    src = jnp.zeros(8, jnp.int32)
    dst = jnp.zeros(8, jnp.int32)
    w = jnp.zeros(8, jnp.float32)
    y = np.asarray(gossip_edges(jnp.asarray(x), src, dst, w,
                                interpret=True))
    np.testing.assert_array_equal(y, x)


def _numpy_edge_walk(x, src, dst, w):
    """The edge kernel's arithmetic in NumPy float32: y starts at x and
    each edge, in table order, adds w_e (x[src_e] - x[dst_e]) to its
    destination row."""
    y = x.copy()
    for s, d, we in zip(src, dst, w):
        y[d] = y[d] + we * (x[s] - x[d])
    return y


# W=12 at 70,001 columns: the tile rule gives 32,768 columns, so three
# tiles, the last one ragged
MULTI_TILE = (12, 70_001)


def test_gossip_edges_multi_tile_matches_numpy_walk_bit_for_bit():
    rng = np.random.default_rng(9)
    n, p = MULTI_TILE
    e = 40                  # repeated destinations: the order matters
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    # powers of two make each product w_e (x_s - x_d) exact, so the walk
    # rounds the same whether or not a backend fuses the multiply-add
    # (XLA's CPU backend does, NumPy does not)
    w = (2.0 ** -rng.integers(1, 5, e)).astype(np.float32)
    x = rng.standard_normal((n, p)).astype(np.float32)
    y = np.asarray(gossip_edges(jnp.asarray(x), jnp.asarray(src),
                                jnp.asarray(dst), jnp.asarray(w),
                                interpret=True))
    assert y.shape == x.shape and y.dtype == x.dtype
    np.testing.assert_array_equal(y, _numpy_edge_walk(x, src, dst, w))


def test_gossip_edges_multi_tile_zero_weights_return_x():
    rng = np.random.default_rng(10)
    n, p = MULTI_TILE
    x = rng.standard_normal((n, p)).astype(np.float32)
    src = jnp.asarray(rng.integers(0, n, 24), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, 24), jnp.int32)
    y = np.asarray(gossip_edges(jnp.asarray(x), src, dst,
                                jnp.zeros(24, jnp.float32), interpret=True))
    assert y.shape == x.shape
    np.testing.assert_array_equal(y, x)


def test_pad_edges_pads_to_multiple_with_noop_rows():
    src, dst, w = (np.array([1, 2, 3]), np.array([0, 1, 2]),
                   np.array([0.1, 0.2, 0.3], np.float32))
    ps, pd, pw = pad_edges(src, dst, w)
    assert ps.shape == pd.shape == pw.shape == (8,)
    np.testing.assert_array_equal(pw[3:], 0.0)
    ps2, pd2, pw2 = pad_edges(src, dst, w, e_max=16)
    assert ps2.shape == (16,)


def test_gossip_edges_preserves_mean():
    """Symmetric weights (both orientations of every undirected edge)
    make the implied mixing matrix doubly stochastic: the fleet mean is
    invariant under the sparse mix."""
    rng = np.random.default_rng(9)
    n = 16
    adj = _random_adj(rng, n)
    e = topo.edges_from_adj(adj)
    w = topo.edge_mixing_weights(e, n, "uniform")
    src, dst, ww = topo.directed_edges(e, w)
    x = rng.standard_normal((n, 40)).astype(np.float32)
    y = np.asarray(kref.gossip_edges_ref(
        jnp.asarray(x), jnp.asarray(src), jnp.asarray(dst),
        jnp.asarray(ww)))
    np.testing.assert_allclose(y.mean(0), x.mean(0), atol=1e-5)


# ---------------------------------------------------------------------------
# engine differential: cfg.gossip="sparse" vs "dense"
# ---------------------------------------------------------------------------

def _assert_equivalent(h_dense, h_sparse, device_tol=DEVICE_TOL):
    assert len(h_dense.records) == len(h_sparse.records)
    a, b = h_dense.as_arrays(), h_sparse.as_arrays()
    for k in EXACT:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    for k, tol in device_tol.items():
        np.testing.assert_allclose(a[k], b[k], rtol=tol, atol=tol,
                                   err_msg=k)


def _pair(algo, churn, rounds=10, cfg=CFG, **kw):
    h_d = run_algorithm(algo, cfg, non_iid_p=0.4, rounds=rounds,
                        churn=churn, **kw)
    h_s = run_algorithm(algo, replace(cfg, gossip="sparse"), non_iid_p=0.4,
                        rounds=rounds, churn=churn, **kw)
    return h_d, h_s


def test_sparse_matches_dense_reference_smoke():
    """Fast gate: D-PSGD, 6 rounds, reference engine."""
    _assert_equivalent(*_pair("dpsgd", None, rounds=6))


def test_sparse_matches_dense_fused_smoke():
    """Fast gate: D-PSGD, 6 rounds, fused engine (sparse fused routes
    through the Pallas gather-mix-scatter kernel inside the scan)."""
    _assert_equivalent(*_pair("dpsgd", None, rounds=6, fused=True))


@pytest.mark.slow
@pytest.mark.parametrize("churn", [None, SCHED], ids=["nochurn", "churn"])
@pytest.mark.parametrize("fused", [False, True], ids=["reference", "fused"])
@pytest.mark.parametrize("algo", ["dpsgd", "ldsgd", "fedhp"])
def test_sparse_matches_dense(algo, fused, churn):
    """Strategy x engine x churn: the edge-list path is a drop-in for the
    dense mixing matrix everywhere the dense path runs. LD-SGD exercises
    the no-communication rounds (all-zero-weight edge tables must be an
    exact no-op); FedHP closes the control loop, so the exact match on
    mean_tau / num_links proves the sparse measurements feed back
    identically."""
    _assert_equivalent(*_pair(algo, churn, fused=fused))


@pytest.mark.slow
@pytest.mark.parametrize("compress", ["int8", "topk:0.25", "randk:0.25"],
                         ids=["int8", "topk", "randk"])
@pytest.mark.parametrize("fused", [False, True], ids=["reference", "fused"])
def test_sparse_matches_dense_compressed(fused, compress):
    """Compressed gossip over edges: the codecs mix through the shared
    mix_delta closure (segment_sum / kernel vs tensordot), so compressed
    trajectories stay within the compressed tolerance band."""
    cfg = replace(CFG, compress=compress)
    _assert_equivalent(*_pair("dpsgd", SCHED, cfg=cfg, fused=fused),
                       device_tol=COMPRESSED_TOL)


@pytest.mark.slow
def test_sparse_metropolis_matches_dense():
    _assert_equivalent(*_pair("dpsgd", SCHED, mixing="metropolis",
                              fused=True))


@pytest.mark.slow
def test_sparse_fused_vmapped_seeds_match_dense():
    """The edge tables broadcast across vmapped seed lanes."""
    seeds = (11, 12)
    dense = run_algorithm("dpsgd", CFG, non_iid_p=0.4, rounds=6,
                          fused=True, seeds=jnp.asarray(seeds))
    sparse = run_algorithm("dpsgd", SPARSE, non_iid_p=0.4, rounds=6,
                           fused=True, seeds=jnp.asarray(seeds))
    for hd, hs in zip(dense, sparse):
        _assert_equivalent(hd, hs)


def test_sparse_fused_matches_sparse_reference():
    """Both sparse engines against each other (kernel vs segment_sum on
    the same edge stream)."""
    h_ref = run_algorithm("dpsgd", SPARSE, non_iid_p=0.4, rounds=6)
    h_fus = run_algorithm("dpsgd", SPARSE, non_iid_p=0.4, rounds=6,
                          fused=True)
    _assert_equivalent(h_ref, h_fus)


# ---------------------------------------------------------------------------
# capped Floyd-Warshall (large-W planner path)
# ---------------------------------------------------------------------------

def test_floyd_warshall_cap_exact_below_threshold():
    from repro.core import consensus as cns
    rng = np.random.default_rng(10)
    n = 40
    adj = _random_adj(rng, n, p=0.2)
    pd = rng.random((n, n)) + 0.1
    pd = (pd + pd.T) / 2
    m = cns.measured_distance_matrix(adj, pd)
    np.testing.assert_array_equal(
        cns.floyd_warshall_estimate(m),
        cns.floyd_warshall_estimate(m, max_dense=10**9))


def test_floyd_warshall_cap_upper_bounds_exact():
    """Above the threshold the bounded-hop relaxation is the exact
    shortest path over at-most-(hops+1)-edge routes: it never undershoots
    the true shortest path, never exceeds any short route it can see
    (direct edges, 2-edge detours), and leaves unreached pairs at inf for
    the EMA fallback."""
    from repro.core import consensus as cns
    rng = np.random.default_rng(11)
    n = 60
    adj = _random_adj(rng, n, p=0.1)
    pd = rng.random((n, n)) + 0.1
    pd = (pd + pd.T) / 2
    m = cns.measured_distance_matrix(adj, pd)
    exact = cns.floyd_warshall_estimate(m, max_dense=10**9)
    capped = cns.floyd_warshall_estimate(m, max_dense=1, hops=3)
    fin = np.isfinite(capped)
    assert (capped[fin] >= exact[fin] - 1e-12).all()
    # never worse than the direct measurement on measured edges
    assert (capped[adj > 0] <= m[adj > 0] + 1e-12).all()
    # never worse than the best 2-edge route min_p (m_ip + m_pj)
    best2 = np.min(m[:, :, None] + m[None, :, :], axis=1)
    mask = np.isfinite(best2)
    np.fill_diagonal(mask, False)
    assert (capped[mask] <= best2[mask] + 1e-12).all()


def test_floyd_warshall_cap_ring_leaves_far_pairs_inf():
    from repro.core import consensus as cns
    n = 64
    adj = topo.ring_topology(n)
    m = cns.measured_distance_matrix(adj, np.ones((n, n)))
    capped = cns.floyd_warshall_estimate(m, max_dense=1, hops=3)
    # within 4 ring hops: exact integer distances; beyond: inf
    assert capped[0, 4] == 4.0
    assert not np.isfinite(capped[0, 5])


def test_tracker_large_w_uses_capped_estimate():
    """ConsensusTracker.update stays finite (EMA fallback covers the
    hop-capped infs) and cheap at W beyond the dense threshold."""
    from repro.core import consensus as cns
    n = cns.FW_DENSE_MAX + 8
    rng = np.random.default_rng(12)
    adj = topo.ring_topology(n)
    pd = np.abs(rng.standard_normal((n, n))) + 0.1
    pd = (pd + pd.T) / 2
    tr = cns.ConsensusTracker(n)
    out = tr.update(adj, pd, 1.0)
    assert np.isfinite(out).all()
    assert out.shape == (n, n)


@pytest.mark.parametrize("base", ["ba:2", "ws:4:0.2"], ids=["ba", "ws"])
def test_sparse_matches_dense_complex_topologies(base):
    """Differential matrix over the complex-network families: the
    edge-list path must be a drop-in on Barabasi-Albert and
    Watts-Strogatz graphs too. The "base" strategy gossips over the raw
    family graph every round (dpsgd would substitute a ring), so hubs
    and rewired chords actually reach the segment ops."""
    cfg = replace(CFG, base_topology=base)
    _assert_equivalent(*_pair("base", SCHED, cfg=cfg))
    _assert_equivalent(*_pair("base", SCHED, cfg=cfg, fused=True))
