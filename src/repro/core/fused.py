"""Fused scan-based DFL engines — the fast paths next to ``run_dfl``
and ``run_adpsgd``.

``run_dfl_fused`` executes whole blocks of rounds on device inside one
``jax.lax.scan`` instead of the reference engine's one Python iteration
(~10 dispatches + host syncs) per round:

- Static-plan baselines (D-PSGD ring, LD-SGD alternation, the plain base
  strategy) fuse the entire horizon into a single scan.
- Adaptive strategies (FedHP, PENS) scan in segments of
  ``cfg.replan_every`` rounds; measurements (Alg. 1 lines 4-5) surface to
  the host only at segment boundaries, where the strategy's
  ``observe``/``plan`` cycle is replayed round by round. With
  ``replan_every=1`` the fused engine replans every round exactly like
  the reference; larger segments freeze (A^h, tau^h) within a segment —
  a documented behavioral deviation bought for throughput (README.md).
- Gossip (Eq. 5-6) runs through the Pallas ``gossip_mix_rows`` kernel
  on the flattened [W, P] parameter matrix as it lies (column tiles as
  wide as a fixed VMEM budget allows at W rows, all worker rows
  resident), so P need not be a tile multiple and no relayout or
  padded copy feeds the kernel; the edge-list kernel below tiles the
  same way.
- ``cfg.gossip == "sparse"`` swaps the dense [W, W] mixing for the
  edge-list path: per-round directed edge arrays (padded to a static
  E_max with zero-weight no-op edges) ride the scan instead of [K, W, W]
  mixing matrices, and the mix runs through the
  ``kernels/gossip_edges.py`` gather-mix-scatter kernel — O(E P) per
  round instead of O(W² P), which is what lets W scale past the dense
  wall (composes with churn masks, every codec, and ``seeds=``).
- Churn masks (alive / joined / donor weights) become traced arrays
  threaded through the scan — join re-init, metric masking and mixing all
  happen on device. The schedule itself is replayed host-side so the
  cluster's RNG stream matches the reference engine draw for draw.
- ``seeds=jnp.arange(S)`` adds a ``jax.vmap`` axis over model-init /
  batch-sampling seeds: S experiments amortize one scan (sweep
  workloads). Static-plan strategies only — an adaptive plan is feedback
  from one seed's trajectory.
- ``cfg.compress`` ("int8" / "topk:<k>" / "randk:<k>") swaps the gossip
  for the codec's compensated update (core/compression.py): per-worker
  error-feedback residuals ride in the scan carry, the wire round trip
  runs through the Pallas kernels on the [W, P] layout
  (``quantize_block_2d``/``dequantize_block_2d`` for int8,
  ``sparsify_block_2d`` mask-and-pack for top-k / rand-k), and Eq. 10
  charges comm time / the codec's wire_ratio — composing with churn
  masks, the vmapped ``seeds`` axis, and FedHP's per-plan codec
  tightening (``RoundPlan.codec``, frozen per segment).

``run_adpsgd_fused`` does the same for the event-driven AD-PSGD
baseline: the host precomputes the full event schedule
(``engine.adpsgd_schedule`` — partners, event clocks, staleness) and the
scan replays every event with snapshots, int8 residuals and staleness
counters carried in the scan state, pairwise-averaging through the
Pallas ``gossip_mix_2d`` kernel on a 2-row slice.

Interchangeability with the reference engines is proven by the
differential harness in ``tests/test_fused_equivalence.py``.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import FedHPConfig
from repro.core import compression
from repro.core import modelspec
from repro.core import robust as robust_agg
from repro.core import topology as topo
from repro.core.algorithms import Strategy
from repro.core.engine import (AdpsgdSchedule, History, RoundRecord,
                               _adpsgd_delta, _blend_joined,
                               _cross_loss_matrix, _draw_batches,
                               _flatten_row, _flatten_workers,
                               _measure_worker, _sgd_worker,
                               _unflatten, _unflatten_row, adpsgd_schedule)
from repro.data.synthetic import Dataset
from repro.kernels.gossip_edges import gossip_edges
from repro.kernels.gossip_mix import gossip_mix_2d, gossip_mix_rows
from repro.kernels.robust_gossip import robust_gossip
from repro.runtime.collectives import (_shard_map, edge_shard_tables,
                                       routed_mix_delta)
from repro.runtime.sharding import worker_stack_pspecs, worker_stack_spec
from repro.simulation.cluster import SimCluster

# static-plan strategies would otherwise stage the whole horizon's batch
# tensors host-side at once ([S, K, W, tau, B, D] f32); chunking the scan
# bounds that at ~64 rounds per dispatch with no semantic difference
# (static plans are recomputed per round either way)
MAX_FUSE_ROUNDS = 64

# AD-PSGD stages one batch tensor PER EVENT ([S, K, N, tau, B, D] — an
# extra N factor over the synchronous engine), so its segments are shorter
ADPSGD_FUSE_ROUNDS = 32


# ---------------------------------------------------------------------------
# device code: one scan over the rounds of a segment
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("adapter", "tau_cap", "measure",
                                   "needs_cross", "interpret", "kind", "k",
                                   "ef", "sparse", "lcodec", "robust", "rb",
                                   "attack"))
def _scan_segment(stacked, err, bx, by, ex, ey, px, py, taus, lrs, mixes,
                  esrc, edst, ewt, comms, ew, cw, keep, rw, hs, nbrs, degs,
                  byz, atk_scale, skey, gamma, tx, ty, *, adapter,
                  tau_cap: int, measure: bool, needs_cross: bool,
                  interpret: bool, kind: str, k: int, ef: bool, sparse: bool,
                  lcodec=None, robust: str = "none", rb: float = 0.0,
                  attack: str = ""):
    """Run K rounds on device. Batched over a leading seed axis S on
    (stacked, err, bx, by, ex, ey, px, py); control inputs (taus .. rw
    plus the round indices ``hs``, all [K]-leading), the rand-k mask key
    ``skey`` and the test set are shared across seeds. ``adapter`` (a
    hashable ``modelspec.ModelAdapter``) supplies loss/accuracy — the
    scan itself only sees the flattened [W, P] layout.

    ``err`` is the [S, W, P] error-feedback residual carried as scan
    state on compressed runs (untouched otherwise); ``kind``/``k`` name
    the segment's wire codec ("none" uncompressed — a frozen adaptive
    plan fixes the codec for the whole segment). ``lcodec`` is the
    segment's compiled per-leaf codec map when ``kind == "leafmap"``
    (None otherwise) — its shared oracle payload keeps reference and
    fused leafmap trajectories bit-identical by construction.

    ``sparse`` selects the edge-list gossip path: the round topology
    arrives as directed edge arrays (``esrc``/``edst``/``ewt``,
    [K, E_max] padded with zero-weight edges — exact no-ops), the mixing
    delta runs through the ``kernels/gossip_edges.py`` gather-mix-scatter
    kernel on [W, P], and ``mixes`` is a [K, 1, 1] dummy (no dense
    [W, W] matrix is ever staged). Dense mode carries [K, 8] edge
    dummies instead.

    The Byzantine scenario axis rides the scan too: ``attack`` (the
    attack kind, "" for an honest fleet) makes byzantine rows (``byz``,
    [W] bool shared across seeds) transmit a corrupted wire copy
    (``core/robust.apply_attack`` scaled by ``atk_scale``), and
    ``robust`` ("trimmed"/"median" with trim knob ``rb``) replaces the
    weighted mix with the coordinate-wise robust aggregation over the
    per-round padded neighbor tables (``nbrs``/``degs``, [K, W, Dp] /
    [K, W]) through the Pallas ``kernels/robust_gossip.py``
    gather-sort-trim kernel — robust rounds gather their own dense
    window, so dense and sparse gossip share one lowering. Honest
    uncompressed rounds never touch any of this (dead static branches).

    Returns ((stacked', err'), outs) where outs is a dict of [S, K, ...]
    metric trajectories.
    """
    leafmap = lcodec is not None
    compress = kind != "none" and not leafmap
    # which codecs evolve the state buffer (int8 residual / top-k x̂) —
    # rand-k carries nothing; mirrors compression.carries_state so the
    # scan state matches the reference engine bit for bit
    stateful = compress and compression.carries_state(kind, ef)

    def one_seed(stacked, err, bx, by, ex, ey, px, py):

        def body(carry, xs):
            carry, err_c = carry
            (bxh, byh, tau_h, lr_h, mix_h, src_h, dst_h, wgt_h, comm_h,
             ew_h, cw_h, keep_h, rw_h, h_h, nbr_h, deg_h) = xs

            def mix_delta(v):
                # (W @ v - v): through the edge kernel when sparse (zero-
                # weight padding edges make no-comm rounds exact no-ops),
                # dense tensordot otherwise
                with jax.named_scope("dfl.mix"):
                    if sparse:
                        return gossip_edges(v, src_h, dst_h, wgt_h,
                                            interpret=interpret) - v
                    return jnp.tensordot(mix_h, v, axes=1) - v

            # --- join re-init: the reference's _reinit_joined with
            # (keep, donor weights) precomputed host-side; an all-False
            # keep_h makes the blend an exact no-op ---
            with jax.named_scope("dfl.join"):
                carry = _blend_joined(carry, keep_h, rw_h)
                if stateful:
                    # joined rows adopt a blended model; their codec state
                    # resets the same way as in the reference engine (zeroed
                    # residual / x̂ re-anchored at the blended row)
                    err_c = compression.state_after_join(
                        err_c, keep_h[:, None], _flatten_workers(carry),
                        kind, ef)
                elif leafmap:
                    err_c = compression.leafmap_state_after_join(
                        err_c, keep_h[:, None], _flatten_workers(carry),
                        lcodec, ef)
            prev = carry

            # --- local updating (Eq. 3), masked to tau_i — the SAME
            # per-worker step function the reference engine vmaps ---
            with jax.named_scope("dfl.local_sgd"):
                carry = jax.vmap(
                    lambda p, bxw, byw, tau: _sgd_worker(adapter, p, bxw, byw,
                                                         tau, lr_h, tau_cap))(
                    carry, bxh, byh, tau_h)

            # the exchange: robust aggregation, a codec round trip or
            # the plain mix, each under its own scope (mix_delta nests
            # "dfl.mix" inside the codec's)
            phase = ("dfl.robust" if robust != "none" or attack else
                     "dfl.codec" if leafmap or compress else "dfl.mix")
            with jax.named_scope(phase):
                flat = _flatten_workers(carry)
                if robust != "none":
                    # --- robust aggregation (core/robust.py lowered): the
                    # wire carries the (possibly corrupted) transmitted copy;
                    # each worker sort-trims its gathered closed neighborhood
                    # through the Pallas gather-sort-trim kernel. No-comm
                    # rounds carry all-zero degrees (keep-own-row) and are
                    # additionally comm_h-gated to the reference's skipped
                    # gossip — an exact no-op either way ---
                    transmitted = (robust_agg.apply_attack(
                        flat, byz, atk_scale, kind=attack) if attack else flat)
                    mixed = robust_gossip(flat, transmitted, nbr_h, deg_h,
                                          b=rb, mode=robust,
                                          interpret=interpret)
                    y_flat = jnp.where(comm_h > 0, mixed, flat)
                elif attack:
                    # --- plain (non-robust) mixing of a lying wire — the
                    # attacked baseline the robust modes are measured
                    # against: Eq. 5 consumes the transmitted copies ---
                    transmitted = robust_agg.apply_attack(flat, byz, atk_scale,
                                                          kind=attack)
                    if sparse:
                        mixed = robust_agg.gossip_byz_edges(
                            flat, transmitted, src_h, dst_h, wgt_h)
                    else:
                        mixed = robust_agg.gossip_byz_dense(flat, transmitted,
                                                            mix_h)
                    y_flat = jnp.where(comm_h > 0, mixed, flat)
                elif leafmap:
                    # --- per-leaf codec map: the SAME shared payload round
                    # trip as the reference (compression.leafmap_payload),
                    # one mixing delta on the combined payload, per-segment
                    # gamma damping, comm_h gating both params and codec
                    # state to an exact no-op on no-communication rounds ---
                    payload, new_err = compression.leafmap_payload(
                        flat, err_c, lcodec, error_feedback=ef, key=skey,
                        step=h_h)
                    err_c = jnp.where(comm_h > 0, new_err, err_c)
                    gmask = jnp.asarray(
                        compression.leafmap_gamma_mask(lcodec, ef))
                    gvec = gmask * gamma + (1.0 - gmask)
                    y_flat = flat + comm_h * gvec[None, :] * mix_delta(payload)
                elif kind == "topk" and ef:
                    # --- x̂-tracked top-k (ChocoSGD form, the same update as
                    # compression.compressed_gossip_ref): the wire carries
                    # the top-k innovation against the tracked public copy,
                    # through the Pallas sparsify kernel; the damped
                    # consensus step mixes the advanced copies. comm_h gates
                    # no-communication rounds to an exact no-op (nothing is
                    # sent: neither params nor x̂ move) ---
                    q = compression.sparsify_rows(flat - err_c, "topk", k,
                                                  use_kernel=True,
                                                  interpret=interpret)
                    xhat = err_c + q
                    err_c = jnp.where(comm_h > 0, xhat, err_c)
                    y_flat = flat + comm_h * gamma * mix_delta(xhat)
                elif compress:
                    # --- int8 / rand-k / naive top-k: the codec round trip
                    # of z = x + e per worker through the Pallas kernels on
                    # the [W, rows, cols] layout (quantize/dequantize or the
                    # sparsify mask-and-pack), then the same tensordot mixing
                    # of ŷ as the reference's _gossip_compressed, with comm_h
                    # gating as above ---
                    z = flat + err_c if stateful else flat
                    yhat = compression.encode_rows(z, kind, k, key=skey,
                                                   step=h_h, use_kernel=True,
                                                   interpret=interpret)
                    if stateful:
                        err_c = jnp.where(comm_h > 0, z - yhat, err_c)
                    y_flat = flat + comm_h * mix_delta(yhat)
                elif sparse:
                    # --- sparse gossip (Eq. 5-6) through the edge kernel on
                    # [W, P]: y_i = x_i + sum_e w_e (x_src - x_i) over the
                    # round's directed edges; no-communication rounds carry
                    # all-zero-weight edges — an exact no-op ---
                    y_flat = gossip_edges(flat, src_h, dst_h, wgt_h,
                                          interpret=interpret)
                else:
                    # --- gossip (Eq. 5-6) through the Pallas kernel on the
                    # [W, P] rows: y_i = x_i + sum_j w_ij (x_j - x_i) =
                    # sum_j w_ij x_j for a row-stochastic mix; rounds without
                    # communication carry an identity mix, which the kernel
                    # maps to an exact no-op ---
                    y_flat = gossip_mix_rows(flat, mix_h, interpret=interpret)
                carry = _unflatten(y_flat, carry)

            # --- per-round metrics: fleet accuracy/loss over alive
            # workers + consensus distance to the alive mean ---
            with jax.named_scope("dfl.evaluation"):
                accs = jax.vmap(lambda p: adapter.accuracy(p, tx, ty))(carry)
                tloss = jax.vmap(
                    lambda p: adapter.loss(p, {"x": tx, "y": ty}))(carry)
                dmean = jnp.tensordot(cw_h, y_flat, axes=1)
                dists = jnp.sqrt(jnp.sum((y_flat - dmean[None]) ** 2, axis=1))
                outs = {"acc": jnp.dot(ew_h, accs),
                        "loss": jnp.dot(ew_h, tloss),
                        "consensus": jnp.dot(cw_h, dists)}

            if measure:
                with jax.named_scope("dfl.alg1_measure"):
                    # --- Alg. 1 lines 4-5: the SAME per-worker measurement
                    # function as the reference engine's _measure (eval/probe
                    # tensors passed whole, only params vmapped) ---
                    losses, _, ls, sigs, upds = jax.vmap(
                        lambda p, q: _measure_worker(adapter, p, q, ex, ey, px,
                                                     py))(carry, prev)
                    # consensus.pairwise_distances' f32 gram trick, including
                    # its cancellation noise floor for near-identical models —
                    # that floor feeds FedHP's tracker, so it is part of the
                    # behavior being reproduced
                    sq = jnp.sum(y_flat * y_flat, axis=1)
                    d2 = jnp.maximum(
                        sq[:, None] + sq[None, :] - 2.0 * (y_flat @ y_flat.T),
                        0.0)
                    d2 = d2 * (1.0 - jnp.eye(d2.shape[0]))
                    outs.update(losses=losses, ls=ls, sigs=sigs, upds=upds,
                                edge=jnp.sqrt(d2))
                    if needs_cross:
                        outs["cross"] = _cross_loss_matrix(
                            adapter, carry, ex[:, :64], ey[:, :64])
            return (carry, err_c), outs

        return jax.lax.scan(body, (stacked, err),
                            (bx, by, taus, lrs, mixes, esrc, edst, ewt,
                             comms, ew, cw, keep, rw, hs, nbrs, degs))

    return jax.vmap(one_seed,
                    in_axes=(0, 0, 0, 0, 0, 0, 0, 0))(stacked, err, bx, by,
                                                      ex, ey, px, py)


# ---------------------------------------------------------------------------
# device code: the sharded twin — shard_map around the whole segment scan
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("adapter", "tau_cap", "measure", "kind",
                                   "k", "ef", "mesh", "axes", "offsets",
                                   "n_shards"))
def _scan_segment_sharded(stacked, err, bx, by, ex, ey, px, py, taus, lrs,
                          esl, edl, ewl, comms, ew, cw, keep, rw, hs, skey,
                          gamma, tx, ty, *, adapter, tau_cap: int,
                          measure: bool, kind: str, k: int, ef: bool,
                          mesh, axes, offsets, n_shards: int):
    """``_scan_segment`` with the [W, P] worker matrix sharded over the
    ``axes`` of ``mesh`` (the ``runtime/shardexec`` layout): the WHOLE
    K-round ``lax.scan`` runs inside one ``shard_map``, so per-round
    device work stays on each shard's ``rows = w_pad / n_shards`` block
    and only the cross-shard gossip contributions move — one ``ppermute``
    per distinct shard offset, via ``runtime/collectives.
    routed_mix_delta`` on the per-round [D, n_shards, width] edge tables
    ``esl``/``edl``/``ewl`` (built by the driver against the static
    ``offsets`` so every round of the segment shares one specialization).

    Differences from the unsharded scan, none of them behavioral:

    - no seed axis: the driver runs S=1 and re-adds the axis host-side
      (a batched ``seeds`` sweep stays unsharded);
    - gossip is ALWAYS the edge-list form (per-edge weights bit-identical
      to the dense off-diagonals) and the codecs run the
      ``use_kernel=False`` oracle row path (bit-identical to the Pallas
      kernels by the kernel differential tests) — payloads are row-local,
      so each shard compresses its own block and only the routed mixing
      delta crosses shards;
    - fleet scalars (join-blend mean, acc/loss/consensus dots) are psums
      of per-shard partials; the measure-mode [W, W] edge-distance gram
      ``all_gather``s the flat matrix (FedHP's tracker consumes the full
      gram — a measurement cost at segment boundaries, not a per-round
      training cost);
    - inputs arrive PADDED to ``w_pad`` rows (inert rows: zero params,
      tau 0, no edges, zero metric weights — exact no-ops end to end);
      the driver slices [W]-shaped outputs back to the real fleet.

    Returns ((stacked', err'), outs) with NO leading seed axis.
    """
    compress = kind != "none"
    stateful = compress and compression.carries_state(kind, ef)
    lead = axes if len(axes) > 1 else axes[0]

    def xspec(ndim):
        # [K, w_pad, ...] per-round control input: worker axis second
        return P(*([None, lead] + [None] * (ndim - 2)))

    def rspec(ndim):
        # fully replicated (eval tensors, scalars, [K] vectors)
        return P(*([None] * ndim))

    def scanned(stacked, err, bx, by, ex, ey, px, py, taus, lrs, esl, edl,
                ewl, comms, ew, cw, keep, rw, hs, skey, gamma, tx, ty):

        def body(carry, xs):
            carry, err_c = carry
            (bxh, byh, tau_h, lr_h, sl_h, dl_h, wl_h, comm_h, ew_h, cw_h,
             keep_h, rw_h, h_h) = xs

            def mix_delta(v):
                with jax.named_scope("dfl.mix"):
                    return routed_mix_delta(v, sl_h, dl_h, wl_h, offsets,
                                            axes, n_shards)

            # --- join re-init: _blend_joined with the fleet mean as a
            # psum of per-shard partial tensordots (rw_h is zero outside
            # the donor rows, so partials just add up) ---
            def blend(l):
                part = jnp.tensordot(rw_h, l.astype(jnp.float32), axes=1)
                mean = jax.lax.psum(part, axes)
                kk = keep_h.reshape((-1,) + (1,) * (l.ndim - 1))
                return jnp.where(kk, mean[None].astype(l.dtype), l)

            with jax.named_scope("dfl.join"):
                carry = jax.tree.map(blend, carry)
                if stateful:
                    err_c = compression.state_after_join(
                        err_c, keep_h[:, None], _flatten_workers(carry), kind,
                        ef)
            prev = carry

            # --- local updating (Eq. 3): row-local, the same vmapped
            # per-worker step on each shard's block ---
            with jax.named_scope("dfl.local_sgd"):
                carry = jax.vmap(
                    lambda p, bxw, byw, tau: _sgd_worker(adapter, p, bxw, byw,
                                                         tau, lr_h, tau_cap))(
                    carry, bxh, byh, tau_h)

            # the exchange: a codec round trip under its own scope, or
            # the plain routed mix (mix_delta opens "dfl.mix" either way)
            with (jax.named_scope("dfl.codec") if compress
                  else contextlib.nullcontext()):
                flat = _flatten_workers(carry)
                if kind == "topk" and ef:
                    # x̂-tracked top-k: identical update to the unsharded
                    # scan; the oracle sparsify is per-row, so each shard
                    # compresses its own rows
                    q = compression.sparsify_rows(flat - err_c, "topk", k,
                                                  use_kernel=False)
                    xhat = err_c + q
                    err_c = jnp.where(comm_h > 0, xhat, err_c)
                    y_flat = flat + comm_h * gamma * mix_delta(xhat)
                elif compress:
                    # int8 / rand-k / naive top-k round trip per shard block
                    # (rand-k's mask is recomputed identically on every shard
                    # from the shared key + step), then the routed delta
                    z = flat + err_c if stateful else flat
                    yhat = compression.encode_rows(z, kind, k, key=skey,
                                                   step=h_h, use_kernel=False)
                    if stateful:
                        err_c = jnp.where(comm_h > 0, z - yhat, err_c)
                    y_flat = flat + comm_h * mix_delta(yhat)
                else:
                    # sparse gossip (Eq. 5-6): zero-weight padding edges make
                    # no-comm rounds exact no-ops, same contract as the edge
                    # kernel
                    y_flat = flat + mix_delta(flat)
                carry = _unflatten(y_flat, carry)

            # --- per-round fleet metrics: per-shard partial dots, psum'd
            # (metric weights are zero on the inert padding rows) ---
            with jax.named_scope("dfl.evaluation"):
                accs = jax.vmap(lambda p: adapter.accuracy(p, tx, ty))(carry)
                tloss = jax.vmap(
                    lambda p: adapter.loss(p, {"x": tx, "y": ty}))(carry)
                dmean = jax.lax.psum(jnp.tensordot(cw_h, y_flat, axes=1), axes)
                dists = jnp.sqrt(jnp.sum((y_flat - dmean[None]) ** 2, axis=1))
                outs = {"acc": jax.lax.psum(jnp.dot(ew_h, accs), axes),
                        "loss": jax.lax.psum(jnp.dot(ew_h, tloss), axes),
                        "consensus": jax.lax.psum(jnp.dot(cw_h, dists), axes)}

            if measure:
                with jax.named_scope("dfl.alg1_measure"):
                    # per-worker measurements are row-local (the eval/probe
                    # stacks are replicated — historical full-stack
                    # semantics); the [W, W] gram needs every row, so the
                    # flat matrix is all_gathered once per measured round
                    losses, _, ls, sigs, upds = jax.vmap(
                        lambda p, q: _measure_worker(adapter, p, q, ex, ey, px,
                                                     py))(carry, prev)
                    yg = jax.lax.all_gather(y_flat, axes, axis=0, tiled=True)
                    sq = jnp.sum(yg * yg, axis=1)
                    d2 = jnp.maximum(
                        sq[:, None] + sq[None, :] - 2.0 * (yg @ yg.T), 0.0)
                    d2 = d2 * (1.0 - jnp.eye(d2.shape[0]))
                    outs.update(losses=losses, ls=ls, sigs=sigs, upds=upds,
                                edge=jnp.sqrt(d2))
            return (carry, err_c), outs

        return jax.lax.scan(body, (stacked, err),
                            (bx, by, taus, lrs, esl, edl, ewl, comms, ew,
                             cw, keep, rw, hs))

    s_specs = worker_stack_pspecs(stacked, axes)
    e_spec = worker_stack_spec(err.ndim, axes)
    t_spec = P(None, None, lead, None)
    in_specs = (s_specs, e_spec, xspec(bx.ndim), xspec(by.ndim),
                rspec(ex.ndim), rspec(ey.ndim), rspec(px.ndim),
                rspec(py.ndim), xspec(2), P(None), t_spec, t_spec, t_spec,
                P(None), xspec(2), xspec(2), xspec(2), xspec(2), P(None),
                rspec(jnp.ndim(skey)), P(), rspec(tx.ndim), rspec(ty.ndim))
    outs_spec = {"acc": P(None), "loss": P(None), "consensus": P(None)}
    if measure:
        outs_spec.update(losses=xspec(2), ls=xspec(2), sigs=xspec(2),
                         upds=xspec(2), edge=rspec(3))
    fn = _shard_map(scanned, mesh, in_specs, ((s_specs, e_spec), outs_spec))
    return fn(stacked, err, bx, by, ex, ey, px, py, taus, lrs, esl, edl,
              ewl, comms, ew, cw, keep, rw, hs, skey, gamma, tx, ty)


# ---------------------------------------------------------------------------
# host code: segment precompute replaying the reference engine's streams
# ---------------------------------------------------------------------------

@dataclass
class _Segment:
    """Per-round control inputs + host-side record fields for K rounds."""
    bx: np.ndarray            # [S, K, W, T, B, *feat] (data.x dtype)
    by: np.ndarray            # [S, K, W, T, B]
    taus: np.ndarray          # [K, W] i32
    lrs: np.ndarray           # [K] f32
    mixes: np.ndarray         # [K, W, W] f32 ([K, 1, 1] dummy when sparse)
    esrc: np.ndarray          # [K, E_max] i32 directed edge sources
    edst: np.ndarray          # [K, E_max] i32 directed edge destinations
    ewt: np.ndarray           # [K, E_max] f32 edge weights (0 == padding)
    comms: np.ndarray         # [K] f32  1.0 on rounds with communication
    ew: np.ndarray            # [K, W] f32  eval (accuracy/loss) weights
    cw: np.ndarray            # [K, W] f32  consensus weights
    keep: np.ndarray          # [K, W] bool join re-init mask
    rw: np.ndarray            # [K, W] f32  donor weights
    hs: np.ndarray            # [K] i32 absolute round indices (rand-k step)
    nbrs: np.ndarray          # [K, W, Dp] i32 padded neighbor tables
    degs: np.ndarray          # [K, W] i32 neighbor counts (robust rounds)
    tau_cap: int
    codec: object             # the segment's wire codec (compression.Codec)
    wire_ratio: list[float]   # per-round Eq. 10 comm divisor (observe fb)
    meas: list[np.ndarray]    # honest-alive measurement masks
    alive: list[np.ndarray]
    adjs: list[np.ndarray]
    mus: list[np.ndarray]
    betas: list[np.ndarray]
    round_time: list[float]
    waiting: list[float]
    mean_tau: list[float]
    num_links: list[int]
    cum_time: list[float]

    def __len__(self) -> int:
        return len(self.round_time)


def _precompute_segment(h0: int, seg_len: int, cluster: SimCluster,
                        strategy: Strategy, cfg: FedHPConfig, rngs, data,
                        shards, mixfn, clock: float,
                        time_budget: float | None, adaptive: bool,
                        codec0, p_model: int, sparse: bool = False,
                        mixing: str = "uniform", byz: np.ndarray | None = None,
                        robust: bool = False):
    """Advance cluster/strategy/batch RNG streams for rounds h0..h0+K-1 in
    the exact order ``run_dfl`` would, and pack the device inputs.

    For an adaptive strategy the plan is frozen at the segment's first
    round; static strategies re-plan every round (observation-free, so
    this is exactly the reference behavior). The frozen plan also fixes
    the segment's wire codec (``plan.codec`` falling back to ``codec0``,
    the parsed ``cfg.compress``; an uncompiled leafmap in the plan is
    replaced by the driver's compiled ``codec0``), whose
    ``wire_ratio(p_model)`` — the adapter's true parameter count —
    divides the Eq. 10 comm term exactly like the reference engine's
    clock.

    ``byz`` (a [W] bool mask, None when the fleet is honest) shifts the
    measurement weights onto the honest alive workers (``meas``) exactly
    like the reference engine; ``robust`` additionally packs per-round
    padded neighbor tables (``core/robust.neighbor_table`` of the
    repaired adjacency, segment max degree bucketed to the next power of
    two) for the fused trimmed/median sort window.
    """
    n = cfg.num_workers
    compress = codec0.kind != "none"
    drifting = hasattr(shards, "shards_at")
    per: list[dict] = []
    plan = None
    stop = False
    for t in range(seg_len):
        h = h0 + t
        alive = cluster.advance_round(h)
        joined = cluster.last_joined.copy()
        crashed = cluster.last_crashed.copy()
        mu = cluster.sample_mu()
        beta = cluster.sample_beta()
        if plan is None or not adaptive:
            with jax.profiler.TraceAnnotation("dfl.plan", h=h):
                plan = strategy.plan(h, alive=alive)
        rcodec = plan.codec if plan.codec is not None else codec0
        if codec0.kind == "leafmap" and rcodec.kind == "leafmap":
            rcodec = codec0           # the compiled copy
        comm_ratio = rcodec.wire_ratio(p_model) if compress else 1.0
        adj = plan.adj.copy()
        adj[~alive, :] = 0
        adj[:, ~alive] = 0
        # churn safety net: reconnect survivors whenever the strategy
        # intended communication this round (plan.adj has links) but
        # departures may have disconnected — or fully severed — them
        if not alive.all() and alive.sum() > 1 and plan.adj.sum() > 0:
            adj = topo.repair_connectivity(adj, alive, cost=beta)
        taus = np.where(alive, np.clip(plan.taus, 1, cfg.tau_max), 0)
        tau_cap = int(max(taus.max(), 1))
        sh = shards.shards_at(h) if drifting else shards
        batches = [_draw_batches(rng, data, sh, tau_cap, cfg.batch_size)
                   for rng in rngs]

        # --- clock (Eq. 10-11), formulas identical to run_dfl ---
        comm = np.where(adj.sum(1) > 0,
                        np.where(adj > 0, beta, 0.0).max(1), 0.0)
        if compress:
            comm = comm / comm_ratio
        t_i = taus * mu + comm
        if plan.extra_time is not None:
            t_i = t_i + plan.extra_time * alive
        t_round = float(t_i[alive].max()) if alive.any() else 0.0
        if crashed.any():
            t_round += cfg.crash_timeout
        waiting = float((t_round - t_i[alive]).mean()) if alive.any() else 0.0
        clock += t_round

        # --- device-side control inputs ---
        if sparse:
            # edge-list round topology: per-edge weights from degrees
            # (bit-identical to the dense matrices' off-diagonals); the
            # dense mix is never built — [K, 1, 1] dummies ride the scan
            mix = np.zeros((1, 1), np.float32)
            if adj.sum() > 0:
                e_und = topo.edges_from_adj(adj)
                e_w = topo.edge_mixing_weights(e_und, n, mixing)
                src, dst, wts = topo.directed_edges(e_und, e_w)
            else:
                src = dst = np.zeros(0, np.int32)
                wts = np.zeros(0, np.float32)
        else:
            mix = mixfn(adj) if adj.sum() > 0 else np.eye(n)
            src = dst = np.zeros(0, np.int32)
            wts = np.zeros(0, np.float32)
        donors = alive & ~joined
        do_reinit = joined.any() and donors.any()
        keep = joined if do_reinit else np.zeros(n, bool)
        rw = donors / max(donors.sum(), 1.0) if do_reinit else np.zeros(n)
        # fleet metrics cover the honest alive workers only (identical to
        # the reference engine's meas mask — equal to alive when the
        # fleet is honest, so honest runs are untouched bit for bit)
        meas = alive
        if byz is not None and byz.any() and (alive & ~byz).any():
            meas = alive & ~byz
        if meas.any() and not meas.all():
            ew = meas / meas.sum()
        else:
            ew = np.full(n, 1.0 / n)
        cw = meas / meas.sum() if meas.any() else np.full(n, 1.0 / n)
        # padded closed-neighborhood index table of the repaired round
        # topology — the fused trimmed/median sort window (dummy [W, 1]
        # zeros otherwise; deg 0 == keep-own-row, an exact no-op)
        if robust:
            nbr_t, deg_t = robust_agg.neighbor_table(adj)
        else:
            nbr_t = np.zeros((n, 1), np.int32)
            deg_t = np.zeros(n, np.int32)

        per.append(dict(alive=alive, adj=adj, mu=mu, beta=beta, taus=taus,
                        tau_cap=tau_cap, batches=batches, mix=mix,
                        src=src, dst=dst, wts=wts, meas=meas,
                        nbr=nbr_t, deg=deg_t,
                        comm=1.0 if adj.sum() > 0 else 0.0,
                        keep=keep, rw=rw, ew=ew, cw=cw, h=h,
                        codec=rcodec, wire_ratio=comm_ratio,
                        lr=cfg.lr * (cfg.lr_decay ** h),
                        t_round=t_round, waiting=waiting,
                        mean_tau=float(taus[alive].mean())
                        if alive.any() else 0.0,
                        num_links=int(adj.sum() // 2), cum=clock))
        if time_budget is not None and clock >= time_budget:
            stop = True
            break

    # bucket the scan's tau extent to the next power of two: the masked
    # step makes extra iterations no-ops, and bucketing caps the number of
    # distinct (seg_len, tau_cap) jit specializations the adaptive path
    # (whose taus change every replan) can trigger at ~log2(tau_max)
    cap = max(p["tau_cap"] for p in per)
    cap = 1 << (cap - 1).bit_length() if cap > 1 else 1
    n_seeds = len(rngs)

    def pad(b, tc):
        return np.pad(b, ((0, 0), (0, cap - tc)) + ((0, 0),) * (b.ndim - 2))

    bx = np.stack([np.stack([pad(p["batches"][s][0], p["tau_cap"])
                             for p in per]) for s in range(n_seeds)])
    by = np.stack([np.stack([pad(p["batches"][s][1], p["tau_cap"])
                             for p in per]) for s in range(n_seeds)])
    # pad per-round edge arrays to one static E_max (zero-weight edges are
    # exact kernel no-ops), bucketed to the next power of two like tau_cap
    # so adaptive topologies trigger ~log2(E) jit specializations, not one
    # per distinct edge count
    e_max = max((len(p["src"]) for p in per), default=0)
    e_max = max(8, 1 << (e_max - 1).bit_length()) if e_max > 1 else 8
    esrc = np.zeros((len(per), e_max), np.int32)
    edst = np.zeros((len(per), e_max), np.int32)
    ewt_a = np.zeros((len(per), e_max), np.float32)
    for t, p in enumerate(per):
        ne = len(p["src"])
        esrc[t, :ne] = p["src"]
        edst[t, :ne] = p["dst"]
        ewt_a[t, :ne] = p["wts"]
    # pad per-round neighbor tables to one segment-wide D, bucketed to the
    # next power of two like tau_cap/e_max so adaptive topologies trigger
    # ~log2(W) sort-window jit specializations (padding slots sit above
    # deg and are masked to +inf on device — exact no-ops)
    d_max = max(p["nbr"].shape[1] for p in per)
    d_max = 1 << (d_max - 1).bit_length() if d_max > 1 else 1
    nbrs = np.zeros((len(per), n, d_max), np.int32)
    degs = np.zeros((len(per), n), np.int32)
    for t, p in enumerate(per):
        nbrs[t, :, :p["nbr"].shape[1]] = p["nbr"]
        degs[t] = p["deg"]
    seg = _Segment(
        bx=bx, by=by.astype(np.int32),
        taus=np.stack([p["taus"] for p in per]).astype(np.int32),
        lrs=np.array([p["lr"] for p in per], np.float32),
        mixes=np.stack([p["mix"] for p in per]).astype(np.float32),
        esrc=esrc, edst=edst, ewt=ewt_a,
        comms=np.array([p["comm"] for p in per], np.float32),
        ew=np.stack([p["ew"] for p in per]).astype(np.float32),
        cw=np.stack([p["cw"] for p in per]).astype(np.float32),
        keep=np.stack([p["keep"] for p in per]),
        rw=np.stack([p["rw"] for p in per]).astype(np.float32),
        hs=np.array([p["h"] for p in per], np.int32),
        nbrs=nbrs, degs=degs,
        tau_cap=cap,
        codec=per[0]["codec"],
        wire_ratio=[p["wire_ratio"] for p in per],
        meas=[p["meas"] for p in per],
        alive=[p["alive"] for p in per], adjs=[p["adj"] for p in per],
        mus=[p["mu"] for p in per], betas=[p["beta"] for p in per],
        round_time=[p["t_round"] for p in per],
        waiting=[p["waiting"] for p in per],
        mean_tau=[p["mean_tau"] for p in per],
        num_links=[p["num_links"] for p in per],
        cum_time=[p["cum"] for p in per])
    return seg, clock, stop


def _pad_rows(a, pad: int, axis: int = 1, fill=0):
    """Pad ``a``'s worker ``axis`` with ``pad`` inert rows (host numpy)."""
    if pad == 0:
        return np.asarray(a)
    widths = [(0, 0)] * np.ndim(a)
    widths[axis] = (0, pad)
    return np.pad(a, widths, constant_values=fill)


def _sharded_edge_tables(seg: "_Segment", plan):
    """Per-round routed edge tables for one segment, unioned to a single
    static (offsets, width) so all K rounds share one ``shard_map``
    specialization: [K, D, n_shards, width] arrays whose zero-weight
    padding slots contribute exactly 0 to the routed delta."""
    rows = plan.rows
    offs = {0}      # padding edges (src=dst=0) always land in offset 0
    for t in range(seg.esrc.shape[0]):
        src, dst = seg.esrc[t], seg.edst[t]
        offs.update(int(d) for d in np.unique(
            (dst // rows - src // rows) % plan.n_shards))
    offsets = tuple(sorted(offs))
    per = []
    for t in range(seg.esrc.shape[0]):
        _, sl, dl, wl = edge_shard_tables(
            seg.esrc[t], seg.edst[t], seg.ewt[t], plan.w_pad,
            plan.n_shards, offsets=offsets)
        per.append((sl, dl, wl))
    # bucket the per-(offset, dest-shard) slot width to the next power of
    # two so adaptive topologies trigger ~log2(E) specializations
    width = max(max(sl.shape[2] for sl, _, _ in per), 8)
    width = 1 << (width - 1).bit_length()

    def padw(a):
        return np.pad(a, ((0, 0), (0, 0), (0, width - a.shape[2])))

    esl = np.stack([padw(sl) for sl, _, _ in per])
    edl = np.stack([padw(dl) for _, dl, _ in per])
    ewl = np.stack([padw(wl) for _, _, wl in per])
    return offsets, esl, edl, ewl


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_dfl_fused(data: Dataset, test_x, test_y, shards,
                  cluster: SimCluster, cfg: FedHPConfig, strategy: Strategy,
                  *, rounds: int | None = None, hidden: int = 64,
                  eval_subset: int = 512, mixing: str = "uniform",
                  time_budget: float | None = None, seeds=None,
                  interpret: bool | None = None,
                  adapter: modelspec.ModelAdapter | None = None,
                  init_params=None, mesh=None):
    """Drop-in fused replacement for ``engine.run_dfl``.

    With ``seeds=None`` runs one experiment from ``cfg.seed`` and returns
    a ``History`` matching the reference engine's to tolerance. With an
    array of ``seeds`` returns ``list[History]``, one per seed, batched
    through a single vmapped scan: each lane uses its seed for the model
    init PRNGKey and the batch-sampling RNG while sharing the data split,
    cluster and (static) plans. ``adapter``/``init_params`` mirror
    ``run_dfl`` (``init_params`` resumes a single run — incompatible with
    batched ``seeds``).

    ``mesh`` (or ``cfg.sharded``) runs the scan through
    ``_scan_segment_sharded``: the [W, P] worker matrix splits over the
    mesh's worker axis, gossip takes the ppermute-routed edge-list form,
    and the host control plane is byte-identical to the unsharded run.
    Single lane only (no batched ``seeds``); PENS and per-leaf codec
    maps are excluded (see ``engine.run_dfl``'s sharded contract).
    """
    rounds = rounds or cfg.rounds
    n = cfg.num_workers
    sharded = mesh is not None or getattr(cfg, "sharded", False)
    # Byzantine scenario axis (core/robust.py): attackers corrupt the
    # wire copy inside the scan, trimmed/median rounds sort-trim the
    # gathered closed neighborhood through the Pallas robust kernel —
    # no delegation to the reference engine
    byz = robust_agg.byzantine_mask(cfg.byzantine, n)
    has_byz = bool(byz.any())
    robust_mode, robust_b = robust_agg.parse_robust(cfg.robust)
    if robust_mode == "screen":
        raise ValueError(
            "cfg.robust='screen:<z>' is the AD-PSGD accept/reject rule; "
            "synchronous engines use 'trimmed:<b>' / 'median'")
    robust_active = has_byz or robust_mode != "none"
    if robust_active and sharded:
        raise ValueError(
            "the sharded path does not compose with cfg.byzantine / "
            "cfg.robust (data-dependent sorts are single-device-only)")
    atk_kind, atk_scale = (robust_agg.parse_attack(cfg.byzantine_attack)
                           if has_byz else ("signflip", 1.0))
    adaptive = getattr(strategy, "adaptive", False)
    batched = seeds is not None
    if sharded:
        if batched:
            raise ValueError(
                "the sharded fused scan runs one lane (S=1); a batched "
                "seeds axis would stack S copies of the sharded fleet — "
                "run seeds sequentially or drop the mesh")
        if strategy.name == "pens":
            raise ValueError(
                "pens needs the [W, W] cross-loss matrix every round; "
                "the sharded path excludes it (engine.run_dfl contract)")
    if init_params is not None and batched:
        raise ValueError(
            "init_params resumes ONE run's stacked params; it does not "
            "compose with a batched seeds axis")
    seed_list = ([int(s) for s in np.asarray(seeds).reshape(-1)]
                 if batched else [int(cfg.seed)])
    if adapter is None:
        adapter = modelspec.adapter_for(cfg, data, hidden=hidden)
    if adaptive and len(seed_list) > 1:
        raise ValueError(
            f"strategy {strategy.name!r} adapts its plan to per-round "
            "measurements; a batched seeds axis would need one plan per "
            "seed. Batch static-plan strategies (dpsgd/ldsgd) or run "
            "seeds sequentially.")
    interp = (jax.default_backend() == "cpu") if interpret is None \
        else interpret

    # host spans (dfl.*) put the control plane on the profiler's clock
    # beside the round programs' device scopes
    with jax.profiler.TraceAnnotation("dfl.init", h=0):
        # per-seed setup, consuming each seed's RNG exactly like run_dfl
        rngs = [np.random.default_rng(s) for s in seed_list]
        stacked0, exs, eys = [], [], []
        for s, rng in zip(seed_list, rngs):
            if init_params is not None:
                stacked0.append(jax.tree.map(jnp.asarray, init_params))
            else:
                key = jax.random.PRNGKey(s)
                p0 = adapter.init(key)
                stacked0.append(jax.tree.map(
                    lambda l: jnp.broadcast_to(l, (n,) + l.shape), p0))
            exs.append(np.stack([data.x[sh[rng.integers(0, len(sh), 256)]]
                                 for sh in shards]))
            eys.append(np.stack([data.y[sh[rng.integers(0, len(sh), 256)]]
                                 for sh in shards]))
        plan = None
        if sharded:
            from repro.runtime import shardexec
            plan = shardexec.WorkerShardPlan(
                mesh if mesh is not None else shardexec.default_worker_mesh(),
                n)
            # one lane, padded to w_pad inert rows and committed to the mesh
            # (no leading seed axis — the scan runs S=1)
            stacked = plan.put_stacked(stacked0[0])
        else:
            stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *stacked0)
        codec0 = compression.parse_mode(cfg.compress)
        if codec0.kind == "leafmap":
            codec0 = codec0.compile(adapter.leaf_offsets())
        leafmap = codec0.kind == "leafmap"
        if sharded and leafmap:
            raise ValueError(
                "per-leaf codec maps are single-device only: their shared "
                "payload spans leaf segments, which would need per-segment "
                "routing tables on the sharded path")
        compress = codec0.kind != "none"
        if robust_active and compress:
            raise ValueError(
                "cfg.byzantine / cfg.robust do not compose with cfg.compress")
        p_model = adapter.param_count
        # rand-k mask stream: derived from cfg.seed (not the lane seeds) so
        # vmapped lanes share the masks like they share the rest of the
        # host-side control plane
        skey = compression.sparsify_base_key(cfg.seed)
        # per-seed codec state (int8 residual / top-k x̂ / leafmap segment
        # buffer), carried across segments; a [S, W, 1] dummy keeps the carry
        # structure static for stateless runs (uncompressed, rand-k, EF off)
        # without hauling a dead fleet-sized buffer through the scan
        if leafmap:
            err = compression.leafmap_state_init(
                jnp.stack([_flatten_workers(s) for s in stacked0]),
                codec0, cfg.error_feedback)
        elif compress and compression.carries_state(codec0.kind,
                                                    cfg.error_feedback):
            # sharded: state rows follow the padded [w_pad, P] layout (the
            # inert rows' zero params give zero residual / zero x̂)
            err = (compression.state_init(_flatten_workers(stacked),
                                          codec0.kind, cfg.error_feedback)
                   if plan is not None else
                   compression.state_init(
                       jnp.stack([_flatten_workers(s) for s in stacked0]),
                       codec0.kind, cfg.error_feedback))
        elif plan is not None:
            err = jnp.zeros((plan.w_pad, 1), jnp.float32)
        else:
            err = jnp.zeros((len(seed_list), n, 1), jnp.float32)
        ex = jnp.asarray(np.stack(exs))
        ey = jnp.asarray(np.stack(eys))
        px, py = ex[:, :, :32], ey[:, :, :32]
        tx = jnp.asarray(test_x[:eval_subset])
        ty = jnp.asarray(test_y[:eval_subset])

    mixfn = (topo.mixing_matrix_metropolis if mixing == "metropolis"
             else topo.mixing_matrix_uniform)
    needs_cross = strategy.name == "pens"
    replan = max(int(getattr(cfg, "replan_every", 1)), 1)
    # the sharded scan always routes gossip through the edge-list form
    # (weights bit-identical to the dense off-diagonals), so the segment
    # precompute builds edge arrays instead of [K, W, W] mixing matrices
    sparse = cfg.gossip == "sparse" or plan is not None

    hists = [History() for _ in seed_list]
    clock = 0.0
    h = 0
    stop = False
    while h < rounds and not stop:
        with jax.profiler.StepTraceAnnotation("dfl.segment", step_num=h):
            seg_len = (min(replan, rounds - h) if adaptive
                       else min(rounds - h, MAX_FUSE_ROUNDS))
            with jax.profiler.TraceAnnotation("dfl.precompute", h=h):
                seg, clock, stop = _precompute_segment(
                    h, seg_len, cluster, strategy, cfg, rngs, data, shards,
                    mixfn, clock, time_budget, adaptive, codec0, p_model,
                    sparse=sparse, mixing=mixing,
                    byz=byz if has_byz else None,
                    robust=robust_mode in ("trimmed", "median"))
                if plan is not None:
                    offsets, esl, edl, ewl = _sharded_edge_tables(seg, plan)
            if plan is not None:
                pd = plan.pad
                with jax.profiler.TraceAnnotation("dfl.upload", h=h):
                    (bx, by, taus, lrs, esl, edl, ewl, comms, ew, cw, keep,
                     rw, hs) = (jnp.asarray(a) for a in (
                         _pad_rows(seg.bx[0], pd), _pad_rows(seg.by[0], pd),
                         _pad_rows(seg.taus, pd), seg.lrs, esl, edl, ewl,
                         seg.comms, _pad_rows(seg.ew, pd),
                         _pad_rows(seg.cw, pd), _pad_rows(seg.keep, pd),
                         _pad_rows(seg.rw, pd), seg.hs))
                    gamma = jnp.float32(cfg.sparse_gamma)
                with jax.profiler.TraceAnnotation("dfl.dispatch", h=h):
                    (stacked, err), outs = _scan_segment_sharded(
                        stacked, err, bx, by, ex[0], ey[0], px[0], py[0],
                        taus, lrs, esl, edl, ewl, comms, ew, cw, keep, rw,
                        hs, skey, gamma, tx, ty, adapter=adapter,
                        tau_cap=seg.tau_cap, measure=adaptive,
                        kind=seg.codec.kind,
                        k=seg.codec.resolve_k(p_model),
                        ef=cfg.error_feedback, mesh=plan.mesh,
                        axes=plan.axes, offsets=offsets,
                        n_shards=plan.n_shards)
                # the running program holds its inputs until it ends;
                # dropping ours lets them go then, not at the next segment
                del bx, by, taus, lrs, esl, edl, ewl, comms, ew, cw, keep
                del rw, hs
                with jax.profiler.TraceAnnotation("dfl.sync", h=h):
                    outs = {k2: np.asarray(v) for k2, v in outs.items()}
                # slice the inert padding rows off, then re-add the S=1
                # seed axis the record/observe loops below index with si=0
                for k2 in ("losses", "ls", "sigs", "upds"):
                    if k2 in outs:
                        outs[k2] = outs[k2][:, :n]
                if "edge" in outs:
                    outs["edge"] = outs["edge"][:, :n, :n]
                outs = {k2: v[None] for k2, v in outs.items()}
            else:
                with jax.profiler.TraceAnnotation("dfl.upload", h=h):
                    (bx, by, taus, lrs, mixes, esrc, edst, ewt, comms, ew,
                     cw, keep, rw, hs, nbrs, degs, byz_d) = (
                         jnp.asarray(a) for a in (
                             seg.bx, seg.by, seg.taus, seg.lrs, seg.mixes,
                             seg.esrc, seg.edst, seg.ewt, seg.comms, seg.ew,
                             seg.cw, seg.keep, seg.rw, seg.hs, seg.nbrs,
                             seg.degs, byz))
                    scale = jnp.float32(atk_scale)
                    gamma = jnp.float32(cfg.sparse_gamma)
                with jax.profiler.TraceAnnotation("dfl.dispatch", h=h):
                    (stacked, err), outs = _scan_segment(
                        stacked, err, bx, by, ex, ey, px, py, taus, lrs,
                        mixes, esrc, edst, ewt, comms, ew, cw, keep, rw, hs,
                        nbrs, degs, byz_d, scale, skey, gamma, tx, ty,
                        adapter=adapter, tau_cap=seg.tau_cap,
                        measure=adaptive, needs_cross=needs_cross,
                        interpret=interp, kind=seg.codec.kind,
                        k=seg.codec.resolve_k(p_model),
                        ef=cfg.error_feedback, sparse=sparse,
                        lcodec=seg.codec if leafmap else None,
                        robust=robust_mode, rb=robust_b,
                        attack=atk_kind if has_byz else "")
                del bx, by, taus, lrs, mixes, esrc, edst, ewt, comms, ew, cw
                del keep, rw, hs, nbrs, degs, byz_d
                with jax.profiler.TraceAnnotation("dfl.sync", h=h):
                    outs = {k: np.asarray(v) for k, v in outs.items()}

            with jax.profiler.TraceAnnotation("dfl.observe", h=h):
                for t in range(len(seg)):
                    hh = h + t
                    for si, hist in enumerate(hists):
                        hist.records.append(RoundRecord(
                            round=hh, round_time=seg.round_time[t],
                            waiting_time=seg.waiting[t],
                            accuracy=float(outs["acc"][si, t]),
                            loss=float(outs["loss"][si, t]),
                            mean_tau=seg.mean_tau[t],
                            num_links=seg.num_links[t],
                            consensus=float(outs["consensus"][si, t]),
                            cumulative_time=seg.cum_time[t]))
                    if adaptive:
                        a = seg.alive[t]
                        m = seg.meas[t]  # honest alive workers (a sans byz)
                        strategy.observe(
                            hh, adj=seg.adjs[t], mu=seg.mus[t],
                            beta=seg.betas[t],
                            edge_dist=np.asarray(outs["edge"][0, t],
                                                 np.float64),
                            update_norms=(outs["upds"][0, t][m] if m.any()
                                          else [0.0]),
                            smooth_l=float(np.median(outs["ls"][0, t][m])),
                            sigma=float(np.median(outs["sigs"][0, t][m])),
                            loss=float(np.mean(outs["losses"][0, t][m])),
                            cross_loss=np.asarray(outs["cross"][0, t],
                                                  np.float64)
                            if needs_cross else None,
                            alive=a, wire_ratio=seg.wire_ratio[t])
        h += len(seg)
    for si, hist in enumerate(hists):
        # sharded: one lane, no seed axis — hand back the real W rows
        # (still device-sharded when W divides the shard count)
        hist.final_params = (plan.unpad(stacked) if plan is not None else
                             jax.tree.map(lambda l, si=si: l[si], stacked))
    return hists if batched else hists[0]


# ---------------------------------------------------------------------------
# Fused event-driven AD-PSGD
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("adapter", "tau", "interpret", "kind",
                                   "k", "ef", "screen", "attack"))
def _adpsgd_scan(stacked, snap, err, stale, histn, bx, by, iidx, jidx,
                 eidx, lrs, keep, rw, ew, cw, byz, atk_scale, z, skey,
                 gamma, tx, ty, *, adapter, tau: int, interpret: bool,
                 kind: str, k: int, ef: bool, screen: bool = False,
                 attack: str = ""):
    """Run K AD-PSGD rounds (K*N events) on device in one nested scan.

    The outer scan walks rounds, the inner scan the round's N events;
    the carry is the full asynchronous state the reference event loop
    keeps between dispatches: live parameter rows (``stacked``), the
    per-worker snapshots deltas are computed from (``snap``), the
    error-feedback residuals (``err``, [S, W, P] on compressed runs) and
    the per-worker staleness counters (``stale``, [S, W] i32). Batched
    over a leading seed axis S on (stacked, snap, err, stale, bx, by);
    the event schedule (iidx/jidx [K, N] and the global event indices
    eidx [K, N] — the rand-k mask step), learning rates, join masks,
    metric weights and the mask key ``skey`` are shared across seeds.

    The pairwise average runs through the Pallas ``gossip_mix_2d`` kernel
    on the 2-row slice (partner row as the single neighbor buffer,
    weight ½); compressed runs instead route the codec round trip of
    both rows through the Pallas kernels (int8 quantize/dequantize or
    the sparsify mask-and-pack, per the static ``kind``/``k``) and apply
    the compensated half-mix (``compression.compressed_pair_ref``).

    The lie-on-wire scenario axis rides the event scan when ``attack``
    names an attack kind: byzantine endpoints (``byz``, [W] bool shared
    across seeds) transmit a corrupted copy of their row
    (``core/robust.attack_row`` scaled by ``atk_scale``), and with
    ``screen`` on each endpoint z-tests the incoming payload against its
    own-delta-norm EMA (``histn``, [S, W] carried in the scan state,
    threshold ``z``) and keeps its self-model on rejection — the same
    accept/reject primitives the reference loop calls, so decisions
    match. Screening is data-plane only: event order, staleness and the
    clock are untouched. Self-events (i == j) have no wire. Attack-free
    screened exchanges reduce to the plain kernel average bit for bit
    (the payload-as-base half-mix below).

    Returns ((stacked', snap', err', stale', histn'), outs) where outs
    carries [S, K] metric trajectories (plus per-round screen-reject
    counts) and the [S, K, N] per-event staleness actually observed by
    the scan (host schedule replay must agree)."""
    compress = kind != "none"
    lying = screen or bool(attack)
    leaves = jax.tree.leaves(stacked)
    p_total = sum(int(np.prod(l.shape[2:])) for l in leaves)
    rows, cols = compression.flat_tile_shape(p_total)

    def one_seed(stacked, snap, err, stale, histn, bx, by):
        # the scan carries FLAT [W, P] matrices (params + snapshots): one
        # row scatter per event instead of one per pytree leaf; the
        # single-worker ``template`` pytree only supplies shapes for the
        # per-event unflatten around the SGD steps
        template = jax.tree.map(lambda l: l[0], stacked)
        flat0 = _flatten_workers(stacked)
        snap0 = _flatten_workers(snap)

        def half_mix(base, other):
            # 2-row slice through the gossip kernel: one neighbor
            # buffer, weight 1/2, so y = base + ½ (other - base) —
            # the atomic pairwise average
            pad = rows * cols - p_total
            b2d = jnp.pad(base, (0, pad)).reshape(rows, cols)
            u = jnp.pad(other, (0, pad)).reshape(1, rows, cols)
            y2d = gossip_mix_2d(b2d, u, jnp.full((1,), 0.5, jnp.float32),
                                interpret=interpret)
            return y2d.reshape(-1)[:p_total]

        def event_body(carry, xs):
            flat, snapf, err, stale, histn = carry
            i, j, bxe, bye, e_h, lr_h = xs
            p_snap = _unflatten_row(snapf[i], template)
            delta = _adpsgd_delta(adapter, p_snap, bxe, bye, lr_h, tau)
            dflat = _flatten_row(delta)
            xi = flat[i] + dflat
            xj = flat[j]
            nrej = jnp.int32(0)
            if compress:
                xi2, xj2, ei2, ej2 = compression.compressed_pair_ref(
                    xi, xj, err[i], err[j], error_feedback=ef,
                    kind=kind, k=k, key=skey, step=e_h, gamma=gamma,
                    use_kernel=True, interpret=interpret)
                err = err.at[i].set(ei2).at[j].set(ej2)
                flat = flat.at[i].set(xi2).at[j].set(xj2)
            elif lying:
                # lying wire: each endpoint receives the partner's
                # TRANSMITTED copy; screening keeps the self-model on
                # rejection. Both accepted rows are half-mixes with the
                # incoming payload as one operand — attack-free this is
                # literally the plain kernel average on both sides
                wire = i != j
                ti = robust_agg.attack_row(xi, byz[i] & wire, atk_scale,
                                           kind=attack or "signflip")
                tj = robust_agg.attack_row(xj, byz[j] & wire, atk_scale,
                                           kind=attack or "signflip")
                if screen:
                    h_i = robust_agg.screen_fold(histn[i],
                                                 jnp.linalg.norm(dflat))
                    histn = histn.at[i].set(h_i)
                    acc_i = ~wire | robust_agg.screen_accept(xi, tj, h_i, z)
                    acc_j = ~wire | robust_agg.screen_accept(xj, ti,
                                                             histn[j], z)
                    nrej = ((~acc_i).astype(jnp.int32)
                            + (~acc_j).astype(jnp.int32))
                else:
                    acc_i = acc_j = jnp.bool_(True)
                row_i = jnp.where(acc_i, half_mix(xi, tj), xi)
                row_j = jnp.where(acc_j, half_mix(ti, xj), xj)
                flat = flat.at[i].set(row_i).at[j].set(row_j)
            else:
                avg = half_mix(xi, xj)
                flat = flat.at[i].set(avg).at[j].set(avg)
            # fresh snapshot for i = its live row after the exchange
            snapf = snapf.at[i].set(flat[i])
            st_i = stale[i]
            stale = stale.at[i].set(0)
            stale = stale.at[j].add(jnp.where(j != i, 1, 0))
            return (flat, snapf, err, stale, histn), (st_i, nrej)

        def round_body(carry, xs):
            flat, snapf, err, stale, histn = carry
            bxh, byh, i_h, j_h, e_h, lr_h, keep_h, rw_h, ew_h, cw_h = xs
            # --- join re-init before the round's events: joined rows
            # adopt the donor average, get a fresh snapshot, and drop
            # residual + staleness + screening history (exact no-op when
            # keep_h is all-False)
            mean = jnp.tensordot(rw_h, flat, axes=1)
            flat = jnp.where(keep_h[:, None], mean[None], flat)
            snapf = jnp.where(keep_h[:, None], flat, snapf)
            if compress and compression.carries_state(kind, ef):
                # same reset as the reference: zeroed residual, or x̂
                # re-anchored at the (shared-knowledge) blended row
                err = compression.state_after_join(err, keep_h[:, None],
                                                   flat, kind, ef)
            stale = jnp.where(keep_h, 0, stale)
            histn = jnp.where(keep_h, 0.0, histn)

            lrs_ev = jnp.broadcast_to(lr_h, i_h.shape)
            (flat, snapf, err, stale, histn), (st, rej) = jax.lax.scan(
                event_body, (flat, snapf, err, stale, histn),
                (i_h, j_h, bxh, byh, e_h, lrs_ev))

            carry_tree = _unflatten(flat, stacked)
            accs = jax.vmap(lambda p: adapter.accuracy(p, tx, ty))(
                carry_tree)
            tloss = jax.vmap(
                lambda p: adapter.loss(p, {"x": tx, "y": ty}))(carry_tree)
            dmean = jnp.tensordot(cw_h, flat, axes=1)
            dists = jnp.sqrt(jnp.sum((flat - dmean[None]) ** 2, axis=1))
            outs = {"acc": jnp.dot(ew_h, accs),
                    "loss": jnp.dot(ew_h, tloss),
                    "consensus": jnp.dot(cw_h, dists),
                    "event_staleness": st,
                    "rejects": rej.sum()}
            return (flat, snapf, err, stale, histn), outs

        (flat, snapf, err, stale, histn), outs = jax.lax.scan(
            round_body, (flat0, snap0, err, stale, histn),
            (bx, by, iidx, jidx, eidx, lrs, keep, rw, ew, cw))
        return (_unflatten(flat, stacked), _unflatten(snapf, snap),
                err, stale, histn), outs

    return jax.vmap(one_seed, in_axes=(0, 0, 0, 0, 0, 0, 0))(
        stacked, snap, err, stale, histn, bx, by)


def run_adpsgd_fused(data: Dataset, test_x, test_y, shards,
                     cluster: SimCluster, cfg: FedHPConfig, *,
                     rounds: int | None = None, hidden: int = 64,
                     eval_subset: int = 512,
                     time_budget: float | None = None, seeds=None,
                     interpret: bool | None = None,
                     schedule: AdpsgdSchedule | None = None,
                     adapter: modelspec.ModelAdapter | None = None):
    """Drop-in fused replacement for ``engine.run_adpsgd``.

    The event-driven loop lowers to one ``jax.lax.scan`` per segment of
    ``ADPSGD_FUSE_ROUNDS`` rounds: the host precomputes the full event
    schedule (``engine.adpsgd_schedule`` — per-event worker, pairwise
    partner, event time, staleness; Eq. 10 event clock, compressed runs
    charging beta / wire_ratio) and the per-event batch tensors, then the
    device replays every event with the same per-event math as the
    reference loop — snapshot deltas, atomic pairwise averaging through
    the Pallas ``gossip_mix_2d`` kernel (or the compensated int8 exchange
    through the quantize kernels when ``cfg.compress == "int8"``), and
    staleness counters carried in the scan state.

    With ``seeds=None`` this matches ``run_adpsgd`` record for record
    (host fields, including ``staleness``, bit-identical; device
    trajectories to float tolerance — tests/test_fused_equivalence.py).
    With an array of ``seeds`` it returns ``list[History]``: all lanes
    share the cfg.seed-derived event schedule and cluster draws while the
    model init / batch streams come from each lane's seed (the lane whose
    seed equals ``cfg.seed`` reproduces the unbatched run exactly). Pass
    an explicit ``schedule`` to replay a custom event sequence verbatim
    (``rounds``/``time_budget`` are generation-time knobs).

    ``cfg.byzantine`` / ``cfg.robust="screen:<z>"`` replay the reference
    lying-wire exchange inside the event scan (same accept/reject
    primitives, ``core/robust.py``), with per-round reject counts in
    ``History.screen_rejects``; measurements mask attackers out exactly
    like ``run_adpsgd`` does."""
    rounds = rounds or cfg.rounds
    n = cfg.num_workers
    byz = robust_agg.byzantine_mask(cfg.byzantine, n)
    has_byz = bool(byz.any())
    robust_mode, screen_z = robust_agg.parse_robust(cfg.robust)
    if robust_mode in ("trimmed", "median"):
        raise ValueError(
            "trimmed/median robust gossip is synchronous-engine only "
            "(a 2-sample pairwise exchange has no trim window); AD-PSGD "
            "takes cfg.robust='screen:<z>'")
    screen = robust_mode == "screen"
    atk_kind, atk_scale = (robust_agg.parse_attack(cfg.byzantine_attack)
                           if has_byz else ("signflip", 1.0))
    batched = seeds is not None
    seed_list = ([int(s) for s in np.asarray(seeds).reshape(-1)]
                 if batched else [int(cfg.seed)])
    interp = (jax.default_backend() == "cpu") if interpret is None \
        else interpret
    codec = compression.parse_mode(cfg.compress)
    if codec.kind == "leafmap":
        raise ValueError(
            "per-leaf codec maps (compress='leafmap:...') are "
            "synchronous-engine only; AD-PSGD's pairwise exchange has no "
            "leafmap form yet")
    compress = codec.kind != "none"
    if (has_byz or screen) and compress:
        raise ValueError(
            "cfg.byzantine / cfg.robust do not compose with cfg.compress")
    if adapter is None:
        adapter = modelspec.adapter_for(cfg, data, hidden=hidden)
    skey = compression.sparsify_base_key(cfg.seed)  # rand-k mask stream
    if schedule is None:
        schedule = adpsgd_schedule(cluster, cfg, rounds=rounds,
                                   time_budget=time_budget,
                                   p_model=adapter.param_count)
    elif time_budget is not None:
        raise ValueError(
            "time_budget only applies while GENERATING a schedule; an "
            "explicit schedule= replays verbatim (apply the budget in "
            "adpsgd_schedule instead)")
    tau = schedule.tau

    rngs = [np.random.default_rng(s) for s in seed_list]
    stacked0 = []
    for s in seed_list:
        key = jax.random.PRNGKey(s)
        p0 = adapter.init(key)
        stacked0.append(jax.tree.map(
            lambda l: jnp.broadcast_to(l, (n,) + l.shape), p0))
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *stacked0)
    snap = stacked                       # snapshots start at the init rows
    k_abs = codec.resolve_k(adapter.param_count)
    # codec state rows, or a [S, W, 1] dummy for stateless runs (see
    # run_dfl_fused) — the stateless pair exchange returns its state
    # rows untouched, so the dummy shape survives the event scan
    err = (compression.state_init(
        jnp.stack([_flatten_workers(s) for s in stacked0]),
        codec.kind, cfg.error_feedback)
        if compress and compression.carries_state(codec.kind,
                                                  cfg.error_feedback)
        else jnp.zeros((len(seed_list), n, 1), jnp.float32))
    stale = jnp.zeros((len(seed_list), n), jnp.int32)
    histn = jnp.zeros((len(seed_list), n), jnp.float32)  # screening EMA
    tx = jnp.asarray(test_x[:eval_subset])
    ty = jnp.asarray(test_y[:eval_subset])

    counts = {len(r.events) for r in schedule.rounds}
    if len(counts) > 1:
        raise ValueError(
            f"fused AD-PSGD scans a rectangular [rounds, events] grid; "
            f"got rounds with differing event counts {sorted(counts)} "
            f"(generated schedules always have N events per round)")
    n_ev = counts.pop() if counts else 0

    hists = [History() for _ in seed_list]
    if screen:
        for hist in hists:
            hist.screen_rejects = []
    done = 0
    while done < len(schedule.rounds):
        seg = schedule.rounds[done:done + ADPSGD_FUSE_ROUNDS]
        iidx = np.array([[e.worker for e in r.events] for r in seg],
                        np.int32)
        jidx = np.array([[e.partner for e in r.events] for r in seg],
                        np.int32)
        # global event indices — the reference loop's per-event counter,
        # i.e. the rand-k mask step (every round has exactly n_ev events)
        eidx = (done * n_ev + np.arange(len(seg) * n_ev)).reshape(
            len(seg), n_ev).astype(np.int32)
        lrs = np.array([r.lr for r in seg], np.float32)
        keep = np.stack([r.keep for r in seg])
        rw = np.stack([r.donor_w for r in seg]).astype(np.float32)
        ew, cw = [], []
        for r in seg:
            a = r.alive
            # metrics describe the HONEST fleet (same mask as run_adpsgd)
            m = (a & ~byz) if has_byz and (a & ~byz).any() else a
            ew.append(m / m.sum() if m.any() and not m.all()
                      else np.full(n, 1.0 / n))
            cw.append(m / m.sum() if m.any() else np.full(n, 1.0 / n))
        # per-seed batch tensors in event order, replaying the reference
        # loop's batch-stream consumption draw for draw
        bx = np.zeros((len(seed_list), len(seg), n_ev, tau,
                       cfg.batch_size) + data.x.shape[1:], data.x.dtype)
        by = np.zeros((len(seed_list), len(seg), n_ev, tau,
                       cfg.batch_size), np.int32)
        for si, rng in enumerate(rngs):
            for t, r in enumerate(seg):
                round_shards = (shards.shards_at(done + t)
                                if hasattr(shards, "shards_at") else shards)
                for k, e in enumerate(r.events):
                    shard = round_shards[e.worker]
                    ix = rng.integers(0, len(shard), (tau, cfg.batch_size))
                    bx[si, t, k] = data.x[shard[ix]]
                    by[si, t, k] = data.y[shard[ix]]

        (stacked, snap, err, stale, histn), outs = _adpsgd_scan(
            stacked, snap, err, stale, histn,
            jnp.asarray(bx), jnp.asarray(by),
            jnp.asarray(iidx), jnp.asarray(jidx), jnp.asarray(eidx),
            jnp.asarray(lrs), jnp.asarray(keep), jnp.asarray(rw),
            jnp.asarray(np.stack(ew), dtype=jnp.float32),
            jnp.asarray(np.stack(cw), dtype=jnp.float32),
            jnp.asarray(byz), jnp.float32(atk_scale),
            jnp.float32(screen_z), skey, jnp.float32(cfg.sparse_gamma),
            tx, ty, adapter=adapter, tau=tau, interpret=interp,
            kind=codec.kind, k=k_abs, ef=cfg.error_feedback,
            screen=screen, attack=atk_kind if has_byz else "")
        outs = {k: np.asarray(v) for k, v in outs.items()}
        # the scan carries its own staleness counters; they must agree
        # with the host schedule replay event for event (the documented
        # invariant — a drifted join-reset or partner-increment rule in
        # either implementation fails every fused run immediately)
        sched_st = np.array([[e.staleness for e in r.events] for r in seg])
        if not np.array_equal(outs["event_staleness"][0], sched_st):
            raise AssertionError(
                "fused AD-PSGD scan staleness counters diverged from the "
                "host schedule replay (engine.adpsgd_schedule)")

        for t, r in enumerate(seg):
            for si, hist in enumerate(hists):
                hist.records.append(RoundRecord(
                    round=done + t, round_time=0.0, waiting_time=0.0,
                    accuracy=float(outs["acc"][si, t]),
                    loss=float(outs["loss"][si, t]),
                    mean_tau=float(tau), num_links=schedule.num_links,
                    consensus=float(outs["consensus"][si, t]),
                    cumulative_time=r.clock,
                    staleness=r.mean_staleness))
                if screen:
                    hist.screen_rejects.append(int(outs["rejects"][si, t]))
        done += len(seg)
    for si, hist in enumerate(hists):
        hist.final_params = jax.tree.map(lambda l, si=si: l[si], stacked)
    return hists if batched else hists[0]
