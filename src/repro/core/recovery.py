"""Failure injection and single-source recovery (paper §II, §III-B/C).

The paper's recovery model (REBUILD semantics): a failed process is respawned
with the same rank and its state is reconstructed from

  * its own slice of the *initial* matrix (re-read from the data source), and
  * the recovery bundle held by exactly ONE surviving process — its buddy at
    the current tree level: {W, T, C'_failed, Y2, role}.

The reconstruction is ``C_hat_failed = C'_failed - Y_failed @ W`` where
``Y_failed = I`` if the failed lane was the top block of its pair and ``Y2``
otherwise (paper §III-C bullet list).

This module executes the FT trailing update level by level in SimComm mode so
tests can kill a lane at any level, run the paper's recovery, resume, and
compare against the failure-free run. The level-stepping code calls the same
``_combine`` the production path uses.

These per-artifact reconstruction primitives (``recompute_leaf``,
``rebuild_cprime_after_level``, ``rebuild_block_row_through_panel``) are the
recompute seams every REBUILD path routes through: the scheduled driver and
the online orchestrator (``repro.ft.driver.rebuild_state``, shared by
``repro.ft.online``) both express a full mid-sweep rebuild as compositions
of exactly these calls plus single-source ``fetch_lane`` reads.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.comm import SimComm
from repro.core.householder import apply_qt, householder_qr_masked, mm
from repro.core.trailing import _combine
from repro.core.tsqr import DistTSQRFactors, _levels, _xor_perm, ft_tsqr


class LaneState(NamedTuple):
    """Per-lane trailing-update state between tree levels (SimComm layout:
    leading lane axis)."""

    C_local: jax.Array  # (P, m_loc, n) full block-rows (leaf-updated)
    C_prime: jax.Array  # (P, b, n) current C' per lane
    level: int


class LevelBundle(NamedTuple):
    """Recovery bundle each lane stores after completing a level (Alg. 2)."""

    W: jax.Array        # (P, b, n)
    C_buddy: jax.Array  # (P, b, n)  the buddy's C' entering the level
    Y2: jax.Array       # (P, b, b)
    T: jax.Array        # (P, b, b)
    buddy_was_top: jax.Array  # (P,) bool


def trailing_begin(
    C_stacked: jax.Array, factors: DistTSQRFactors, comm: SimComm
) -> LaneState:
    """Leaf Q^T apply; C' = top-b rows (single-panel, paper setting)."""
    b = factors.R.shape[-1]
    C_local = jax.vmap(apply_qt)(factors.leaf_Y, factors.leaf_T, C_stacked)
    return LaneState(C_local=C_local, C_prime=C_local[:, :b], level=0)


def trailing_level(
    state: LaneState,
    factors: DistTSQRFactors,
    comm: SimComm,
    target: Optional[int] = None,
) -> Tuple[LaneState, LevelBundle]:
    """Execute one tree level of Algorithm 2 on all lanes."""
    P = comm.axis_size()
    if target is None:
        target = P - 1
    step = state.level
    idx = comm.axis_index()
    C_prime = state.C_prime
    C_buddy = comm.ppermute(C_prime, _xor_perm(P, step))
    tbit = (target >> step) & 1
    is_top = ((idx >> step) & 1) == tbit
    C_top = comm.where(is_top, C_prime, C_buddy)
    C_bot = comm.where(is_top, C_buddy, C_prime)
    Y2 = factors.level_Y2[step]
    T = factors.level_T[step]
    new_top, new_bot, W = _combine(Y2, T, C_top, C_bot)
    C_next = comm.where(is_top, new_top, new_bot)
    bundle = LevelBundle(
        W=W, C_buddy=C_buddy, Y2=Y2, T=T, buddy_was_top=~is_top
    )
    return LaneState(state.C_local, C_next, step + 1), bundle


def trailing_finish(state: LaneState) -> jax.Array:
    b = state.C_prime.shape[-2]
    return state.C_local.at[:, :b].set(state.C_prime)


def kill_lane(state: LaneState, lane: int) -> LaneState:
    """Simulate process death: the lane's state is obliterated."""
    return LaneState(
        C_local=state.C_local.at[lane].set(jnp.nan),
        C_prime=state.C_prime.at[lane].set(jnp.nan),
        level=state.level,
    )


def recover_cprime(
    bundle: LevelBundle, failed: int, source: int
) -> jax.Array:
    """Paper §III-C recovery: rebuild the failed lane's post-level C' from
    the bundle of ONE surviving lane (its buddy at that level).

    C_hat = C'_failed - Y_failed @ W, with Y_failed = I if the failed lane
    was the top block of the pair, Y2 otherwise. Reads ONLY `bundle[source]`.
    """
    W = bundle.W[source]
    C_failed = bundle.C_buddy[source]  # buddy's (== failed lane's) entry C'
    failed_was_top = bundle.buddy_was_top[source]
    Y2 = bundle.Y2[source]
    top_update = C_failed - W
    bot_update = C_failed - mm(Y2, W)
    return jnp.where(failed_was_top, top_update, bot_update)


def recover_lane_local(
    A_slice: jax.Array, factors_leaf_Y: jax.Array, factors_leaf_T: jax.Array
) -> jax.Array:
    """Rebuild the failed lane's full leaf-updated block-row from its slice
    of the INITIAL matrix (re-read from the data source) + its leaf factors
    (recomputable from the same slice; here we reuse the stored ones)."""
    return apply_qt(factors_leaf_Y, factors_leaf_T, A_slice)


def inject_and_recover(
    state: LaneState,
    bundle: LevelBundle,
    failed: int,
    A_slice: jax.Array,
    factors: DistTSQRFactors,
) -> Tuple[LaneState, int]:
    """Kill `failed` after a level, then run the paper's REBUILD recovery.

    Returns the repaired state and the single source lane that was read.
    The source is the XOR-buddy of the failed lane at the completed level
    (level state.level - 1); by the doubling-redundancy property any of the
    2^level lanes of the failed lane's redundancy group would do — we use
    exactly one, which is the paper's headline claim.
    """
    assert state.level >= 1, "leaf-level failure is handled by recompute"
    dead = kill_lane(state, failed)
    source = failed ^ (1 << (state.level - 1))
    # (1) local rows: re-read input slice, re-apply local reflectors
    C_local_rebuilt = recover_lane_local(
        A_slice, factors.leaf_Y[failed], factors.leaf_T[failed]
    )
    # (2) C': one fetch from the single source lane's bundle
    C_prime_rebuilt = recover_cprime(bundle, failed, source)
    repaired = LaneState(
        C_local=dead.C_local.at[failed].set(C_local_rebuilt),
        C_prime=dead.C_prime.at[failed].set(C_prime_rebuilt),
        level=dead.level,
    )
    return repaired, source


# ---------------------------------------------------------------------------
# Sweep-level single-source reconstruction primitives.
#
# These are the per-artifact REBUILD formulas the FT sweep driver
# (``repro.ft.driver``) applies when a lane dies mid-sweep. Each function
# receives ONLY the respawned lane's own re-read data plus the state of ONE
# surviving lane (its buddy at the relevant tree level) — the single-source
# property is enforced by the signatures, not by convention. All recompute
# routes through the same kernel-dispatch seam as the failure-free path
# (``householder_qr_masked`` / ``apply_qt`` / ``_combine``), so the rebuilt
# values are bit-identical to what the dead lane would have computed.
#
# Ragged/wide geometry: the driver runs (and re-reads) at the *padded*
# ``caqr.sweep_geometry`` shape, so every argument here — rows, col0,
# row_start, panel slices — is already padded-space data. Zero pad
# rows/columns flow through these formulas exactly like any other rows
# (they are plain floats that happen to be zero), which is why recovery
# stays single-source on general shapes with no extra bookkeeping.
# ---------------------------------------------------------------------------


def recompute_leaf(
    rows: jax.Array, col0: int, b: int, row_start: int, active: bool
):
    """Recompute a respawned lane's masked leaf panel factors from its own
    rebuilt block-row (paper: leaf state is never fetched — it is recomputed
    from the re-read initial data). Returns ``(leaf_Y, leaf_T, R_leaf)`` with
    the sweep's inactive-lane masking applied."""
    if not active:
        m_loc = rows.shape[0]
        z = jnp.zeros((b, b), rows.dtype)
        return jnp.zeros((m_loc, b), rows.dtype), z, z
    wy = householder_qr_masked(
        rows[:, col0:col0 + b], jnp.asarray(row_start, jnp.int32)
    )
    return wy.Y, wy.T, wy.R


def rebuild_cprime_after_level(
    C_fail_entering: jax.Array,
    C_source_entering: jax.Array,
    Y2: jax.Array,
    T: jax.Array,
    failed_was_top: bool,
    pair_live: bool,
) -> jax.Array:
    """Paper §III-C REBUILD: the failed lane's C' *after* a tree level, from
    the bundle of its buddy at that level (the single source).

    The source's bundle holds both pair inputs (its own C' and the exchanged
    copy of the failed lane's), so the recovery replays the exact pair
    combine through ``_combine`` — the same seam-routed computation the level
    originally ran — and keeps the failed lane's side. ``pair_live=False``
    (a pair with a fully-consumed member) is the sweep's per-lane
    pass-through. ``failed_was_top`` is static role data (derived from lane
    index and tree target), the paper's ``role`` bundle field.
    """
    if not pair_live:
        return C_fail_entering
    C_top = C_fail_entering if failed_was_top else C_source_entering
    C_bot = C_source_entering if failed_was_top else C_fail_entering
    new_top, new_bot, _W = _combine(Y2, T, C_top, C_bot)
    return new_top if failed_was_top else new_bot


def rebuild_block_row_through_panel(
    rows: jax.Array,
    leaf_Y: jax.Array,
    leaf_T: jax.Array,
    C_prime_final: jax.Array,
    col0: int,
    row_start: int,
    active: bool,
) -> jax.Array:
    """Advance a respawned lane's block-row through one completed panel:
    re-apply the (recomputed) leaf reflectors to the live window and write
    back the recovered final C' — the replay analogue of the sweep's
    leaf-apply + writeback. ``C_prime_final`` comes from ONE survivor via
    ``rebuild_cprime_after_level`` at the tree's last level."""
    window = apply_qt(leaf_Y, leaf_T, rows[:, col0:])
    if active:
        window = window.at[row_start:row_start + C_prime_final.shape[0]].set(
            C_prime_final
        )
    return jnp.concatenate([rows[:, :col0], window], axis=1)


# The XOR pairing moved to the coding seam (repro.ft.coding): XORPairScheme
# is the f=1 instance of the generalized redundancy, and xor_buddy /
# pairing_table are its pairing algebra. Re-exported here for the existing
# import sites (tests, elastic docs); the definitions are identical.
from repro.ft.coding import pairing_table, xor_buddy  # noqa: E402,F401


def tsqr_recover_r(factors: DistTSQRFactors, failed: int, source: int) -> jax.Array:
    """FT-TSQR recovery (§III-B): the restarted lane obtains R from any
    single member of its redundancy group — R is bit-identical there."""
    return factors.R[source]


def run_ft_trailing(
    C_stacked: jax.Array,
    factors: DistTSQRFactors,
    comm: SimComm,
    fail_at_level: Optional[int] = None,
    failed_lane: int = 0,
    A_stacked: Optional[jax.Array] = None,
):
    """Drive the level machine end to end, optionally killing + recovering
    one lane after ``fail_at_level`` completes. Returns the updated matrix."""
    P = comm.axis_size()
    levels = _levels(P)
    state = trailing_begin(C_stacked, factors, comm)
    for lvl in range(levels):
        state, bundle = trailing_level(state, factors, comm)
        if fail_at_level is not None and lvl == fail_at_level:
            assert A_stacked is not None
            state, _src = inject_and_recover(
                state, bundle, failed_lane, A_stacked[failed_lane], factors
            )
    return trailing_finish(state)
