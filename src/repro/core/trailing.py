"""Trailing-matrix update trees (paper §III-C, Algorithms 1 and 2).

After a panel's TSQR, the implicit ``Q^T`` is applied to the trailing columns
through the same tree the R factors were reduced on:

* leaf: each lane applies its local WY reflectors to its block-row;
* per level: the buddy pair combines the top-b rows ``C'`` of their active
  blocks through the stacked (Y2, T) factors of that level:
      W      = T^T (C'_top + Y2^T C'_bot)
      C'_top = C'_top - W            (top block's Y is the identity)
      C'_bot = C'_bot - Y2 W

``trailing_update_baseline``  — Algorithm 1: one-directional tree. The odd
lane sends C', the even lane computes T and W, sends W back; each updates its
own block. Half the lanes retire per level; no redundancy is created.

``trailing_update_ft``        — Algorithm 2: the pair *exchanges* C' in a
single sendrecv (ppermute both ways), BOTH compute W redundantly, and both
keep the bundle {W, T, C'_self, C'_buddy, Y2} — the recovery invariant: a
failed lane's output is ``C'_failed - Y_failed @ W``, computable from ONE
surviving buddy (Y_failed = I if the buddy was the top block, Y2 otherwise).

Note: the paper's Algorithm 2 exchanges ``C' + Y`` because it presents the
trailing tree standalone. Under FT-TSQR both lanes of a pair already hold
identical (Y2, T) from the panel reduction, so only C' needs to travel —
a (b x b) per-level saving we record as an enabled-by-FT-TSQR optimization.

Both functions are SPMD programs over a Comm (see ``repro.core.comm``) and
consume the combine factors produced by the matching TSQR variant.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.householder import apply_qt, mm
from repro.core.tsqr import DistTSQRFactors, _levels, _xor_perm


class RecoveryBundle(NamedTuple):
    """What each lane retains per tree level under Algorithm 2.

    Enough to rebuild the buddy's update from this lane alone:
    ``C_hat_buddy = C_buddy - Y_buddy @ W`` where ``Y_buddy`` is ``I`` if the
    buddy was the top lane of the pair and ``Y2`` if it was the bottom.
    All arrays carry a leading ``levels`` axis (in SimComm additionally a
    lane axis right after it).
    """

    W: jax.Array        # (L, b, n) the shared W of each level
    C_self: jax.Array   # (L, b, n) this lane's C' entering each level
    C_buddy: jax.Array  # (L, b, n) the buddy's C' received at each level
    Y2: jax.Array       # (L, b, b) the level's structured Householder block
    T: jax.Array        # (L, b, b) the level's T factor
    self_was_top: jax.Array  # (L,) bool: was this lane the top of its pair


def _combine(Y2, T, C_top, C_bot):
    """Paper's W-form combine. Per-lane (2-D) calls go through the
    kernel-dispatch seam (``stacked_apply_qt``): the fused trailing-combine
    kernel where the policy dispatches, the pure-jnp form elsewhere.

    Lane-stacked SimComm arrays run the batched jnp form — unless the
    kernel dispatch is on (TPU), where they are vmapped through the
    per-lane seam instead: the REBUILD replay (``core.recovery``) combines
    per lane under ``map_local``, and a replayed combine must be the same
    floating-point program as the one it replaces.
    """
    if Y2.ndim == 2:
        from repro.core.householder import StackedQR, stacked_apply_qt

        return stacked_apply_qt(StackedQR(Y2=Y2, T=T, R=T), C_top, C_bot)
    from repro.kernels import backend

    if backend.dispatch_enabled():
        return jax.vmap(_combine)(Y2, T, C_top, C_bot)
    W = mm(T.mT, C_top + mm(Y2.mT, C_bot))
    return C_top - W, C_bot - mm(Y2, W), W


class TrailingLevelStep(NamedTuple):
    """Output of one trailing-combine level: the advanced C' plus this
    level's slice of the recovery bundle (what each lane must retain)."""

    C_prime: jax.Array  # (b, n) advanced C' per lane
    W: jax.Array        # (b, n) the level's shared W (pair_live-masked)
    C_self: jax.Array   # (b, n) this lane's C' entering the level
    C_buddy: jax.Array  # (b, n) the buddy's C' received at the level
    is_top: jax.Array   # ()    was this lane the top of its pair


def trailing_combine_level(
    comm,
    C_prime: jax.Array,
    Y2: jax.Array,
    T: jax.Array,
    step: int,
    target,
    dead_threshold,
    paper_semantics: bool = False,
) -> TrailingLevelStep:
    """One tree level of Algorithm 2's trailing update.

    The pair exchanges C' in a single sendrecv, BOTH lanes compute the
    T-dependent W redundantly (paper Alg. 2 lines 5/14 and 9/18), and each
    keeps the level's recovery bundle slice. Zeroed (Y2, T) make the combine
    a pass-through; a pair with a dead member passes through per lane.

    The whole-tree ``trailing_update_ft`` loops over this function, and the
    level-stepped FT sweep driver (``repro.ft.driver``) interleaves it with
    failure checkpoints — both paths run the same floating-point program.
    """
    P = comm.axis_size()
    idx = comm.axis_index()
    # sendrecv: one bidirectional collective-permute — the paper's
    # exchange; on full-duplex links this costs one one-way hop.
    C_buddy = comm.ppermute(C_prime, _xor_perm(P, step))
    tbit = (target >> step) & 1
    is_top = ((idx >> step) & 1) == tbit
    C_top = comm.where(is_top, C_prime, C_buddy)
    C_bot = comm.where(is_top, C_buddy, C_prime)
    new_top, new_bot, W = _combine(Y2, T, C_top, C_bot)
    # Per-lane pass-through: a pair with a dead member does not combine.
    buddy_idx = idx ^ (1 << step)
    pair_live = jnp.logical_and(
        idx >= dead_threshold, buddy_idx >= dead_threshold
    )
    if paper_semantics:
        # Alg. 2 verbatim: only lanes that survived all earlier levels
        # (low bits zero) participate; the top lane retires afterwards.
        participates = (idx % (1 << step)) == 0
        pair_live = jnp.logical_and(pair_live, participates)
    W = comm.where(pair_live, W, jnp.zeros_like(W))
    C_next = comm.where(is_top, new_top, new_bot)
    C_next = comm.where(pair_live, C_next, C_prime)
    return TrailingLevelStep(
        C_prime=C_next, W=W, C_self=C_prime, C_buddy=C_buddy, is_top=is_top
    )


def _leaf_apply(comm, factors: DistTSQRFactors, C_local, row_start,
                active=None, skip_consumed: bool = False):
    """Local Q^T apply + extract the C' block at each lane's row_start.

    ``skip_consumed``: lanes with ``active == False`` are fully consumed by
    the sweep — their leaf Y is all zeros and the apply is the identity.
    Under ``lax.cond`` the SPMD (shard_map) execution skips the dead lanes'
    leaf GEMMs at runtime; the branch outputs are bit-identical to running
    the zero-Y apply, so results do not depend on the flag. (SimComm's vmap
    lowers the cond to a select and computes both — it is a simulator.)
    """
    b = comm.local_shape(factors.R)[-1]

    def leaf(Y, T, C, rs):
        C2 = apply_qt(Y, T, C)
        Cp = jax.lax.dynamic_slice_in_dim(C2, rs, b, axis=0)
        return C2, Cp

    # a batched comm's vmap would lower the cond to a select computing BOTH
    # branches — strictly more work, identical results — so the skip only
    # engages on comms with one lane per device.
    if not skip_consumed or active is None or comm.batched:
        return comm.map_local(leaf)(
            factors.leaf_Y, factors.leaf_T, C_local, row_start
        )

    def leaf_or_skip(Y, T, C, rs, act):
        return jax.lax.cond(
            act,
            lambda: leaf(Y, T, C, rs),
            lambda: (C, jax.lax.dynamic_slice_in_dim(C, rs, b, axis=0)),
        )

    return comm.map_local(leaf_or_skip)(
        factors.leaf_Y, factors.leaf_T, C_local, row_start, active
    )


def _writeback(comm, C_local, C_prime, row_start, active):
    def wb(C, Cp, rs, act):
        blk = jax.lax.dynamic_slice_in_dim(C, rs, Cp.shape[0], axis=0)
        new = jnp.where(act, Cp, blk)
        return jax.lax.dynamic_update_slice_in_dim(C, new, rs, axis=0)

    return comm.map_local(wb)(C_local, C_prime, row_start, active)


def trailing_update_ft(
    C_local: jax.Array,
    factors: DistTSQRFactors,
    comm,
    target=None,
    row_start=None,
    active=None,
    dead_threshold=None,
    paper_semantics: bool = False,
):
    """Algorithm 2: fault-tolerant trailing update.

    C_local: (m_loc, n) this lane's block-row of the trailing matrix.
    factors: the panel's FT-TSQR factors (leaf WY + per-level Y2/T; zeroed
        levels encode pass-throughs, e.g. consumed lanes in a CAQR sweep).
    target: root lane of the tree orientation (default P-1, the paper's
        odd-on-top convention). Must match the TSQR call.
    row_start: per-lane row offset of the C' block (default 0).
    active: per-lane participation flag (default all active).
    dead_threshold: lanes < this are fully consumed (CAQR sweep). A pair
        with a dead member passes through *per lane* — a live lane must not
        mix its residual slot with a dead lane's phantom zeros (the R-side
        group masking is coarser and cannot express this).
    paper_semantics: True = the paper's exact Algorithm 2, where the
        sender lane RETIRES after its level (line 11's ``return``) and
        non-participants idle — per-lane outputs then equal Algorithm 1
        exactly (tested). Use with factors built at target=0 (receiver-on-
        top stacking, the classical survivor chain) and pass target=0 here.
        False (default) = the full-butterfly generalization: every lane
        keeps combining at every level, which leaves every lane a recovery
        bundle for *every* level (strictly more redundancy) and replicated
        tree state — this is the variant the CAQR sweep uses. Both are
        valid orthogonal reductions.

    Factors built on zero-padded lanes (``ft_tsqr`` with short lanes, or a
    ragged ``sweep_geometry``) carry more leaf rows than a caller's raw
    C_local: C is zero-row-padded here to conform, and the *padded* layout
    is returned — the C' deposit of the tree root may land on pad rows, so
    slicing them off would lose it. Aligned callers are untouched.

    Returns (updated block-row, per-level recovery bundles, final C').
    """
    P = comm.axis_size()
    levels = _levels(P)
    idx = comm.axis_index()
    b = comm.local_shape(factors.R)[-1]
    m_fac = comm.local_shape(factors.leaf_Y)[0]
    m_c = comm.local_shape(C_local)[0]
    if m_c != m_fac:
        assert m_c < m_fac, (m_c, m_fac)
        C_local = comm.map_local(
            lambda x: jnp.pad(x, ((0, m_fac - m_c), (0, 0)))
        )(C_local)
    if target is None:
        target = jnp.asarray(P - 1)
    if row_start is None:
        row_start = idx * 0
    if active is None:
        active = idx >= 0
    if dead_threshold is None:
        dead_threshold = jnp.zeros((), jnp.int32)

    C_local, C_prime = _leaf_apply(comm, factors, C_local, row_start,
                                   active=active)
    C_prime = comm.where(active, C_prime, jnp.zeros_like(C_prime))

    Ws, Cs_self, Cs_buddy, tops = [], [], [], []
    for step in range(levels):
        out = trailing_combine_level(
            comm, C_prime, factors.level_Y2[step], factors.level_T[step],
            step, target, dead_threshold, paper_semantics=paper_semantics,
        )
        Ws.append(out.W)
        Cs_self.append(out.C_self)
        Cs_buddy.append(out.C_buddy)
        tops.append(out.is_top)
        C_prime = out.C_prime

    C_out = _writeback(comm, C_local, C_prime, row_start, active)

    if levels:
        bundle = RecoveryBundle(
            W=jnp.stack(Ws),
            C_self=jnp.stack(Cs_self),
            C_buddy=jnp.stack(Cs_buddy),
            Y2=factors.level_Y2,
            T=factors.level_T,
            self_was_top=jnp.stack(tops),
        )
    else:
        zshape = (0,) + tuple(jnp.shape(C_prime))
        zb = (0,) + tuple(jnp.shape(factors.R))
        bundle = RecoveryBundle(
            jnp.zeros(zshape, C_prime.dtype),
            jnp.zeros(zshape, C_prime.dtype),
            jnp.zeros(zshape, C_prime.dtype),
            jnp.zeros(zb, C_prime.dtype),
            jnp.zeros(zb, C_prime.dtype),
            jnp.zeros((0,) + tuple(jnp.shape(idx)), jnp.bool_),
        )
    return C_out, bundle, C_prime


def trailing_update_baseline(
    C_local: jax.Array,
    factors: DistTSQRFactors,
    comm,
) -> jax.Array:
    """Algorithm 1: one-directional trailing update tree (paper baseline).

    At level s the odd lane of each pair sends its C' up, the even lane
    computes W and sends it back; the odd lane then retires from the tree.
    No redundancy is created — a failure loses state that only the dead lane
    held. Kept for overhead comparison against Algorithm 2. Uses the paper's
    fixed odd-on-top orientation (target = P-1); single-panel use.
    """
    P = comm.axis_size()
    levels = _levels(P)
    idx = comm.axis_index()
    row_start = idx * 0

    C_local, C_prime = _leaf_apply(comm, factors, C_local, row_start)

    for step in range(levels):
        stride = 1 << step
        group = 1 << (step + 1)
        # odd -> even: C' travels up the tree (Alg. 1 line 7 / 16)
        up = [(i, i - stride) for i in range(P) if i % group == stride]
        C_from_odd = comm.ppermute(C_prime, up)
        is_even = (idx % group) == 0
        Y2 = factors.level_Y2[step]
        T = factors.level_T[step]
        # Receiver (even, the survivor) is the TOP/identity block: it keeps
        # C'_own - W, so the R-slot content stays with the survivor chain.
        # W = T^T (C'_own + Y2^T C'_odd)   (paper Alg. 1 line 17, with the
        # receiver-on-top stacking that makes the slot bookkeeping close).
        even_new, _, W = _combine(Y2, T, C_prime, C_from_odd)
        # even -> odd: the sender's update product V = Y2 @ W travels back
        # (same b x n wire bytes as the paper's W; the paper has the sender
        # apply its own "Y_0" to W, but the stacked Y2 is not computable from
        # the sender's R alone — shipping V resolves this; adaptation noted
        # in DESIGN.md).
        V = mm(Y2, W)
        down = [(i - stride, i) for i in range(P) if i % group == stride]
        V_from_even = comm.ppermute(V, down)
        is_odd = (idx % group) == stride
        odd_update = C_prime - V_from_even
        C_prime = comm.where(
            is_even, even_new, comm.where(is_odd, odd_update, C_prime)
        )

    active = idx >= 0
    return _writeback(comm, C_local, C_prime, row_start, active)
