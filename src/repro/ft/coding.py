"""Coded checksum lanes: survive any ``f`` simultaneous lane deaths.

The paper's XOR buddy-pairing (``xor_buddy`` / ``pairing_table``, canonical
home here since the coding seam subsumes them) is one-level redundancy
doubling: every artifact exists on exactly two lanes, so a single death per
pair is recoverable from ONE survivor, but a whole pair dying at the same
sweep point is ``UnrecoverableFailure`` — the hard wall ROADMAP open item 2
names. This module generalizes the redundancy to MDS-coded checksum slots in
the ABFT checksum tradition (Bosilca et al. 2008; "Coded Computing for
Fault-Tolerant Parallel QR Decomposition", 2023): ``f`` parity slots encode
every protected ``SweepState`` leaf over a Vandermonde generator in GF(2^8),
so ANY ``t <= f`` simultaneously-dead lanes are jointly decodable from the
``P - t`` survivors plus the parity slots.

Bitwise exactness
-----------------
Checksums over *float arithmetic* cannot promise the repo's bitwise recovery
oracle (rounding in the encode/decode round trip). We therefore code over
the RAW BYTES: each protected leaf is bitcast to ``uint8``
(``jax.lax.bitcast_convert_type``), parity row ``j`` is
``P_j = XOR_i g[j,i] (x) B_i`` with GF(2^8) constant-multiplies (table
lookups), and decode solves the ``t x t`` GF Vandermonde system exactly
(integer Gaussian elimination on the host). GF arithmetic on bit patterns
is exact, and survivors do not change between the boundary encode and the
boundary decode, so the decoded bytes — hence the floats — are
bit-identical to the dead lanes' pre-death state.

Generator
---------
``g[j, i] = (alpha^i)^j`` for ``j = 0..f-1``, ``i = 0..P-1`` with ``alpha``
the primitive element of GF(2^8) (poly 0x11D) — a Vandermonde matrix on the
distinct nonzero points ``alpha^i`` (so ``P <= 255``). Row 0 is all-ones:
the ``f=1`` parity is the plain XOR checksum lane of the ABFT tradition.
Any ``t`` erased columns against the FIRST ``t`` rows form a standard
Vandermonde submatrix on distinct points, hence invertible — the MDS
property this scheme needs (decode always uses rows ``0..t-1``).

Hybrid rebuild rule (the f=1 == XOR argument)
---------------------------------------------
``MDSScheme`` only *augments* the paper's protocol, it never replaces the
single-death path: exactly one newly-dead lane is rebuilt by the existing
XOR-buddy REBUILD (``repro.ft.driver.rebuild_state``), preserving the
paper's single-source ledger property at every ``f`` — which makes
``MDSScheme(f=1)`` trivially bitwise-identical to ``XORPairScheme``
including the event ledgers (the one parity row is maintained but never
consumed). Only ``2 <= t <= f`` simultaneous deaths route to the joint GF
decode (multi-source ledger: all survivors + the parity slots). ``t > f``
falls back to the per-lane XOR loop — MDS is monotonically stronger than
XOR — and ``UnrecoverableFailure`` moves to the honest ``f+1``-deaths
boundary.

Checksum lifecycle
------------------
``scheme.refresh(comm, state)`` re-encodes the parity slots at every
interruptible boundary, *after* the sweep point executes and *before* fault
injection/detection runs, from live state — so a boundary decode sees
survivors exactly as encoded. Parities live in ``SweepState.code`` (a
pytree child; skip-axis ``-1`` in ``state_lane_axes``; excluded from the
host wire format, which stays version 1 — a resumed sweep re-encodes at its
first boundary). Joint decode of runtime-detected deaths assumes fail-stop
at boundaries: a lane silently poisoned mid-segment would contaminate the
boundary encode (the single-death late-detection path is unaffected — it
never reads parity).

Under ``AxisComm`` (traced scheduled SPMD) encode/decode are expressed with
``comm.xor_reduce`` (a bit-plane psum-mod-2 all-reduce), so the same scheme
object threads through ``repro.launch.spmd_qr``; the online SPMD path
encodes host-side on the global SimComm-layout state between shard_map
segments.
"""
from __future__ import annotations

import dataclasses
from typing import AbstractSet, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm import SimComm
from repro.ft.failures import UnrecoverableFailure


# -- GF(2^8) arithmetic (poly 0x11D) -----------------------------------------


_POLY = 0x11D


def _gf_tables():
    exp = np.zeros(510, np.int32)
    log = np.zeros(256, np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    exp[255:510] = exp[:255]
    return exp, log


GF_EXP, GF_LOG = _gf_tables()

# full 256x256 product table: one gather per constant-multiply under jit
_MUL = GF_EXP[np.add.outer(GF_LOG, GF_LOG)].astype(np.uint8)
_MUL[0, :] = 0
_MUL[:, 0] = 0


def gf_mul(a: int, b: int) -> int:
    return int(_MUL[a, b])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no GF(2^8) inverse")
    return int(GF_EXP[255 - GF_LOG[a]])


def generator(f: int, P: int) -> np.ndarray:
    """The (f, P) Vandermonde MDS generator: ``g[j, i] = (alpha^i)^j``.
    Row 0 is all-ones (plain XOR checksum); rows depend only on ``j``, so
    decode with ``t <= f`` rows uses the same coefficients regardless of
    ``f``."""
    if P > 255:
        raise ValueError(f"GF(2^8) coding supports at most 255 lanes, got {P}")
    j = np.arange(f)[:, None]
    i = np.arange(P)[None, :]
    return GF_EXP[(j * i) % 255].astype(np.uint8)


def gf_inv_matrix(M: np.ndarray) -> np.ndarray:
    """Exact GF(2^8) matrix inverse by Gaussian elimination (host side;
    the decode systems are tiny ``t x t`` Vandermonde submatrices, always
    invertible)."""
    M = np.asarray(M)
    t = M.shape[0]
    aug = np.concatenate([M.astype(np.int32),
                          np.eye(t, dtype=np.int32)], axis=1)
    for c in range(t):
        piv = c + int(np.nonzero(aug[c:, c])[0][0])
        aug[[c, piv]] = aug[[piv, c]]
        aug[c] = _MUL[gf_inv(int(aug[c, c])), aug[c]]
        for r in range(t):
            if r != c and aug[r, c]:
                aug[r] ^= _MUL[aug[r, c], aug[c]].astype(np.int32)
    return aug[:, t:].astype(np.uint8)


# -- the XOR pairing (paper SSIII-B/C), canonical home -----------------------


def xor_buddy(lane: int, level: int) -> int:
    """The XOR butterfly partner of ``lane`` at ``level`` — the single
    source every per-level artifact can be refetched from, and the
    designated adopter (level 0) when a SHRINK world re-owns a dead
    lane's rows (``repro.ft.elastic``)."""
    return lane ^ (1 << level)


def pairing_table(P: int):
    """The full ladder pairing of a ``P``-lane world: one ppermute
    permutation per butterfly level. An elastic transition never remaps
    pairs explicitly — it re-enters this table at the new world size, so
    the P-1-lane (padded-pow2) world's ladder is just ``pairing_table``
    of the new slot count (DESIGN.md SS11). The MDS generator remaps the
    same way: ``generator(f, P)`` is a pure function of the slot count,
    so a post-SHRINK world re-encodes over its own column set."""
    from repro.core.tsqr import _levels, _xor_perm

    return [_xor_perm(P, s) for s in range(_levels(P))]


# -- protected-leaf selection -------------------------------------------------


def _protected(state) -> List[Tuple[int, int]]:
    """``(flat_leaf_index, lane_axis)`` of every parity-protected leaf, in
    flattening order: float leaves with a lane axis — exactly what
    ``obliterate_state`` poisons. ``A0`` (the re-readable source, never
    poisoned) and the parity field itself (skip-axis ``-1``) are excluded.
    Works on ``jax.eval_shape`` structs too."""
    from repro.ft.online.state import state_lane_axes

    axes = state_lane_axes(state).replace(A0=-1)
    out = []
    leaves = jax.tree_util.tree_leaves(state)
    ax_leaves = jax.tree_util.tree_leaves(axes)
    for i, (x, ax) in enumerate(zip(leaves, ax_leaves)):
        if ax >= 0 and jnp.issubdtype(x.dtype, jnp.floating):
            out.append((i, ax))
    return out


def _bytes_of(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint8)


def _xor_axis0(x):
    return jax.lax.reduce(x, np.uint8(0), jax.lax.bitwise_xor, (0,))


# -- encode / decode bodies ---------------------------------------------------


def _encode_sim(state, G):
    """Parity tuple over the protected leaves, SimComm (global) layout:
    one ``(f, *byte_shape)`` uint8 array per protected leaf."""
    mul = jnp.asarray(_MUL)
    f, P = G.shape
    leaves = jax.tree_util.tree_leaves(state)
    out = []
    for i, ax in _protected(state):
        bl = jnp.moveaxis(_bytes_of(leaves[i]), ax, 0)  # (P, ...)
        rows = []
        for j in range(f):
            coef = G[j].reshape((P,) + (1,) * (bl.ndim - 1))
            rows.append(_xor_axis0(mul[coef, bl]))
        out.append(jnp.stack(rows))
    return tuple(out)


@jax.jit
def _encode_sim_jit(state, G):
    # cache key = (treedef, shapes): one compile per cursor, shared across
    # every run of the same geometry (the exhaustive kill matrices)
    return _encode_sim(state, G)


def _encode_axis(comm, state, G):
    """The same encode inside a traced AxisComm program: per-lane terms,
    reduced with the bit-plane XOR all-reduce. Every lane holds the
    (replicated) parity — layout-consistent with the no-lane-axis SimComm
    parity slot."""
    mul = jnp.asarray(_MUL)
    f, _P = G.shape
    idx = comm.axis_index()
    leaves = jax.tree_util.tree_leaves(state)
    out = []
    for i, _ax in _protected(state):
        b = _bytes_of(leaves[i])  # local: no lane axis
        rows = []
        for j in range(f):
            rows.append(comm.xor_reduce(mul[jnp.asarray(G[j])[idx], b]))
        out.append(jnp.stack(rows))
    return tuple(out)


def _decode_sim(state, live_mask, dead_idx, inv):
    """Joint reconstruction of ``t = dead_idx.shape[0]`` lanes' slices of
    every protected leaf, from the survivors (``live_mask``) and parity
    rows ``0..t-1`` of ``state.code``. All lane data is traced (the jit
    cache is shared across every dead-set of the same size at a cursor);
    only shapes are static."""
    mul = jnp.asarray(_MUL)
    P = live_mask.shape[0]
    t = dead_idx.shape[0]
    Gt = jnp.asarray(generator(t, P))
    leaves, treedef = jax.tree_util.tree_flatten(state)
    prot = _protected(state)
    code = state.code
    assert code is not None and len(code) == len(prot), (
        "parity slots out of step with the protected leaves")
    new = list(leaves)
    for parity, (i, ax) in zip(code, prot):
        bl = jnp.moveaxis(_bytes_of(leaves[i]), ax, 0)  # (P, ...)
        mask = live_mask.reshape((P,) + (1,) * (bl.ndim - 1))
        synd = []
        for j in range(t):
            coef = Gt[j].reshape((P,) + (1,) * (bl.ndim - 1))
            term = jnp.where(mask, mul[coef, bl], jnp.uint8(0))
            synd.append(parity[j] ^ _xor_axis0(term))
        xs = []
        for r in range(t):
            acc = mul[inv[r, 0], synd[0]]
            for j in range(1, t):
                acc = acc ^ mul[inv[r, j], synd[j]]
            xs.append(acc)
        bl = bl.at[dead_idx].set(jnp.stack(xs))
        new[i] = jax.lax.bitcast_convert_type(
            jnp.moveaxis(bl, 0, ax), leaves[i].dtype)
    return jax.tree_util.tree_unflatten(treedef, new)


_decode_sim_jit = jax.jit(_decode_sim)


def _decode_axis(comm, state, newly: Sequence[int], dead: AbstractSet[int],
                 inv: np.ndarray):
    """The joint decode inside a traced AxisComm program (static dead set:
    schedules are trace-time data on the scheduled SPMD path)."""
    mul = jnp.asarray(_MUL)
    P = comm.axis_size()
    t = len(newly)
    Gt = generator(t, P)
    idx = comm.axis_index()
    own_dead = jnp.zeros_like(idx, dtype=bool)
    for d in sorted(dead):
        own_dead = own_dead | (idx == d)
    leaves, treedef = jax.tree_util.tree_flatten(state)
    prot = _protected(state)
    code = state.code
    assert code is not None and len(code) == len(prot)
    new = list(leaves)
    for parity, (i, _ax) in zip(code, prot):
        b = _bytes_of(leaves[i])
        synd = []
        for j in range(t):
            term = mul[jnp.asarray(Gt[j])[idx], b]
            term = comm.where(own_dead, jnp.zeros_like(term), term)
            synd.append(parity[j] ^ comm.xor_reduce(term))
        for r, d in enumerate(sorted(newly)):
            acc = mul[int(inv[r, 0]), synd[0]]
            for j in range(1, t):
                acc = acc ^ mul[int(inv[r, j]), synd[j]]
            b = comm.where(idx == d, acc, b)
        new[i] = jax.lax.bitcast_convert_type(b, leaves[i].dtype)
    return jax.tree_util.tree_unflatten(treedef, new)


# -- the schemes --------------------------------------------------------------


class CodingScheme:
    """The redundancy seam of the FT stack.

    ``f``        guaranteed number of simultaneous deaths recoverable;
    ``joint``    whether ``decode_lanes`` exists (multi-death GF decode);
    ``refresh``  re-encode the parity slots at an interruptible boundary
                 (identity for pure XOR pairing: its redundancy is the pair
                 mirroring already inside the sweep arithmetic);
    ``decode_lanes``  jointly reconstruct all newly-dead lanes, returning
                 ``(state, reads)`` with the multi-source decode ledger.

    ``recover_lanes`` (``repro.ft.driver``) consults the scheme: one newly
    dead lane always takes the paper's single-source XOR REBUILD; ``2 <= t
    <= f`` takes ``decode_lanes``; ``t > f`` falls back to the per-lane XOR
    loop (best effort) and an exhausted fallback raises
    ``UnrecoverableFailure`` at the f+1-deaths boundary."""

    name = "base"
    f = 0
    joint = False

    def refresh(self, comm, state):
        return state

    def decode_lanes(self, comm, state, newly, dead):
        raise UnrecoverableFailure(
            f"scheme {self.name!r} cannot jointly decode {sorted(newly)}")


class XORPairScheme(CodingScheme):
    """The paper's scheme, as a (stateless) instance of the seam: pairwise
    XOR-buddy redundancy, single-source REBUILD, f=1 per pair. The bitwise
    differential oracle every other scheme is gated against."""

    name = "xor"
    f = 1
    joint = False


@dataclasses.dataclass(frozen=True)
class MDSScheme(CodingScheme):
    """Vandermonde GF(2^8) MDS checksum slots tolerating any ``f``
    simultaneous deaths (module docstring has the construction and the
    exactness argument). ``f`` is the config knob traded against the
    per-boundary encode overhead."""

    f: int = 2
    name = "mds"
    joint = True

    def __post_init__(self):
        if not 1 <= self.f <= 8:
            raise ValueError(f"MDS redundancy f={self.f} out of range [1, 8]")

    def refresh(self, comm, state):
        P = comm.axis_size()
        G = jnp.asarray(generator(self.f, P))
        if isinstance(comm, SimComm):
            code = _encode_sim_jit(state.replace(code=None), G)
        else:
            code = _encode_axis(comm, state.replace(code=None), G)
        return state.replace(code=code)

    def decode_lanes(self, comm, state, newly, dead
                     ) -> Tuple[object, Dict[str, int]]:
        newly = sorted(newly)
        t = len(newly)
        P = comm.axis_size()
        if t > self.f:
            raise UnrecoverableFailure(
                f"{t} simultaneous deaths exceed MDS tolerance f={self.f}")
        if state.code is None:
            raise UnrecoverableFailure(
                "no parity slots encoded yet (death before the first "
                "boundary refresh)")
        inv = gf_inv_matrix(generator(t, P)[:, newly])
        if isinstance(comm, SimComm):
            live = np.ones(P, bool)
            live[sorted(dead)] = False
            state = _decode_sim_jit(
                state, jnp.asarray(live), jnp.asarray(newly, jnp.int32),
                jnp.asarray(inv))
        else:
            live = np.ones(P, bool)
            live[sorted(dead)] = False
            state = _decode_axis(comm, state, newly, dead, inv)
        reads: Dict[str, int] = {
            f"coded.parity{j}": P + j for j in range(t)}
        for i in range(P):
            if live[i]:
                reads[f"coded.survivor{i}"] = i
        return state, reads
