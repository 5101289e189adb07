"""Communication abstraction: one SPMD code path, three executions.

The paper's algorithms are written as per-process (per-lane) SPMD programs
with pairwise exchanges. We express them once against this small ``Comm``
interface and run them three ways:

* ``AxisComm``  — inside ``jax.shard_map`` over a named mesh axis, one lane
  per device; collectives lower to real ICI ``collective-permute`` /
  ``all-reduce`` ops (the scheduled SPMD path and the dry-run path).

* ``MeshComm``  — inside ``jax.shard_map`` over a named mesh axis of chips,
  ``L`` lanes per chip: the two-level comm of the online SPMD path. Local
  arrays carry a leading axis of the chip's ``L`` lanes (``map_local``
  vmaps over it as ``SimComm`` does); a pair exchange between lanes of one
  chip is a gather on the chip, one between chips is one
  ``collective-permute`` of the lanes that cross. The online SPMD path
  takes ``AxisComm`` at one lane per device (``launch.spmd_qr``).

* ``SimComm``   — a P-lane simulator on a single device: every per-lane array
  carries a leading ``P`` axis, local compute is ``vmap``-ed, and ppermute is
  an explicit gather. This is how tests inject failures (blank a lane,
  corrupt a lane) and exercise recovery without killable processes, with
  bit-identical numerics to the SPMD path.

Rules for code written against Comm:
  * use ``x.mT`` (never ``x.T``) so matrices batch under SimComm/MeshComm;
  * use ``comm.where(cond, a, b)`` for lane-dependent selects;
  * wrap per-lane subroutines in ``comm.map_local(fn)``;
  * shapes of local arrays via ``comm.local_shape(x)``;
  * ``comm.batched`` says whether local arrays carry a lane axis.

Death-mask primitives (the FT seam; contract in DESIGN.md §8):
``where_lane`` / ``poison`` / ``fetch_lane`` express process
death and single-source REBUILD as *masked selects keyed by static lane
indices*, so the FT driver (``repro.ft.driver``) is one program that runs on
every comm. Lane arguments are Python ints (failure schedules are static
data); under AxisComm/MeshComm each primitive is a program every chip
enters, under SimComm it is indexing on the lane axis. ``lane_axis`` names
which axis of a batched array is the lane axis (stored level-stacked state
carries it at position 1); AxisComm ignores it — local arrays carry no lane
axis.
"""
from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _xor_allreduce(x, axis_name: str):
    """Bitwise-XOR all-reduce of a uint8 array over a mesh axis. XLA has no
    XOR all-reduce, so it lowers as 8 bit-planes summed with ``psum`` mod 2
    (exact: integer arithmetic)."""
    bits = jnp.stack([(x >> k) & jnp.uint8(1) for k in range(8)])
    bits = jax.lax.psum(bits.astype(jnp.int32), axis_name) % 2
    out = jnp.zeros(x.shape, jnp.uint8)
    for k in range(8):
        out = out | (bits[k].astype(jnp.uint8) << k)
    return out


def _batched_where(cond, a, b):
    """``jnp.where`` with ``cond`` broadcast from the leading lane axis."""
    cond = jnp.asarray(cond)
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    ndim = max(a.ndim, b.ndim)
    if cond.ndim < ndim:
        cond = cond.reshape(cond.shape + (1,) * (ndim - cond.ndim))
    return jnp.where(cond, a, b)


def _lane_select(idx, lane: int, a, b, lane_axis: int):
    """Lane ``lane`` sees ``a``, every other lane ``b``: ``idx`` holds the
    global ids of the local lanes, which sit on ``lane_axis``."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    ndim = max(a.ndim, b.ndim)
    cond = (idx == lane).reshape(
        (1,) * lane_axis + (idx.shape[0],) + (1,) * (ndim - lane_axis - 1))
    return jnp.where(cond, a, b)


class AxisComm:
    """Comm over a named mesh axis; use inside shard_map."""

    batched = False

    def __init__(self, axis_name: str):
        self.axis_name = axis_name
        self.xchip_bytes = 0

    def axis_size(self) -> int:
        return jax.lax.axis_size(self.axis_name)

    def axis_index(self):
        return jax.lax.axis_index(self.axis_name)

    def ppermute(self, x, perm: Sequence[Tuple[int, int]]):
        # one lane per device: every pair crosses devices (``xchip_bytes``
        # as MeshComm's)
        self.xchip_bytes += len(perm) * x.size * x.dtype.itemsize
        return jax.lax.ppermute(x, self.axis_name, perm)

    def psum(self, x):
        return jax.lax.psum(x, self.axis_name)

    def where(self, cond, a, b):
        return jnp.where(cond, a, b)

    def map_local(self, fn: Callable) -> Callable:
        return fn

    def local_shape(self, x) -> Tuple[int, ...]:
        return tuple(x.shape)

    # -- death-mask primitives (DESIGN.md §8) -------------------------------

    def where_lane(self, lane: int, a, b, lane_axis: int = 0):
        """Lane ``lane`` sees ``a``; every other lane sees ``b``. A pure
        select — no communication. ``lane_axis`` is ignored: SPMD-local
        arrays carry no lane axis."""
        del lane_axis
        return jnp.where(self.axis_index() == lane, a, b)

    def poison(self, x, lane: int, lane_axis: int = 0):
        """Mask-based process death: NaN lane ``lane``'s value (float leaves
        only — int/bool bookkeeping is static data a respawn recomputes)."""
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return self.where_lane(lane, jnp.full_like(x, jnp.nan), x, lane_axis)

    def fetch_lane(self, x, dst: int, src: int, lane_axis: int = 0, into=None):
        """Single-source REBUILD fetch: lane ``dst``'s slot of ``into``
        (default ``x``) becomes lane ``src``'s value of ``x``; every other
        lane keeps ``into``. One point-to-point collective-permute — only
        ``src`` sends, only ``dst``'s result changes."""
        into = x if into is None else into
        got = self.ppermute(x, [(src, dst)])
        return self.where_lane(dst, got, into, lane_axis)

    def xor_reduce(self, x, lane_axis: int = 0):
        """Bitwise-XOR all-reduce of a uint8 array over the lane axis — the
        parity collective of the coded checksum lanes (``repro.ft.coding``).
        XLA has no XOR all-reduce, so it lowers as 8 bit-planes summed with
        ``psum`` mod 2 (exact: integer arithmetic). Every lane holds the
        reduced value; ``lane_axis`` is ignored (local arrays carry no lane
        axis)."""
        del lane_axis
        return _xor_allreduce(x, self.axis_name)


class SimComm:
    """P-lane simulator: per-lane arrays carry a leading P axis."""

    batched = True

    def __init__(self, P: int):
        self.P = P

    def axis_size(self) -> int:
        return self.P

    def axis_index(self):
        return jnp.arange(self.P)

    def ppermute(self, x, perm: Sequence[Tuple[int, int]]):
        # lax.ppermute semantics: lanes that receive nothing get zeros.
        out = jnp.zeros_like(x)
        for src, dst in perm:
            out = out.at[dst].set(x[src])
        return out

    def psum(self, x):
        s = jnp.sum(x, axis=0, keepdims=True)
        return jnp.broadcast_to(s, x.shape)

    def where(self, cond, a, b):
        return _batched_where(cond, a, b)

    def map_local(self, fn: Callable) -> Callable:
        return jax.vmap(fn)

    def local_shape(self, x) -> Tuple[int, ...]:
        return tuple(x.shape)[1:]

    # -- death-mask primitives (DESIGN.md §8) -------------------------------

    def _lane_index(self, lane: int, lane_axis: int) -> Tuple:
        return (slice(None),) * lane_axis + (lane,)

    def where_lane(self, lane: int, a, b, lane_axis: int = 0):
        """Lane ``lane`` sees ``a``; every other lane sees ``b``.
        ``lane_axis`` locates the lane axis of the (batched) arrays."""
        return _lane_select(jnp.arange(self.P), lane, a, b, lane_axis)

    def poison(self, x, lane: int, lane_axis: int = 0):
        """Mask-based process death: NaN lane ``lane``'s slice (float leaves
        only — int/bool bookkeeping is static data a respawn recomputes)."""
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return x.at[self._lane_index(lane, lane_axis)].set(jnp.nan)

    def fetch_lane(self, x, dst: int, src: int, lane_axis: int = 0, into=None):
        """Single-source REBUILD fetch: lane ``dst``'s slot of ``into``
        (default ``x``) becomes lane ``src``'s slice of ``x``; every other
        lane keeps ``into``."""
        into = x if into is None else into
        return into.at[self._lane_index(dst, lane_axis)].set(
            x[self._lane_index(src, lane_axis)]
        )

    def xor_reduce(self, x, lane_axis: int = 0):
        """Bitwise-XOR reduction over the lane axis (``repro.ft.coding``'s
        parity collective). The lane axis is reduced away: the parity is a
        checksum-lane value with no per-lane copy (the AxisComm counterpart
        returns the reduced value replicated on every lane — the same
        global object in both layouts)."""
        return jax.lax.reduce(x, np.uint8(0), jax.lax.bitwise_xor,
                              (lane_axis,))

    def lane_slice(self, x, lane: int, lane_axis: int = 0):
        """Host-side extraction of one lane's slice of a batched array.
        Simulator-only (the SPMD path has no global view inside the
        program): the orchestrator's speculative straggler recompute uses
        it to bitwise-compare a rebuilt lane slice against the original
        (``repro.ft.stragglers``)."""
        return x[self._lane_index(lane, lane_axis)]


def _chip_rounds(chip_pairs):
    """Split ``(src_chip, dst_chip)`` pairs into rounds in which no chip
    sends twice and none receives twice: each round is one
    ``collective-permute``."""
    rounds = []
    for sc, dc in sorted(chip_pairs):
        for rnd in rounds:
            if all(sc != s and dc != d for s, d in rnd):
                rnd.append((sc, dc))
                break
        else:
            rounds.append([(sc, dc)])
    return rounds


class MeshComm:
    """Two-level comm: ``lanes_per_chip`` lanes on each of ``n_chips``
    devices of a named mesh axis; use inside shard_map.

    Lane ``i`` lives on chip ``i // L`` at local position ``i % L``
    (consecutive lanes share a chip). Local arrays carry a leading axis of
    the chip's ``L`` lanes, as SimComm's carry all ``P``: ``map_local``
    vmaps over it, ``axis_index`` returns the global ids of the local
    lanes. ``ppermute`` moves a lane's value within its chip by a gather
    and between chips by ``lax.ppermute`` of exactly the lanes that cross;
    the butterfly's levels below ``log2 L`` stay on the chip, the levels
    above move the chip's whole lane block to one partner chip.

    ``xchip_bytes`` counts, while a program is traced, the bytes that its
    exchanges move from one chip to another (every sending chip's payload);
    it is static data of the traced program, the same on each run."""

    batched = True

    def __init__(self, axis_name: str, lanes_per_chip: int, n_chips: int):
        assert lanes_per_chip >= 1 and n_chips >= 1
        self.axis_name = axis_name
        self.L = lanes_per_chip
        self.n_chips = n_chips
        self.xchip_bytes = 0

    def axis_size(self) -> int:
        return self.L * self.n_chips

    def chip_index(self):
        return jax.lax.axis_index(self.axis_name)

    def axis_index(self):
        return self.chip_index() * self.L + jnp.arange(self.L)

    def _on_chips(self, chips, a, b):
        """``a`` on the chips listed, ``b`` on the others."""
        chips = sorted(set(chips))
        if len(chips) == self.n_chips:
            return a
        hit = jnp.isin(self.chip_index(), jnp.asarray(chips))
        return jnp.where(hit, a, b)

    def ppermute(self, x, perm: Sequence[Tuple[int, int]]):
        # lax.ppermute semantics on global lane ids: lanes that receive
        # nothing get zeros; the lane axis is axis 0 (as SimComm's)
        L = self.L
        out = jnp.zeros_like(x)
        on_chip: dict = {}
        across: dict = {}
        for src, dst in perm:
            if src // L == dst // L:
                on_chip.setdefault(src // L, []).append((src % L, dst % L))
            else:
                across.setdefault((src // L, dst // L), []).append(
                    (src % L, dst % L))
        # on the chip: one gather per distinct local map
        by_map: dict = {}
        for chip, pairs in on_chip.items():
            by_map.setdefault(tuple(sorted(pairs)), []).append(chip)
        for pairs, chips in by_map.items():
            src_loc = np.array([s for s, _ in pairs])
            dst_loc = np.array([d for _, d in pairs])
            moved = out.at[dst_loc].set(x[src_loc])
            out = self._on_chips(chips, moved, out)
        # across chips: chip pairs that move the same local lanes share a
        # collective-permute, as far as a permutation of chips allows
        by_lanes: dict = {}
        for chip_pair, pairs in across.items():
            by_lanes.setdefault(tuple(sorted(pairs)), []).append(chip_pair)
        for pairs, chip_pairs in by_lanes.items():
            src_loc = [s for s, _ in pairs]
            dst_loc = [d for _, d in pairs]
            whole = src_loc == dst_loc == list(range(L))
            payload = x if whole else x[np.array(src_loc)]
            for rnd in _chip_rounds(chip_pairs):
                got = jax.lax.ppermute(payload, self.axis_name, rnd)
                self.xchip_bytes += (len(rnd) * payload.size
                                     * payload.dtype.itemsize)
                moved = got if whole else out.at[np.array(dst_loc)].set(got)
                out = self._on_chips([dc for _, dc in rnd], moved, out)
        return out

    def psum(self, x):
        s = jax.lax.psum(jnp.sum(x, axis=0, keepdims=True), self.axis_name)
        return jnp.broadcast_to(s, x.shape)

    def where(self, cond, a, b):
        return _batched_where(cond, a, b)

    def map_local(self, fn: Callable) -> Callable:
        return jax.vmap(fn)

    def local_shape(self, x) -> Tuple[int, ...]:
        return tuple(x.shape)[1:]

    # -- death-mask primitives (DESIGN.md §8) -------------------------------

    def where_lane(self, lane: int, a, b, lane_axis: int = 0):
        """Lane ``lane`` sees ``a``; every other lane sees ``b``. A pure
        select — no communication."""
        return _lane_select(self.axis_index(), lane, a, b, lane_axis)

    def poison(self, x, lane: int, lane_axis: int = 0):
        """Mask-based process death: NaN lane ``lane``'s slice (float leaves
        only), a select on the lane's own chip."""
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return self.where_lane(lane, jnp.full_like(x, jnp.nan), x, lane_axis)

    def fetch_lane(self, x, dst: int, src: int, lane_axis: int = 0, into=None):
        """Single-source REBUILD fetch: lane ``dst``'s slot of ``into``
        (default ``x``) becomes lane ``src``'s slice of ``x``; every other
        lane keeps ``into``. A gather when both lanes share a chip, else one
        point-to-point collective-permute of the one slice: only ``src``'s
        chip sends."""
        into = x if into is None else into
        got = jnp.moveaxis(
            self.ppermute(jnp.moveaxis(x, lane_axis, 0), [(src, dst)]),
            0, lane_axis)
        return self.where_lane(dst, got, into, lane_axis)

    def xor_reduce(self, x, lane_axis: int = 0):
        """Bitwise-XOR reduction over all lanes: over the chip's lanes, then
        across chips (``_xor_allreduce``). The lane axis is reduced away and
        every chip holds the result, as SimComm's global value."""
        local = jax.lax.reduce(x, np.uint8(0), jax.lax.bitwise_xor,
                               (lane_axis,))
        return _xor_allreduce(local, self.axis_name)
