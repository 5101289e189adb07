"""Communication abstraction: one SPMD code path, two executions.

The paper's algorithms are written as per-process (per-lane) SPMD programs
with pairwise exchanges. We express them once against this small ``Comm``
interface and run them two ways:

* ``AxisComm``  — inside ``jax.shard_map`` over a named mesh axis; collectives
  lower to real ICI ``collective-permute`` / ``all-reduce`` ops. This is the
  production path (and the dry-run path).

* ``SimComm``   — a P-lane simulator on a single device: every per-lane array
  carries a leading ``P`` axis, local compute is ``vmap``-ed, and ppermute is
  an explicit gather. This is how tests inject failures (blank a lane,
  corrupt a lane) and exercise recovery without killable processes, with
  bit-identical numerics to the SPMD path.

Rules for code written against Comm:
  * use ``x.mT`` (never ``x.T``) so matrices batch under SimComm;
  * use ``comm.where(cond, a, b)`` for lane-dependent selects;
  * wrap per-lane subroutines in ``comm.map_local(fn)``;
  * shapes of local arrays via ``comm.local_shape(x)``.

Death-mask primitives (the FT seam; contract in DESIGN.md §8):
``where_lane`` / ``poison`` / ``fetch_lane`` express process
death and single-source REBUILD as *masked selects keyed by static lane
indices*, so the FT driver (``repro.ft.driver``) is one program that runs on
both comms. Lane arguments are Python ints (failure schedules are static
data); under AxisComm each primitive is a collective the whole axis enters,
under SimComm it is indexing on the lane axis. ``lane_axis`` names which
axis of a SimComm array is the lane axis (stored level-stacked state carries
it at position 1); AxisComm ignores it — local arrays carry no lane axis.
"""
from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class AxisComm:
    """Comm over a named mesh axis; use inside shard_map."""

    def __init__(self, axis_name: str):
        self.axis_name = axis_name

    def axis_size(self) -> int:
        return jax.lax.axis_size(self.axis_name)

    def axis_index(self):
        return jax.lax.axis_index(self.axis_name)

    def ppermute(self, x, perm: Sequence[Tuple[int, int]]):
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
        bits = jnp.stack([(x >> k) & jnp.uint8(1) for k in range(8)])
        bits = self.psum(bits.astype(jnp.int32)) % 2
        out = jnp.zeros(x.shape, jnp.uint8)
        for k in range(8):
            out = out | (bits[k].astype(jnp.uint8) << k)
        return out


class SimComm:
    """P-lane simulator: per-lane arrays carry a leading P axis."""

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
        cond = jnp.asarray(cond)
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        ndim = max(a.ndim, b.ndim)
        if cond.ndim < ndim:
            cond = cond.reshape(cond.shape + (1,) * (ndim - cond.ndim))
        return jnp.where(cond, a, b)

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
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        ndim = max(a.ndim, b.ndim)
        cond = (jnp.arange(self.P) == lane).reshape(
            (1,) * lane_axis + (self.P,) + (1,) * (ndim - lane_axis - 1)
        )
        return jnp.where(cond, a, b)

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
