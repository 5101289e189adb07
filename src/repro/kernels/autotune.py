"""Wall-clock block-shape autotuner for the kernel fast path.

CAQR's payoff is notoriously shape-sensitive (Demmel et al. 2008 tune panel
and block sizes per machine); this module does the equivalent for the Pallas
kernels: for each **cell** — an (op, geometry, dtype, engine) tuple — it
times every candidate block shape (median of ``reps`` wall-clock runs,
compile excluded) and records the winner.

Tunables per op (variant-dependent — see ``candidates``):
  * ``panel_qr`` / ``stacked_qr``: pallas/interpret variants tune
    ``lane_pad`` — the lane multiple the ops wrapper pads panel widths to
    (Mosaic is pinned to the full 128-lane VREG width; the interpreter,
    where padding is pure overhead, may prefer less). The ``xla`` engine
    has no padding contract; its knob is ``unroll``, the column-loop unroll
    factor (loop overhead dominates these small-body loops on CPU).
  * ``wy_apply`` / ``stacked_apply``: ``block_n`` — the trailing-dim column
    tile per grid program (pallas/interpret only; the xla engine is
    untiled).

Consultation: ``ops.py`` calls ``lookup(op, geometry, dtype, variant)`` on
every dispatch (cheap dict probe) and falls back to the static defaults when
the cell was never tuned. Tuning is explicit (``tune`` / ``tune_all`` — run
from ``tools/kernel_smoke.py`` or a user script), never implicit at call
time: a jitted sweep must not suddenly block on a timing loop.

Persistence: ``save``/``load`` round-trip the winners through a JSON cache::

    {"version": 1,
     "cells": {"<backend_fingerprint>": {
         "wy_apply|256x64x512|float32|interpret": {
             "params": {"block_n": 128}, "us": 812.4},
         ...}}}

keyed by ``backend.backend_fingerprint()`` (backend + device kind + jax
version). A cache file from another machine or after an upgrade is *valid
but inert*: foreign fingerprints are preserved on save and ignored on load,
which is the whole invalidation story — no staleness heuristics.
``REPRO_AUTOTUNE_CACHE=<path>`` names a cache to auto-load on first lookup.
"""
from __future__ import annotations

import json
import os
import statistics
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.kernels import backend

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

# In-memory winners for THIS process's fingerprint: cell key -> record
# {"params": {...}, "us": float}.
_CELLS: Dict[str, Dict] = {}
# Cells of other fingerprints, carried through load->save round-trips.
_FOREIGN: Dict[str, Dict[str, Dict]] = {}
_ENV_LOADED = False


def cell_key(op: str, geometry: Sequence[int], dtype, variant: str) -> str:
    """``op|geom|dtype|variant``; variant is the execution flavor the timing
    is valid for (``pallas``/``xla`` engine or ``interpret``)."""
    geom = "x".join(str(int(g)) for g in geometry)
    import jax.numpy as jnp

    return f"{op}|{geom}|{jnp.dtype(dtype).name}|{variant}"


def current_variant(op: str) -> str:
    """The flavor ``op`` would execute right now under the active policy."""
    mode = backend.kernel_mode(op)
    if mode == backend.MODE_COMPILED:
        return backend.compiled_engine(op)
    return mode  # interpret / oracle


def _ensure_env_loaded() -> None:
    global _ENV_LOADED
    if _ENV_LOADED:
        return
    _ENV_LOADED = True
    path = os.environ.get(CACHE_ENV, "").strip()
    if path and os.path.exists(path):
        load(path)


def lookup(op: str, geometry: Sequence[int], dtype, variant: Optional[str] = None
           ) -> Dict[str, int]:
    """Tuned params for the cell, or ``{}`` (use static defaults)."""
    _ensure_env_loaded()
    if not _CELLS:
        return {}
    if variant is None:
        variant = current_variant(op)
    rec = _CELLS.get(cell_key(op, geometry, dtype, variant))
    return dict(rec["params"]) if rec else {}


def clear() -> None:
    """Drop all in-memory winners (tests)."""
    global _ENV_LOADED
    _CELLS.clear()
    _FOREIGN.clear()
    _ENV_LOADED = True  # a cleared tuner stays cleared; load() re-fills


def candidates(op: str, variant: str) -> List[Dict[str, int]]:
    """The block-shape search space for one (op, variant)."""
    if op in ("panel_qr", "stacked_qr"):
        if variant == backend.ENGINE_XLA:
            # no padding contract; the knob is the column-loop unroll
            return [{"unroll": u} for u in (1, 2, 4)]
        if variant == backend.ENGINE_PALLAS:
            pads = (backend.LANE,)  # Mosaic wants full VREG lanes
        else:
            pads = (backend.SUBLANE, 32, backend.LANE)
        return [{"lane_pad": p} for p in pads]
    # wy_apply, stacked_apply
    if variant == backend.ENGINE_XLA:
        return [{}]  # untiled: column tiling is a pallas-grid concept
    return [{"block_n": n} for n in (64, 128, 256, 512)]


def _median_us(fn, reps: int) -> float:
    fn()  # compile + warm
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        samples.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(samples)


def _runner(op: str, geometry: Sequence[int], dtype, params: Dict[str, int]):
    """Build a nullary timed callable for one candidate: the real ops-layer
    dispatch with the candidate's block shape forced."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.RandomState(0)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    if op == "panel_qr":
        m, b = geometry
        A = arr(m, b)
        return lambda: jax.block_until_ready(
            ops.panel_qr(A, 0, lane_pad=params.get("lane_pad"),
                         unroll=params.get("unroll")))
    if op == "stacked_qr":
        (b,) = geometry
        R1 = jnp.triu(arr(b, b))
        R2 = jnp.triu(arr(b, b))
        return lambda: jax.block_until_ready(
            ops.stacked_qr(R1, R2, lane_pad=params.get("lane_pad"),
                           unroll=params.get("unroll")))
    if op == "wy_apply":
        m, b, n = geometry
        Y, T, C = arr(m, b), jnp.triu(arr(b, b)), arr(m, n)
        return lambda: jax.block_until_ready(
            ops.wy_apply(Y, T, C, block_n=params.get("block_n")))
    if op == "stacked_apply":
        b, n = geometry
        Y2, T = jnp.triu(arr(b, b)), jnp.triu(arr(b, b))
        Ct, Cb = arr(b, n), arr(b, n)
        return lambda: jax.block_until_ready(
            ops.stacked_apply(Y2, T, Ct, Cb, block_n=params.get("block_n")))
    raise ValueError(f"no tuning runner for op {op!r}")


def tune(op: str, geometry: Sequence[int], dtype=None, reps: int = 5,
         variant: Optional[str] = None) -> Optional[Dict]:
    """Time every candidate for one cell and record the winner in memory.

    Returns the winning record ``{"params", "us"}``, or ``None`` when the
    active policy routes ``op`` to the oracle (nothing to tune)."""
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.float32
    if variant is None:
        variant = current_variant(op)
    if variant == backend.MODE_ORACLE:
        return None
    best: Optional[Tuple[float, Dict[str, int]]] = None
    for params in candidates(op, variant):
        us = _median_us(_runner(op, geometry, dtype, params), reps)
        if best is None or us < best[0]:
            best = (us, params)
    record = {"params": best[1], "us": round(best[0], 2)}
    _ensure_env_loaded()
    _CELLS[cell_key(op, geometry, dtype, variant)] = record
    return record


# Representative cells: the bench geometry plus the small combine shapes the
# sweep actually issues.
DEFAULT_CELLS = (
    ("panel_qr", (256, 64)),
    ("stacked_qr", (64,)),
    ("wy_apply", (256, 64, 512)),
    ("stacked_apply", (64, 512)),
)


def tune_all(cells=DEFAULT_CELLS, dtype=None, reps: int = 5) -> Dict[str, Dict]:
    """Tune a set of cells; returns {cell_key: winner record}."""
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.float32
    out = {}
    for op, geometry in cells:
        rec = tune(op, geometry, dtype=dtype, reps=reps)
        if rec is not None:
            out[cell_key(op, geometry, dtype, current_variant(op))] = rec
    return out


def _default_path() -> str:
    return os.environ.get(CACHE_ENV, "").strip() or os.path.join(
        os.path.expanduser("~"), ".cache", "repro_autotune.json")


def save(path: Optional[str] = None) -> str:
    """Persist all known winners (ours + foreign fingerprints) to JSON."""
    path = path or _default_path()
    cells = dict(_FOREIGN)
    if _CELLS:
        cells[backend.backend_fingerprint()] = _CELLS
    payload = {"version": 1, "cells": cells}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    return path


def load(path: Optional[str] = None) -> int:
    """Load a cache file; adopt only cells matching this process's backend
    fingerprint (foreign cells are kept for round-tripping, not consulted).
    Returns the number of cells adopted."""
    global _ENV_LOADED
    path = path or _default_path()
    with open(path) as f:
        payload = json.load(f)
    assert payload.get("version") == 1, payload.get("version")
    _ENV_LOADED = True
    fp = backend.backend_fingerprint()
    adopted = 0
    for fingerprint, cells in payload.get("cells", {}).items():
        if fingerprint == fp:
            _CELLS.update(cells)
            adopted += len(cells)
        else:
            _FOREIGN.setdefault(fingerprint, {}).update(cells)
    return adopted
