"""Backend policy for the Pallas kernels — the single source of truth.

Three independent decisions live here:

* ``kernel_mode(op)`` — HOW an op in ``repro.kernels.ops`` executes::

      compiled   the fast path, on the engine of the static policy below.
      interpret  the Pallas interpreter (traced-Python-over-VMEM-blocks).
                 Slow; the validation vehicle for the kernel programs on
                 CPU. Never chosen automatically — request it explicitly
                 (tests, parity matrices).
      oracle     the pure-jnp reference in ``repro.kernels.ref``.

* ``compiled_engine(op)`` — which engine ``compiled`` runs, a written,
  static policy (no probe, no fallback):

      on TPU     ``pallas`` (native Mosaic ``pallas_call``) for every op:
                 the four tile kernels.
      elsewhere  ``xla`` for every op (the tile program as plain compiled
                 XLA; the Pallas TPU kernels do not lower on CPU).

  A pallas kernel that fails to lower raises the compiler's error where the
  program that calls it is compiled; nothing reroutes it.

* ``dispatch_enabled()`` — WHETHER the core hot path (``repro.core``) routes
  its panel/combine/apply operations through ``ops`` at all. Default: only
  on TPU, where the fused kernels beat XLA's op-by-op lowering. The ops
  layer itself runs its compiled engine on every backend.

Overrides, strongest first:
  1. ``use_kernels(True/False)`` — programmatic (tests, benchmarks);
     ``use_kernels(None)`` restores the automatic policy. True forces the
     core dispatch on AND pins ops to compiled mode; False pins everything
     to the oracle.
  2. ``force_mode(mode, op=None)`` — programmatic per-op (or global) mode
     pin; ``force_mode(None)`` clears.
  3. ``REPRO_NO_KERNELS=1``    — kill switch, wins over the backend default.
  4. ``REPRO_KERNEL_MODE=compiled|interpret|oracle|auto`` — global mode, and
     ``REPRO_KERNEL_MODE_<OP>`` (e.g. ``REPRO_KERNEL_MODE_WY_APPLY``) per op.
  5. ``REPRO_FORCE_KERNELS=1`` — force the core dispatch on (parity tests
     exercise the padded kernel path on CPU this way).

Note the decisions are read at *trace* time: flipping a flag does not
invalidate already-jitted callers. Tests flip flags before building jits.

The autotune cache (``repro.kernels.autotune``) is keyed by
``backend_fingerprint()`` so tuned block shapes never leak across machines
or backend/jax upgrades.
"""
from __future__ import annotations

import os
import warnings
from typing import Dict, Optional

import jax

_OVERRIDE: Optional[bool] = None

# -- kernel modes ------------------------------------------------------------

MODE_COMPILED = "compiled"
MODE_INTERPRET = "interpret"
MODE_ORACLE = "oracle"
MODE_AUTO = "auto"
KERNEL_MODES = (MODE_COMPILED, MODE_INTERPRET, MODE_ORACLE)

ENGINE_PALLAS = "pallas"
ENGINE_XLA = "xla"

# Every op the ops layer dispatches.
OPS = ("panel_qr", "stacked_qr", "wy_apply", "stacked_apply")

_MODE_OVERRIDE: Dict[str, str] = {}  # op (or "*") -> mode


def use_kernels(flag: Optional[bool]) -> None:
    """Force the core->kernel dispatch on/off; None = automatic policy."""
    global _OVERRIDE
    _OVERRIDE = flag


def force_mode(mode: Optional[str], op: Optional[str] = None) -> None:
    """Pin ``kernel_mode`` for one op (or all ops when ``op is None``).
    ``force_mode(None)`` / ``force_mode(None, op)`` clears the pin(s)."""
    key = "*" if op is None else op
    if mode is None:
        if op is None:
            _MODE_OVERRIDE.clear()
        else:
            _MODE_OVERRIDE.pop(key, None)
        return
    assert mode in KERNEL_MODES + (MODE_AUTO,), mode
    _MODE_OVERRIDE[key] = mode


def _env_mode(op: str) -> Optional[str]:
    for key in (f"REPRO_KERNEL_MODE_{op.upper()}", "REPRO_KERNEL_MODE"):
        val = os.environ.get(key, "").strip().lower()
        if val:
            if val not in KERNEL_MODES + (MODE_AUTO,):
                warnings.warn(f"{key}={val!r} is not one of "
                              f"{KERNEL_MODES + (MODE_AUTO,)}; ignoring")
                return None
            return val
    return None


def kernel_mode(op: str) -> str:
    """Resolve the execution mode for ``op``: compiled | interpret | oracle.

    Read at trace time by ``repro.kernels.ops``. ``auto`` (the default)
    resolves to ``compiled`` — ``compiled_engine`` decides pallas vs xla.
    """
    assert op in OPS, op
    if _OVERRIDE is False:
        return MODE_ORACLE
    mode = _MODE_OVERRIDE.get(op, _MODE_OVERRIDE.get("*"))
    if _OVERRIDE is True and mode is None:
        return MODE_COMPILED
    if os.environ.get("REPRO_NO_KERNELS", "0") == "1" and mode is None:
        return MODE_ORACLE
    if mode is None:
        mode = _env_mode(op) or MODE_AUTO
    if mode == MODE_AUTO:
        return MODE_COMPILED
    return mode


# -- the static engine policy -------------------------------------------------

def platform() -> str:
    """The default backend's platform (``tpu``, ``cpu``, ...)."""
    return jax.default_backend()


def compiled_engine(op: str) -> str:
    """Which engine ``compiled`` mode runs for ``op`` on this backend."""
    assert op in OPS, op
    if platform() == "tpu":
        return ENGINE_PALLAS
    return ENGINE_XLA


def engine_report() -> Dict[str, str]:
    """{op: the route the active policy takes} — ``pallas`` / ``xla`` under
    compiled mode, else ``interpret`` / ``oracle``. ``chip_smoke.py`` and
    ``tools/kernel_smoke.py`` print it."""
    report = {}
    for op in OPS:
        mode = kernel_mode(op)
        report[op] = compiled_engine(op) if mode == MODE_COMPILED else mode
    return report


def backend_fingerprint() -> str:
    """Stable identity of (backend, device kind, jax version) — the autotune
    cache key, so tuned shapes never leak across machines or upgrades."""
    kind = jax.devices()[0].device_kind
    return f"{jax.default_backend()}:{kind}:jax-{jax.__version__}"


# -- legacy interpret seam (kept: kernel modules resolve interpret=None) -----


def interpret_default() -> bool:
    """True everywhere except a real TPU backend."""
    return platform() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve a kernel's ``interpret=None`` default against the backend."""
    return interpret_default() if interpret is None else interpret


# -- core dispatch (whether repro.core routes through ops at all) ------------


def dispatch_enabled() -> bool:
    """Should repro.core route through the Pallas kernels right now?"""
    if _OVERRIDE is not None:
        return _OVERRIDE
    if os.environ.get("REPRO_NO_KERNELS", "0") == "1":
        return False
    if os.environ.get("REPRO_FORCE_KERNELS", "0") == "1":
        return True
    return platform() == "tpu"


def ops_kernels_enabled() -> bool:
    """Should ops.* run a kernel engine (vs. the jnp oracle)?

    Compatibility shim over the per-op policy: True iff no op is pinned to
    the oracle globally. Shares the ``use_kernels`` override and the env
    kill switch with the core dispatch so the two layers can never disagree
    (both read at call/trace time).
    """
    return kernel_mode("panel_qr") != MODE_ORACLE


# Alignment contract (VREG/MXU tiling): panel rows in sublane multiples,
# panel widths in lane multiples. The contract belongs to the *pallas*
# engines (Mosaic tiles / the interpreter's block model); the xla engine
# runs at natural shapes. ``ops`` pads up to the contract and slices back,
# so callers never see it — but aligned shapes skip the copies. Sublane is
# dtype-dependent: (8, 128) packs f32, (16, 128) bf16.
SUBLANE = 8
LANE = 128


def sublane(dtype) -> int:
    """Second-to-last-dim tile multiple for ``dtype`` (f32: 8, bf16: 16)."""
    import jax.numpy as jnp

    return 16 if dtype == jnp.bfloat16 else SUBLANE


def pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult
