#!/usr/bin/env python
"""Compiled-kernel smoke tier (tools/ci.sh).

Three checks, in order:

1. **Engine report** — the route the static policy
   (``backend.compiled_engine``) gives every op on this backend: native
   Pallas on TPU for the tile kernels, the ``xla`` engine elsewhere.
   Purely informational, always printed.
2. **Compiled-dispatch parity** — run every op through the real ``ops``
   dispatch under the active policy (whatever engine ``compiled`` resolves
   to here) on an aligned and a ragged geometry, in f32 and bf16, and
   compare against the jnp oracle at ``ref.tolerances(dtype)``. This is the
   smoke guarantee that the fast path *computes the right thing* on this
   machine, whichever engine it got.
3. **Autotune cache round-trip** — tune one cell, save to a temp file,
   clear, load, and require the looked-up params to be identical (the
   persistence format and the fingerprint keying actually work).

When no op runs native Pallas (any backend but TPU) the tier prints a
LOUD skip for the pallas-engine half (the xla-engine parity still runs —
that is the compiled path CI actually exercises on CPU images).
``CI_REQUIRE_COMPILED_KERNELS=1`` turns that skip into an error. Exit
codes: 0 OK / 1 failure (or required-but-missing native Pallas).
"""
from __future__ import annotations

import os
import sys
import tempfile

import numpy as np


def main() -> int:
    import jax
    import jax.numpy as jnp

    from repro.kernels import autotune, backend, fused_sweep, ops, ref

    print(f"backend fingerprint: {backend.backend_fingerprint()}")
    report = backend.engine_report()
    native = [op for op, engine in report.items()
              if engine == backend.ENGINE_PALLAS]
    for op, engine in report.items():
        print(f"  {op:14s} engine={engine}")

    if not native:
        print("LOUD SKIP: no op runs native Pallas on this backend — the "
              "pallas engine is untested here; compiled dispatch runs via "
              "the xla engine below.")
        if os.environ.get("CI_REQUIRE_COMPILED_KERNELS") == "1":
            print("CI_REQUIRE_COMPILED_KERNELS=1: treating the skip as an "
                  "error (this image is supposed to lower Pallas).",
                  file=sys.stderr)
            return 1

    # -- compiled-dispatch parity vs oracle --------------------------------
    failures = []
    rng = np.random.default_rng(0)
    for dt in (jnp.float32, jnp.bfloat16):
        rtol, atol = ref.tolerances(dt)
        for m, b, n in ((64, 16, 96), (37, 12, 55)):  # aligned-ish + ragged
            A = jnp.asarray(rng.standard_normal((m, b)), dt)
            Y = jnp.asarray(rng.standard_normal((m, b)), dt) * 0.1
            T = jnp.triu(jnp.asarray(rng.standard_normal((b, b)), dt)) * 0.1
            C = jnp.asarray(rng.standard_normal((m, n)), dt)
            R1 = jnp.asarray(np.linalg.qr(rng.standard_normal((m, b)))[1], dt)
            R2 = jnp.asarray(np.linalg.qr(rng.standard_normal((m, b)))[1], dt)
            Ct = jnp.asarray(rng.standard_normal((b, n)), dt)
            Cb = jnp.asarray(rng.standard_normal((b, n)), dt)
            W = jnp.asarray(rng.standard_normal((m, b + 8)), dt)
            pairs = [
                ("panel_qr", lambda: ops.panel_qr(A, 0),
                 lambda: ref.panel_qr(A, 0)),
                ("stacked_qr", lambda: ops.stacked_qr(R1, R2),
                 lambda: ref.stacked_qr(R1, R2)),
                ("wy_apply", lambda: ops.wy_apply(Y, T, C),
                 lambda: ref.wy_apply(Y, T, C)),
                ("stacked_apply", lambda: ops.stacked_apply(T, T, Ct, Cb),
                 lambda: ref.stacked_apply(T, T, Ct, Cb)),
                ("fused_sweep", lambda: ops.panel_qr_apply(W, 0, b),
                 lambda: fused_sweep.panel_qr_apply_ref(W, 0, b)),
            ]
            for op, k_fn, r_fn in pairs:
                mode = backend.kernel_mode(op)
                got, want = k_fn(), r_fn()
                for g, w in zip(jax.tree_util.tree_leaves(got),
                                jax.tree_util.tree_leaves(want)):
                    g = np.asarray(g, dtype=np.float32)
                    w = np.asarray(w, dtype=np.float32)
                    if not np.allclose(g, w, rtol=rtol, atol=atol):
                        failures.append(
                            f"{op} [{mode}] {jnp.dtype(dt).name} "
                            f"({m},{b},{n}): max err "
                            f"{np.abs(g - w).max():.2e} > {atol}")
                        break
    if failures:
        print("PARITY FAILURES:", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    modes = {op: backend.kernel_mode(op) for op in backend.OPS}
    print(f"parity OK (modes: {modes})")

    # -- autotune cache round-trip -----------------------------------------
    # panel_qr has a non-trivial candidate set on every engine (unroll on
    # xla, lane_pad elsewhere), so the reloaded params are never vacuous.
    autotune.clear()
    rec = autotune.tune("panel_qr", (64, 16), reps=3)
    if rec is None:
        print("autotune round-trip skipped: policy routes panel_qr to the "
              "oracle (nothing to tune)")
        return 0
    key_params = autotune.lookup("panel_qr", (64, 16), jnp.float32)
    assert key_params, "tuned cell has no params — round-trip would be vacuous"
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "autotune.json")
        autotune.save(path)
        autotune.clear()
        assert autotune.lookup("panel_qr", (64, 16), jnp.float32) == {}
        adopted = autotune.load(path)
        reloaded = autotune.lookup("panel_qr", (64, 16), jnp.float32)
    if reloaded != key_params:
        print(f"autotune round-trip MISMATCH: {key_params!r} != {reloaded!r}",
              file=sys.stderr)
        return 1
    print(f"autotune round-trip OK ({adopted} cell(s), params {key_params})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
