#!/usr/bin/env bash
# CI entrypoint: docs check + tier-1 tests + example smoke + benchmark smoke.
#
# Test tiers (see also pytest.ini):
#   tier-1     the bare `python -m pytest -x -q` — deterministic tests only,
#              slow-marked tests excluded; must pass on a bare image.
#   slow       `-m slow`: subprocess SPMD cells + exhaustive kill matrices
#              (aligned AND ragged geometries); run via `tools/ci.sh --slow`.
#   property   the hypothesis-driven differential harnesses
#              (tests/test_general_shapes.py, tests/test_properties.py,
#              tests/test_elastic_properties.py).
#              They run inside tier-1 whenever hypothesis is importable; the
#              guard below makes a missing hypothesis a LOUD failure instead
#              of a silent skip, so the property tier cannot quietly vanish
#              from CI. Set CI_ALLOW_MISSING_HYPOTHESIS=1 to acknowledge an
#              image without it (the deterministic tiers still run).
#
#   tools/ci.sh          docs check (tools/check_docs.py), tier-1 pytest,
#                        end-to-end example smoke (quickstart + the FT
#                        driver/training demo), the SPMD smoke tier
#                        (examples/spmd_quickstart.py: shard_map FT sweep +
#                        kill on a forced 4-device host mesh, checked
#                        bitwise vs SimComm), the serve smoke tier
#                        (repro.launch.serve_qr: a QR-service traffic burst
#                        with a mid-batch lane kill, every retired R
#                        verified against numpy), the repro.ft
#                        docstring-example doctests, the compiled-kernel
#                        smoke tier
#                        (tools/kernel_smoke.py: capability probe report,
#                        compiled-dispatch parity vs the jnp oracles, and an
#                        autotune cache round-trip — loud skip when no op
#                        lowers native Pallas, an error under
#                        CI_REQUIRE_COMPILED_KERNELS=1), then
#                        `benchmarks/run.py --quick`, which
#                        also refreshes BENCH_core.json (incl. the `spmd`
#                        SimComm-vs-shard_map section)
#   tools/ci.sh --slow   additionally run the slow-marked tests
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== property-tier dependency check =="
if python -c "import hypothesis" 2>/dev/null; then
    echo "hypothesis present: property harnesses run in tier-1"
else
    echo "ERROR: hypothesis is not installed — the property tier" >&2
    echo "(tests/test_general_shapes.py, tests/test_properties.py," >&2
    echo "tests/test_elastic_properties.py)" >&2
    echo "would be silently skipped. Install hypothesis, or set" >&2
    echo "CI_ALLOW_MISSING_HYPOTHESIS=1 to acknowledge the gap." >&2
    if [[ "${CI_ALLOW_MISSING_HYPOTHESIS:-0}" != "1" ]]; then
        exit 1
    fi
    echo "CI_ALLOW_MISSING_HYPOTHESIS=1 set: continuing without the property tier"
fi

echo "== docs check =="
python tools/check_docs.py

echo "== tier-1 tests =="
python -m pytest -x -q

if [[ "${1:-}" == "--slow" ]]; then
    echo "== slow tests =="
    python -m pytest -q -m slow
fi

echo "== example smoke =="
python examples/quickstart.py
python examples/failure_recovery_training.py --steps 8
python examples/online_recovery.py   # runtime-detected kill + suspend/resume

echo "== train smoke (FT training runtime: CAQR-Muon orthogonalization =="
echo "== through the FT-QR engine, a lane killed inside the =="
echo "== optimizer-internal sweep, params + loss curve asserted bitwise =="
echo "== vs failure-free) =="
python examples/train_tiny_lm.py --steps 6

echo "== SPMD smoke (shard_map FT sweep on a forced 4-device host mesh) =="
python examples/spmd_quickstart.py

echo "== serve smoke (QR-as-a-service traffic burst + mid-batch lane =="
echo "== kill; every retired R verified against numpy QR/lstsq) =="
python -m repro.launch.serve_qr --requests 8 --kill-lane 2 --kill-tick 2

echo "== multi-failure smoke (coded checksum lanes: a former XOR-buddy =="
echo "== pair killed simultaneously at runtime, healed by the joint GF =="
echo "== decode under MDSScheme(f=2), checked bitwise vs failure-free; =="
echo "== the same schedule must still raise under the XOR scheme) =="
python - <<'PYEOF'
import numpy as np, jax
from repro.core import SimComm
from repro.ft import (MDSScheme, UnrecoverableFailure, ft_caqr_sweep,
                      ft_caqr_sweep_online, sweep_point)
from repro.ft.online.detect import ScriptedKiller

P, m_loc, n, b = 4, 6, 10, 4
A = np.random.default_rng(3).standard_normal((P, m_loc, n)).astype(np.float32)
comm = SimComm(P)
pt = sweep_point(1, "trailing", 0)
free = ft_caqr_sweep(A, comm, b)
try:
    ft_caqr_sweep_online(A, comm, b,
                         fault_hooks=[ScriptedKiller({pt: [2, 3]})])
    raise SystemExit("XOR scheme recovered a buddy-pair double kill?!")
except UnrecoverableFailure:
    pass
got = ft_caqr_sweep_online(A, comm, b,
                           fault_hooks=[ScriptedKiller({pt: [2, 3]})],
                           scheme=MDSScheme(f=2))
for g, r in zip(jax.tree_util.tree_leaves((got.R, got.factors, got.bundles)),
                jax.tree_util.tree_leaves((free.R, free.factors, free.bundles))):
    assert np.array_equal(np.asarray(g), np.asarray(r)), "decode not bitwise"
assert all("coded.parity0" in e.reads for e in got.events)
print("multi-failure smoke OK: buddy-pair kill decoded bitwise, f=2")
PYEOF

echo "== repro.ft API doctest examples =="
python -m doctest src/repro/ft/driver.py src/repro/ft/failures.py \
    src/repro/ft/semantics.py && echo "doctests OK"

echo "== compiled-kernel smoke (probe report + dispatch parity + autotune =="
echo "== cache round-trip; CI_REQUIRE_COMPILED_KERNELS=1 to demand Pallas) =="
python tools/kernel_smoke.py

echo "== benchmark smoke (writes BENCH_core.json; fails loudly if the =="
echo "== online stepped overhead, the elastic SHRINK continuation, the =="
echo "== serve continuous-batching overhead, the coded-lane f=2 encode =="
echo "== overhead, or the train per-boundary cost regresses >25% over =="
echo "== the recorded baseline — and the train tier's async segments =="
echo "== must be strictly cheaper than sync; escapes: =="
echo "== CI_ALLOW_ONLINE_REGRESSION=1 / =="
echo "== CI_ALLOW_ELASTIC_REGRESSION=1 / CI_ALLOW_SERVE_REGRESSION=1 / =="
echo "== CI_ALLOW_CODING_REGRESSION=1 / CI_ALLOW_TRAIN_REGRESSION=1) =="
python -m benchmarks.run --quick

echo "CI OK"
