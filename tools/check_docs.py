#!/usr/bin/env python
"""Docs drift check: every code path README.md / DESIGN.md cite must exist.

Extracts backtick-quoted references of two kinds and resolves each against
the working tree:

* file paths (``src/repro/core/caqr.py``, ``bench/run_cell.py``,
  ``core/trailing.py`` — relative forms resolve by suffix anywhere under
  the repo);
* dotted module names (``repro.ft.driver`` -> ``src/repro/ft/driver.py``
  or a package directory).

Exit non-zero listing every dangling reference, so renames/deletions cannot
silently orphan the documentation. Run by ``tools/ci.sh``.
"""
from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCS = ["README.md", "DESIGN.md"]

# Load-bearing sections: documentation a refactor must keep (referenced from
# code docstrings and tests). A heading rename/removal fails the gate.
REQUIRED_HEADINGS = {
    "README.md": [
        "## Shape support",
        "## Execution model: one program, two paths",
        "### Semantics support",
        "### Coded redundancy: the `f` knob",
        "## Serving: QR-as-a-service",
        "## Training: the FT runtime",
    ],
    "DESIGN.md": [
        "## 5. Recovery data-flow",
        "## 7. Ragged-panel geometry and padding semantics",
        "## 8. SPMD execution model",
        "## 9. Online recovery and the sweep state machine",
        "## 10. Kernel fast path",
        "## 11. Elastic execution",
        "## 12. Serving: QR-as-a-service",
        "## 13. Coded redundancy",
        "## 14. Fault-tolerant training runtime",
    ],
}

FILE_RE = re.compile(r"`([A-Za-z0-9_\-./]+\.(?:py|sh|json|md))`")
MODULE_RE = re.compile(r"`(repro(?:\.[A-Za-z_][A-Za-z0-9_]*)+)`")


def file_ok(token: str) -> bool:
    if (ROOT / token).exists():
        return True
    # relative citation (e.g. `core/trailing.py`): accept a unique-suffix
    # match anywhere in the tree
    name = token.lstrip("./")
    return any(
        str(p).endswith("/" + name)
        for p in ROOT.rglob(pathlib.Path(name).name)
    )


def module_ok(token: str) -> bool:
    rel = pathlib.Path("src", *token.split("."))
    return (ROOT / rel).is_dir() or (ROOT / rel.with_suffix(".py")).exists()


def main() -> int:
    missing = []
    for doc in DOCS:
        path = ROOT / doc
        if not path.exists():
            missing.append((doc, "(document itself missing)"))
            continue
        text = path.read_text()
        for tok in sorted(set(FILE_RE.findall(text))):
            if not file_ok(tok):
                missing.append((doc, tok))
        for tok in sorted(set(MODULE_RE.findall(text))):
            if not module_ok(tok):
                missing.append((doc, tok))
        for heading in REQUIRED_HEADINGS.get(doc, []):
            if not any(line.startswith(heading) for line in text.splitlines()):
                missing.append((doc, f"required section {heading!r}"))
    if missing:
        print("dangling documentation references:")
        for doc, tok in missing:
            print(f"  {doc}: {tok}")
        return 1
    print(f"docs check OK ({', '.join(DOCS)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
