"""The benchmark's files hold together: every cell names a config, a traffic
mix and a driver that exist, every metric has its reader and reports only
in cells that report what it moves, every name and unit keeps to the
allowed characters, and ``run_cell.py`` refuses to run off a TPU."""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _metrics():
    return SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run_cell.py"
    assert (ROOT / SPEC["command"][1]).is_file()
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_files_name_existing_pieces(w):
    cell = json.loads((BENCH / "workloads" / f"{w['name']}.json")
                      .read_text())
    assert cell["config"] == w["config"]
    assert cell["traffic"] == w["traffic"]
    assert (BENCH / "configs" / f"{w['config']}.json").is_file()
    assert (BENCH / "mixes" / f"{w['traffic']}.json").is_file()
    assert (BENCH / "drivers" / f"{cell['driver']}.py").is_file()
    assert "gram_residual" in cell["limits"]
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(c):
    path = ROOT / c["file"]
    assert path.is_file() and c["file"].startswith("bench/")
    data = json.loads(path.read_text())
    assert data["reduced"] == c["reduced"]
    assert "assumed" in data and "source" in data
    for key in c["reduced"]:
        assert not key.endswith(("_dim", "_rank")), key
    assert any(w["config"] == c["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_reader_and_moves(m):
    assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    e2e = {x["name"]: x for x in SPEC["end_to_end"]}
    moved = e2e[m["moves"]]
    cells = m.get("workloads", [w["name"] for w in SPEC["workloads"]])
    for cell in cells:
        assert cell in moved.get("workloads", [cell]), (m["name"], cell)


def test_names_units_and_sources():
    names = [m["name"] for m in _metrics()]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [c["name"] for c in SPEC["configs"]]
    for w in SPEC["workloads"]:
        names += [w["config"], w["traffic"]]
    for c in SPEC["configs"]:
        names += c["reduced"]
    for n in names:
        assert NAME.match(n), n
    for group in (SPEC["end_to_end"], SPEC["per_layer"], SPEC["workloads"],
                  SPEC["configs"]):
        assert len({x["name"] for x in group}) == len(group)
    for m in _metrics():
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_setup_another_e2e_and_a_layer():
    for w in SPEC["workloads"]:
        name = w["name"]
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if name in m.get("workloads", [name])]
        layer = [m["name"] for m in SPEC["per_layer"]
                 if name in m.get("workloads", [name])]
        assert "setup_s" in e2e and len(e2e) >= 2, name
        assert layer, name


def test_run_cell_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(BENCH / "run_cell.py"), "--workload",
         "tsqr_tall.free", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "not a TPU" in out.stderr
