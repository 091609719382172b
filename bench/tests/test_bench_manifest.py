"""`BENCHMARK.json` against the files it names and the rules it keeps,
and the command's refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}


def test_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"][0] == "python3"
    assert all((ROOT / p).is_dir() for p in MANIFEST["paths"])
    assert (ROOT / MANIFEST["command"][1]).is_file()
    assert 1 <= MANIFEST["run_seconds"] <= 51


def test_names_and_units():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = ([c["name"] for c in MANIFEST["configs"]] + list(CELLS)
             + [m["name"] for m in metrics])
    for n in names:
        assert NAME.match(n), n
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in MANIFEST["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k), k


def test_every_cell_has_its_files_and_metrics():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for name, w in CELLS.items():
        cell = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["traffic"] == w["traffic"]
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "drivers" / f"{cell['driver']}.py").is_file()
        assert (ROOT / configs[w["config"]]["file"]).is_file()
        reported = harness.cell_metrics(MANIFEST, name, False)
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        layer = harness.cell_metrics(MANIFEST, name, True)
        assert layer, name
        for m in layer:
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in MANIFEST["per_layer"]:
        # every cell the metric names reports the metric it moves
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            em = e2e[m["moves"]]
            assert "workloads" not in em or cell in em["workloads"], m
    assert {w["config"] for w in CELLS.values()} == set(configs)
    for c in configs.values():
        kind = json.loads((ROOT / c["file"]).read_text())["model"]["kind"]
        assert (harness.MODELS / f"{kind}.py").is_file(), (c["name"], kind)


def test_config_files_state_their_cuts():
    for c in MANIFEST["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]
        assert all(k in f["model"] for k in c["reduced"])
        assert f["numerics"]["matmul_operands"] in ("float32", "bfloat16")


def test_at_most_half_the_cells_take_four_chips():
    four = sum(1 for w in CELLS.values() if w["chips"] == 4)
    assert all(w["chips"] in (1, 4) for w in CELLS.values())
    assert four <= max(1, len(CELLS) // 2)


def test_per_layer_layers_are_named_alike():
    layers = {}
    for m in MANIFEST["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cli_refuses_without_a_tpu(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", str(2 ** 31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_result_line_keeps_the_checks_last():
    ctx = harness.Context(name="x", cell={"chips": 1}, config={},
                          traffic={}, seed=1, seconds=1.0, trace=False,
                          t_start=0.0)
    out = harness.Outcome(attempted=3, failed=0,
                          end_to_end={"setup_s": 1.5, "serve_p95_ms": 2.0},
                          checks={"out_err": (1e-6, 1e-4)}, correct=True,
                          memory_peak_bytes=5, window_s=1.0)
    metrics = [m for m in MANIFEST["end_to_end"]
               if m["name"] in ("setup_s", "serve_p95_ms")]
    line = harness.result_line(ctx, out, metrics)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["metrics"]["serve_p95_ms"] == {"value": 2.0, "unit": "ms"}
    assert line["checks"]["out_err"] == {"value": 1e-6, "limit": 1e-4}
