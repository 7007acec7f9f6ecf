"""The harness's refusals and the shape of ``BENCHMARK.json``.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
REPO = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

import cellspec  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELL = BENCH["workloads"][0]["name"]


def run_py(root: pathlib.Path, env=None):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, **(env or {})))


def test_refuses_without_a_tpu():
    out = run_py(REPO, {"JAX_PLATFORMS": "cpu",
                        "PYTHONPATH": str(REPO / "src")})
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = run_py(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    assert cellspec.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(cellspec.SpecError):
        cellspec.peaks_for("TPU v0 imaginary")


def test_benchmark_json_names_only_what_exists():
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    names = [c["name"] for c in BENCH["configs"]]
    cells = [w["name"] for w in BENCH["workloads"]]
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    for n in names + cells + metrics:
        assert NAME.match(n), n
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    assert len(set(metrics)) == len(metrics)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(names)
    for c in BENCH["configs"]:
        assert (REPO / c["file"]).is_file()
        assert json.loads((REPO / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    for w in BENCH["workloads"]:
        cellspec.load_cell(REPO / "BENCHMARK.json", w["name"])
        assert w["chips"] in (1, 4)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= set(cells)
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()
    for w in BENCH["workloads"]:
        assert any(w["name"] in m.get("workloads", cells)
                   for m in BENCH["per_layer"]), w["name"]


def test_flops_rule_matches_the_dense_count():
    c = cellspec.read_json(CHIP / "configs" / "qwen1.5-0.5b.json")
    rule = cellspec.load_plugin("flops", "dense_decoder")
    assert rule.params(c) == 463_987_712
    assert rule.per_token(c, {"seq": 2048}, {}) == pytest.approx(3.388e9,
                                                                 rel=1e-3)
