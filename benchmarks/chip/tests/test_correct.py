"""The comparison that decides ``correct``, driven through a whole run of
each training cell at a size the CPU holds: the look for a chip is
skipped, the rest of ``run.run_cell`` runs with the cell's own lane and
with limits read at this size.  The program passes; the control (the
plain reference in the program's place, its matmuls in float8) and each
fault the cell can have come out not correct.  The Piper-IR lane is
driven too, on four host devices, on the four-chip cell that waits for
the IR lane to run the model's own layers (``PERF.md``).  A traced run
hands the per-layer readers the program's scopes.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import copy
import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import cellspec  # noqa: E402
import run as bench_run  # noqa: E402

BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
# the pp2 x dp2, 1f1b over 4 microbatches, ZeRO-3 cell of the IR lane
IR_CELL = "qwen1.5-0.5b-proxy.pp2dp2-zero3-1f1b"
IR_STRATEGY = {
    "fragments": [
        {"axis": "pp", "cap_offset": None, "kind": "pipeline",
         "mb_split": None, "n_mb": 4, "n_stages": None,
         "p2p_stream": "pp_comm", "schedule": "1f1b",
         "split_backward": None},
        {"axis": "dp", "bucket_mb": 0, "gather_stream": "ag",
         "kind": "zero", "reduce_stream": "dp", "stage": 3}],
    "mesh": {"axes": [["pp", 2], ["dp", 2]]}, "schema": 3}
CELLS = [w["name"] for w in BENCH["workloads"]] + [IR_CELL]
FIXTURE = HERE / "fixtures" / "scopes_one_chip.xplane.pb.xz"

SMALL = {"hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256}
PROGRAM_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                "num_hidden_layers": "n_layers",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
                "vocab_size": "vocab"}
# Limits at this size, read on the CPU over seeds 11-13 as the chip's
# were at the cells' own sizes (PERF.md): the program reads at most
# 1e-4 (loss), 2.7e-3 (gradient) and 1.8e-2 (change); the control and
# the faults read at least 2e-2 on the gradient or 0.98 on the change.
# Narrow leaves in bf16 round more than the published widths do, so the
# cells' own limits would fail the program here.
SMALL_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 1e-2,
                "change_norm_gap": 0.1}


def full_cell(name: str) -> cellspec.Cell:
    if name != IR_CELL:
        return cellspec.load_cell(CHIP.parents[1] / "BENCHMARK.json", name)
    return cellspec.Cell(
        name=name, chips=4, config_name="qwen1.5-0.5b-proxy",
        traffic_name="ir-t4096",
        config=cellspec.read_json(CHIP / "configs"
                                  / "qwen1.5-0.5b-proxy.json"),
        traffic=cellspec.read_json(CHIP / "traffic" / "ir-t4096.json"),
        workload={"lane": "piper_spmd", "strategy": IR_STRATEGY,
                  "limits": {k: SMALL_LIMITS[k]
                             for k in ("loss_gap", "grad_norm_gap")}})


def small_cell(name: str) -> cellspec.Cell:
    """The cell with its configuration's widths and depth, and its
    tokens a step, cut to a CPU's size, with limits for that size; the
    lane, the strategy and the batch's row count as they are."""
    cell = full_cell(name)
    c = copy.deepcopy(cell.config)
    c.update(SMALL)
    for k, v in SMALL.items():
        c["program"][PROGRAM_KEYS[k]] = v
    traffic = dict(cell.traffic)
    if "seq" in traffic:
        traffic.update(batch=min(traffic["batch"], 2), seq=32)
    else:
        traffic.update(tokens=256)
    workload = dict(cell.workload, limits={
        k: SMALL_LIMITS[k] for k in cell.workload["limits"]})
    return cellspec.Cell(name=name, chips=cell.chips,
                         config_name=cell.config_name,
                         traffic_name=cell.traffic_name, config=c,
                         traffic=traffic, workload=workload)


def run_small(name: str, modes) -> dict:
    """{mode: result} of a run of the small cell for each mode, in this
    process for a one-chip cell, else in a child that fakes the cell's
    chips as host devices."""
    cell = small_cell(name)
    if cell.chips > 1:
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
            f"--xla_force_host_platform_device_count={cell.chips}"))
        out = subprocess.run(
            [sys.executable, __file__, name, *modes], env=env,
            capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-4000:]
        return json.loads(out.stdout.strip().splitlines()[-1])
    return run_here(cell, modes)


def run_here(cell: cellspec.Cell, modes) -> dict:
    import jax
    lane = cellspec.load_plugin("lanes", cell.lane)
    results = {}
    for mode in modes:
        res = bench_run.run_cell(
            cell, 2**33 + 7, 0.5, False, make_step=lane.fault_step(mode),
            devices=(jax.devices()[:cell.chips],
                     {"bf16_flops_per_s": 1e12}))
        assert res is not None and list(res)[-1] == "checks"
        results[mode] = res
    return results


@pytest.mark.parametrize("name", CELLS)
def test_program_correct_control_and_faults_not(name):
    lane = cellspec.load_plugin("lanes", small_cell(name).lane)
    results = run_small(name, ("program", "fp8") + lane.FAULTS)
    prog = results.pop("program")
    assert prog["correct"], prog["checks"]
    assert prog["attempted"] > 0 and prog["failed"] == 0
    for mode, res in results.items():
        assert not res["correct"], (mode, res["checks"])


def test_traced_run_hands_readers_the_scope_reading(monkeypatch, tmp_path):
    """A traced run on the CPU, its profiler's trace replaced by the one
    recorded on the chip: ``run_cell`` keys the compile cache with the
    programs' metadata and hands every reader the trace's scope
    reading."""
    import lzma

    import jax

    import scopes
    recorded = tmp_path / "scopes_one_chip.xplane.pb"
    recorded.write_bytes(lzma.decompress(FIXTURE.read_bytes()))

    def start_trace(log_dir):
        out = pathlib.Path(log_dir) / "plugins" / "profile" / "run"
        out.mkdir(parents=True)
        (out / recorded.name).write_bytes(recorded.read_bytes())

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    seen = {}
    load_plugin = cellspec.load_plugin

    def recording(kind, name, *args):
        mod = load_plugin(kind, name, *args)
        if kind != "metrics":
            return mod

        class Reader:
            @staticmethod
            def read(r):
                seen[name] = r
                return mod.read(r)
        return Reader

    monkeypatch.setattr(cellspec, "load_plugin", recording)
    cell = small_cell(BENCH["workloads"][0]["name"])
    lane = cellspec.load_plugin("lanes", cell.lane)
    try:
        res = bench_run.run_cell(
            cell, 2**33 + 11, 0.5, True, make_step=lane.fault_step("program"),
            devices=(jax.devices()[:1], cellspec.peaks_for("TPU v5 lite")))
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
    want = {m["name"] for m in BENCH["per_layer"]
            if cell.name in m.get("workloads", CELLS)}
    assert set(seen) == want
    sr = seen["attn_device_ms_per_step"]["scopes"]
    assert isinstance(sr, scopes.ScopeReading)
    assert all(r["scopes"] is sr for r in seen.values())
    assert sr.busy_ns == scopes.read(str(recorded)).busy_ns > 0
    assert res["correct"]
    assert "attn_device_ms_per_step" in res["metrics"]
    assert res["device"]["busy_s"] == pytest.approx(sr.busy_ns * 1e-9)


if __name__ == "__main__":
    print(json.dumps(run_here(small_cell(sys.argv[1]), sys.argv[2:])))
