#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic, lane and limits are read from the files named
after it (``cellspec.py``).  One process holds every chip of the cell.
The run builds the lane's entry from the seed, warms up every shape in
set-up, measures for ``--seconds``, then checks what the timed path
produced against the plain reference.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
per-layer ones, read by ``metrics/<name>.py`` from the reduced trace
(``tracereduce.py``), the program's scopes, kernels and spans in it
(``scopes.py``) and the benchmark's host spans.  A traced run keys the
compile cache with the programs' metadata, so that the ops in its trace
carry the program's own names.  The last line of standard
output is one JSON object; the numbers compared for ``correct`` are
also the last lines of standard error.  Off a TPU, with fewer chips than
the cell asks for, on a device kind missing from ``peaks.json``, or
outside a checkout of the repository, it prints no result and exits 1.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    pass


class Context:
    """What a lane gets: the cell, the seed, the window's length, the
    host spans, and the calls that open and close the measured window."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 chips: list, make_step=None) -> None:
        from hostspans import Spans
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.chips = chips
        self.make_step = make_step
        self.spans = Spans()
        self.marks: dict[str, float] = {}
        self.memory_peak_bytes = None
        self.trace_dir = None
        self.compiles_in_window = 0
        self._in_window = False

    def mark(self, name: str) -> None:
        self.marks[name] = time.perf_counter() - T_START

    def on_compile(self, event: str, *args, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT and self._in_window:
            self.compiles_in_window += 1

    def window_opens(self) -> None:
        import jax
        self.mark("window_opens")
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            jax.profiler.start_trace(self.trace_dir)
        self.spans.open("window")
        self._in_window = True

    def window_closes(self) -> None:
        import jax
        self._in_window = False
        self.spans.close("window")
        if self.trace:
            jax.profiler.stop_trace()
        # the CPU backend, which tests drive, keeps no such counter
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.chips)


def quantile(values, q: float) -> float:
    """The ``q`` quantile (0 < q < 1) of ``values``, by linear
    interpolation between order statistics."""
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def end_to_end(bench: dict, cell, out: dict, ctx, peaks: dict) -> dict:
    from cellspec import flops_per_token
    tokens_per_s = out["tokens"] / out["window_s"]
    fpt = flops_per_token(cell, out["lane_info"])
    values = {
        "tokens_per_s": tokens_per_s,
        "mfu": 100.0 * fpt * tokens_per_s
        / (cell.chips * peaks["bf16_flops_per_s"]),
        "step_ms_p90": 1e3 * quantile(out["intervals"], 0.9),
        "peak_hbm_gib": ctx.memory_peak_bytes / 2**30,
        "setup_s": out["window"][0],
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if "workloads" not in m or cell.name in m["workloads"]}


def per_layer(bench: dict, cell, out: dict, ctx, reduced, scoped,
              peaks: dict) -> dict:
    from cellspec import load_plugin
    reported = {m["name"] for m in bench["end_to_end"]
                if "workloads" not in m or cell.name in m["workloads"]}
    readings = {"cell": cell, "out": out, "spans": ctx.spans,
                "reduced": reduced, "scopes": scoped, "peaks": peaks}
    metrics = {}
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell.name not in m["workloads"]:
                continue
        elif m["moves"] not in reported:
            continue
        value = load_plugin("metrics", m["name"]).read(readings)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench_json = REPO / "BENCHMARK.json"
    if not (REPO / "src" / "repro").is_dir() or not bench_json.is_file():
        print(f"run.py: no src/repro or BENCHMARK.json under {REPO}; run "
              "it from a checkout of the repository", file=sys.stderr)
        return 1
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(REPO / "src"))
    from cellspec import SpecError, load_cell
    try:
        cell = load_cell(bench_json, args.workload)
    except SpecError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return 1
    print(json.dumps(result))
    return 0


def device_check(cell):
    """The cell's chips; raises off a TPU, with too few chips, or on a
    device kind that ``peaks.json`` does not hold."""
    import jax
    from cellspec import peaks_for
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found platform {devs[0].platform}")
    if len(devs) < cell.chips:
        raise BenchError(f"{cell.name} needs {cell.chips} chips, JAX "
                         f"found {len(devs)}")
    return devs, peaks_for(devs[0].device_kind)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             make_step=None, devices=None):
    """One run of ``cell``; returns the result object, or None (with the
    reason on standard error) where it cannot run.  ``devices`` skips the
    look for a chip: ``(devices, peaks)``, for tests."""
    import jax
    from cellspec import SpecError, load_plugin
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # without it a traced run may load a program compiled with other op
    # names, and no op in its trace carries a scope
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      trace)
    try:
        devs, peaks = devices if devices is not None else device_check(cell)
    except (BenchError, SpecError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return None
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    ctx = Context(cell, seed, seconds, trace, devs[:cell.chips], make_step)
    ctx.mark("devices")
    jax.monitoring.register_event_duration_secs_listener(ctx.on_compile)
    lane = load_plugin("lanes", cell.lane)
    out = lane.run(ctx)
    out["window_host"] = out["window"]
    out["window"] = (out["window"][0] - T_START, out["window"][1] - T_START)
    if trace:
        import scopes
        import tracereduce
        path = next(pathlib.Path(ctx.trace_dir).rglob("*.xplane.pb"))
        tr = tracereduce.load(str(path), tracereduce.GAP_LABELS
                              + (tracereduce.WINDOW_SPAN,))
        reduced = tracereduce.reduce(tr)
        scoped = scopes.read(str(path))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        metrics = per_layer(bench, cell, out, ctx, reduced, scoped, peaks)
    else:
        reduced = None
        metrics = end_to_end(bench, cell, out, ctx, peaks)
    checks = out["checks"]
    correct = out["failed"] == 0 and all(v <= lim for _, v, lim in checks)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    if reduced is not None:
        device["busy_s"] = reduced.busy_ns * 1e-9
        device["window_s"] = reduced.window_ns * 1e-9
    log = sys.stderr
    print(f"set-up marks (s from process start): "
          f"{json.dumps({k: round(v, 3) for k, v in ctx.marks.items()})}",
          file=log)
    print(f"window: {out['steps']} steps, {len(out['intervals'])} "
          f"intervals (the step_ms_p90 samples) in {out['window_s']!r} s; "
          f"compiles in the window: {ctx.compiles_in_window}", file=log)
    for k, v in out.get("readings", {}).items():
        print(f"reading {k}: {v!r}", file=log)
    for name, v, lim in checks:
        print(f"check {name}={v!r} limit={lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=log)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced.top_ops,
                               "idle_gaps": reduced.idle_by_label}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


if __name__ == "__main__":
    sys.exit(main())
