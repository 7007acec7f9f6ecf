#!/usr/bin/env python3
"""Compile each named cell's step for a described TPU v5e chip, from
shapes alone, and print what the compiler says it needs per device:
arguments, outputs (less those aliased to donated arguments) and
temporaries.  Nothing runs; no chip is needed.  A four-chip cell is
compiled for the described 2x2 mesh.

  PYTHONPATH=src JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_check.py \
      qwen1.5-0.5b.train-s2048-b4 minicpm-2b-8l.train-s4096-b1
"""
from __future__ import annotations

import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]


def main(names) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(REPO / "src"))
    from cellspec import load_cell, load_plugin
    cells = [load_cell(REPO / "BENCHMARK.json", n) for n in names]
    # a multi-chip lane builds its executor on as many host devices
    # before it is pointed at the described chips
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count="
                               f"{max(c.chips for c in cells)}")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    gib = 2.0 ** -30
    for cell in cells:
        lane = load_plugin("lanes", cell.lane)
        devices = topo.devices[:cell.chips]
        mem = lane.compile_for(
            cell, devices if cell.chips > 1 else devices[0]).memory_analysis()
        print(json.dumps({
            "cell": cell.name,
            "argument_gib": mem.argument_size_in_bytes * gib,
            "output_gib": mem.output_size_in_bytes * gib,
            "alias_gib": mem.alias_size_in_bytes * gib,
            "temp_gib": mem.temp_size_in_bytes * gib,
            "total_gib": (mem.argument_size_in_bytes
                          + mem.output_size_in_bytes
                          - mem.alias_size_in_bytes
                          + mem.temp_size_in_bytes) * gib}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
