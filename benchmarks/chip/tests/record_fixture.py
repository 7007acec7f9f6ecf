"""Record the small profiler trace that ``test_trace.py`` reads.

Run on one chip from the root of a checkout:

  python3 benchmarks/chip/tests/record_fixture.py <out-dir>

It traces five steps of a small jitted program (a matmul, an
elementwise op and a reduction) inside the benchmark's own host spans
(``loader``, ``dispatch``, ``block``), copies the ``.xplane.pb`` to
``<out-dir>/fixture.xplane.pb`` and writes ``<out-dir>/planes.json``, a
summary of the trace's planes and lines.
"""
from __future__ import annotations

import glob
import json
import pathlib
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData, TraceAnnotation

    if jax.devices()[0].platform != "tpu":
        print("record_fixture: no TPU", file=sys.stderr)
        return 1

    @jax.jit
    def step(w, x):
        h = jnp.tanh(x @ w)
        return w - 1e-3 * (x.T @ h), jnp.sum(h * h)

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((1024, 1024)), jnp.bfloat16)
    xs = [rng.standard_normal((2048, 1024)).astype(np.float32)
          for _ in range(6)]
    w, _ = step(w, jnp.asarray(xs[0], jnp.bfloat16))
    jax.block_until_ready(w)
    out_dir = pathlib.Path(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    tmp = tempfile.mkdtemp(prefix="fixture_trace_")
    jax.profiler.start_trace(tmp)
    for x in xs[1:]:
        with TraceAnnotation("loader"):
            xb = np.tanh(x)          # host work the device waits for
            time.sleep(0.002)
        with TraceAnnotation("dispatch"):
            w, s = step(w, jnp.asarray(xb, jnp.bfloat16))
        with TraceAnnotation("block"):
            jax.block_until_ready((w, s))
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, out_dir / "fixture.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    pd = ProfileData.from_file(str(out_dir / "fixture.xplane.pb"))
    summary = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"name": line.name, "n": len(evs), "first": [
                {"name": e.name, "start_ns": e.start_ns,
                 "duration_ns": e.duration_ns,
                 "stats": [[k, str(v)] for k, v in e.stats]}
                for e in evs[:4]]})
        summary.append({"plane": plane.name,
                        "stats": [[k, str(v)] for k, v in plane.stats],
                        "lines": lines})
    (out_dir / "planes.json").write_text(json.dumps(summary, indent=1))
    print(json.dumps({"planes": [p["plane"] for p in summary],
                      "bytes": (out_dir / "fixture.xplane.pb").stat().st_size}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
