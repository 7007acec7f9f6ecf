"""Record the small profiler trace that ``test_scopes.py`` reads.

Run on one chip from the root of a checkout:

  python3 benchmarks/chip/tests/record_scopes_fixture.py <out-dir>

It trains a small model of the program (the qwen1.5-0.5b family at two
layers of width 128, bf16, full remat: ``build_step``, ``TokenLoader``
over ``SyntheticTokenSource``, ``Supervisor.run``) for two steps, then
traces five more inside a ``window`` span, and writes the
``.xplane.pb``, compressed by ``lzma``, to
``<out-dir>/scopes_one_chip.xplane.pb.xz``.  The trace holds the
program's device scopes and host spans.  The compile cache is left off,
so the step is compiled with its own op names, and so is the Python
tracer, whose event per function call would swell the file.  It prints
the file's size and what ``scopes.py`` reads from it.
"""
from __future__ import annotations

import dataclasses
import json
import lzma
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[2]
STEPS = 5
# the program's host spans, in ``Supervisor.run`` and ``TokenLoader``
SPANS = ("train_step", "data.block", "ft.sync", "ft.metrics")


def main(out: str) -> int:
    sys.path.insert(0, str(HERE.parent))
    sys.path.insert(0, str(REPO / "src"))
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    import cellspec
    import scopes
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config
    from repro.data import SyntheticTokenSource, TokenLoader
    from repro.ft import Supervisor
    from repro.launch.train import build_step
    from repro.models import init
    from repro.optim import adamw_init, cosine_schedule

    if jax.devices()[0].platform != "tpu":
        print("record_scopes_fixture: no TPU", file=sys.stderr)
        return 1
    cfg = dataclasses.replace(
        get_config("qwen1.5-0.5b").reduced(n_layers=2, d_model=128,
                                           d_ff=256, vocab=512, n_heads=2,
                                           dtype="bfloat16"),
        remat="full")
    params = init(cfg, jax.random.PRNGKey(0))
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    loader = TokenLoader(SyntheticTokenSource(cfg.vocab, seed=0), batch=2,
                         seq=256)
    ckpt_dir = tempfile.mkdtemp(prefix="fixture_ckpt_")
    sup = Supervisor(CheckpointManager(ckpt_dir, keep=1, async_save=False),
                     loader, checkpoint_every=1 << 30)
    step = build_step(cfg, cosine_schedule(1e-3, 100))
    state = sup.run(state, step, n_steps=2, log_every=0)
    trace_dir = tempfile.mkdtemp(prefix="fixture_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with TraceAnnotation("window"):
        state = sup.run(state, step, n_steps=2 + STEPS, log_every=0)
        jax.block_until_ready(state)
    jax.profiler.stop_trace()
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    out_dir = pathlib.Path(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = next(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    red = scopes.read(str(path))
    dest = out_dir / "scopes_one_chip.xplane.pb.xz"
    dest.write_bytes(lzma.compress(path.read_bytes()))
    shutil.rmtree(trace_dir, ignore_errors=True)
    program = cellspec.load_plugin("metrics", "unscoped_device_share").SCOPES
    print(json.dumps({
        "bytes": dest.stat().st_size, "busy_ns": red.busy_ns,
        "scope_busy_ns": {sc: red.busy_under((sc,)) for sc in program},
        "unscoped_ns": red.unscoped_ns(program),
        "span_count": {n: red.span_count(n) for n in SPANS}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
