#!/usr/bin/env python3
"""Bring-up check on a TPU: the trainer and the Piper-IR lane through
their normal entry points, at qwen1.5-0.5b's published widths.

  python chip_smoke.py               # one chip: phases (a)-(c)
  python chip_smoke.py --four-chips  # four chips: phase (e) only

(a) Device check: prints platform, device kind and count; anything but
    a TPU fails.
(b) Trainer: ``repro.launch.train.main`` trains qwen1.5-0.5b as
    published (24 layers, d_model 1024, 16 heads, vocab 151936, bf16,
    full remat) for 10 steps at batch 4 x seq 1024, lr 3e-4, from a
    random seed.
    It prints the median step time after the warm-up step and the
    device's peak bytes in use; the loss must be finite and fall.
(c) IR lane: a one-device 1f1b strategy document runs one training step
    of the same config's proxy program on the ``spmd`` and ``reference``
    backends, on the same params and batch; their losses must agree to
    ``LOSS_RTOL``.
(e) ``--four-chips``: ZeRO-3 1f1b over a pp2 x dp2 mesh, on ``spmd`` and
    on ``mpmd``, each compared with ``reference`` as in (c).

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Everything runs in this one process, which holds the chips throughout.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import shutil
import sys
import tempfile

REPO = pathlib.Path(__file__).resolve().parent
ARCH = "qwen1.5-0.5b"
BATCH, SEQ, STEPS = 4, 1024, 10
# a peak lr usual at this model size; the trainer's default 3e-3 is
# sized for the reduced CPU configs and moves each bf16 weight of this
# model (std 0.02) by ~15% per Adam step
LR = 3e-4
# The IR lanes run the same bf16 chunk functions on the same params and
# batch; they differ only in how XLA fuses and orders the f32-accumulated
# reductions.  The loss is a mean over batch x d_model squared errors, so
# those rounding differences average out: the losses must agree to
# better than one bf16 rounding of the loss itself (2^-8 relative).
LOSS_RTOL = 2.0 ** -8


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_check(jax, want: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"(a) device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    check(dev["platform"] == "tpu",
          f"no TPU: JAX found platform {dev['platform']}")
    check(dev["count"] >= want,
          f"this phase needs {want} chips, JAX found {dev['count']}")
    return dev


def trainer_phase() -> None:
    from repro.configs import get_config
    from repro.launch import train
    cfg = get_config(ARCH)
    check(train.model_config(cfg) is cfg
          and (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab)
          == (24, 1024, 16, 151936)
          and (cfg.dtype, cfg.remat) == ("bfloat16", "full"),
          f"{ARCH} is not the published config: {cfg}")
    print(f"(b) trainer: {ARCH} as published, {STEPS} steps at batch "
          f"{BATCH} x seq {SEQ}", flush=True)
    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        rc = train.main(["--arch", ARCH, "--steps", str(STEPS),
                         "--batch", str(BATCH), "--seq", str(SEQ),
                         "--lr", str(LR), "--ckpt-dir", ckpt,
                         "--ckpt-every", str(STEPS)])
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    check(rc == 0, f"trainer exited {rc}")


def compare_lanes(strat, backends) -> None:
    """One training step of ``strat`` per backend on the same params and
    batch; each loss must match the reference lane's."""
    from repro.configs import get_config
    from repro.core.strategy import Strategy
    from repro.launch import train
    doc = Strategy.from_json(strat.to_json())    # the --strategy path
    cfg = get_config(ARCH)
    _, ref, _ = train.run_backend(cfg, doc, "reference", BATCH * SEQ)
    print(f"    reference: loss={ref.loss!r}", flush=True)
    check(math.isfinite(ref.loss), "reference loss is not finite")
    for name in backends:
        ex, res, batch = train.run_backend(cfg, doc, name, BATCH * SEQ)
        rel = abs(res.loss - ref.loss) / abs(ref.loss)
        line = (f"    {name}: loss={res.loss!r} "
                f"rel_diff_vs_reference={rel!r} (tolerance {LOSS_RTOL!r})")
        if name == "spmd":
            line += f" step_time={ex.measure(batch, reps=3) * 1e3:.3f}ms"
        print(line, flush=True)
        check(math.isfinite(res.loss) and rel <= LOSS_RTOL,
              f"{name} loss {res.loss!r} disagrees with reference "
              f"{ref.loss!r} (relative {rel!r} > {LOSS_RTOL!r})")


def ir_lane_phase() -> None:
    from repro.core.strategy import Mesh, Pipeline, Strategy
    strat = Strategy(Mesh(pp=1, dp=1), Pipeline("1f1b", n_mb=4))
    print(f"(c) IR lane: {strat.label()} on the {ARCH} proxy, "
          f"{BATCH * SEQ} tokens", flush=True)
    compare_lanes(strat, ["spmd"])


def four_chip_phase() -> None:
    from repro.core.strategy import Mesh, Pipeline, Strategy, ZeRO
    strat = Strategy(Mesh(pp=2, dp=2),
                     Pipeline("1f1b", n_mb=4) | ZeRO(stage=3))
    print(f"(e) four chips: {strat.label()} on the {ARCH} proxy, "
          f"{BATCH * SEQ} tokens", flush=True)
    compare_lanes(strat, ["spmd", "mpmd"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pp2 x dp2 ZeRO-3 phase on 4 chips")
    args = ap.parse_args(argv)
    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {__file__}; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        dev = device_check(jax, 4 if args.four_chips else 1)
        if args.four_chips:
            four_chip_phase()
        else:
            trainer_phase()
            ir_lane_phase()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
