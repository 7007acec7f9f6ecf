"""Faked host XLA devices, without import-time side effects.

jax locks the host-platform device count when its backend first
initializes, controlled by ``XLA_FLAGS=--xla_force_host_platform_device_
count=N``.  Historically ``launch/dryrun.py`` mutated ``os.environ`` at
import time to get 512 devices — which silently poisoned the device
count of ANY process that imported it for its roofline helpers.  This
module is the explicit replacement: callers that need N devices (the
``--backend spmd`` executor, dryrun's ``__main__``, the spmd test
subprocesses) request them deliberately, and library imports never touch
jax state.

This module must stay importable before jax: it only touches
``os.environ`` until a caller asks for verification.
"""
from __future__ import annotations

import os

_FLAG = "--xla_force_host_platform_device_count="


def requested_host_devices() -> int | None:
    """The count currently requested via XLA_FLAGS, if any."""
    for part in os.environ.get("XLA_FLAGS", "").split():
        if part.startswith(_FLAG):
            try:
                return int(part[len(_FLAG):])
            except ValueError:
                return None
    return None


def ensure_host_devices(n: int, *, verify: bool = True) -> int:
    """Request at least ``n`` faked host-platform devices.

    Sets ``XLA_FLAGS`` (idempotently; an existing larger request is
    kept) and, with ``verify=True``, initializes jax and checks the
    request took effect.  Must be called before jax's backend first
    initializes — importing jax is fine, calling ``jax.devices()`` is
    not.  Raises ``RuntimeError`` with subprocess advice when the
    backend is already locked to fewer devices.

    Returns the number of devices available (``n`` unverified)."""
    if n < 1:
        raise ValueError(f"need a positive device count, got {n}")
    cur = requested_host_devices()
    if cur is None or cur < n:
        parts = [p for p in os.environ.get("XLA_FLAGS", "").split()
                 if not p.startswith(_FLAG)]
        parts.append(_FLAG + str(n))
        os.environ["XLA_FLAGS"] = " ".join(parts)
    if not verify:
        return n
    import jax
    devs = jax.devices()
    have = len(devs)
    if have < n and devs[0].platform != "cpu":
        raise RuntimeError(
            f"the plan needs {n} devices but platform "
            f"{devs[0].platform} has {have} ({devs[0].device_kind}); "
            "host devices are faked only on the cpu platform")
    if have < n:
        raise RuntimeError(
            f"jax initialized with {have} device(s) before "
            f"ensure_host_devices({n}) could take effect — the host "
            "device count locks at first backend use.  Call "
            "ensure_host_devices earlier (before anything touches jax "
            "devices), or run in a subprocess with "
            f"XLA_FLAGS={_FLAG}{n} set in its environment (see "
            "tests/test_spmd_executor.py)")
    return have
