"""Device idle time per measured step while the program's ``ft.metrics``
span is open: ``Supervisor.run``'s host copies of the step's metrics,
its watchdog and its history record, which the chip waits for since the
loop dispatches the next step only after them; averaged over the cell's
chips."""
from __future__ import annotations

from scopes import span_reading


def read(r: dict):
    sr = span_reading(r, "ft.metrics")
    if sr is None:
        return None
    return sr.idle_under_ns["ft.metrics"] * 1e-6 / r["out"]["steps"]
