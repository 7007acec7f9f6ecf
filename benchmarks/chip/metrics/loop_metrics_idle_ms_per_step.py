"""Device idle time per measured step while the program's ``ft.metrics``
span is open: ``Supervisor.run``'s host copies of the step's metrics,
its watchdog and its history record, which the chip waits for since the
loop dispatches the next step only after them; averaged over the cell's
chips."""
from __future__ import annotations

from scopes import idle_ms_per_step


def read(r: dict):
    return idle_ms_per_step(r, "ft.metrics")
