"""Run guard: a wall-clock budget watchdog, so a hung sustained loop fails
loudly with stacks instead of eating the CI job timeout."""
from __future__ import annotations

import faulthandler
import os
import sys
import threading


class Watchdog:
    """Context manager: if the body runs longer than ``budget_s``, dump all
    thread stacks to stderr and (by default) hard-exit. Benches wrap their
    sustained loops in one so a deadlocked ring barrier is diagnosable."""

    def __init__(self, budget_s: float, *, hard: bool = True,
                 label: str = "bench"):
        self.budget_s = float(budget_s)
        self.hard = hard
        self.label = label
        self._timer: threading.Timer | None = None

    def _fire(self) -> None:
        sys.stderr.write(
            f"\nWATCHDOG: {self.label} exceeded {self.budget_s:.0f}s "
            "wall-clock budget; dumping stacks\n")
        faulthandler.dump_traceback(file=sys.stderr)
        if self.hard:
            os._exit(2)

    def __enter__(self) -> "Watchdog":
        self._timer = threading.Timer(self.budget_s, self._fire)
        self._timer.daemon = True
        self._timer.start()
        return self

    def __exit__(self, *exc) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

