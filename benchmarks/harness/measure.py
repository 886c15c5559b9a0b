"""What one run measured, in the form every reader takes it."""

from __future__ import annotations

import dataclasses
import sys
import time

_T0 = time.monotonic()


def log(msg: str) -> None:
    """Progress, on stderr, with the seconds since the harness was imported."""
    print(f"[bench {time.monotonic() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Measurement:
    config: dict                    # the configuration file
    traffic: dict                   # the traffic file (with any override)
    peaks: dict                     # this device's row of the peak table
    family: object = None           # the configuration's family module (its shapes functions)
    series: dict = dataclasses.field(default_factory=dict)    # name -> [seconds]
    counters: dict = dataclasses.field(default_factory=dict)  # name -> number
    notes: dict = dataclasses.field(default_factory=dict)     # goes to "diag"
    trace: object = None            # xplane.TraceSummary of a --trace 1 run
    correct: bool = False
    attempted: int = 0
    failed: int = 0
