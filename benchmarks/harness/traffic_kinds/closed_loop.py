"""Closed loop: `clients` callers, each sending its next request when the
last one has completed (an offline queue worked off by a fixed pool)."""

from benchmarks.harness.schedule import closed_loop_plan as plan  # noqa: F401
from benchmarks.harness.serve_cell import run  # noqa: F401
