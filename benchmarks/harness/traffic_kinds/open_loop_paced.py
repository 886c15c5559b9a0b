"""Open loop, independent users: arrivals on a schedule whether or not
earlier requests have finished, each clocked from its due time."""

from benchmarks.harness.schedule import open_loop_plan as plan  # noqa: F401
from benchmarks.harness.serve_cell import run  # noqa: F401
