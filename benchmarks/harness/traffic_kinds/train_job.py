"""A training job: whole steps on fresh seeded batches of packed sequences
for the length of the window."""

from benchmarks.harness.train_cell import plan, run  # noqa: F401
