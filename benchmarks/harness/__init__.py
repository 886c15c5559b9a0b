"""The benchmark's own code: traffic generation, measurement, the reduction
from spans and traces to metrics, the table of peaks, the shapes functions.
Nothing here is imported by the program under test."""
