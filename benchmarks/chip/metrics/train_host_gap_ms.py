"""Mean device-idle time between consecutive train steps, in ms."""

from benchmarks.chip import readers


def read(run):
    return readers.mean_host_gap_ms(run, "step")
