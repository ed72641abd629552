"""Mean device-idle time between consecutive decode_step programs, in ms."""

from benchmarks.chip import readers


def read(run):
    return readers.mean_host_gap_ms(run, "decode_step")
