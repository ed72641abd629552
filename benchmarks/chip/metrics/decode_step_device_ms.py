"""Mean device time of one decode_step program, in ms."""

from benchmarks.chip import readers


def read(run):
    return readers.mean_device_ms(run, "decode_step")
