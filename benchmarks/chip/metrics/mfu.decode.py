"""Least chip time of the decode cell's required work over its window."""

from benchmarks.chip import readers


def read(run):
    return readers.mfu(run)
