"""Least chip time of the training window's required work over it."""

from benchmarks.chip import readers


def read(run):
    return readers.mfu(run)
