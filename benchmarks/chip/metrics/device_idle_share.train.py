"""Share of the traced training window with no program on the chip."""

from benchmarks.chip import readers


def read(run):
    return readers.idle_share(run)
