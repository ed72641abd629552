"""Share of the traced prefill-cell window with no program on the chip."""

from benchmarks.chip import readers


def read(run):
    return readers.idle_share(run)
