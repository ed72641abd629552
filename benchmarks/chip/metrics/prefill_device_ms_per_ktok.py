"""Device time of the prefill programs per thousand prompt tokens."""

from benchmarks.chip import readers


def read(run):
    return readers.device_ms_per_ktok(run, "prefill", "prefill_tokens")
