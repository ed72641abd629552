"""Device time of the ``ssm`` scope (the mamba mixers with their state
updates) per decode_step, in ms, as ``drive_serve_hybrid.py`` reads it from the
traced window."""


def read(run):
    return run.counters.get("ssm_device_ms.decode")
