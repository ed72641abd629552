"""Device time of the ``ssm`` scope in prefill per 1000 prompt tokens, in
ms, as ``drive_serve_hybrid.py`` reads it from the traced window."""


def read(run):
    return run.counters.get("ssm_device_ms_per_ktok.prefill")
