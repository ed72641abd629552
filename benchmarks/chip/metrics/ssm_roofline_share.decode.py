"""The mamba mixers' share of their roofline in decode, in %: the least
time of the bytes they must move per decode_step (their weights, and each
live request's conv window and SSM state read and written;
``hybrid_shapes.ssm_decode_bytes``) over the ``ssm`` scope's device time
per decode_step."""


def read(run):
    least = run.counters.get("ssm_least_ms.decode")
    ms = run.counters.get("ssm_device_ms.decode")
    return 100.0 * least / ms if least and ms else None
