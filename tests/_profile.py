"""Run a function under the JAX profiler and read back the program's own
host spans from the trace it writes."""

import glob
import os
import tempfile

import jax


def spans(fn, prefix: str) -> list[tuple[str, dict]]:
    """``(name, stats)`` of each host span named ``prefix...`` that
    ``fn()`` emits, in start order."""
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            fn()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
        found = [(ev.start_ns, ev.name, dict(ev.stats))
                 for line in host.lines for ev in line.events
                 if ev.name.startswith(prefix)]
    return [(name, stats) for _, name, stats in sorted(found,
                                                       key=lambda f: f[0])]
