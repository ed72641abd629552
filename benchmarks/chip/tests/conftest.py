import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[3]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


import pytest  # noqa: E402


@pytest.fixture
def on_cpu(monkeypatch):
    """Let a whole run drive the CPU: skip the look for a chip, and count
    the work against the v5e's peaks."""
    import jax

    from benchmarks.chip import run, shapes

    peaks = shapes.peaks_for("TPU v5 lite")
    monkeypatch.setattr(run, "devices_for",
                        lambda cell: jax.devices()[:cell.chips])
    monkeypatch.setattr(shapes, "peaks_for", lambda kind: peaks)
