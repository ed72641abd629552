"""Record ``data/layers.xplane.pb``, the fixture of ``test_layer_trace.py``,
on a TPU:

    python3 benchmarks/chip/tests/record_layers.py <out.xplane.pb>

One ``window`` span holds a 2-layer granite model at smoke-test widths
served through ``Engine.generate`` (2 requests of 8 tokens, 3 new tokens,
under a ``generate`` span) and trained one step through ``Trainer.run``
(batch 2 x 64), each compiled before the trace starts.  To keep the file
small the Python tracer is off, and ``trim`` drops what the reductions do
not read: the ``/host:metadata`` plane (the programs' HLO) and all but the
instruction's name, ``tf_op`` and ``program_id`` of each operation's event
metadata.
"""

from __future__ import annotations

import glob
import os
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[3]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

DROP = "/host:metadata"
# of each operation's event metadata on the device plane, the reduction
# reads the name and these stats
KEEP_STATS = {"tf_op", "program_id"}


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _raw(buf):
    """(field number, value, the field's own bytes) of each field."""
    from benchmarks.chip.layer_trace import _fields, _varint as read

    buf = memoryview(buf)
    i = 0
    for field, value in _fields(buf):
        key, j = read(buf, i)
        wire = key & 7
        if wire == 2:
            size, j = read(buf, j)
            j += size
        elif wire == 0:
            _, j = read(buf, j)
        else:
            j += 8 if wire == 1 else 4
        yield field, value, buf[i:j]
        i = j


def _message(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def trim(data: bytes) -> bytes:
    """The serialized XSpace without the ``DROP`` plane, and with the
    device planes' event metadata cut to ``KEEP_STATS`` and the name of its
    instruction (XPlane.event_metadata = 4, a map entry's value = 2,
    XEventMetadata.name = 2, .stats = 5; XPlane.stat_metadata = 5)."""
    from benchmarks.chip.layer_trace import _fields, _map_value, _str

    out = bytearray()
    for field, plane, raw in _raw(data):
        name = (_str(next((v for f, v in _fields(plane) if f == 2), b""))
                if field == 1 else "")
        if name == DROP:
            continue
        if not name.startswith("/device:TPU:"):
            out += raw
            continue
        keep = {dict(_fields(_map_value(e))).get(1)
                for f, e in _fields(plane) if f == 5
                and _str(dict(_fields(_map_value(e))).get(2, b""))
                in KEEP_STATS}
        body = bytearray()
        for f, entry, entry_raw in _raw(plane):
            if f != 4:
                body += entry_raw
                continue
            meta = bytearray()
            for k, v, r in _raw(_map_value(entry)):
                if k == 1 or (k == 5 and dict(_fields(v)).get(1) in keep):
                    meta += r
                elif k == 2:        # "%fusion.3 = <shape> fusion(...)"
                    meta += _message(2, _str(v).split(" = ")[0].encode())
            key = b"".join(bytes(r) for k, _, r in _raw(entry) if k == 1)
            body += _message(4, key + _message(2, bytes(meta)))
        out += _message(1, bytes(body))
    return bytes(out)


def main(out_path: str) -> None:
    import jax
    import numpy as np

    from repro.configs import registry
    from repro.data.pipeline import DataConfig
    from repro.models import model as model_lib
    from repro.optim import adamw
    from repro.serve.engine import Engine, ServeConfig
    from repro.train import train_step as ts
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = registry.get("granite-3-2b").reduced().with_depth(2)
    model = model_lib.build(cfg)
    params = model.init(jax.random.key(0))
    engine = Engine(model, params, ServeConfig(max_batch=2, max_len=32))
    prompts = np.random.default_rng(0).integers(
        2, cfg.vocab_size, size=(2, 8)).tolist()
    engine.generate(prompts, max_new=3)
    opt = adamw.AdamWConfig(total_steps=4)
    step = jax.jit(ts.make_train_step(model, opt), donate_argnums=(0,))
    loop = Trainer(step, ts.make_train_state(model, opt, jax.random.key(1)),
                   DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                              global_batch=2),
                   None, TrainerConfig(total_steps=1, log_every=1))
    loop.run()
    loop.start_step, loop.cfg.total_steps = 1, 2

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp, profiler_options=options):
            with jax.profiler.TraceAnnotation("window"):
                with jax.profiler.TraceAnnotation("generate"):
                    engine.generate(prompts, max_new=3)
                loop.run()
        (found,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                          "*.xplane.pb"))
        data = trim(pathlib.Path(found).read_bytes())
    pathlib.Path(out_path).write_bytes(data)
    print(f"{out_path}: {len(data)} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
