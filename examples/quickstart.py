"""Quickstart: the paper in five minutes.

1. Reproduce Table II (copy latency/energy) from the command-level models.
2. Run the Fig-8 matrix-multiply workload through the cycle-accurate
   scheduler under both interconnects and see the concurrency win.
3. Compute with the pLUTo LUT-ALU (bit-exact in-DRAM-style arithmetic).
4. Train a reduced LM for a few steps with the framework's trainer.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import copy_models, scheduler, taskgraph
from repro.core.pluto import Interconnect
from repro.core import pluto_alu


def copy_latency_demo():
    print("== Table II: 8KB inter-subarray copy ==")
    for name, (lat, en) in copy_models.table2().items():
        print(f"  {name:28s} {lat:9.2f} ns   {en:6.3f} uJ")
    bc = copy_models.sharedpim_broadcast(dests=(1, 2, 3, 4))
    print(f"  broadcast to 4 subarrays     {bc.latency_ns:9.2f} ns "
          f"(vs {4 * 52.75:.2f} serial)")


def scheduler_demo():
    print("\n== Fig 8: matrix multiply, LISA vs Shared-PIM ==")
    res = {m: scheduler.schedule(taskgraph.build("mm", m, n=200), m)
           for m in Interconnect}
    lisa, sp = res[Interconnect.LISA], res[Interconnect.SHARED_PIM]
    print(f"  LISA:       {lisa.makespan_ns/1e3:9.1f} us  "
          f"(stalled {lisa.stall_ns/1e3:.1f} us of PE time)")
    print(f"  Shared-PIM: {sp.makespan_ns/1e3:9.1f} us  "
          f"(stall -> NOP; bus busy {sp.move_busy_ns/1e3:.1f} us)")
    print(f"  improvement: {(1 - sp.makespan_ns/lisa.makespan_ns)*100:.1f}% "
          f"(paper: 40%)")


def lut_alu_demo():
    print("\n== pLUTo LUT-ALU: arithmetic as table lookups ==")
    x = jnp.asarray(np.array([123456789, 7, 2**31], dtype=np.uint32))
    y = jnp.asarray(np.array([987654321, 6, 2], dtype=np.uint32))
    print(f"  add: {np.asarray(pluto_alu.pluto_add(x, y))}")
    print(f"  mul: {np.asarray(pluto_alu.pluto_mul(x, y))}")
    print("  (bit-identical to uint32 arithmetic, computed via 4-bit LUTs)")


def device_demo():
    print("\n== Device scale: mm across a 2-channel x 4-bank device ==")
    from repro import device
    geom = device.DeviceGeometry(channels=2, banks_per_channel=4,
                                 bank_groups_per_channel=2)
    print(f"  geometry: {geom.describe()}")
    for policy in device.POLICIES:
        res = {}
        for m in Interconnect:
            tasks = device.build_partitioned("mm", m, geom, policy=policy,
                                             n=100)
            res[m.value] = device.schedule(tasks, m, geom)
        sp = res["shared_pim"]
        print(f"  {policy:20s} improvement {device.improvement(res)*100:5.1f}%"
              f"  cross-bank rows {sp.cross_rows:6d}"
              f"  (LISA stalled {res['lisa'].stall_ns/1e3:.0f} us of PE time)")


def train_demo():
    print("\n== Train a reduced granite-3-2b for 10 steps ==")
    from repro.launch.train import main as train_main
    train_main(["--arch", "granite-3-2b", "--smoke", "--steps", "10",
                "--batch", "4", "--seq", "64"])


if __name__ == "__main__":
    copy_latency_demo()
    scheduler_demo()
    lut_alu_demo()
    device_demo()
    train_demo()
