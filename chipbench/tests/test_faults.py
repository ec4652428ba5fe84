"""A run with the timed path broken underneath comes out not correct, once
for each fault the cell can have; the same run unbroken comes out correct.
The harness's look for a chip is skipped: the run is on CPU devices, at a
tiny configuration, through the repository's traffic mixes."""
import time

import jax
import pytest

from chipbench import faults, harness


def run(root, workload, fault=None):
    cell = harness.load_cell(root, workload)
    devices = jax.devices()[:cell["chips"]]
    if fault is None:
        rec = harness.run_cell(cell, 2**33 + 11, 0.5, False, devices,
                               time.perf_counter())
    else:
        with faults.plant(fault, cell["family"]):
            rec = harness.run_cell(cell, 2**33 + 11, 0.5, False, devices,
                                   time.perf_counter())
    out, lines = harness.result(cell, rec, False, devices)
    assert len(lines) == len(out["compared"]) > 0
    return out


CASES = [("tiny.adaptive.r4", None), ("tiny.single.r1", None),
         ("tiny.adaptive.r4.sharded", None)] + [
    (f"tiny.{traffic}", fault)
    for traffic, placement in (("adaptive.r4", "vmap"), ("single.r1", "vmap"),
                               ("adaptive.r4.sharded", "sharded"))
    for fault in faults.applicable({"placement": placement})
]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_makes_run_incorrect(tiny_root, workload, fault):
    out = run(tiny_root, workload, fault)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is (fault is None), out["compared"]
