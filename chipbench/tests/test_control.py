"""The control: the reference computed in bfloat16, one step below the
configuration's float32, put in the program's place. Held to each cell's
limits it comes out not correct, while the program itself passes them."""
import time

import jax
import pytest

from chipbench import compare, harness


@pytest.mark.parametrize("traffic", ["adaptive.r4", "single.r1",
                                     "adaptive.r4.sharded"])
def test_control_fails_the_limits(tiny_root, traffic):
    cell = harness.load_cell(tiny_root, f"tiny.{traffic}")
    devices = jax.devices()[:cell["chips"]]
    rec = harness.run_cell(cell, 2**33 + 21, 0.0, False, devices,
                           time.perf_counter(), warm_only=True,
                           controls=("bfloat16",))
    assert rec["check"]["ok"], rec["check"]["compared"]
    ok, compared = compare.judge(rec["check"]["controls"]["bfloat16"],
                                 cell["limits"])
    assert not ok, compared
