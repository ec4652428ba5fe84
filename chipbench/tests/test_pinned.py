"""The three tiny cells read what they read before the sparse MLP's parts
moved from the harness and the reference into ``families/xml_mlp.py``: the
same data from the same seeds, the same plans and sample counts, the same
program and reference losses and deltas, and the same work counts.

``data/tiny_readings.json`` was written by the harness as it stood before
that move, on the CPU, at the seed below. The CPU backend repeats these
numbers to the bit from run to run, so they are compared exactly."""
import json
import os
import time

import jax
import pytest

from chipbench import harness, reference, work

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "tiny_readings.json")) as f:
    PINNED = json.load(f)


def jsonified(x):
    return json.loads(json.dumps(x))


@pytest.mark.parametrize("traffic", ["adaptive.r4", "adaptive.r4.sharded",
                                     "single.r1"])
def test_readings_equal_the_pinned(tiny_root, traffic, monkeypatch):
    pin = PINNED[traffic]
    seed = PINNED["seed"]
    cell = harness.load_cell(tiny_root, f"tiny.{traffic}")
    config, tr, family = cell["config"], cell["traffic"], cell["family"]
    devices = jax.devices()[:cell["chips"]]
    monkeypatch.setitem(work.PEAKS, devices[0].device_kind, work.PEAKS["TPU v5e"])

    rec = harness.run_cell(cell, seed, 0.0, False, devices, time.perf_counter(),
                           warm_only=True)
    assert jsonified(rec["data"]) == pin["data"]
    assert jsonified(rec["program"]) == pin["program"]
    assert jsonified(rec["check"]["reference"]) == pin["reference"]
    assert jsonified(rec["check"]["values"]) == pin["values"]

    # the pieces run_cell is made of, one by one
    seeds = harness.child_seeds(seed)
    data = family.make_data(config, tr, seeds)
    provider, test_batches = family.make_provider(data, config, tr, seeds["stream"])
    trainer = harness.build_trainer(family, config, tr, provider, devices,
                                    seeds["program"])
    loop = harness.Loop(trainer, trainer.init_state(), test_batches)
    for _ in range(tr["checked_megabatches"]):
        loop.step()
    loop.collect()
    grids = provider.grids[:tr["checked_megabatches"]]
    assert [float(i["train_loss"]) for i in loop.infos] == pin["loop_losses"]
    assert [sum(len(ids) for row in g for ids in row if ids is not None)
            for g in grids] == pin["samples"]
    ref = reference.run(family, config, tr, data, grids, seeds["program"])
    assert jsonified(ref) == pin["reference_run"]
    counts = harness.window_work(cell, data, grids, test_batches, len(grids),
                                 devices)
    assert jsonified(counts) == pin["work"]
