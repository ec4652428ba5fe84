"""The three tiny cells read what they read before the sparse MLP's parts
moved from the harness and the reference into ``families/xml_mlp.py``: the
same data from the same seeds, the same plans and sample counts, the same
program and reference losses and deltas, and the same work counts.

``data/tiny_readings.json`` was written by the harness as it stood before
that move, on the CPU, at the seed below. The CPU backend repeats these
numbers to the bit from run to run, so they are compared exactly.

Under the sharded placement the reference later came to hold its replicas
one a device, as the program does, where it had held all four on one.
That rounds its numbers otherwise in the last bits, so the reference's
readings of that traffic are pinned anew under ``one_replica_a_device``,
beside the old ones, and the two are held within 1e-6 of each other."""
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
    pin = {**PINNED[traffic], **PINNED[traffic].get("one_replica_a_device", {})}
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
    ref = reference.run(family, config, tr, data, grids, seeds["program"],
                        devices=devices)
    assert jsonified(ref) == pin["reference_run"]
    counts = harness.window_work(cell, data, grids, test_batches, len(grids),
                                 devices)
    assert jsonified(counts) == pin["work"]


def leaves(x, path=""):
    if isinstance(x, dict):
        for k, v in x.items():
            yield from leaves(v, f"{path}/{k}")
    elif isinstance(x, list):
        for i, v in enumerate(x):
            yield from leaves(v, f"{path}/{i}")
    else:
        yield path, x


def test_one_replica_a_device_moves_only_the_last_bits():
    old = PINNED["adaptive.r4.sharded"]
    new = old["one_replica_a_device"]
    for key in ("reference", "reference_run"):
        a, b = dict(leaves(new[key])), dict(leaves(old[key]))
        assert a.keys() == b.keys()
        assert all(abs(a[k] - b[k]) <= 1e-6 * abs(b[k]) for k in a), key
    # the gaps are shares of the reference's numbers already
    assert new["values"].keys() == old["values"].keys()
    assert all(abs(new["values"][k] - old["values"][k]) <= 1e-6
               for k in new["values"])
