"""Each per-layer metric's reader, on the small trace recorded on a chip:
it reads its layer, returns a share of at most 100%, and returns nothing
where there is nothing to read."""
import glob
import os

import pytest

from chipbench import harness, trace

from conftest import ROOT
from test_trace import RECORDED

READERS = sorted(os.path.basename(p)[:-3] for p in
                 glob.glob(os.path.join(ROOT, "chipbench", "metrics", "*.py")))


def reduced(work=None):
    x = trace.load(RECORDED)
    r = trace.reduce(x, tuple(x["window"]))
    r.update(megabatches=2, eval_batches=16, work=work or {
        # stand-in counts of the size harness.window_work gives for two
        # mega-batches and their evaluations at Amazon widths
        "spmm_least_s": 0.0041, "weighted_merge_least_s": 0.0071,
        "model_flops": 2.64e12, "trained_samples": 5120,
        "peak_flops": 197e12, "chips": 1})
    return r


def record():
    return {"run": {"window": {"compiles": 0}}}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_recorded_trace(name):
    value = harness.metric_reader(os.path.join(ROOT, "chipbench"), name)(
        reduced(), record())
    assert value is not None and value >= 0
    if name.endswith("_roofline") or "share" in name or "mfu" in name:
        assert 0 < value <= 100 or name == "device_idle_share"


def test_readers_of_absent_layers_return_nothing():
    r = reduced()
    for d in r["devices"]:
        d["modules"] = {k: v for k, v in d["modules"].items() if "merge" not in k}
        d["ops"] = {k: v for k, v in d["ops"].items() if "weighted_merge" not in k}
    here = os.path.join(ROOT, "chipbench")
    assert harness.metric_reader(here, "merge_device_ms")(r, record()) is None
    assert harness.metric_reader(here, "weighted_merge_roofline")(r, record()) is None


def test_module_times_on_recorded_trace():
    r = reduced()
    here = os.path.join(ROOT, "chipbench")
    mb = harness.metric_reader(here, "megabatch_device_ms")(r, record())
    merge = harness.metric_reader(here, "merge_device_ms")(r, record())
    ev = harness.metric_reader(here, "eval_device_ms")(r, record())
    # per mega-batch, the three programs account for nearly all busy time
    busy_ms = 1e3 * r["devices"][0]["busy_s"] / r["megabatches"]
    assert 0.9 * busy_ms < mb + merge + ev <= 1.05 * busy_ms
