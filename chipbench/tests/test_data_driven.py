"""A new configuration, traffic mix, model family and per-layer metric are
picked up from new files and new BENCHMARK.json entries alone: no file the
benchmark already has is edited. The readers the benchmark has read on the
recorded chip trace what they read before families were split out."""
import hashlib
import json
import os
import time

import jax
import pytest

from chipbench import harness, work

from conftest import ROOT, make_root
from test_metrics import reduced, record


def digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "chipbench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_from_files(tmp_path, monkeypatch):
    root = make_root(tmp_path, cells=("adaptive.r4",))
    before = digest(root)
    here = os.path.join(root, "chipbench")
    with open(os.path.join(here, "configs", "tiny.json")) as f:
        config = json.load(f)
    config.update(name="tiny-wide", n_classes=1536, avg_nnz=24)
    with open(os.path.join(here, "configs", "tiny-wide.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(here, "traffic", "tiny.adaptive.r4.json")) as f:
        traffic = json.load(f)
    traffic.update(replicas=2, b_max=64, b_min=8, beta=4.0)
    with open(os.path.join(here, "traffic", "adaptive.r2.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(here, "metrics", "traced_megabatches.py"), "w") as f:
        f.write("def read(trace, record):\n    return trace['megabatches']\n")
    with open(os.path.join(here, "limits", "wide.adaptive.r2.json"), "w") as f:
        json.dump({"loss_gap": {"limit": 1e-3}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-wide", "source": "test",
                             "file": "chipbench/configs/tiny-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "wide.adaptive.r2", "config": "tiny-wide",
                               "traffic": "adaptive.r2", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "traced_megabatches", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "scan engine", "moves": "samples_per_s",
                               "workloads": ["wide.adaptive.r2"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = harness.load_cell(root, "wide.adaptive.r2")
    assert cell["config"]["n_classes"] == 1536 and cell["traffic"]["replicas"] == 2
    monkeypatch.setitem(work.PEAKS, jax.devices()[0].device_kind,
                        work.PEAKS["TPU v5e"])
    devices = jax.devices()[:1]
    rec = harness.run_cell(cell, 5, 0.5, True, devices, time.perf_counter())
    out, _ = harness.result(cell, rec, True, devices)
    assert out["correct"], out["compared"]
    assert out["metrics"]["traced_megabatches"]["value"] == rec["window"]["attempted"] > 0
    assert list(out["compared"]) == ["loss_gap"]
    assert rec["data"]["samples"] == traffic["samples"]
    after = digest(root)
    assert all(after[p] == h for p, h in before.items())


# a second family, written as a new file: the sparse MLP trained through the
# program's dense autodiff path, its work counted without the kernel
DENSE_FAMILY = """
import os

from chipbench import harness

_base = harness.load_named(os.path.dirname(os.path.dirname(__file__)),
                           "families", "xml_mlp")

make_data, make_provider, make_model = (
    _base.make_data, _base.make_provider, _base.make_model)
n_params, init_params, loss, pack = (
    _base.n_params, _base.init_params, _base.loss, _base.pack)
TRAINER_KWARGS = {"sparse_grads": False}
CALLS = []


def train_work(config, data, grids, kind):
    CALLS.append("train_work")
    counts = _base.train_work(config, data, grids, kind)
    return {"model_flops": counts["model_flops"],
            "trained_samples": counts["trained_samples"]}


def eval_work(config, data, test_batches, kind):
    CALLS.append("eval_work")
    return {}
"""


def test_new_family_from_files(tmp_path, monkeypatch):
    root = make_root(tmp_path, cells=("adaptive.r4",))
    before = digest(root)
    here = os.path.join(root, "chipbench")
    with open(os.path.join(here, "families", "xml_mlp_dense.py"), "w") as f:
        f.write(DENSE_FAMILY)
    with open(os.path.join(here, "configs", "tiny.json")) as f:
        config = json.load(f)
    config.update(name="tiny-dense", family="xml_mlp_dense")
    with open(os.path.join(here, "configs", "tiny-dense.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(here, "limits", "dense.adaptive.r4.json"), "w") as f:
        with open(os.path.join(here, "limits", "tiny.adaptive.r4.json")) as g:
            f.write(g.read())
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-dense", "source": "test",
                             "file": "chipbench/configs/tiny-dense.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dense.adaptive.r4", "config": "tiny-dense",
                               "traffic": "tiny.adaptive.r4", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in ("train_step_mfu", "window_compiles"):
            m["workloads"].append("dense.adaptive.r4")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = harness.load_cell(root, "dense.adaptive.r4")
    family = cell["family"]
    assert family.TRAINER_KWARGS == {"sparse_grads": False}
    monkeypatch.setitem(work.PEAKS, jax.devices()[0].device_kind,
                        work.PEAKS["TPU v5e"])
    devices = jax.devices()[:1]
    rec = harness.run_cell(cell, 6, 0.5, True, devices, time.perf_counter())
    out, _ = harness.result(cell, rec, True, devices)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert family.CALLS == ["train_work", "eval_work"]
    assert "spmm_least_s" not in rec["trace"]["work"]
    assert rec["trace"]["work"]["trained_samples"] == rec["window"]["samples"]
    assert set(out["metrics"]) == {"train_step_mfu", "window_compiles"}
    after = digest(root)
    assert all(after[p] == h for p, h in before.items())


# each reader on the recorded trace, as read before families were split out
PINNED_READINGS = {
    "device_idle_share": 1.0814017849062885,
    "eval_device_ms": 63.40865499999998,
    "megabatch_device_ms": 380.9966645,
    "merge_device_ms": 153.441105,
    "spmm_roofline": 4.4530503443733895,
    "train_step_mfu": 1.100623642774749,
    "weighted_merge_roofline": 18.614897465736398,
    "window_compiles": 0,
}


@pytest.mark.parametrize("name", sorted(PINNED_READINGS))
def test_reader_reads_as_pinned(name):
    value = harness.metric_reader(os.path.join(ROOT, "chipbench"), name)(
        reduced(), record())
    assert value == PINNED_READINGS[name]
