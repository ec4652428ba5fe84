"""A new configuration, traffic mix and per-layer metric are picked up from
new files and new BENCHMARK.json entries alone: no file the benchmark
already has is edited."""
import hashlib
import json
import os
import time

import jax

from chipbench import harness, work

from conftest import make_root


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
