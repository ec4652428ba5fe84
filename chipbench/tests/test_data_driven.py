"""A new configuration, traffic mix, model family and per-layer metric are
picked up from new files and new BENCHMARK.json entries alone: no file the
benchmark already has is edited. The readers the benchmark has read on the
recorded chip trace what they read before families were split out."""
import contextlib
import hashlib
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import faults, harness, reference, work

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


def add_family_cell(root, family, source, cell, traffic):
    """Write the family ``family`` from ``source``, a configuration of the
    tiny sizes that names it, and the cell ``cell`` of that configuration
    under ``traffic``, taking the limits of ``tiny.<traffic>``: new files and
    new BENCHMARK.json entries alone."""
    here = os.path.join(root, "chipbench")
    with open(os.path.join(here, "families", f"{family}.py"), "w") as f:
        f.write(source)
    with open(os.path.join(here, "configs", "tiny.json")) as f:
        config = json.load(f)
    config.update(name=f"tiny-{family}", family=family)
    with open(os.path.join(here, "configs", f"tiny-{family}.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(here, "limits", f"{cell}.json"), "w") as f:
        with open(os.path.join(here, "limits", f"tiny.{traffic}.json")) as g:
            f.write(g.read())
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": f"tiny-{family}", "source": "test",
                             "file": f"chipbench/configs/tiny-{family}.json",
                             "reduced": [], "why": "test"})
    with open(os.path.join(here, "traffic", f"tiny.{traffic}.json")) as f:
        sharded = json.load(f)["placement"] == "sharded"
    bench["workloads"].append({"name": cell, "config": f"tiny-{family}",
                               "traffic": f"tiny.{traffic}",
                               "chips": 4 if sharded else 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in ("train_step_mfu", "window_compiles"):
            m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def test_new_family_from_files(tmp_path, monkeypatch):
    root = make_root(tmp_path, cells=("adaptive.r4",))
    before = digest(root)
    add_family_cell(root, "xml_mlp_dense", DENSE_FAMILY, "dense.adaptive.r4",
                    "adaptive.r4")

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


# a family whose parameters are a nested tree, written as a new file: the
# sparse MLP with its four leaves under ``input`` and ``head``, on the
# program's TrainableModel protocol, and its reference half nested alike
NESTED_FAMILY = """
import os

from chipbench import harness

_base = harness.load_named(os.path.dirname(os.path.dirname(__file__)),
                           "families", "xml_mlp")

make_data, make_provider, pack, n_params = (
    _base.make_data, _base.make_provider, _base.pack, _base.n_params)
train_work, eval_work, half_batch = (
    _base.train_work, _base.eval_work, _base.half_batch)
TRAINER_KWARGS = {"sparse_grads": False}


def nest(p):
    return {"input": {"w": p["w1"], "b": p["b1"]},
            "head": {"w": p["w2"], "b": p["b2"]}}


def flat(t):
    return {"w1": t["input"]["w"], "b1": t["input"]["b"],
            "w2": t["head"]["w"], "b2": t["head"]["b"]}


def make_model(config):
    from repro.models.protocol import TrainableModel

    # no ``config``: the trainer reads a flat ``w1`` of a config that has
    # ``n_features``
    base = _base.make_model(config)
    return TrainableModel(
        init=lambda rng: nest(base.init(rng)),
        loss_fn=lambda params, batch: base.loss_fn(flat(params), batch))


def init_params(seed, config, dtype):
    return nest(_base.init_params(seed, config, dtype))


def loss(params, batch):
    return _base.loss(flat(params), batch)
"""

NESTED_LEAVES = {"input/w", "input/b", "head/w", "head/b"}


@pytest.fixture(scope="module")
def nested_root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("nested"),
                     cells=("adaptive.r4", "adaptive.r4.sharded"))
    for traffic in ("adaptive.r4", "adaptive.r4.sharded"):
        add_family_cell(root, "xml_mlp_nested", NESTED_FAMILY,
                        f"nested.{traffic}", traffic)
    return root


@pytest.mark.parametrize("fault", [None, "unchanged_state"])
@pytest.mark.parametrize("traffic", ["adaptive.r4", "adaptive.r4.sharded"])
def test_nested_family_from_files(nested_root, traffic, fault):
    cell = harness.load_cell(nested_root, f"nested.{traffic}")
    devices = jax.devices()[:cell["chips"]]
    assert len(devices) == (4 if traffic.endswith("sharded") else 1)
    with faults.plant(fault, cell["family"]) if fault else contextlib.nullcontext():
        rec = harness.run_cell(cell, 2**33 + 31, 0.5, False, devices,
                               time.perf_counter())
    out, _ = harness.result(cell, rec, False, devices)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is (fault is None), out["compared"]
    for side in (rec["program"], rec["check"]["reference"]):
        assert all(set(d) == NESTED_LEAVES for d in side["deltas"].values())
    assert set(rec["check"]["reference"]["norms0"]) == NESTED_LEAVES


def test_leaf_names_are_tree_paths():
    tree = {"w1": jnp.ones(2), "b1": jnp.ones(1),
            "blocks": {"pos0": {"attn": {"wq": jnp.ones((3, 2, 2))}}},
            "prefix": [{"mlp": {"wi": jnp.full((2,), 2.0)}}]}
    norms = {k: float(v) for k, v in reference.leaf_norms(tree).items()}
    # a stacked leaf is one leaf; a list index is a key
    assert norms == pytest.approx({"w1": 2.0**0.5, "b1": 1.0,
                                   "blocks/pos0/attn/wq": 12.0**0.5,
                                   "prefix/0/mlp/wi": 8.0**0.5}, rel=1e-6)


@pytest.mark.parametrize("traffic", ["adaptive.r4", "adaptive.r4.sharded"])
def test_reference_places_replicas_as_the_program(tiny_root, traffic, monkeypatch):
    """Under the sharded placement every leaf of the reference's replicas,
    and of each round's batch, is split one replica a device over the
    cell's four devices, and the merged model is whole on each; under
    vmap all of it is on one device."""
    cell = harness.load_cell(tiny_root, f"tiny.{traffic}")
    config, tr, family = cell["config"], cell["traffic"], cell["family"]
    devices = jax.devices()[:cell["chips"]]
    seen = {"round": [], "merged": []}
    place_round, place_merge = reference.Placement.round, reference.Placement.merge

    def placed(tree):
        return [(frozenset(x.sharding.device_set),
                 sorted((s.device.id, s.data.shape[0]) for s in x.addressable_shards),
                 x.sharding.is_fully_replicated)
                if isinstance(x, jax.Array) else "host"
                for x in jax.tree_util.tree_leaves(tree)]

    def round_(self, loss):
        step = place_round(self, loss)

        def recorded(replicas, batch, lr, live):
            seen["round"].append((placed(replicas), placed(batch)))
            return step(replicas, batch, lr, live)
        return recorded

    def merge(self):
        fn = place_merge(self)

        def recorded(*args):
            out = fn(*args)
            seen["merged"].append(placed(out))
            return out
        return recorded

    monkeypatch.setattr(reference.Placement, "round", round_)
    monkeypatch.setattr(reference.Placement, "merge", merge)
    data = family.make_data(config, tr, harness.child_seeds(7))
    n_rep = tr["replicas"]
    ids = iter(range(len(data.train["indptr"]) - 1))
    grids = [[[[next(ids) for _ in range(8)] for _ in range(n_rep)]
              for _ in range(2)] for _ in range(3)]
    ref = reference.run(family, config, tr, data, grids, 7,
                        devices=jax.devices()[:cell["chips"]])
    assert len(ref["losses"]) == 3 and len(seen["round"]) == 6
    assert len(seen["merged"]) == 3
    if cell["chips"] == 1:
        one = frozenset(devices)
        for replicas, batch in seen["round"]:
            assert all(d == one for d, _, _ in replicas)
            # the batch goes in as the family packed it, on the host
            assert batch and set(batch) == {"host"}
        assert all(d == one for leaf in seen["merged"] for d, _, _ in leaf)
        return
    four = frozenset(devices)
    split = sorted((d.id, 1) for d in devices)
    for replicas, batch in seen["round"]:
        assert replicas and batch
        assert all(d == four and shards == split and not full
                   for d, shards, full in replicas + batch)
    for leaf in seen["merged"]:
        assert all(d == four and full for d, _, full in leaf)


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
