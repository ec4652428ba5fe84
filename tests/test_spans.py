"""The trainer's own instrumentation: host spans on the profiler's clock,
named scopes in the op metadata of its device programs, and the slot
counters of each mega-batch's record.

* spans — two overlapped mega-batches under ``jax.profiler.trace`` leave
  every ``repro.*`` span in the xplane's host plane, nested as the trainer
  opens them, each carrying its ``megabatch`` argument;
* scopes — the lowered mega-batch, merge and eval programs name the input
  layer, the head, the sparse and dense updates and the merge;
* counters — ``sample_slots`` / ``samples`` / ``nnz_slots`` / ``nnz`` in
  ``info`` agree with the plan, on both mega-batch paths; ``w1_pad_rows``
  with w1's stored shape; ``merge_flat_leaves`` is 0 for the XML model;
* cost — the arithmetic does not change: a traced run is bit-identical to
  an untraced one.
"""
from __future__ import annotations

import glob
import os
import re

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from golden.generate import build_case_trainer, make_case_dataset
from repro.checkpoint.store import CheckpointManager
from repro.core.fleet import FleetController
from repro.core.trainer import _next_pow2
from repro.data.sparse import train_test_split
from repro.utils.logging import SPAN_PREFIX

# child span -> the span it opens inside
NESTED = {
    "stage.plan": "stage",
    "stage.pack": "stage",
    "stage.upload": "stage",
    "sync.norms": "merge",
}
SPANS = {
    "dispatch", "adapt", "stage", "stage.plan", "stage.pack", "stage.upload",
    "sync.metrics", "sync.guard", "sync.norms", "merge", "eval.dispatch",
    "sync.eval", "checkpoint", "resize", "fleet",
}


@pytest.fixture(scope="module")
def split():
    return train_test_split(make_case_dataset(), 0.25, seed=1)


def _host_spans(trace_dir: str) -> list[tuple[str, float, float, dict]]:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _final_params(tr, state):
    return [np.asarray(l) for l in jtu.tree_leaves(
        (state.replicas, state.global_model))]


def test_host_spans_in_profiler_trace(split, tmp_path):
    train, test = split
    tr = build_case_trainer("adaptive", "scan", True, train)
    batches = tr.provider.test_batches(test, tr.cfg.b_max)
    R = tr.cfg.n_replicas
    with jax.profiler.trace(str(tmp_path / "trace")):
        _, mlog = tr.run(
            2, test_batches=batches, resize_schedule={1: R},
            fleet=FleetController(),
            checkpoint=CheckpointManager(str(tmp_path / "ckpt"), every=1),
        )
    assert len(mlog.records) == 2
    spans = _host_spans(str(tmp_path / "trace"))
    assert {name for name, *_ in spans} == SPANS
    for name, _, _, args in spans:
        assert args.get("megabatch") in (0, 1), (name, args)
    for name, s, e, args in spans:
        parent = NESTED.get(name)
        if parent is None:
            continue
        assert any(p == parent and ps <= s and e <= pe
                   and pargs["megabatch"] == args["megabatch"]
                   for p, ps, pe, pargs in spans), name
    # mega-batch 0 stages itself and prefetches 1; the last stages nothing
    staged = sorted(a["megabatch"] for n, _, _, a in spans if n == "stage")
    assert staged == [0, 1]


def _scopes(lowered) -> set[str]:
    """The name-stack components of a lowered program's op locations, with
    transform wrappers (``vmap(...)``, ``transpose(jvp(...))``) peeled."""
    out = set()
    for loc in re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)):
        for part in loc.split("/"):
            while part.endswith(")") and "(" in part:
                part = part[part.index("(") + 1:-1]
            out.add(part)
    return out


def test_named_scopes_in_lowered_programs(split):
    train, test = split
    tr = build_case_trainer("adaptive", "scan", True, train)
    state = tr.init_state()
    assert {"input_layer", "head", "sparse_update", "dense_update"} <= \
        _scopes(tr.lower_megabatch(state, 2))
    alphas = jnp.full((tr.cfg.n_replicas,), 1.0 / tr.cfg.n_replicas)
    assert "merge" in _scopes(tr._merge.lower(
        state.replicas, alphas, state.global_model, state.prev_global, 0.9))
    batch = tr._staged_test_batches(tr.provider.test_batches(test, 32))[0]
    assert {"input_layer", "head"} <= _scopes(
        tr._eval.lower(state.global_model, batch))


@pytest.mark.parametrize("overlap", [True, False])
def test_slot_counters_follow_the_plan(split, overlap):
    train, _ = split
    tr = build_case_trainer("adaptive", "scan", True, train)
    tr.overlap = overlap
    cfg = tr.cfg
    k = tr.provider.max_nnz
    state = tr.init_state()
    for _ in range(3):
        state, info = tr.run_megabatch(state, prefetch=True)
        rounds = _next_pow2(info["n_rounds"])
        assert info["samples"] == cfg.mega_batch * cfg.b_max
        assert info["sample_slots"] == rounds * cfg.n_replicas * cfg.b_max
        assert info["samples"] <= info["sample_slots"]
        assert info["nnz_slots"] == info["samples"] * k
        assert 0 < info["nnz"] <= info["nnz_slots"]
        assert info["w1_pad_rows"] == 0  # 512 features: a whole tile
        assert info["merge_flat_leaves"] == 0  # every leaf read as stored


def test_slot_counters_match_across_paths(split):
    train, _ = split

    def records(overlap):
        tr = build_case_trainer("adaptive", "scan", True, train)
        tr.overlap = overlap
        _, mlog = tr.run(3)
        keys = ("sample_slots", "samples", "nnz_slots", "nnz", "w1_pad_rows",
                "merge_flat_leaves")
        return [{k: r[k] for k in keys} for r in mlog.records]

    assert records(True) == records(False)


def test_token_provider_reports_no_nnz_counters():
    from repro.configs.base import ElasticConfig, ModelConfig
    from repro.core.trainer import ElasticTrainer
    from repro.data.providers import TokenProvider
    from repro.models import model as MDL

    cfg = ModelConfig(
        name="tiny-test", arch_type="dense", n_layers=1, d_model=32,
        n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64, dtype="float32",
    )
    tr = ElasticTrainer(
        MDL.make_model(cfg), TokenProvider.make(cfg.vocab_size, 16, seed=0),
        ElasticConfig.from_bmax(8, algorithm="adaptive", n_replicas=2,
                                mega_batch=3),
        base_lr=0.1, seed=0, engine="scan",
    )
    _, info = tr.run_megabatch(tr.init_state())
    assert info["samples"] == 3 * 8
    assert "nnz" not in info and "nnz_slots" not in info
    assert "w1_pad_rows" not in info


@pytest.mark.parametrize("nf,pad", [(512, 0), (509, 3)],
                         ids=["aligned", "unaligned"])
def test_w1_pad_rows_reads_the_stored_shape(nf, pad):
    from repro.configs.base import ElasticConfig
    from repro.core.trainer import ElasticTrainer
    from repro.data.providers import SparseProvider
    from repro.data.xml_synth import make_xml_dataset
    from repro.models.xml_mlp import XMLMLPConfig, make_model

    ds = make_xml_dataset(n_samples=256, n_features=nf, n_classes=32,
                          avg_nnz=16, seed=0)
    tr = ElasticTrainer(
        make_model(XMLMLPConfig(n_features=nf, n_classes=32, hidden=16)),
        SparseProvider.make(ds, seed=0),
        ElasticConfig.from_bmax(8, algorithm="adaptive", n_replicas=2,
                                mega_batch=3),
        base_lr=0.1, seed=0,
    )
    state, info = tr.run_megabatch(tr.init_state())
    assert info["w1_pad_rows"] == pad == state.replicas["w1"].shape[1] - nf


def test_metrics_log_records_no_wall_stamp(split):
    train, _ = split
    tr = build_case_trainer("single", "scan", True, train)
    _, mlog = tr.run(1)
    assert "wall_s" not in mlog.records[0]
    assert "wall_clock" in mlog.records[0]


def test_trajectory_identical_under_profiler(split, tmp_path):
    train, test = split

    def go(traced):
        tr = build_case_trainer("adaptive", "scan", True, train)
        batches = tr.provider.test_batches(test, tr.cfg.b_max)
        if traced:
            with jax.profiler.trace(str(tmp_path)):
                state, mlog = tr.run(3, test_batches=batches)
        else:
            state, mlog = tr.run(3, test_batches=batches)
        keys = ("train_loss", "accuracy", "test_loss", "virtual_time", "u")
        return [{k: r[k] for k in keys} for r in mlog.records], \
            _final_params(tr, state)

    rec_on, params_on = go(True)
    rec_off, params_off = go(False)
    assert rec_on == rec_off
    for a, b in zip(params_on, params_off):
        np.testing.assert_array_equal(a, b)
