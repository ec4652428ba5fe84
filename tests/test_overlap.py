"""Overlapped mega-batch pipeline (DESIGN.md §8).

Layers:

* bit-identity — ``overlap=True`` (prefetch + async eval) must reproduce
  ``overlap=False`` (the same path, staged just in time, eval synchronous)
  exactly: loss trajectory, eval metrics, virtual clock, final params —
  for every registered algorithm on both engines;
* staging primitives — ``StagingBuffers`` double buffering and its in-use
  latch, lazy fetch + fused whole-plan gather vs the eager per-sample pack;
* prefetch lifecycle — cursor snapshot/rollback on ``invalidate_prefetch``
  and on consume-time mismatch, checkpoint-mid-prefetch cursor
  substitution;
* async eval — ``evaluate_async`` equals the sync path; ``run()`` backfills
  eval metrics into the record of the mega-batch they were dispatched for;
* per-shard measured timing — ``ShardWindowTimer`` + ``observe_shards``
  under an injected fake timer (2-fast-1-slow fleet converges to the true
  factor ratios), and the sharded + measured + overlap end-to-end smoke.

Multi-device (8 virtual CPU devices) overlap parity runs in a subprocess,
same pattern as tests/test_sharded_placement.py — the CI multi-device job
executes this whole file.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax.tree_util as jtu
import numpy as np
import pytest

from golden.generate import build_case_trainer, make_case_dataset
from tools.jaxlint.sentinel import RetraceSentinel
from repro.core import algorithms
from repro.core.heterogeneity import (
    MeasuredSpeedModel,
    ShardWindowTimer,
)
from repro.core.trainer import ElasticTrainer
from repro.data.batcher import StagingBuffers
from repro.data.providers import SparseProvider, TokenProvider


@pytest.fixture(scope="module")
def case_ds():
    return make_case_dataset()


def leaves_np(tree):
    return [np.asarray(l) for l in jtu.tree_leaves(tree)]


def _trainer(algo, engine, case_ds, overlap):
    tr = build_case_trainer(algo, engine, True, case_ds)
    tr.overlap = overlap
    return tr


# --------------------------------------------------------------------------
# bit-identity: pipelined vs sequential oracle
# --------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["scan", "legacy_loop"])
@pytest.mark.parametrize("algo", sorted(algorithms.available()))
def test_overlap_bit_identical(case_ds, algo, engine):
    """run(overlap on) == run(overlap off): losses, clock, final params."""
    def go(overlap):
        tr = _trainer(algo, engine, case_ds, overlap)
        state, mlog = tr.run(3)
        return state, mlog.records

    st_on, rec_on = go(True)
    st_off, rec_off = go(False)
    assert [r["train_loss"] for r in rec_on] == \
           [r["train_loss"] for r in rec_off]
    assert [r["virtual_time"] for r in rec_on] == \
           [r["virtual_time"] for r in rec_off]
    assert [r["u"] for r in rec_on] == [r["u"] for r in rec_off]
    for a, b in zip(leaves_np(st_on.replicas), leaves_np(st_off.replicas)):
        np.testing.assert_array_equal(a, b)
    if st_on.global_model is not None:
        for a, b in zip(leaves_np(st_on.global_model),
                        leaves_np(st_off.global_model)):
            np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(st_on.b, st_off.b)
    np.testing.assert_array_equal(st_on.lr, st_off.lr)


def test_overlap_steady_state_never_retraces(case_ds):
    """After the warmup mega-batch, the pipelined path must be compile-free:
    staging, async dispatch, and the scan executor all reuse their first
    programs (DESIGN.md §8 — a retrace inside the overlap window would
    serialize the pipeline it exists to hide)."""
    tr = _trainer("elastic", "scan", case_ds, True)
    state = tr.init_state()
    state, _ = tr.run_megabatch(state, prefetch=True)   # compiles everything
    with RetraceSentinel(budget=0, label="overlap steady state"):
        for _ in range(2):
            state, info = tr.run_megabatch(state, prefetch=True)
    assert np.isfinite(info["train_loss"])


@pytest.mark.parametrize("engine", ["scan", "legacy_loop"])
def test_overlap_bit_identical_with_eval(case_ds, engine):
    """Async eval (dispatched at the boundary, collected one boundary
    later) must publish the same metrics into the same records."""
    from repro.data.sparse import train_test_split

    train, test = train_test_split(case_ds, 0.25, seed=1)

    def go(overlap):
        tr = build_case_trainer("adaptive", engine, True, train)
        tr.overlap = overlap
        batches = tr.provider.test_batches(test, tr.cfg.b_max)
        _, mlog = tr.run(4, test_batches=batches, eval_every=2)
        return mlog.records

    rec_on, rec_off = go(True), go(False)
    assert [r.get("accuracy") for r in rec_on] == \
           [r.get("accuracy") for r in rec_off]
    assert [r.get("test_loss") for r in rec_on] == \
           [r.get("test_loss") for r in rec_off]
    # eval landed on the mega-batches the cadence names, despite the
    # one-boundary collection delay
    assert [i for i, r in enumerate(rec_on) if "accuracy" in r] == [1, 3]


def test_overlap_token_provider(case_ds):
    """The eager-fetch staging path (token batches have no lazy form)."""
    from repro.configs.base import ElasticConfig, ModelConfig
    from repro.models import model as MDL

    cfg = ModelConfig(
        name="tiny-test", arch_type="dense", n_layers=1, d_model=32,
        n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64, dtype="float32",
    )
    def go(overlap):
        model = MDL.make_model(cfg)
        prov = TokenProvider.make(cfg.vocab_size, 16, seed=0)
        ecfg = ElasticConfig.from_bmax(8, algorithm="adaptive",
                                       n_replicas=2, mega_batch=3)
        tr = ElasticTrainer(model, prov, ecfg, base_lr=0.1, seed=0,
                            engine="scan", overlap=overlap)
        state, mlog = tr.run(3)
        return [r["train_loss"] for r in mlog.records]

    assert go(True) == go(False)


# --------------------------------------------------------------------------
# staging primitives
# --------------------------------------------------------------------------

SPEC = {"x": ((2, 3), np.float32), "m": ((2,), bool)}


def test_staging_buffers_alternate_and_zero():
    bufs = StagingBuffers()
    k0, s0 = bufs.acquire(SPEC)
    s0["x"][...] = 7.0
    k1, s1 = bufs.acquire(SPEC)
    assert k0 != k1 and s1["x"] is not s0["x"]
    bufs.release(k0)
    k2, s2 = bufs.acquire(SPEC)      # slot 0 again, re-zeroed in place
    assert k2 == k0 and s2 is s0
    assert (s2["x"] == 0).all()


def test_staging_buffers_busy_latch():
    bufs = StagingBuffers()
    bufs.acquire(SPEC)
    bufs.acquire(SPEC)
    with pytest.raises(RuntimeError, match="in flight"):
        bufs.acquire(SPEC)           # both slots staged, none collected
    bufs.reset()
    bufs.acquire(SPEC)               # reset clears the latches


def test_staging_buffers_reallocate_on_spec_change():
    bufs = StagingBuffers()
    k0, s0 = bufs.acquire(SPEC)
    bufs.release(k0)
    bufs.acquire(SPEC)               # move _next past slot 1... no: use both
    bufs.reset()
    k, s = bufs.acquire({"x": ((4, 3), np.float32), "m": ((4,), bool)})
    assert s["x"].shape == (4, 3)
    bufs.reset()
    k, s = bufs.acquire({"y": ((2,), np.int32)})   # new key set
    assert set(s) == {"y"}


def test_lazy_stack_matches_eager(case_ds):
    """fetch_staged + fused stack == fetch + per-sample pack, same cursor."""
    b_slots = 16
    eager = SparseProvider.make(case_ds, seed=9)
    lazy = SparseProvider.make(case_ds, seed=9)
    grid_e, grid_l = [], []
    for takes in ((8, 3), (16, 0), (5, 16)):
        row_e, row_l = [], []
        for t in takes:
            if t == 0:
                row_e.append(None), row_l.append(None)
                continue
            row_e.append(eager.fetch(t, b_slots))
            p, work = lazy.fetch_staged(t, b_slots)
            assert work == eager.work_units(row_e[-1])
            row_l.append(p)
        grid_e.append(row_e), grid_l.append(row_l)
    assert eager.state_dict() == lazy.state_dict()   # same stream cursor
    st_e, mask_e = eager.stack_plan(grid_e, b_slots)
    bufs = StagingBuffers()
    _, out = bufs.acquire(lazy.staging_spec(len(grid_l), 2, b_slots))
    st_l, mask_l = lazy.stack_plan(grid_l, b_slots, out=out)
    np.testing.assert_array_equal(mask_e, mask_l)
    assert set(st_e) == set(st_l)
    for k in st_e:
        np.testing.assert_array_equal(st_e[k], st_l[k], err_msg=k)


# --------------------------------------------------------------------------
# prefetch lifecycle
# --------------------------------------------------------------------------


def test_prefetch_leaves_no_dangling_state_by_default(case_ds):
    tr = _trainer("adaptive", "scan", case_ds, True)
    state = tr.init_state()
    state, _ = tr.run_megabatch(state)      # prefetch not requested
    assert tr._staged is None


def test_overlap_off_stages_nothing_ahead(case_ds):
    """With overlap off, ``prefetch=True`` is ignored: nothing is staged
    and the host cursors stay where one sequential mega-batch leaves them."""
    tr = _trainer("adaptive", "scan", case_ds, False)
    ref = _trainer("adaptive", "scan", case_ds, False)
    tr.run_megabatch(tr.init_state(), prefetch=True)
    ref.run_megabatch(ref.init_state())
    assert tr._staged is None
    assert tr.provider.state_dict() == ref.provider.state_dict()
    np.testing.assert_array_equal(tr.scheduler.clock.t, ref.scheduler.clock.t)
    assert repr(tr.speed.state_dict()) == repr(ref.speed.state_dict())


def test_invalidate_prefetch_rolls_cursors_back(case_ds):
    tr = _trainer("adaptive", "scan", case_ds, True)
    oracle = _trainer("adaptive", "scan", case_ds, False)
    state = tr.init_state()
    o_state = oracle.init_state()
    state, _ = tr.run_megabatch(state, prefetch=True)
    o_state, _ = oracle.run_megabatch(o_state)
    assert tr._staged is not None
    # staging advanced the live cursors past the oracle's...
    assert tr.provider.state_dict() != oracle.provider.state_dict()
    tr.invalidate_prefetch()
    # ...and revocation restores them exactly
    assert tr._staged is None
    assert tr.provider.state_dict() == oracle.provider.state_dict()
    np.testing.assert_array_equal(tr.scheduler.clock.t,
                                  oracle.scheduler.clock.t)
    assert repr(tr.speed.state_dict()) == repr(oracle.speed.state_dict())
    # and the continued run matches the oracle bit-for-bit
    state, info = tr.run_megabatch(state, prefetch=False)
    o_state, o_info = oracle.run_megabatch(o_state)
    assert info["train_loss"] == o_info["train_loss"]


def test_stale_prefetch_discarded_on_mismatch(case_ds):
    """A staged plan that no longer matches (b, lr) is replayed, not used."""
    tr = _trainer("adaptive", "scan", case_ds, True)
    state = tr.init_state()
    state, _ = tr.run_megabatch(state, prefetch=True)
    assert tr._staged is not None
    state.b = state.b * 0 + float(tr.cfg.b_min)     # out-of-band mutation
    state.lr = state.lr * 0 + 0.125
    state, info = tr.run_megabatch(state)           # discard + restage
    assert tr._staged is None and np.isfinite(info["train_loss"])


def test_checkpoint_mid_prefetch_uses_snapshot_cursors(case_ds):
    """A pending prefetched plan must checkpoint the *pre-staging* cursors
    so a restore replays it instead of skipping its samples."""
    tr = _trainer("adaptive", "scan", case_ds, True)
    oracle = _trainer("adaptive", "scan", case_ds, False)
    state = tr.init_state()
    o_state = oracle.init_state()
    state, _ = tr.run_megabatch(state, prefetch=True)
    o_state, _ = oracle.run_megabatch(o_state)
    tree, meta = tr.checkpoint_payload(state)
    o_tree, o_meta = oracle.checkpoint_payload(o_state)
    assert meta["provider"] == o_meta["provider"]
    assert repr(meta["speed_meta"]) == repr(o_meta["speed_meta"])
    np.testing.assert_array_equal(tree["clock_t"], o_tree["clock_t"])
    for k in tree["speed"]:
        np.testing.assert_array_equal(tree["speed"][k], o_tree["speed"][k])


def test_overlap_off_consumes_stale_prefetch_safely(case_ds):
    """Flipping overlap off between calls consumes the pending prefetch,
    which holds the plan a sequential run makes."""
    tr = _trainer("adaptive", "scan", case_ds, True)
    oracle = _trainer("adaptive", "scan", case_ds, False)
    state = tr.init_state()
    o_state = oracle.init_state()
    state, _ = tr.run_megabatch(state, prefetch=True)
    o_state, _ = oracle.run_megabatch(o_state)
    tr.overlap = False
    for _ in range(2):
        state, info = tr.run_megabatch(state)
        o_state, o_info = oracle.run_megabatch(o_state)
        assert info["train_loss"] == o_info["train_loss"]


# --------------------------------------------------------------------------
# async eval
# --------------------------------------------------------------------------


def test_evaluate_async_equals_sync(case_ds):
    from repro.data.sparse import train_test_split

    train, test = train_test_split(case_ds, 0.25, seed=2)
    tr = build_case_trainer("adaptive", "scan", True, train)
    batches = tr.provider.test_batches(test, tr.cfg.b_max)
    state = tr.init_state()
    state, _ = tr.run_megabatch(state)
    sync = tr.evaluate(state.global_model, batches)
    collect = tr.evaluate_async(state.global_model, batches)
    state, _ = tr.run_megabatch(state)      # eval overlaps the mega-batch
    assert collect() == sync


def test_run_backfills_every_due_record(case_ds):
    from repro.data.sparse import train_test_split

    train, test = train_test_split(case_ds, 0.25, seed=3)
    tr = build_case_trainer("adaptive", "scan", True, train)
    batches = tr.provider.test_batches(test, tr.cfg.b_max)
    _, mlog = tr.run(5, test_batches=batches, eval_every=2)
    due = [i for i, r in enumerate(mlog.records) if "accuracy" in r]
    assert due == [1, 3]                    # the eval_every=2 cadence
    assert all(np.isfinite(mlog.records[i]["accuracy"]) for i in due)


# --------------------------------------------------------------------------
# per-shard measured timing
# --------------------------------------------------------------------------


class FakeTimer:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_shard_window_timer_basic():
    ft = FakeTimer()
    t = ShardWindowTimer(timer=ft)
    t.reset(2)
    t.mark_start(0)
    ft.t = 0.5
    t.mark_start(1)
    t.mark_start(0)              # duplicate start: first wins
    ft.t = 1.0
    t.mark_end(0)
    ft.t = 2.0
    t.mark_end(1)
    ft.t = 2.5
    t.mark_end(1)                # duplicate end: last wins
    w = t.take()
    np.testing.assert_allclose(w, [1.0, 2.0])
    assert t.take() is None      # self-clearing


def test_shard_window_timer_incomplete_is_none():
    ft = FakeTimer()
    t = ShardWindowTimer(timer=ft)
    t.reset(2)
    t.mark_start(0)
    ft.t = 1.0
    t.mark_end(0)                # shard 1 never reported
    assert t.take() is None
    t.reset(1)
    t.mark_start(0)
    t.mark_end(0)                # zero-width window
    assert t.take() is None


def test_observe_shards_attributes_per_shard_contrast():
    """2-fast-1-slow: per-shard windows converge to the true ratios that
    whole-window attribution cannot see through the lockstep barrier."""
    sm = MeasuredSpeedModel(3, warmup_windows=0, timer=FakeTimer())
    work = np.array([100.0, 100.0, 100.0])
    for _ in range(6):
        # shard 2's device is 3x slower; the barrier would stretch a single
        # host window to 3.0 for everyone
        sm.observe_shards(np.array([1.0, 1.0, 3.0]), work)
    f = sm.factors
    np.testing.assert_allclose(f, [1.0, 1.0, 3.0])
    # the whole-window fallback measures the same fleet as homogeneous
    sm2 = MeasuredSpeedModel(3, warmup_windows=0, timer=FakeTimer())
    for _ in range(6):
        sm2.observe_plan(work, 3.0)
    np.testing.assert_allclose(sm2.factors, np.ones(3))


def test_observe_shards_share_normalization():
    sm = MeasuredSpeedModel(4, warmup_windows=0, timer=FakeTimer())
    # 2 shards x 2 replicas; replica 3 was scheduled half the rounds (and
    # so did half the work): same per-round throughput as its shard-mate
    # must measure the same speed, not "twice as fast"
    sm.observe_shards(np.array([1.0, 2.0]), np.array([100.0, 100.0, 100.0, 50.0]),
                      u=np.array([4, 4, 4, 2]), n_rounds=4)
    f = sm.factors
    assert f[0] == f[1] == 1.0
    np.testing.assert_allclose(f[2], 2.0)
    np.testing.assert_allclose(f[3], 2.0)   # half window, half work


def test_observe_shards_rejects_stale_shard_count():
    sm = MeasuredSpeedModel(4, warmup_windows=0, timer=FakeTimer())
    sm.observe_shards(np.array([1.0, 1.0, 1.0]), np.array([100.0] * 4))
    assert (sm.n_obs == 0).all()            # 3 shards !| 4 replicas
    assert sm.n_windows == 1                # but the window was consumed


def test_observe_shards_warmup_gate_shared():
    sm = MeasuredSpeedModel(2, timer=FakeTimer())   # warmup_windows=1
    sm.observe_shards(np.array([9.0, 9.0]), np.array([100.0, 100.0]))
    assert (sm.n_obs == 0).all()
    sm.observe_shards(np.array([1.0, 2.0]), np.array([100.0, 100.0]))
    np.testing.assert_allclose(sm.factors, [1.0, 2.0])


def test_sharded_measured_overlap_smoke(case_ds):
    """End-to-end: sharded placement + measured speed + overlap records
    per-shard windows via the debug-callback markers (single-shard mesh
    in-process; the multi-shard path runs in the subprocess suite)."""
    base = build_case_trainer("adaptive", "scan", True, case_ds,
                              placement="sharded")
    tr = ElasticTrainer(
        base.model, base.provider, base.cfg, base_lr=0.5, seed=3,
        engine="scan", speed=MeasuredSpeedModel(base.cfg.n_replicas),
        overlap=True,
    )
    assert tr._shard_timer is not None
    state = tr.init_state()
    state, _ = tr.run_megabatch(state, prefetch=True)   # warmup window
    state, _ = tr.run_megabatch(state, prefetch=False)
    assert (tr.speed.n_obs > 0).all()
    assert np.isfinite(tr.speed.t_per_work).all()


# --------------------------------------------------------------------------
# multi-device overlap parity (subprocess; the CI multi-device job)
# --------------------------------------------------------------------------

OVERLAP_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

    import numpy as np
    import jax
    import jax.tree_util as jtu

    assert len(jax.devices()) == 8, jax.devices()

    from golden.generate import build_case_trainer, make_case_dataset
    from repro.core import algorithms

    ds = make_case_dataset()

    def run(algo, overlap):
        tr = build_case_trainer(algo, "scan", True, ds, placement="sharded")
        tr.overlap = overlap
        state, mlog = tr.run(2)
        return state, [r["train_loss"] for r in mlog.records]

    for algo in sorted(algorithms.available()):
        st_on, losses_on = run(algo, True)
        st_off, losses_off = run(algo, False)
        assert losses_on == losses_off, (algo, losses_on, losses_off)
        for a, b in zip(jtu.tree_leaves(st_on.replicas),
                        jtu.tree_leaves(st_off.replicas)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), algo
        print(f"OK {algo}")
    print("OVERLAP-PARITY-PASSED")
""")


@pytest.mark.slow
def test_overlap_sharded_multidevice_parity():
    """Overlap on == off, bitwise, on a real multi-shard replica mesh."""
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), os.path.join(root, "tests"),
         env.get("PYTHONPATH", "")]
    )
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", OVERLAP_PARITY_SCRIPT],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, (
        f"overlap parity subprocess failed\n--- stdout ---\n{proc.stdout}"
        f"\n--- stderr ---\n{proc.stderr[-4000:]}"
    )
    assert "OVERLAP-PARITY-PASSED" in proc.stdout
