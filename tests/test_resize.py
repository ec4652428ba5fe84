"""Elastic replica membership (DESIGN.md §6): R changes between mega-batches.

Layers:

* state-carry semantics — momentum rows survive a grow/shrink, joiners
  start at zero momentum / the merged global, CROSSBOW survivors keep their
  diverged parameters (``resize_policy='preserve'``);
* speed-model carry — measured EMAs and simulated factors survive for
  survivors, joiners start at the homogeneous prior;
* re-planning — scheduler/virtual-clock widths follow R, joiners enter at
  the barrier;
* zero-recompile contract — resizing back to a previously-seen population
  shape adds no compiled variants (``compile_cache_size``);
* bit-identity — a constant ``resize_schedule`` ({0: R}) reproduces the
  unscheduled run exactly, for every registered algorithm;
* convergence — a grow-then-shrink schedule stays within 5% of the fixed-R
  run's final loss (the acceptance bar for ``--elastic-schedule``);
* multi-device parity — vmap and sharded placements agree across resizes on
  a real 8-virtual-device mesh (subprocess, same pattern as
  tests/test_sharded_placement.py), including the sharded zero-recompile
  check.
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
from repro.configs.base import ElasticConfig
from repro.core import algorithms
from repro.core.heterogeneity import (
    CostModel,
    MeasuredSpeedModel,
    SpeedModel,
    VirtualClock,
)
from repro.core.scheduler import DynamicScheduler
from repro.core.trainer import ElasticTrainer
from repro.launch.train import parse_elastic_schedule
from repro.optim.sgd import SGDConfig


@pytest.fixture(scope="module")
def case_ds():
    return make_case_dataset()


def leaves_np(tree):
    return [np.asarray(l) for l in jtu.tree_leaves(tree)]


# --------------------------------------------------------------------------
# schedule parsing (the launcher's --elastic-schedule)
# --------------------------------------------------------------------------


def test_parse_elastic_schedule():
    assert parse_elastic_schedule("0:4,20:6,40:3") == {0: 4, 20: 6, 40: 3}
    assert parse_elastic_schedule(" 5:2 ") == {5: 2}
    assert parse_elastic_schedule("1:2,1:3") == {1: 3}  # last wins


@pytest.mark.parametrize("bad", ["", "x", "1", "1:", ":2", "1:0", "-1:2"])
def test_parse_elastic_schedule_rejects(bad):
    with pytest.raises(ValueError):
        parse_elastic_schedule(bad)


# --------------------------------------------------------------------------
# re-planning: clock / scheduler / speed models follow R
# --------------------------------------------------------------------------


def test_virtual_clock_resize_carries_survivors_joiners_at_barrier():
    c = VirtualClock(3)
    c.t[:] = [5.0, 3.0, 4.0]
    c.resize(5)
    np.testing.assert_allclose(c.t, [5.0, 3.0, 4.0, 5.0, 5.0])
    c.resize(2)
    np.testing.assert_allclose(c.t, [5.0, 3.0])


def test_scheduler_resize_plans_new_population():
    cfg = ElasticConfig(n_replicas=2)
    sched = DynamicScheduler(cfg, CostModel(SpeedModel(2, seed=0)))
    sched.plan_megabatch(np.full(2, 32), 32 * 4)
    sched.cost.speed.resize(4)
    sched.resize(ElasticConfig(n_replicas=4))
    plan = sched.plan_megabatch(np.full(4, 32), 32 * 8)
    assert len(plan.u) == 4
    assert plan.u.sum() > 0
    assert sched.clock.t.shape == (4,)


def test_speed_model_resize_prior_and_renorm():
    sm = SpeedModel(4, max_gap=0.32, jitter=0.0, seed=1)
    old = sm.factors.copy()
    sm.resize(6)
    np.testing.assert_allclose(sm.factors[:4], old)
    np.testing.assert_allclose(sm.factors[4:], 1.0)  # homogeneous prior
    # shrink to a population that may exclude the fastest: renormalized
    sm2 = SpeedModel(4, max_gap=0.32, jitter=0.0, seed=1)
    sm2.factors = np.array([1.2, 1.32, 1.0, 1.1])  # fastest is replica 2
    sm2.resize(2)
    assert sm2.factors.min() == 1.0
    np.testing.assert_allclose(sm2.factors, [1.0, 1.1], atol=1e-12)


def test_measured_speed_resize_carries_emas():
    sm = MeasuredSpeedModel(3, warmup_windows=0)
    sm.observe(0, 100, 1.0)
    sm.observe(1, 100, 2.0)
    sm.observe(2, 100, 4.0)
    sm.resize(5)  # grow: survivors keep EMAs, joiners unmeasured
    assert sm.n_replicas == 5
    np.testing.assert_allclose(sm.t_per_work[:3], [0.01, 0.02, 0.04])
    assert np.isnan(sm.t_per_work[3:]).all()
    f = sm.factors
    np.testing.assert_allclose(f[:3], [1.0, 2.0, 4.0])
    np.testing.assert_allclose(f[3:], 1.0)  # prior until min_obs windows
    sm.resize(2)  # shrink: the slowest replica leaves
    np.testing.assert_allclose(sm.factors, [1.0, 2.0])
    np.testing.assert_array_equal(sm.n_obs, [1, 1])


def test_measured_speed_resize_discards_compile_window():
    """A resize to a first-visit population shape jit-compiles inside the
    next timed window; those seconds must not corrupt the EMAs. The window
    is still counted (warmup alignment) and the one after is attributed."""
    sm = MeasuredSpeedModel(2)  # warmup_windows=1
    sm.observe_plan(np.array([10.0, 10.0]), 9.0)  # cold-start: warmup
    sm.resize(3)
    assert sm.n_windows == 1  # warmup alignment survives the resize
    sm.observe_plan(np.array([10.0, 10.0, 10.0]), 60.0,
                    u=np.array([1, 1, 1]), n_rounds=1)  # first-visit compile
    assert sm.n_windows == 2
    assert (sm.n_obs == 0).all()  # compile window never reached an EMA
    sm.observe_plan(np.array([10.0, 10.0, 10.0]), 1.0,
                    u=np.array([1, 1, 1]), n_rounds=1)  # steady state
    assert (sm.n_obs == 1).all()
    np.testing.assert_allclose(sm.factors, np.ones(3))


# --------------------------------------------------------------------------
# trainer state carry
# --------------------------------------------------------------------------


def test_resize_grow_carries_momentum_and_clones_global(case_ds):
    base = build_case_trainer("adaptive", "scan", True, case_ds)
    tr = ElasticTrainer(
        base.model, base.provider, base.cfg, sgd=SGDConfig(momentum=0.9),
        base_lr=0.5, seed=3,
    )
    state = tr.init_state()
    state, _ = tr.run_megabatch(state)
    mom_before = leaves_np(state.momentum)
    new = tr.resize(state, 6)
    assert tr.cfg.n_replicas == 6
    for old_l, new_l in zip(mom_before, leaves_np(new.momentum)):
        np.testing.assert_array_equal(new_l[:4], old_l)      # survivors
        assert (new_l[4:] == 0).all()                        # joiners: zero
    # 'merge' policy: every replica (joiners included) restarts from the
    # merged global, which is also the new global/prev-global pair
    for g_l, r_l in zip(leaves_np(new.global_model), leaves_np(new.replicas)):
        for r in range(6):
            np.testing.assert_array_equal(r_l[r], g_l)
    for g_l, p_l in zip(leaves_np(new.global_model), leaves_np(new.prev_global)):
        np.testing.assert_array_equal(p_l, g_l)
    assert new.b.shape == (6,) and new.lr.shape == (6,)
    # training continues at the new width
    new, info = tr.run_megabatch(new)
    assert len(info["u"]) == 6 and np.isfinite(info["train_loss"])


def test_resize_shrink_merges_leavers(case_ds):
    """A leaving replica's updates must fold into the merged global: the
    post-shrink global differs from a merge over the survivors alone."""
    base = build_case_trainer("crossbow", "scan", True, case_ds)
    tr = ElasticTrainer(
        base.model, base.provider, base.cfg, sgd=SGDConfig(momentum=0.9),
        base_lr=0.5, seed=3,
    )
    state = tr.init_state()
    state, _ = tr.run_megabatch(state)  # crossbow: replicas stay diverged
    reps = leaves_np(state.replicas)
    mom_before = leaves_np(state.momentum)
    alphas = np.asarray(state.b) / np.asarray(state.b).sum()
    new = tr.resize(state, 2)
    assert tr.cfg.n_replicas == 2
    for old_l, gl in zip(reps, leaves_np(new.global_model)):
        # all four old replicas (incl. the two leavers) entered the merge
        want = np.tensordot(alphas, old_l.astype(np.float64), axes=(0, 0))
        np.testing.assert_allclose(gl, want.astype(gl.dtype), rtol=1e-5,
                                   atol=1e-6)
        survivors_only = old_l[:2].mean(axis=0)
        if not np.allclose(old_l[:2], old_l[2:], atol=1e-7):
            assert not np.allclose(gl, survivors_only, atol=1e-7)
    for old_l, new_l in zip(mom_before, leaves_np(new.momentum)):
        np.testing.assert_array_equal(new_l, old_l[:2])


def test_resize_preserve_policy_keeps_survivor_params(case_ds):
    """CROSSBOW (resize_policy='preserve'): survivors keep their diverged
    parameters bit-for-bit; only joiners clone the merged center."""
    tr = build_case_trainer("crossbow", "scan", True, case_ds)
    state = tr.init_state()
    state, _ = tr.run_megabatch(state)
    reps = leaves_np(state.replicas)
    new = tr.resize(state, 6)
    for old_l, new_l, gl in zip(reps, leaves_np(new.replicas),
                                leaves_np(new.global_model)):
        np.testing.assert_array_equal(new_l[:4], old_l)   # survivors as-is
        for r in range(4, 6):
            np.testing.assert_array_equal(new_l[r], gl)   # joiners: center


def test_resize_merge_policy_resets_all_replicas(case_ds):
    tr = build_case_trainer("adaptive", "scan", True, case_ds)
    state = tr.init_state()
    state, _ = tr.run_megabatch(state)
    new = tr.resize(state, 2)
    for r_l, g_l in zip(leaves_np(new.replicas), leaves_np(new.global_model)):
        np.testing.assert_array_equal(r_l[0], g_l)
        np.testing.assert_array_equal(r_l[1], g_l)


def test_resize_same_R_is_noop(case_ds):
    tr = build_case_trainer("adaptive", "scan", True, case_ds)
    state = tr.init_state()
    assert tr.resize(state, 4) is state


def test_resize_single_clamps_to_noop(case_ds):
    tr = build_case_trainer("single", "scan", True, case_ds)
    state = tr.init_state()
    assert tr.resize(state, 4) is state  # resolve_n_replicas pins R=1
    assert tr.cfg.n_replicas == 1


def test_resize_fixed_policy_raises(case_ds):
    tr = build_case_trainer("elastic", "scan", True, case_ds)
    tr.algo.resize_policy = "fixed"  # instance-level override for the test
    state = tr.init_state()
    with pytest.raises(ValueError, match="resize_policy"):
        tr.resize(state, 2)


def test_resize_invalid_count_raises(case_ds):
    tr = build_case_trainer("elastic", "scan", True, case_ds)
    state = tr.init_state()
    with pytest.raises(ValueError):
        tr.resize(state, 0)


def test_sync_resize_rederives_equal_shares(case_ds):
    tr = build_case_trainer("sync", "scan", True, case_ds)
    state = tr.init_state()
    state, _ = tr.run_megabatch(state)
    cfg = tr.cfg
    np.testing.assert_allclose(
        state.b, max(cfg.b_min, cfg.b_max // 4)
    )
    new = tr.resize(state, 2)
    np.testing.assert_allclose(
        new.b, max(tr.cfg.b_min, tr.cfg.b_max // 2)
    )  # global batch stays b_max at the new R


def test_resize_feeds_measured_speed_at_new_width(case_ds):
    base = build_case_trainer("adaptive", "scan", True, case_ds)
    tr = ElasticTrainer(
        base.model, base.provider, base.cfg, base_lr=0.5, seed=3,
        speed=MeasuredSpeedModel(base.cfg.n_replicas, warmup_windows=0),
    )
    state = tr.init_state()
    state, _ = tr.run_megabatch(state)
    emas = tr.speed.t_per_work.copy()
    state = tr.resize(state, 6)
    np.testing.assert_array_equal(tr.speed.t_per_work[:4], emas)
    # first post-resize window: R=6 is a first-visit shape, so the executor
    # compiles inside the timed window — discarded, EMAs untouched
    state, _ = tr.run_megabatch(state)
    np.testing.assert_array_equal(tr.speed.t_per_work[:4], emas)
    assert (tr.speed.n_obs[4:] == 0).all()
    # second window is clean: every replica of the new width is measured
    state, _ = tr.run_megabatch(state)
    assert tr.speed.n_obs.shape == (6,)
    assert (tr.speed.n_obs > 0).all()


def test_resize_legacy_engine(case_ds):
    """The per-round host-loop engine resizes through the same path (its
    jitted round is shape-keyed exactly like the scan executor)."""
    tr = build_case_trainer("adaptive", "legacy_loop", True, case_ds)
    state = tr.init_state()
    state, _ = tr.run_megabatch(state)
    state = tr.resize(state, 2)
    state, info = tr.run_megabatch(state)
    assert len(info["u"]) == 2 and np.isfinite(info["train_loss"])


# --------------------------------------------------------------------------
# zero-recompile contract
# --------------------------------------------------------------------------


def test_resize_revisited_population_recompiles_nothing(case_ds):
    """Resizing back to a previously-seen R (same pow2 round bucket) must
    reuse every jitted executor variant (DESIGN.md §6). Checked two ways:
    the trainer's own jit-cache census stays flat, and the RetraceSentinel
    sees zero backend compiles — the latter also covers programs the census
    cannot see (shard_map internals, helper jits)."""
    tr = build_case_trainer("elastic", "scan", True, case_ds)
    state = tr.init_state()
    state, _ = tr.run_megabatch(state)   # R=4 variants compile
    state = tr.resize(state, 2)          # + resize merge @4
    state, _ = tr.run_megabatch(state)   # R=2 variants compile
    state = tr.resize(state, 4)          # + resize merge @2
    state, _ = tr.run_megabatch(state)   # R=4 again: cached
    state = tr.resize(state, 2)          # merge @4 again: cached
    n0 = tr.compile_cache_size()
    with RetraceSentinel(budget=0, label="revisited population"):
        state, info = tr.run_megabatch(state)
    assert np.isfinite(info["train_loss"])
    assert tr.compile_cache_size() == n0, (
        "revisiting a previously-seen population shape recompiled"
    )


# --------------------------------------------------------------------------
# bit-identity and convergence through run(resize_schedule=...)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("algo", sorted(algorithms.available()))
def test_constant_schedule_bit_identical(case_ds, algo):
    """``resize_schedule={0: R}`` (the '0:R' CLI schedule) must reproduce
    the never-resized run exactly, for every registered algorithm."""
    R = algorithms.get(algo).resolve_n_replicas(4)

    def go(schedule):
        tr = build_case_trainer(algo, "scan", True, case_ds)
        state, mlog = tr.run(2, resize_schedule=schedule)
        return state, [r["train_loss"] for r in mlog.records]

    st_plain, losses_plain = go(None)
    st_const, losses_const = go({0: R})
    assert losses_plain == losses_const
    for a, b in zip(leaves_np(st_plain.replicas), leaves_np(st_const.replicas)):
        np.testing.assert_array_equal(a, b)
    if st_plain.global_model is not None:
        for a, b in zip(leaves_np(st_plain.global_model),
                        leaves_np(st_const.global_model)):
            np.testing.assert_array_equal(a, b)


def test_resize_invalidates_pending_prefetch(case_ds):
    """A resize at the boundary revokes the prefetched plan (staged for the
    old population) with a full cursor rollback (DESIGN.md §8): continuing
    at the new width must match a run that never prefetched."""
    def go(prefetch):
        tr = build_case_trainer("adaptive", "scan", True, case_ds)
        tr.overlap = prefetch
        state = tr.init_state()
        state, _ = tr.run_megabatch(state, prefetch=prefetch)
        if prefetch:
            assert tr._staged is not None
        state = tr.resize(state, 6)
        if prefetch:
            assert tr._staged is None       # resize revoked it
        state, info = tr.run_megabatch(state)
        return tr, info

    tr_p, info_p = go(True)
    tr_s, info_s = go(False)
    assert info_p["train_loss"] == info_s["train_loss"]
    assert info_p["u"] == info_s["u"]
    assert tr_p.provider.state_dict() == tr_s.provider.state_dict()
    np.testing.assert_array_equal(tr_p.scheduler.clock.t,
                                  tr_s.scheduler.clock.t)


def test_constant_schedule_keeps_prefetch(case_ds):
    """``resize_schedule={mb: current_R}`` is a no-op boundary: the staged
    plan survives it (and the run stays bit-identical — covered above by
    test_constant_schedule_bit_identical, which runs with overlap on)."""
    tr = build_case_trainer("adaptive", "scan", True, case_ds)
    state = tr.init_state()
    state, _ = tr.run_megabatch(state, prefetch=True)
    assert tr._staged is not None
    state = tr.resize(state, tr.cfg.n_replicas)     # same R: early return
    assert tr._staged is not None


def test_grow_then_shrink_converges_within_5pct(case_ds):
    """The acceptance bar: an elastic run that grows then shrinks stays
    within 5% of the fixed-R final loss on the bench task."""
    def go(schedule):
        tr = build_case_trainer("adaptive", "scan", True, case_ds)
        _, mlog = tr.run(8, resize_schedule=schedule)
        return mlog

    fixed = go(None)
    elastic = go({2: 6, 5: 3})  # grow 4->6, shrink 6->3
    rs = [r["n_replicas"] for r in elastic.records]
    assert rs == [4, 4, 6, 6, 6, 3, 3, 3]
    lf = fixed.records[-1]["train_loss"]
    le = elastic.records[-1]["train_loss"]
    assert np.isfinite(lf) and np.isfinite(le)
    assert abs(le - lf) / lf < 0.05, (lf, le)
    # both runs actually learned
    assert le < elastic.records[0]["train_loss"]


def test_launcher_elastic_schedule_end_to_end():
    from repro.launch import train as train_mod

    state, mlog, _ = train_mod.main([
        "--workload", "xml", "--algorithm", "adaptive",
        "--elastic-schedule", "0:2,2:4,4:2",
        "--megabatches", "6", "--mega-batch", "4", "--b-max", "16",
        "--samples", "512", "--features", "256", "--classes", "64",
        "--avg-nnz", "16", "--hidden", "32", "--lr", "1.0",
    ])
    assert [r["n_replicas"] for r in mlog.records] == [2, 2, 4, 4, 2, 2]
    assert np.isfinite(mlog.records[-1]["train_loss"])


# --------------------------------------------------------------------------
# multi-device parity across resizes (the CI multi-device job runs this)
# --------------------------------------------------------------------------

RESIZE_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

    import numpy as np
    import jax
    import jax.tree_util as jtu

    assert len(jax.devices()) == 8, jax.devices()

    from golden.generate import build_case_trainer, make_case_dataset
    from repro.sharding.rules import REPLICA_AXIS

    ds = make_case_dataset()
    SCHEDULE = {1: 8, 3: 2}   # grow 4->8 (8 shards), shrink 8->2 (2 shards)

    def run(algo, placement):
        tr = build_case_trainer(algo, "scan", True, ds, placement=placement)
        state = tr.init_state()
        losses = []
        for mb in range(4):
            if mb in SCHEDULE:
                state = tr.resize(state, SCHEDULE[mb])
            state, info = tr.run_megabatch(state)
            losses.append(info["train_loss"])
        return tr, state, losses

    def close(a, b, rtol, atol):
        for la, lb in zip(jtu.tree_leaves(a), jtu.tree_leaves(b)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=rtol, atol=atol)

    for algo in ("adaptive", "crossbow", "delayed_sync"):
        tv, sv, lv = run(algo, "vmap")
        ts, ss, ls = run(algo, "sharded")
        np.testing.assert_allclose(lv, ls, rtol=1e-5, atol=1e-6,
                                   err_msg=f"{algo} losses diverged")
        close(sv.replicas, ss.replicas, rtol=2e-3, atol=1e-5)
        if sv.global_model is not None:
            close(sv.global_model, ss.global_model, rtol=2e-3, atol=1e-5)
        print(f"OK {algo}")

    # sharded zero-recompile: revisiting an (R, shard-count) pair reuses
    # the cached executors and their compiled variants
    tr = build_case_trainer("elastic", "scan", True, ds, placement="sharded")
    state = tr.init_state()
    state, _ = tr.run_megabatch(state)   # R=4 over 4 shards
    state = tr.resize(state, 8)
    state, _ = tr.run_megabatch(state)   # R=8 over 8 shards
    state = tr.resize(state, 4)
    state, _ = tr.run_megabatch(state)   # 4-shard executors: cached
    state = tr.resize(state, 8)
    n0 = tr.compile_cache_size()
    state, info = tr.run_megabatch(state)
    assert np.isfinite(info["train_loss"])
    assert tr.compile_cache_size() == n0, "sharded resize revisit recompiled"
    print("OK zero-recompile")
    print("RESIZE-PARITY-PASSED")
""")


@pytest.mark.slow
def test_resize_sharded_vs_vmap_multidevice_parity():
    """Grow 4->8 then shrink 8->2 on a real multi-shard replica mesh: the
    sharded placement must track the vmap oracle through both membership
    changes, and revisiting a shard count must not recompile."""
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), os.path.join(root, "tests"),
         env.get("PYTHONPATH", "")]
    )
    env.pop("XLA_FLAGS", None)  # the script pins its own device count
    proc = subprocess.run(
        [sys.executable, "-c", RESIZE_PARITY_SCRIPT],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, (
        f"resize parity subprocess failed\n--- stdout ---\n{proc.stdout}"
        f"\n--- stderr ---\n{proc.stderr[-4000:]}"
    )
    assert "RESIZE-PARITY-PASSED" in proc.stdout
