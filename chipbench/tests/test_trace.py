"""The trace reduction: busy time as the union of operation intervals, time
by module and by operation, idle gaps labelled with the host span open when
each began; on a hand-made trace, and on a small trace recorded on a chip."""
import os

import pytest

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_amazon670k_adaptive_r4.json.gz")

HAND = {
    "devices": [{
        "name": "/device:TPU:0",
        "lines": {
            "XLA Modules": [["jit_megabatch_fn(1)", 100, 400],
                            ["jit_merge_fn(2)", 600, 100]],
            # overlapping ops: busy is their union, 100-300 and 350-500 and 600-700
            "XLA Ops": [["fusion.1", 100, 150], ["custom-call.2", 200, 100],
                        ["fusion.3", 350, 150], ["merge_kernel", 600, 100]],
        },
    }],
    "spans": [["chipbench.window", 0, 1000], ["chipbench.run_megabatch", 50, 520],
              ["chipbench.eval_dispatch", 700, 50]],
}


def test_hand_made_trace():
    r = trace.reduce(HAND, (0, 1000))
    (d,) = r["devices"]
    assert r["window_s"] == pytest.approx(1000e-9)
    assert d["busy_s"] == pytest.approx((200 + 150 + 100) * 1e-9)
    assert d["modules"] == {"jit_megabatch_fn(1)": pytest.approx(400e-9),
                            "jit_merge_fn(2)": pytest.approx(100e-9)}
    assert d["ops"]["fusion.1"] == pytest.approx(150e-9)
    gaps = [(label, round(s * 1e9)) for label, s, _ in d["gaps"]]
    assert gaps == [("window", 100), ("run_megabatch", 50),
                    ("run_megabatch", 100), ("eval_dispatch", 300)]
    b = trace.breakdown(r, n=2)
    assert b["idle_gaps"][0][0] == "eval_dispatch"
    assert [k for k, _ in b["device_ops"]] == ["fusion.1", "fusion.3"]
    r["megabatches"] = 2
    assert trace.module_ms_per_megabatch(r, ("megabatch",)) == pytest.approx(2e-4)
    assert trace.module_ms_per_megabatch(r, ("nothing",)) is None
    assert trace.op_seconds(r, ("merge_kernel",)) == pytest.approx(100e-9)


def test_window_cuts_events():
    r = trace.reduce(HAND, (250, 650))
    (d,) = r["devices"]
    assert d["busy_s"] == pytest.approx((50 + 150 + 50) * 1e-9)
    assert d["modules"]["jit_megabatch_fn(1)"] == pytest.approx(250e-9)


def test_recorded_chip_trace():
    """Two mega-batches of amazon670k.adaptive.r4 traced on a TPU v5 lite:
    the scan program, the merge and the per-batch evaluation are told
    apart, the kernels are found, and busy time is the operations' time."""
    x = trace.load(RECORDED)
    r = trace.reduce(x, tuple(x["window"]))
    r.update(megabatches=2, eval_batches=16)
    (d,) = r["devices"]
    assert 0.95 < d["busy_s"] / r["window_s"] <= 1.0
    assert sum(d["ops"].values()) == pytest.approx(d["busy_s"], rel=1e-3)
    names = " ".join(d["modules"])
    assert "jit_megabatch_fn" in names and "jit_merge_fn" in names
    assert sorted(d["module_counts"].values())[-1] == 32  # 16 eval batches x 2
    assert trace.op_seconds(r, ("%spmm_replicated",)) > 0
    assert trace.op_seconds(r, ("%weighted_merge",)) > 0
    assert trace.module_ms_per_megabatch(r, ("jit_megabatch_fn",)) > 300
    assert {g[0] for g in d["gaps"]} <= {"window", "run_megabatch",
                                         "eval_collect", "eval_dispatch"}
    assert not any(k.startswith("%while = ") for k in d["ops"])
