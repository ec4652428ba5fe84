#!/usr/bin/env python3
"""Smoke run of the elastic trainer on a TPU, at Amazon-670K widths.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # replica-per-chip placement, 4 chips

One chip: Adaptive SGD with R=4 replicas (vmap placement) trains three
mega-batches through ``repro.launch.train.main`` at the paper's published
Amazon-670K widths (135,909 features, 670,091 labels, ~76 nnz and ~5 labels
per sample, hidden 128), with random weights and data from a seed. Then:
every mega-batch must report a finite loss and a numeric test accuracy; the
compiled mega-batch program must contain the native Pallas spmm kernel
(``tpu_custom_call``) and fit the device with 10% headroom; and the kernel
must match the jnp gather (``_sparse_input_ref``) on a real batch with the
trained weights, and its transpose ``spmm_grad_w`` the scatter-add
reference.

Four chips (``--chips 4``): only the sharded path. The same run, one
mega-batch, once with one replica per chip (``--placement sharded``) and
once with all four on one chip (vmap), same seed, in this one process; the
mesh must span four distinct devices, and losses and merged parameters
must agree within the sharded-placement tests' 2e-3.

There is no CPU fallback: with no TPU the script exits non-zero and prints
no result. Times printed are of a smoke run, not a benchmark. The last line
of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

# The mega-batch program at b_max=256 needs 15.4 GB (memory_analysis of an
# AOT compile for v5e): over 16 GB less 10% headroom. 128 needs 7.2 GB.
B_MAX = 128
HEADROOM = 0.9
TRAIN_ARGS = [
    "--workload", "xml", "--algorithm", "adaptive", "--replicas", "4",
    "--features", "135909", "--classes", "670091", "--avg-nnz", "76",
    "--avg-labels", "5", "--hidden", "128", "--samples", "32768",
    "--mega-batch", "20", "--b-max", str(B_MAX), "--seed", "0",
]
PARITY_RTOL = 2e-3   # tests/test_sharded_placement.py


def check(ok, what) -> None:
    """A failed check ends the run (kept under ``python -O``, unlike assert)."""
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def say(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def tpu_devices():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devices[0].platform})")
    return devices


def train(extra):
    from repro.launch import train as launcher

    return launcher.main(TRAIN_ARGS + extra)


def one_chip(device) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.trainer import _next_pow2
    from repro.kernels.spmm import ops as spmm_ops
    from repro.kernels.spmm.ref import spmm_grad_w_ref
    from repro.models.xml_mlp import _sparse_input_ref

    say(phase="train", b_max=B_MAX, replicas=4, placement="vmap")
    state, mlog, trainer = train(["--placement", "vmap", "--megabatches", "3"])
    records = mlog.records
    check(len(records) == 3, records)
    for r in records:
        check(math.isfinite(r["train_loss"]), r)
        check(isinstance(r["accuracy"], float) and math.isfinite(r["accuracy"]), r)
    walls = np.diff([0.0] + [r["wall_clock"] for r in records])
    say(smoke_wall_s_per_megabatch=[round(float(w), 3) for w in walls],
        note="smoke timings; the first includes compilation")
    say(train_loss=[r["train_loss"] for r in records],
        test_accuracy=[r["accuracy"] for r in records])

    n_rounds = _next_pow2(max(r["n_rounds"] for r in records))
    t = time.perf_counter()
    compiled = trainer.lower_megabatch(state, n_rounds).compile()
    compile_s = time.perf_counter() - t
    mem = compiled.memory_analysis()
    program_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    limit = device.memory_stats()["bytes_limit"]
    say(phase="megabatch_program", n_rounds=n_rounds,
        compile_s=round(compile_s, 3), program_bytes=program_bytes,
        bytes_limit=limit)
    check("tpu_custom_call" in compiled.as_text(), "no Pallas kernel in program")
    check(program_bytes <= HEADROOM * limit, (program_bytes, limit))

    batch = trainer.provider.fetch(B_MAX, B_MAX)
    args = [jnp.asarray(a) for a in (batch.feat_idx, batch.feat_val,
                                     batch.feat_mask)]
    w1 = state.global_model["w1"]
    got = np.asarray(jax.jit(spmm_ops.spmm)(*args, w1))
    want = np.asarray(jax.jit(_sparse_input_ref)(*args, w1))
    compare("spmm_vs_ref", got, want)

    # the transpose (the dense-gradient path's dW) on the same batch
    dh = jax.random.normal(jax.random.PRNGKey(0), got.shape, jnp.float32)
    n_rows = w1.shape[0]
    got = np.asarray(jax.jit(spmm_ops.spmm_grad_w, static_argnums=4)(
        *args, dh, n_rows))
    want = np.asarray(jax.jit(spmm_grad_w_ref, static_argnums=4)(
        *args, dh, n_rows))
    compare("spmm_grad_w_vs_ref", got, want)
    say(peak_bytes_in_use=device.memory_stats()["peak_bytes_in_use"])


def compare(phase, got, want) -> None:
    """float32 agreement of a kernel with its jnp reference."""
    import numpy as np

    scale = float(np.abs(want).max())
    rel = float(np.abs(got - want).max()) / scale
    say(phase=phase, shape=got.shape, max_rel_err=rel)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale)


def four_chips(devices) -> None:
    import numpy as np

    check(len(devices) == 4, devices)
    runs = {}
    for placement in ("sharded", "vmap"):
        state, mlog, trainer = train(["--placement", placement,
                                      "--megabatches", "1"])
        if placement == "sharded":
            mesh_devices = {d.id for d in trainer.mesh.devices.flat}
            shards = state.replicas["w1"].addressable_shards
            shard_devices = {s.device.id for s in shards}
            say(phase="sharded", mesh_devices=sorted(mesh_devices),
                w1_shard_devices=sorted(shard_devices),
                w1_shard_shape=shards[0].data.shape)
            check(len(mesh_devices) == 4 and len(shard_devices) == 4,
                  "mesh spans four devices")
            check(shards[0].data.shape[0] == 1, "one replica per chip")
        rec = mlog.records[0]
        check(math.isfinite(rec["train_loss"]), rec)
        runs[placement] = (rec, {k: np.asarray(v) for k, v in
                                 state.global_model.items()})
        del state, trainer

    (rec_s, g_s), (rec_v, g_v) = runs["sharded"], runs["vmap"]
    say(phase="parity", loss_sharded=rec_s["train_loss"],
        loss_vmap=rec_v["train_loss"], u_sharded=rec_s["u"], u_vmap=rec_v["u"])
    np.testing.assert_allclose(rec_s["train_loss"], rec_v["train_loss"],
                               rtol=PARITY_RTOL)
    check(rec_s["u"] == rec_v["u"], (rec_s["u"], rec_v["u"]))
    for k in g_v:
        rel = float(np.max(np.abs(g_s[k] - g_v[k])) / np.max(np.abs(g_v[k])))
        say(phase="parity", leaf=k, max_rel_diff=rel)
        np.testing.assert_allclose(g_s[k], g_v[k], rtol=PARITY_RTOL, atol=1e-5)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the replica-per-chip (sharded) phase")
    args = ap.parse_args(argv)

    devices = tpu_devices()
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    from repro.launch.train import use_persistent_compilation_cache

    say(device_kind=devices[0].device_kind, device_count=len(devices),
        compilation_cache=use_persistent_compilation_cache())
    if args.chips == 4:
        four_chips(devices)
    else:
        one_chip(devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
