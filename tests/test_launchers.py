"""End-to-end launcher smoke tests (CPU, reduced configs)."""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.launch import serve as serve_mod
from repro.launch import train as train_mod


def test_train_launcher_xml():
    state, mlog, _ = train_mod.main([
        "--workload", "xml", "--algorithm", "adaptive", "--replicas", "2",
        "--megabatches", "2", "--mega-batch", "4", "--b-max", "16",
        "--samples", "512", "--features", "256", "--classes", "64",
        "--avg-nnz", "16", "--hidden", "32", "--lr", "1.0",
    ])
    assert len(mlog.records) == 2
    assert np.isfinite(mlog.records[-1]["train_loss"])


def test_train_launcher_lm_reduced():
    state, mlog, _ = train_mod.main([
        "--workload", "lm", "--arch", "llama3.2-1b", "--reduced",
        "--algorithm", "elastic", "--replicas", "2", "--megabatches", "1",
        "--mega-batch", "2", "--b-max", "4", "--seq-len", "32",
    ])
    assert len(mlog.records) == 1
    assert np.isfinite(mlog.records[-1]["train_loss"])


def test_train_launcher_sharded_placement():
    """--placement sharded through the public launcher (in-process: size-1
    replica mesh; the 4-shard layout runs in the multi-device CI job)."""
    state, mlog, _ = train_mod.main([
        "--workload", "xml", "--algorithm", "adaptive", "--replicas", "2",
        "--placement", "sharded", "--megabatches", "2", "--mega-batch", "4",
        "--b-max", "16", "--samples", "512", "--features", "256",
        "--classes", "64", "--avg-nnz", "16", "--hidden", "32", "--lr", "1.0",
    ])
    assert len(mlog.records) == 2
    assert np.isfinite(mlog.records[-1]["train_loss"])


def test_train_launcher_measured_speed():
    """--speed measured wires the MeasuredSpeedModel feedback loop."""
    state, mlog, _ = train_mod.main([
        "--workload", "xml", "--algorithm", "delayed_sync", "--replicas", "2",
        "--speed", "measured", "--megabatches", "2", "--mega-batch", "4",
        "--b-max", "16", "--samples", "512", "--features", "256",
        "--classes", "64", "--avg-nnz", "16", "--hidden", "32", "--lr", "1.0",
    ])
    assert len(mlog.records) == 2
    assert np.isfinite(mlog.records[-1]["train_loss"])


@pytest.mark.parametrize("placement", ["vmap", "sharded"])
def test_train_launcher_returns_trainer_for_aot_checks(placement):
    """main() hands back the trainer, whose mega-batch program can be
    lowered again after the run (the chip smoke checks its kernels)."""
    state, mlog, trainer = train_mod.main([
        "--workload", "xml", "--algorithm", "adaptive", "--replicas", "2",
        "--placement", placement, "--megabatches", "1", "--mega-batch", "4",
        "--b-max", "16", "--samples", "512", "--features", "256",
        "--classes", "64", "--avg-nnz", "16", "--hidden", "32",
    ])
    compiled = trainer.lower_megabatch(state, n_rounds=4).compile()
    assert compiled.memory_analysis() is not None
    assert "while" in compiled.as_text()  # the scan over rounds


def test_benchmark_reexec_precedes_backend_init():
    """benchmarks/envtune.py re-execs the process: importing the benchmark
    entry modules (everything that runs before the execve) must leave every
    JAX backend uninitialized, or a TPU would stay held by the old image."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import benchmarks.megabatch_engine, benchmarks.run\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(
        [os.path.join(root, "src"), root])}
    r = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr


def test_serve_launcher_reduced():
    toks = serve_mod.main([
        "--arch", "tinyllama-1.1b", "--reduced", "--batch", "2",
        "--context", "4", "--gen", "3",
    ])
    assert toks.shape == (2, 3)


def test_serve_launcher_sliding_window():
    toks = serve_mod.main([
        "--arch", "llama3.2-1b", "--reduced", "--batch", "1",
        "--context", "6", "--gen", "2", "--window", "4",
    ])
    assert toks.shape == (1, 2)
