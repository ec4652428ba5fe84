"""One run of one cell: set-up, the measured window, the optional trace, and
the comparison with the reference that decides ``correct``.

Everything particular to a configuration, a traffic mix, a model family or
a per-layer metric is read from files found by name:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``families/<family>.py`` (the configuration's ``"family"``),
``metrics/<metric>.py`` and ``limits/<workload>.json``, with the cell
itself an entry of the repository's ``BENCHMARK.json``.

A family module holds what is particular to one model; the harness calls
nothing else of it:

* ``make_data(config, traffic, seeds)``: the data set from the seeds of
  ``child_seeds``, an object with a ``stats`` dict (printed) that the
  family's other functions take;
* ``make_provider(data, config, traffic, seed)``: ``(provider,
  test_batches)``, the program's provider over the data and the batches
  the window evaluates. The provider records in ``grids`` what it packs
  for each mega-batch: per round, per replica, None or the entry the
  family's ``pack`` takes, whose ``len`` is its number of samples;
* ``make_model(config)`` and ``TRAINER_KWARGS``: the program's model and
  the ``ElasticTrainer`` arguments particular to it;
* ``n_params(config)``: parameters of one replica, for the merge's work;
* ``train_work(config, data, grids, device_kind)``: ``model_flops``,
  ``trained_samples`` and each kernel's ``<kernel>_least_s`` over the
  trained grids; ``eval_work(config, data, test_batches, device_kind)``:
  each kernel's ``<kernel>_least_s`` of one evaluation on one chip;
* the model's half of the plain reference, which imports nothing of the
  program: ``init_params(seed, config, dtype)``, ``loss(params, batch)``
  and ``pack(data, entries_per_replica, b_max)`` (``reference.py``).
  ``init_params`` may return any pytree of arrays, and the program's
  ``init`` a tree of the same paths: each leaf is read by its path, the
  keys joined by ``/`` (``w1``; ``blocks/pos0/attn/wq``). Under the
  ``sharded`` placement the reference holds one replica a chip, so one
  replica must fit a chip beside its gradient and the two global copies;
* ``half_batch()``: the fault of ``faults.applicable`` that breaks where
  the family packs its batches, a context manager like those of
  ``faults.py`` (``faults.plant`` takes a family's own fault first).

The window drives the program's ``ElasticTrainer.run_megabatch`` in the
order of ``ElasticTrainer.run``: dispatch mega-batch N with the next one
prefetched, collect the evaluation of N-1, dispatch the evaluation of N.
The first ``checked_megabatches`` mega-batches are set-up: they compile
every program the window runs, and the reference follows them.
"""
from __future__ import annotations

import functools
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import compare, work
from chipbench import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKED = (1, 3)  # mega-batches after which the global model's change is read


# ----------------------------------------------------------------------------
# the cell, from files
# ----------------------------------------------------------------------------


def load_cell(root: str, workload: str) -> dict:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, with the files it
    names read from ``<root>/chipbench``."""
    here = os.path.join(root, "chipbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(here, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    family = load_named(here, "families", config["family"])

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "name": workload,
        "dir": here,
        "chips": int(cell["chips"]),
        "config": config,
        "traffic": traffic,
        "family": family,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "limits": compare.load_limits(here, workload),
    }


@functools.lru_cache(maxsize=None)
def load_named(here: str, kind: str, name: str):
    """The module ``<here>/<kind>/<name>.py``, loaded once a process (the
    reference's jitted round is keyed by the family's ``loss``)."""
    path = os.path.join(here, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    # dataclasses look their module up by name while the module runs
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(here: str, name: str):
    return load_named(here, "metrics", name).read


def child_seeds(seed: int) -> dict:
    """Independent 31-bit seeds for each consumer of randomness."""
    s = np.random.SeedSequence(int(seed)).generate_state(4) % (2**31 - 1)
    return dict(zip(("data", "split", "stream", "program"), map(int, s)))


# ----------------------------------------------------------------------------
# the trainer
# ----------------------------------------------------------------------------


def build_trainer(family, config: dict, traffic: dict, provider, devices: list,
                  seed: int):
    """The trainer as ``repro.launch.train.main`` builds it, with the
    traffic's algorithm parameters stated explicitly."""
    from repro.configs.base import ElasticConfig
    from repro.core import algorithms
    from repro.core.heterogeneity import SpeedModel
    from repro.core.trainer import ElasticTrainer
    from repro.optim.sgd import SGDConfig

    n_rep = algorithms.get(traffic["algorithm"]).resolve_n_replicas(traffic["replicas"])
    cfg = ElasticConfig(
        algorithm=traffic["algorithm"], placement=traffic["placement"],
        n_replicas=n_rep, mega_batch=traffic["mega_batch"],
        b_max=traffic["b_max"], b_min=traffic["b_min"], beta=traffic["beta"],
        pert_thr=traffic["pert_thr"], delta=traffic["delta"],
        gamma=traffic["gamma"],
    )
    mesh = None
    if traffic["placement"] == "sharded":
        from repro.launch.mesh import make_replica_mesh

        mesh = make_replica_mesh(n_rep, devices=devices)
    speed = SpeedModel(n_rep, max_gap=traffic["speed_max_gap"], seed=seed)
    return ElasticTrainer(
        model=family.make_model(config), provider=provider, cfg=cfg,
        sgd=SGDConfig(), base_lr=traffic["lr"], speed=speed, seed=seed,
        engine="scan", mesh=mesh, overlap=True, **family.TRAINER_KWARGS,
    )


# ----------------------------------------------------------------------------
# compile events, host spans
# ----------------------------------------------------------------------------


class CompileCounter:
    """JAX's own backend-compile events (a compile or a persistent-cache
    load), counted and timed."""

    def __init__(self):
        from jax._src import dispatch

        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.n, self.seconds = 0, 0.0

    def __call__(self, event, duration, **kwargs):
        if event == self.event:
            self.n += 1
            self.seconds += duration

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + name)


# ----------------------------------------------------------------------------
# the loop the window runs
# ----------------------------------------------------------------------------


class Loop:
    """Mega-batch N: dispatch it (next one staged), collect eval N-1,
    dispatch eval N. Mega-batch N counts as complete when eval N, which
    reads its merged model, has been collected."""

    def __init__(self, trainer, state, test_batches):
        self.trainer, self.state, self.test_batches = trainer, state, test_batches
        self.pending = None
        self.infos, self.evals, self.done_at = [], [], []

    def step(self):
        with span("run_megabatch"):
            self.state, info = self.trainer.run_megabatch(self.state, prefetch=True)
        self.infos.append(info)
        self.collect()
        with span("eval_dispatch"):
            self.pending = self.trainer.evaluate_async(
                self.state.global_model, self.test_batches)

    def collect(self):
        if self.pending is not None:
            with span("eval_collect"):
                self.evals.append(self.pending())
            self.done_at.append(time.perf_counter())
            self.pending = None

    def failed(self, first: int, last: int) -> int:
        bad = 0
        for i in range(first, last):
            info = self.infos[i]
            ok = (i < len(self.evals) and math.isfinite(info["train_loss"])
                  and math.isfinite(self.evals[i]["loss"])
                  and "guard_repaired" not in info)
            bad += not ok
        return bad


def delta_norms_fn(trainer):
    """Per-leaf norm of (global model - the program's initial model), on the
    device, each leaf named by its tree path as the reference names it; the
    initial model is made again from the trainer's seed."""
    import jax

    from chipbench import reference

    # the key is an argument, not a constant: one program serves every seed
    key = jax.random.PRNGKey(trainer.seed)

    @jax.jit
    def fn(g, key):
        return reference.delta_norms(g, trainer.model.init(key))

    return lambda g: {k: float(v) for k, v in fn(g, key).items()}


# ----------------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------------


def say(*lines, file=None):
    for line in lines:
        print(line, file=file or sys.stderr, flush=True)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices: list,
             t0: float, warm_only: bool = False, controls: tuple = ()) -> dict:
    """Set up, run the window (unless ``warm_only``), read memory and the
    trace, free the program's state and run the reference. ``controls``
    names lower precisions in which the reference is also run in the
    program's place, for the limits' upper readings. Returns the run's
    record."""
    import jax

    config, traffic, family = cell["config"], cell["traffic"], cell["family"]
    seeds = child_seeds(seed)
    rec = {"seeds": seeds}
    with CompileCounter() as compiles:
        t_import = time.perf_counter()
        data = family.make_data(config, traffic, seeds)
        rec["data"] = data.stats
        provider, test_batches = family.make_provider(data, config, traffic,
                                                      seeds["stream"])
        t_data = time.perf_counter()
        trainer = build_trainer(family, config, traffic, provider, devices,
                                seeds["program"])
        delta_norms = delta_norms_fn(trainer)
        loop = Loop(trainer, trainer.init_state(), test_batches)
        prog = {"losses": [], "deltas": {}}
        for m in range(1, traffic["checked_megabatches"] + 1):
            loop.step()
            prog["losses"].append(float(loop.infos[-1]["train_loss"]))
            if m in CHECKED:
                prog["deltas"][m] = delta_norms(loop.state.global_model)
        loop.collect()
        jax.block_until_ready(loop.state.replicas)
        # what set-up made lives to the end: keep the collector from
        # walking it again inside the window, where a full collection of
        # the process's objects stalls the host for a tenth of a second
        gc.collect()
        gc.freeze()
        t_window = time.perf_counter()
        setup_compiles = (compiles.n, compiles.seconds)

        rec["setup"] = {
            "setup_s": t_window - t0,
            "import_and_device_s": t_import - t0,
            "data_s": t_data - t_import,
            "compile_or_cache_load_s": setup_compiles[1],
            "build_and_warmup_s": t_window - t_data - setup_compiles[1],
            "compiles": setup_compiles[0],
        }
        n_warm = len(loop.infos)
        trace_dir = None
        if not warm_only:
            if trace:
                trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
                jax.profiler.start_trace(trace_dir)
            t_end = t_window + seconds
            walls, last = [], t_window
            with span("window"):
                while time.perf_counter() < t_end:
                    loop.step()
                    now = time.perf_counter()
                    walls.append(now - last)
                    last = now
                loop.collect()
                jax.block_until_ready(loop.state.replicas)
            t_done = time.perf_counter()
            if trace:
                jax.profiler.stop_trace()
            rec["window"] = {
                "seconds": t_done - t_window,
                "attempted": len(loop.infos) - n_warm,
                "failed": loop.failed(n_warm, len(loop.infos)),
                "samples": sum(
                    sum(len(ids) for row in g for ids in row if ids is not None)
                    for g in provider.grids[n_warm:len(loop.infos)]
                ),
                "compiles": compiles.n - setup_compiles[0],
                "megabatch_wall_s": walls,
                "n_rounds": [i["n_rounds"] for i in loop.infos[n_warm:]],
            }
    # the peak on the fullest chip (a backend without memory stats reads 0)
    stats = [d.memory_stats() or {} for d in devices]
    rec["peak_bytes"] = max(s.get("peak_bytes_in_use", 0) for s in stats)
    rec["bytes_limit"] = min(s.get("bytes_limit", 0) for s in stats)
    if trace_dir is not None:
        rec["trace"] = read_trace(trace_dir, cell, data, provider, test_batches,
                                  n_warm, len(loop.infos), devices)
    grids = provider.grids[:traffic["checked_megabatches"]]
    rec["program"] = prog
    rec["grids_consistent"] = len(provider.grids) == len(loop.infos) + 1
    del loop, trainer, provider, test_batches
    gc.unfreeze()
    gc.collect()
    rec["check"] = check(cell, data, grids, seeds["program"], prog, devices,
                         controls)
    return rec


def check(cell, data, grids, program_seed, prog, devices, controls=()) -> dict:
    import jax.numpy as jnp

    from chipbench import reference

    def ref_run(dtype):
        return reference.run(cell["family"], cell["config"], cell["traffic"], data,
                             grids, program_seed, checked=CHECKED, dtype=dtype,
                             devices=devices)

    t = time.perf_counter()
    ref = ref_run(jnp.float32)
    values = compare.readings(prog, ref)
    ok, compared = compare.judge(values, cell["limits"])
    out = {"ok": ok, "compared": compared, "values": values, "reference": ref,
           "seconds": time.perf_counter() - t}
    if controls:
        out["controls"] = {c: compare.readings(ref_run(getattr(jnp, c)), ref)
                           for c in controls}
    return out


# ----------------------------------------------------------------------------
# the trace
# ----------------------------------------------------------------------------


def read_trace(trace_dir, cell, data, provider, test_batches, first, last,
               devices) -> dict:
    """Reduce the trace of the window and count the work of the mega-batches
    and evaluations it holds."""
    try:
        extracted = tr.extract(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    windows = [s for s in extracted["spans"] if s[0] == tr.SPAN_PREFIX + "window"]
    w = windows[0]
    reduced = tr.reduce(extracted, (w[1], w[1] + w[2]))
    reduced["work"] = window_work(cell, data, provider.grids[first:last],
                                  test_batches, last - first, devices)
    reduced["megabatches"] = last - first
    reduced["eval_batches"] = len(test_batches)
    return reduced


def window_work(cell, data, grids, test_batches, n_evals, devices) -> dict:
    """Work of the window's calls, counted from the batches it trained and
    evaluated on."""
    config, traffic, family = cell["config"], cell["traffic"], cell["family"]
    kind = devices[0].device_kind
    chips = len(devices)
    out = family.train_work(config, data, grids, kind)
    # every chip evaluates every test batch under the sharded placement;
    # under vmap one chip does
    eval_copies = chips if traffic["placement"] == "sharded" else 1
    for key, least in family.eval_work(config, data, test_batches, kind).items():
        out[key] += least * n_evals * eval_copies

    n_params = family.n_params(config)
    merge_least = 0.0
    if traffic["algorithm"] == "adaptive":
        n_rep = traffic["replicas"]
        if traffic["placement"] == "sharded":
            per = work.weighted_merge_bytes(n_params, n_rep // chips, False) * chips
            flops = work.weighted_merge_flops(n_params, n_rep // chips, False) * chips
        else:
            per = work.weighted_merge_bytes(n_params, n_rep, True)
            flops = work.weighted_merge_flops(n_params, n_rep, True)
        merge_least = work.least_seconds(flops, per, kind) * len(grids)
    out.update(weighted_merge_least_s=merge_least,
               peak_flops=work.peaks(kind)["flops"], chips=chips)
    return out


# ----------------------------------------------------------------------------
# the result
# ----------------------------------------------------------------------------


def result(cell: dict, rec: dict, trace: bool, devices: list) -> tuple[dict, list]:
    """The result line and the lines of numbers compared."""
    d0 = devices[0]
    win = rec["window"]
    chk = rec["check"]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(rec["peak_bytes"])}
    correct = bool(chk["ok"] and rec["grids_consistent"])
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": win["failed"]}
    if trace:
        t = rec["trace"]
        busy = [d["busy_s"] for d in t["devices"]]
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = t["window_s"]
        metrics = {}
        record = {"trace": t, "run": rec}
        for m in cell["per_layer"]:
            value = metric_reader(cell["dir"], m["name"])(t, record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = tr.breakdown(t)
    else:
        values = {
            "samples_per_s": win["samples"] / win["seconds"],
            "peak_hbm_gb": rec["peak_bytes"] / 1e9,
            "setup_s": rec["setup"]["setup_s"],
        }
        out["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
                          for m in cell["end_to_end"]}
        out["device"] = device
    out["compared"] = chk["compared"]
    lines = [f"{k} {c['value']!r} limit {c['limit']!r}"
             for k, c in chk["compared"].items()]
    return out, lines


def describe(rec: dict) -> list:
    """Earlier lines of a run: the data, the set-up split, the window."""
    lines = ["data " + json.dumps(rec["data"]),
             "setup " + json.dumps(rec["setup"]),
             "memory " + json.dumps({"peak_bytes_in_use": rec["peak_bytes"],
                                     "bytes_limit": rec["bytes_limit"]})]
    win = rec.get("window")
    if win:
        w = np.asarray(win["megabatch_wall_s"])
        q = np.quantile(w, [0.5, 0.9]) if len(w) else [float("nan")] * 2
        lines.append("megabatch_wall_s " + json.dumps({
            "count": int(len(w)), "median": float(q[0]), "p90": float(q[1]),
            "max": float(w.max()) if len(w) else None,
            "window_s": win["seconds"], "samples": win["samples"],
            "window_compiles": win["compiles"],
            "n_rounds": sorted(set(win["n_rounds"])),
        }))
    lines.append("check " + json.dumps({
        "seconds": rec["check"]["seconds"],
        "program_losses": rec["program"]["losses"],
        "reference_losses": rec["check"]["reference"]["losses"],
        "values": rec["check"]["values"],
    }))
    return lines


def main(args, t0: float) -> int:
    import jax

    cell = load_cell(os.path.dirname(HERE), args.workload)
    devices = jax.devices()
    if devices[0].platform == "cpu":
        say(f"chipbench: no accelerator (JAX found {devices[0].platform})")
        return 2
    if len(devices) < cell["chips"]:
        say(f"chipbench: the cell needs {cell['chips']} chips, found {len(devices)}")
        return 2
    devices = devices[:cell["chips"]]
    from repro.launch.train import use_persistent_compilation_cache

    say(f"compilation_cache {use_persistent_compilation_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    rec = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, t0)
    out, lines = result(cell, rec, bool(args.trace), devices)
    say(*describe(rec))
    print(json.dumps(out), flush=True)
    say(*lines)
    return 0
