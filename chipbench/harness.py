"""One run of one cell: set-up, the measured window, the optional trace, and
the comparison with the reference that decides ``correct``.

Everything particular to a configuration, a traffic mix or a per-layer
metric is read from files found by name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.py`` and
``limits/<workload>.json``, with the cell itself an entry of the
repository's ``BENCHMARK.json``.

The window drives the program's ``ElasticTrainer.run_megabatch`` in the
order of ``ElasticTrainer.run``: dispatch mega-batch N with the next one
prefetched, collect the evaluation of N-1, dispatch the evaluation of N.
The first ``checked_megabatches`` mega-batches are set-up: they compile
every program the window runs, and the reference follows them.
"""
from __future__ import annotations

import dataclasses
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

from chipbench import compare, synth, work
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

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "name": workload,
        "dir": here,
        "chips": int(cell["chips"]),
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "limits": compare.load_limits(here, workload),
    }


def metric_reader(here: str, name: str):
    path = os.path.join(here, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def child_seeds(seed: int) -> dict:
    """Independent 31-bit seeds for each consumer of randomness."""
    s = np.random.SeedSequence(int(seed)).generate_state(4) % (2**31 - 1)
    return dict(zip(("data", "split", "stream", "program"), map(int, s)))


# ----------------------------------------------------------------------------
# data and trainer
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class Data:
    train: dict
    test: dict
    k: int
    n_lab: int
    stats: dict


def make_data(config: dict, traffic: dict, seeds: dict) -> Data:
    csr = synth.make_xml_csr(
        traffic["samples"], config["n_features"], config["n_classes"],
        config["avg_nnz"], config["avg_labels"], config["nnz_sigma"],
        seeds["data"],
    )
    train, test = synth.split(csr, traffic["test_frac"], seeds["split"])
    k, n_lab = synth.slot_widths(train)
    return Data(train, test, k, n_lab, synth.stats(csr, k))


def make_recording_provider(data: Data, config: dict, seed: int):
    """The program's SparseProvider over the benchmark's data, recording the
    sample ids of every plan grid it packs (grid ``m`` feeds mega-batch
    ``m``) and the samples it hands over."""
    from repro.data.batcher import SparseBatcher
    from repro.data.providers import SparseProvider
    from repro.data.sparse import SparseDataset

    @dataclasses.dataclass
    class RecordingProvider(SparseProvider):
        grids: list = dataclasses.field(default_factory=list)

        def stack_plan(self, grid, b_slots, out=None):
            self.grids.append([[None if p is None else np.array(p.ids)
                                for p in row] for row in grid])
            return super().stack_plan(grid, b_slots, out=out)

    def dataset(c):
        return SparseDataset(
            n_features=config["n_features"], n_classes=config["n_classes"],
            indptr=c["indptr"], indices=c["indices"], values=c["values"],
            label_ptr=c["label_ptr"], labels=c["labels"],
        )

    batcher = SparseBatcher(dataset(data.train), max_nnz=data.k,
                            max_labels=data.n_lab, seed=seed)
    return RecordingProvider(batcher), dataset(data.test)


def build_trainer(config: dict, traffic: dict, provider, devices: list, seed: int):
    """The trainer as ``repro.launch.train.main`` builds it, with the
    traffic's algorithm parameters stated explicitly."""
    from repro.configs.base import ElasticConfig
    from repro.core import algorithms
    from repro.core.heterogeneity import SpeedModel
    from repro.core.trainer import ElasticTrainer
    from repro.models.xml_mlp import XMLMLPConfig, make_model
    from repro.optim.sgd import SGDConfig

    if config["dtype"] != "float32":
        raise ValueError(f"unsupported dtype {config['dtype']!r}")
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
    model = make_model(XMLMLPConfig(
        n_features=config["n_features"], n_classes=config["n_classes"],
        hidden=config["hidden"],
    ))
    speed = SpeedModel(n_rep, max_gap=traffic["speed_max_gap"], seed=seed)
    return ElasticTrainer(
        model=model, provider=provider, cfg=cfg, sgd=SGDConfig(),
        base_lr=traffic["lr"], speed=speed, seed=seed, engine="scan",
        sparse_grads=True, mesh=mesh, overlap=True,
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
    device; the initial model is made again from the trainer's seed."""
    import jax
    import jax.numpy as jnp

    # the key is an argument, not a constant: one program serves every seed
    key = jax.random.PRNGKey(trainer.seed)

    @jax.jit
    def fn(g, key):
        g0 = trainer.model.init(key)
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            g[k].astype(jnp.float32) - g0[k].astype(jnp.float32)))) for k in g}

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

    config, traffic = cell["config"], cell["traffic"]
    seeds = child_seeds(seed)
    rec = {"seeds": seeds}
    with CompileCounter() as compiles:
        t_import = time.perf_counter()
        data = make_data(config, traffic, seeds)
        rec["data"] = data.stats
        provider, test_ds = make_recording_provider(data, config, seeds["stream"])
        test_batches = provider.test_batches(test_ds, traffic["b_max"],
                                             max_samples=traffic["eval_samples"])
        t_data = time.perf_counter()
        trainer = build_trainer(config, traffic, provider, devices, seeds["program"])
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
    rec["check"] = check(cell, data, grids, seeds["program"], prog, controls)
    return rec


def check(cell, data, grids, program_seed, prog, controls=()) -> dict:
    import jax.numpy as jnp

    from chipbench import reference

    def ref_run(dtype):
        return reference.run(cell["config"], cell["traffic"], data.train, data.k,
                             data.n_lab, grids, program_seed, checked=CHECKED,
                             dtype=dtype)

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
    config, traffic = cell["config"], cell["traffic"]
    kind = devices[0].device_kind
    hidden, n_classes = config["hidden"], config["n_classes"]
    chips = len(devices)
    spmm_least, model_flops, trained = 0.0, 0.0, 0
    csr = data.train
    for grid in grids:
        for row in grid:
            for ids in row:
                if ids is None:
                    continue
                b = _padded(csr, ids, data.k)
                f, by = work.spmm_work(b["feat_idx"], b["feat_mask"],
                                       b["sample_mask"], hidden)
                spmm_least += work.least_seconds(f, by, kind)
                nnz = b["feat_mask"].sum(axis=1)[b["sample_mask"]]
                model_flops += float(work.model_flops_per_sample(
                    nnz, hidden, n_classes).sum())
                trained += len(ids)
    # every chip evaluates every test batch under the sharded placement;
    # under vmap one chip does
    eval_copies = chips if traffic["placement"] == "sharded" else 1
    eval_least = 0.0
    for batch in test_batches:
        f, by = work.spmm_work(batch.feat_idx, batch.feat_mask,
                               batch.sample_mask, hidden)
        eval_least += work.least_seconds(f, by, kind)
    spmm_least += eval_least * n_evals * eval_copies

    n_params = (config["n_features"] * hidden + hidden
                + hidden * n_classes + n_classes)
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
    return {
        "spmm_least_s": spmm_least,
        "weighted_merge_least_s": merge_least,
        "model_flops": model_flops,
        "trained_samples": trained,
        "peak_flops": work.peaks(kind)["flops"],
        "chips": chips,
    }


def _padded(csr, ids, k):
    from chipbench.reference import pack

    b = pack(csr, [ids], len(ids), k, 1)
    return {key: v[0] for key, v in b.items()}


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
