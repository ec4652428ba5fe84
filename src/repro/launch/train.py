"""Production training launcher.

Runs the Adaptive SGD elastic trainer (or any baseline algorithm) over
either the paper's sparse-XML workload or any assigned LM architecture.

On a real TPU fleet the same entrypoint runs under a production mesh
(``--mesh single|multi``): the trainer's (R, ...) replica leaves are sharded
over the replica mesh axis via the rules in sharding/rules.py. On CPU (CI /
smoke) it runs the reduced config on one device — identical code path,
identical algorithm semantics; only the mesh differs.

``--algorithm`` accepts anything in the core/algorithms registry — the
paper's Adaptive SGD, the baselines, and any plugin registered through the
public Algorithm API (e.g. the ABS-SGD-style ``delayed_sync``).

``--elastic-schedule`` drives the paper's other elasticity axis — workers
joining/leaving mid-run (DESIGN.md §6): a ``megabatch:R`` list resizes the
replica population at those mega-batch boundaries (re-plan, re-shard, carry
momentum) instead of forcing a restart.

Examples:
  PYTHONPATH=src python -m repro.launch.train --workload xml \
      --algorithm adaptive --replicas 4 --megabatches 20
  PYTHONPATH=src python -m repro.launch.train --workload xml \
      --algorithm delayed_sync --replicas 4 --megabatches 20
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --algorithm adaptive --megabatches 5
  PYTHONPATH=src python -m repro.launch.train --workload xml \
      --algorithm adaptive --megabatches 60 --elastic-schedule "0:4,20:6,40:3"
  PYTHONPATH=src python -m repro.launch.train --workload xml \
      --algorithm adaptive --megabatches 30 \
      --faults "seed=7,p_crash=0.05,3:nan:0,5:join" \
      --checkpoint-dir /tmp/run1 --checkpoint-every 5
  PYTHONPATH=src python -m repro.launch.train --workload xml \
      --algorithm adaptive --megabatches 30 \
      --checkpoint-dir /tmp/run1 --restore-from /tmp/run1
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.configs.archs import ARCHS
from repro.configs.base import ElasticConfig
from repro.core import algorithms
from repro.core.heterogeneity import MeasuredSpeedModel, SpeedModel
from repro.core.trainer import ENGINES, PLACEMENTS, ElasticTrainer
from repro.data.providers import SparseProvider, TokenProvider
from repro.data.xml_synth import make_xml_dataset
from repro.data.sparse import train_test_split
from repro.models import model as MDL
from repro.models.xml_mlp import XMLMLPConfig, make_model as make_xml_model
from repro.optim.sgd import SGDConfig
from repro.utils.logging import log


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_persistent_compilation_cache() -> str:
    """Keep compiled programs across processes. ``JAX_COMPILATION_CACHE_DIR``
    wins when set (JAX reads it itself); otherwise the cache is the fixed
    ``<repo>/.jax_cache``, since the directory is part of every entry's
    key. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_elastic_schedule(spec: str) -> dict[int, int]:
    """``"0:4,20:6,40:3"`` -> ``{0: 4, 20: 6, 40: 3}``.

    Keys are 0-based mega-batch indices; values the replica count that
    takes effect before that mega-batch. Entries may come in any order;
    duplicates keep the last occurrence (argparse-style override).
    """
    out: dict[int, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            mb_str, r_str = part.split(":")
            mb, r = int(mb_str), int(r_str)
        except ValueError:
            raise ValueError(
                f"bad --elastic-schedule entry {part!r}; expected"
                " 'megabatch:replicas' (e.g. '0:4,20:6,40:3')"
            ) from None
        if mb < 0 or r < 1:
            raise ValueError(
                f"bad --elastic-schedule entry {part!r}: mega-batch index"
                " must be >= 0 and replica count >= 1"
            )
        out[mb] = r
    if not out:
        raise ValueError("--elastic-schedule is empty")
    return out


def build_xml_workload(args):
    ds = make_xml_dataset(
        n_samples=args.samples,
        n_features=args.features,
        n_classes=args.classes,
        avg_nnz=args.avg_nnz,
        avg_labels=args.avg_labels,
        seed=args.seed,
    )
    train, test = train_test_split(ds, test_frac=0.2, seed=args.seed)
    provider = SparseProvider.make(train, seed=args.seed)
    model = make_xml_model(
        XMLMLPConfig(n_features=ds.n_features, n_classes=ds.n_classes,
                     hidden=args.hidden)
    )
    test_batches = provider.test_batches(test, args.b_max, max_samples=2048)
    return model, provider, test_batches


def build_lm_workload(args):
    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    provider = TokenProvider.make(cfg.vocab_size, args.seq_len, seed=args.seed)
    model = MDL.make_model(cfg)
    test_batches = provider.test_batches(2, args.b_max)
    return model, provider, test_batches


def main(argv=None):
    """Parse ``argv``, train, and return ``(state, metrics_log, trainer)``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lm", choices=["xml", "lm"])
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=list(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU smoke)")
    ap.add_argument("--algorithm", default="adaptive",
                    choices=list(algorithms.available()),
                    help="any algorithm in the core/algorithms registry"
                         " (plugins registered via @algorithms.register"
                         " appear here automatically)")
    ap.add_argument("--engine", default="scan", choices=list(ENGINES),
                    help="mega-batch executor: device-resident scan (default)"
                         " or a per-round host loop over the same staged"
                         " mega-batch (the scan engine's test reference)")
    ap.add_argument("--overlap", default="on", choices=["on", "off"],
                    help="overlapped mega-batch pipeline (DESIGN.md §8):"
                         " stage mega-batch N+1 (plan + pack + upload) while"
                         " N executes, and evaluate asynchronously. 'off'"
                         " stages each mega-batch just in time and evaluates"
                         " synchronously, on the same path — bit-identical"
                         " trajectories under the simulated speed model")
    ap.add_argument("--placement", default="vmap", choices=list(PLACEMENTS),
                    help="replica placement: single-device vmap (default) or"
                         " shard_map over a 1-D replica device mesh (spans"
                         " the local accelerators; on CPU CI, the virtual"
                         " devices from --xla_force_host_platform_device_count)")
    ap.add_argument("--multihost", default="auto", choices=["auto", "off"],
                    help="multi-process fleet bootstrap (DESIGN.md §10):"
                         " 'auto' spans processes when the REPRO_MH_*"
                         " environment (set by scripts/multihost_launch.py)"
                         " is present; 'off' ignores it")
    ap.add_argument("--heartbeat-interval", type=float, default=0.5,
                    help="multi-host lease renewal period (seconds)")
    ap.add_argument("--heartbeat-grace", type=float, default=3.0,
                    help="multi-host liveness deadline: a process whose"
                         " lease has not changed for this long is declared"
                         " crashed and evicted")
    ap.add_argument("--speed", default="simulated",
                    choices=["simulated", "measured"],
                    help="heterogeneity source for the scheduler's virtual"
                         " clock: simulated per-replica factors (paper Fig. 1"
                         " reproduction, deterministic) or relative speeds"
                         " measured from real round times (closes the paper"
                         " §3.1 feedback loop on live hardware)")
    ap.add_argument("--dense-grads", action="store_true",
                    help="force dense autodiff instead of the row-sparse"
                         " gradient path (the differential oracle)")
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--elastic-schedule", default="",
                    help="'megabatch:R' list, e.g. '0:4,20:6,40:3': resize"
                         " the replica population at those mega-batch"
                         " boundaries (workers joining/leaving, DESIGN.md"
                         " §6). An entry at 0 overrides --replicas; the"
                         " trainer re-plans, re-shards and carries momentum"
                         " at each boundary")
    ap.add_argument("--faults", default="",
                    help="fault-injection spec (DESIGN.md §7): comma list of"
                         " injector rates (seed=7,p_crash=0.02,...) and"
                         " scripted events 'MB:kind[:replica[:duration]]'"
                         " with kind in crash|preempt|join|stall|nan, e.g."
                         " 'seed=7,3:crash:1,5:join,7:nan:0'. Runs the"
                         " trainer under a FleetController (reactive"
                         " resize + quarantine)")
    ap.add_argument("--min-replicas", type=int, default=1,
                    help="fleet floor: evictions never shrink below this")
    ap.add_argument("--max-replicas", type=int, default=0,
                    help="fleet ceiling for joins/readmissions (0 = 2x the"
                         " initial replica count)")
    ap.add_argument("--timeout-factor", type=float, default=0.0,
                    help="health detector: evict a replica whose relative"
                         " speed exceeds this multiple of the population"
                         " median (0 disables)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="enable crash-consistent async checkpointing into"
                         " this directory (atomic publish, bounded"
                         " retention)")
    ap.add_argument("--checkpoint-every", type=int, default=5,
                    help="mega-batches between checkpoints")
    ap.add_argument("--checkpoint-retain", type=int, default=3,
                    help="published checkpoints kept on disk")
    ap.add_argument("--restore-from", default="",
                    help="resume from this checkpoint (a ckpt-* directory,"
                         " or a checkpoint dir — the newest complete"
                         " checkpoint is used)")
    ap.add_argument("--megabatches", type=int, default=10)
    ap.add_argument("--mega-batch", type=int, default=20,
                    help="batches per mega-batch (paper default 100)")
    ap.add_argument("--b-max", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--hetero", type=float, default=0.32,
                    help="max relative GPU speed gap (paper Fig.1: 32%%)")
    # XML synth dataset knobs
    ap.add_argument("--samples", type=int, default=8192)
    ap.add_argument("--features", type=int, default=4096)
    ap.add_argument("--classes", type=int, default=1024)
    ap.add_argument("--avg-nnz", type=int, default=64)
    ap.add_argument("--avg-labels", type=int, default=3)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    use_persistent_compilation_cache()

    mh = None
    monitor = None
    if args.multihost != "off":
        from repro.launch import multihost as mhmod

        spec = mhmod.spec_from_env()
        if spec is not None:
            if args.elastic_schedule:
                ap.error("--elastic-schedule is incompatible with a"
                         " multi-host fleet: membership is process-grained"
                         " and signal-driven (DESIGN.md §10)")
            if args.faults:
                ap.error("--faults is incompatible with a multi-host fleet:"
                         " the HeartbeatMonitor is the liveness source; the"
                         " injector stays a single-process test harness")
            if args.speed == "measured":
                ap.error("--speed measured is incompatible with a multi-host"
                         " fleet: per-replica timing only observes the local"
                         " slot block")
            if args.placement != "sharded":
                log("multihost forces --placement sharded")
                args.placement = "sharded"
            mh = mhmod.bootstrap(spec)
            log("multihost bootstrap",
                process=spec.process_id, n_processes=spec.num_processes,
                spanning=mh.spanning, fleet_dir=spec.fleet_dir or "-")
            if mh.spanning == "host":
                from repro.core.fleet import HeartbeatMonitor

                monitor = HeartbeatMonitor(
                    spec.fleet_dir, process_id=spec.process_id,
                    interval=args.heartbeat_interval,
                    grace=args.heartbeat_grace,
                )
                monitor.renew(megabatch=0)
                monitor.start()
                mh.attach_liveness(monitor)
                mh.rendezvous()

    if args.workload == "xml":
        model, provider, test_batches = build_xml_workload(args)
    else:
        model, provider, test_batches = build_lm_workload(args)

    schedule = None
    if args.elastic_schedule:
        schedule = parse_elastic_schedule(args.elastic_schedule)
        if 0 in schedule:
            args.replicas = schedule[0]  # initial membership
        log("elastic schedule",
            events={mb: schedule[mb] for mb in sorted(schedule)})

    ecfg = ElasticConfig.from_bmax(
        args.b_max,
        algorithm=args.algorithm,
        n_replicas=algorithms.get(args.algorithm).resolve_n_replicas(args.replicas),
        mega_batch=args.mega_batch,
        placement=args.placement,
    )
    if args.speed == "measured":
        speed = MeasuredSpeedModel(ecfg.n_replicas)
    else:
        speed = SpeedModel(ecfg.n_replicas, max_gap=args.hetero, seed=args.seed)
    mesh = None
    if args.placement == "sharded" and schedule is None and mh is None:
        # with an elastic schedule the trainer owns the mesh: it draws
        # per-population meshes from the full local device pool as R changes
        from repro.launch.mesh import make_replica_mesh

        mesh = make_replica_mesh(ecfg.n_replicas)
        log("replica mesh",
            devices=mesh.shape["replica"],
            replicas_per_shard=ecfg.n_replicas // mesh.shape["replica"])
    trainer = ElasticTrainer(
        model=model, provider=provider, cfg=ecfg,
        sgd=SGDConfig(), base_lr=args.lr, speed=speed, seed=args.seed,
        engine=args.engine, sparse_grads=not args.dense_grads, mesh=mesh,
        overlap=args.overlap == "on", multihost=mh,
    )
    fleet = None
    if args.faults or args.timeout_factor > 0 or monitor is not None:
        from repro.core.fleet import FleetController, parse_fault_spec

        fleet = FleetController(
            injector=parse_fault_spec(args.faults) if args.faults else None,
            monitor=monitor,
            min_replicas=args.min_replicas,
            max_replicas=args.max_replicas or 2 * ecfg.n_replicas,
            timeout_factor=args.timeout_factor,
            verbose=True,
        )
    manager = None
    if args.checkpoint_dir:
        from repro.checkpoint.store import CheckpointManager

        manager = CheckpointManager(
            args.checkpoint_dir, every=args.checkpoint_every,
            retain=args.checkpoint_retain,
            publisher=mh is None or mh.process_id == 0,
        )
    try:
        state, mlog = trainer.run(
            args.megabatches, test_batches=test_batches, verbose=True,
            resize_schedule=schedule, fleet=fleet, checkpoint=manager,
            restore_from=args.restore_from or None,
        )
    finally:
        if monitor is not None:
            monitor.stop()
    if monitor is not None:
        # completed: flip the lease to 'done' so survivors treat our exit
        # as orderly, not as a missed deadline
        monitor.renew(status="done")
    final = mlog.records[-1] if mlog.records else {}
    log("final",
        algorithm=args.algorithm,
        accuracy=round(final.get("accuracy", float("nan")), 4),
        virtual_time=round(final.get("virtual_time", float("nan")), 3))
    if fleet is not None:
        log("fleet", events=len(fleet.events),
            replicas=trainer.cfg.n_replicas)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(mlog.records, f, indent=1)
    return state, mlog, trainer


if __name__ == "__main__":
    main()
