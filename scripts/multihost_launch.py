"""Spawn an N-process multi-host fleet on one machine (DESIGN.md §10).

Launches N copies of ``python -m repro.launch.train`` with the
``REPRO_MH_*`` bootstrap environment (process id, fleet count, shared
fleet dir) and ``--xla_force_host_platform_device_count=K`` so each
process sees K virtual CPU devices. The processes rendezvous through the
fleet dir's heartbeat leases and exchange merge/metrics partials through
its file exchange — a real multi-process elastic fleet, no injector.

This is the CPU-fleet launcher, not a chip path: every child is pinned to
the CPU (``JAX_PLATFORMS=cpu`` unless the caller sets it). A TPU chip
belongs to one process at a time, so N children on one host cannot share
it; a chip run is one process (``chip_smoke.py``), and a multi-host TPU
fleet starts one ``repro.launch.train`` per host with the ``REPRO_MH_*``
environment (README, "Multi-host fleets").

Exit status is 0 iff every process that was not deliberately killed
exited 0. Per-process output is teed to ``<fleet-dir>/logs/proc<i>.log``
and tails are printed on completion.

Fault drill: ``--kill-proc I --kill-after-mb M`` SIGKILLs process I once
its lease reports mega-batch >= M (the lease's ``megabatch`` field is
renewed by the FleetController each boundary, so the kill lands mid-run,
deterministically after M completed mega-batches). Survivors must detect
the missed heartbeat deadline, evict process I's replicas, and finish.

Example (2 processes x 2 replicas each, global R=4):
  PYTHONPATH=src python scripts/multihost_launch.py \
      --procs 2 --devices-per-proc 2 -- \
      --workload xml --placement sharded --replicas 4 --megabatches 5
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time


def _read_megabatch(leases_dir: str, pid: int) -> int:
    path = os.path.join(leases_dir, f"proc-{pid}.json")
    try:
        with open(path) as f:
            payload = json.load(f)
        return int(payload.get("megabatch") or 0)
    except (OSError, ValueError):
        return -1


def _tail(path: str, lines: int = 15) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError:
        return "<no log>"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--procs", type=int, default=2,
                    help="number of trainer processes to spawn")
    ap.add_argument("--devices-per-proc", type=int, default=2,
                    help="virtual CPU devices per process (XLA host"
                         " platform device count)")
    ap.add_argument("--fleet-dir", default="",
                    help="shared rendezvous/exchange dir (default: a fresh"
                         " mktemp dir, left on disk for post-mortem)")
    ap.add_argument("--kill-proc", type=int, default=-1,
                    help="SIGKILL this process id mid-run (heartbeat drill)")
    ap.add_argument("--kill-after-mb", type=int, default=2,
                    help="kill once the target's lease reports >= this"
                         " many completed mega-batches")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="overall wall-clock budget (seconds)")
    ap.add_argument("train_args", nargs=argparse.REMAINDER,
                    help="arguments after '--' go to repro.launch.train")
    args = ap.parse_args(argv)

    train_args = args.train_args
    if train_args and train_args[0] == "--":
        train_args = train_args[1:]
    if args.procs < 1:
        ap.error("--procs must be >= 1")
    if args.kill_proc >= args.procs:
        ap.error("--kill-proc out of range")

    fleet_dir = args.fleet_dir or tempfile.mkdtemp(prefix="repro-fleet-")
    logs_dir = os.path.join(fleet_dir, "logs")
    leases_dir = os.path.join(fleet_dir, "leases")
    os.makedirs(logs_dir, exist_ok=True)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env = dict(os.environ)
    base_env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), base_env.get("PYTHONPATH", "")]
    )
    base_env["REPRO_MH_NUM_PROCESSES"] = str(args.procs)
    base_env["REPRO_MH_FLEET_DIR"] = fleet_dir
    xla = base_env.get("XLA_FLAGS", "")
    base_env["XLA_FLAGS"] = (
        f"{xla} --xla_force_host_platform_device_count="
        f"{args.devices_per_proc}"
    ).strip()
    base_env.setdefault("JAX_PLATFORMS", "cpu")

    procs: list[subprocess.Popen] = []
    logs: list[str] = []
    for pid in range(args.procs):
        env = dict(base_env)
        env["REPRO_MH_PROCESS_ID"] = str(pid)
        log_path = os.path.join(logs_dir, f"proc{pid}.log")
        logs.append(log_path)
        log_f = open(log_path, "w")
        procs.append(subprocess.Popen(
            [sys.executable, "-u", "-m", "repro.launch.train"] + train_args,
            env=env, stdout=log_f, stderr=subprocess.STDOUT,
        ))
    print(f"[multihost-launch] {args.procs} processes, fleet_dir={fleet_dir}",
          flush=True)

    deadline = time.monotonic() + args.timeout
    killed = False
    timed_out = False
    while True:
        alive = [p for p in procs if p.poll() is None]
        if not alive:
            break
        if time.monotonic() > deadline:
            timed_out = True
            for p in alive:
                p.kill()
            break
        if (args.kill_proc >= 0 and not killed
                and procs[args.kill_proc].poll() is None
                and _read_megabatch(leases_dir, args.kill_proc)
                >= args.kill_after_mb):
            print(f"[multihost-launch] SIGKILL proc {args.kill_proc} "
                  f"(lease mb >= {args.kill_after_mb})", flush=True)
            procs[args.kill_proc].send_signal(signal.SIGKILL)
            killed = True
        time.sleep(0.1)

    failed = False
    for pid, p in enumerate(procs):
        rc = p.wait()
        deliberate = killed and pid == args.kill_proc
        status = "killed" if deliberate else f"rc={rc}"
        print(f"[multihost-launch] proc {pid}: {status}", flush=True)
        if not deliberate and rc != 0:
            failed = True
    if timed_out:
        print(f"[multihost-launch] TIMEOUT after {args.timeout}s", flush=True)
        failed = True
    if args.kill_proc >= 0 and not killed:
        print("[multihost-launch] kill never triggered (target exited or"
              " lease stalled before --kill-after-mb)", flush=True)
        failed = True
    for pid, path in enumerate(logs):
        print(f"--- proc {pid} tail ({path}) ---\n{_tail(path)}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
