"""Readings that set the limits of ``correct``, on the chip, at a cell's own
size, in one process:

* the program on many seeds (each limit's lower reading is the largest);
* the control, the reference computed in bfloat16 in the program's place,
  on a few seeds;
* each fault the cell can have (``faults.py``, or its family's own),
  planted under the timed path, on a few seeds.

    python3 chipbench/calibrate.py --workload <cell> --seeds 12 --few 3

Each run goes through set-up only: the numbers compared are read from the
mega-batches set-up trains, through the window's own call. Writes one JSON
line per run to ``--out`` and prints, per number, the lower reading, the
control's and each fault's smallest reading.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# JAX's persistent compilation cache lives at a fixed path inside the
# checkout (the path is part of every entry's key); the program takes it
# from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

from chipbench import compare, faults, harness  # noqa: E402

FIRST_SEED = 1_000_003


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--few", type=int, default=3)
    ap.add_argument("--faults", default="",
                    help="comma list of faults to read, or 'none' (default: "
                         "every one the cell can have)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    cell = harness.load_cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell["chips"]:
        sys.exit(f"calibrate: needs {cell['chips']} accelerator chips, found {devices}")
    devices = devices[:cell["chips"]]
    from repro.launch.train import use_persistent_compilation_cache

    use_persistent_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    names = (faults.applicable(cell["traffic"]) if not args.faults
             else [] if args.faults == "none" else args.faults.split(","))
    readings = {"program": [], "control": [], **{f: [] for f in names}}
    with open(args.out, "w") as f:
        def run(kind, seed, **kw):
            t = time.perf_counter()
            rec = harness.run_cell(cell, seed, 0.0, False, devices,
                                   time.perf_counter(), warm_only=True, **kw)
            row = {"kind": kind, "seed": seed, "values": rec["check"]["values"],
                   "program_losses": rec["program"]["losses"],
                   "reference_losses": rec["check"]["reference"]["losses"],
                   "program_deltas": rec["program"]["deltas"],
                   "reference_deltas": rec["check"]["reference"]["deltas"],
                   "seconds": time.perf_counter() - t}
            if "controls" in rec["check"]:
                row["control"] = rec["check"]["controls"]["bfloat16"]
                readings["control"].append(row["control"])
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(kind, seed, json.dumps(row["values"]), row.get("control", ""),
                  f"{row['seconds']:.1f}s", flush=True)
            return row["values"]

        for i in range(args.seeds):
            seed = FIRST_SEED + i
            controls = ("bfloat16",) if i < args.few else ()
            readings["program"].append(run("program", seed, controls=controls))
        for name in names:
            for i in range(args.few):
                with faults.plant(name, cell["family"]):
                    readings[name].append(run(name, FIRST_SEED + 100 + i))
    summary = {}
    for number in compare.NAMES:
        summary[number] = {
            **({"lower": max(r[number] for r in readings["program"])}
               if readings["program"] else {}),
            **{k: min(r[number] for r in v) for k, v in readings.items()
               if k != "program" and v},
        }
    print(json.dumps({"workload": args.workload, "summary": summary}, indent=1))


if __name__ == "__main__":
    main()
