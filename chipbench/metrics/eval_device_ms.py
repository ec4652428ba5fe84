"""Device time of the held-out evaluation per mega-batch. The evaluation is
the program that runs once per test batch in every mega-batch, so its
module is the one run (mega-batches x test batches) times on a device."""


def read(t, record):
    n = t["megabatches"] * t["eval_batches"]
    per_device = [
        sum(v for k, v in d["modules"].items() if d["module_counts"][k] == n)
        for d in t["devices"]
    ]
    if not t["megabatches"] or not per_device or not any(per_device):
        return None
    return 1e3 * sum(per_device) / len(per_device) / t["megabatches"]
