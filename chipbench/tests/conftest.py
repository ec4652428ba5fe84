"""CPU test set-up for the chip benchmark: four virtual CPU devices (for the
sharded traffic), the program and the benchmark on the import path, and a
checkout-like root holding the benchmark's files at a tiny configuration."""
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
)
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import pytest  # noqa: E402

TINY = dict(n_features=4096, n_classes=1024, hidden=32, avg_nnz=16, avg_labels=3)
# the repository's Amazon cell whose limits each traffic's tiny cell takes
LIMITS_OF = {"adaptive.r4": "amazon670k.adaptive.r4",
             "adaptive.r4.sharded": "amazon670k.adaptive.r4.chips4",
             "single.r1": "amazon670k.single.r1"}


def make_root(path, cells=("adaptive.r4", "adaptive.r4.sharded", "single.r1")):
    """A root with BENCHMARK.json and chipbench/ data files, where each of
    the repository's traffic mixes runs on a tiny configuration with few
    samples. Cells are named ``tiny.<traffic>``; each takes the limits of
    the repository's Amazon cell in ``LIMITS_OF``."""
    src = os.path.join(ROOT, "chipbench")
    dst = os.path.join(path, "chipbench")
    for d in ("configs", "traffic", "families", "metrics", "limits"):
        shutil.copytree(os.path.join(src, d), os.path.join(dst, d))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(dst, "configs", "xml-amazon-670k.json")) as f:
        config = json.load(f)
    config.update(name="tiny", **TINY)
    with open(os.path.join(dst, "configs", "tiny.json"), "w") as f:
        json.dump(config, f)
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    for traffic in cells:
        with open(os.path.join(dst, "traffic", f"{traffic}.json")) as f:
            t = json.load(f)
        t.update(samples=8192, eval_samples=512)
        with open(os.path.join(dst, "traffic", f"tiny.{traffic}.json"), "w") as f:
            json.dump(t, f)
        name = f"tiny.{traffic}"
        bench["workloads"].append({
            "name": name, "config": "tiny", "traffic": f"tiny.{traffic}",
            "chips": 4 if t["placement"] == "sharded" else 1, "why": "test"})
        shutil.copy(os.path.join(dst, "limits", f"{LIMITS_OF[traffic]}.json"),
                    os.path.join(dst, "limits", f"{name}.json"))
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(path)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("root"))
