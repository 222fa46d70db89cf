"""Each benchmark cell builds and steps at a tiny size on the CPU; the
command refuses a machine with no TPU; new configurations, traffic
mixes and per-layer metrics are found by name from new files alone."""
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cell as C
from chipbench import run as RUN

WORKLOADS = [w["name"] for w in C.load_benchmark()["workloads"]]
SEED = 2 ** 33 + 5  # wider than 32 bits, as the benchmark's seeds may be


def tiny(workload):
    _, cfg, _ = C.resolve(C.load_benchmark(), workload)
    return C.load_module(C.ROOT, "families", cfg["family"]).TINY


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_builds_and_distills_at_tiny_size(workload):
    cell = C.Cell(workload, SEED, overrides=tiny(workload))
    algo = cell.build()
    metrics = algo.step(0)
    for i in range(cell.clients):
        assert math.isfinite(metrics[f"c{i}/loss"])
        assert metrics[f"c{i}/distill_active"] == 1.0


def test_run_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=C.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = tmp_path
    bench = C.load_benchmark()
    base_wl = bench["workloads"][0]
    _, cfg, traffic = C.resolve(bench, base_wl["name"])
    fam = cfg["family"]
    for kind in ("configs", "traffic", "families", "metrics"):
        (root / "chipbench" / kind).mkdir(parents=True)
    shutil.copy(os.path.join(C.ROOT, "chipbench", "families", fam + ".py"),
                root / "chipbench" / "families" / (fam + ".py"))
    cfg = dict(cfg, name="dummy_cfg", arch_name="dummy_cfg_arch")
    (root / "chipbench" / "configs" / "dummy_cfg.json").write_text(
        json.dumps(cfg))
    (root / "chipbench" / "traffic" / "dummy_mix.json").write_text(
        json.dumps(traffic))
    (root / "chipbench" / "metrics" / "dummy_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.steps\n")
    bench["configs"].append(dict(bench["configs"][0], name="dummy_cfg",
                                 file="chipbench/configs/dummy_cfg.json"))
    bench["workloads"].append(dict(base_wl, name="dummy_cfg.dummy_mix",
                                   config="dummy_cfg", traffic="dummy_mix"))
    moves = bench["end_to_end"][0]["name"]
    for name in ("dummy_metric", "dummy_metric.split"):
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "program_counter", "layer": "device",
            "moves": moves, "workloads": ["dummy_cfg.dummy_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = C.Cell("dummy_cfg.dummy_mix", SEED, root=str(root),
                  overrides=tiny(base_wl["name"]))
    assert cell.spec().clients[0].arch.startswith("dummy_cfg_arch-")
    algo = cell.build()
    assert math.isfinite(algo.step(0)["c0/loss"])

    class Ctx:
        steps = 3

    got = RUN.per_layer_metrics(C.load_benchmark(str(root)),
                                "dummy_cfg.dummy_mix", Ctx(), str(root))
    # a split metric is read by its quantity's reader
    assert got == {"dummy_metric": {"value": 6.0, "unit": "x"},
                   "dummy_metric.split": {"value": 6.0, "unit": "x"}}
