"""`chip_smoke.py` at CPU sizes: the fleet spec it runs on the chip, the
pod comparison of its four-chip phase, and its refusal to run without a
TPU."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.update(extra)
    return env


def test_fleet_spec_trains_registered_resnet34(chip_smoke):
    """The smoke's spec, cut to width 8, 8x8 images and 6 labels, runs 2
    steps through `Experiment.run()`: every client publishes and
    distills at every step, with finite losses."""
    spec = chip_smoke.fleet_spec(width=8, image_size=8, num_labels=6,
                                 labels_per_client=3, batch_size=8,
                                 steps=2, eval_batch_size=6)
    assert {c.arch for c in spec.clients} == {"resnet34"}
    assert spec.wire.exchange == "prediction_topk" and spec.wire.topk == 8
    s = chip_smoke.run_fleet(spec)
    assert s["distills"] == {i: 2 for i in range(4)}
    assert all(b > 0 for b in s["published_bytes"].values())
    assert s["rejected_publishes"] == 0
    assert all(np.isfinite(ls).all() for ls in s["losses"].values())


@pytest.mark.parametrize("env_dir", [None, "/shared/jax_cache"])
def test_compile_cache_dir_is_fixed(monkeypatch, env_dir):
    """`JAX_COMPILATION_CACHE_DIR` where set, else `.jax_cache/` at the
    checkout root: never a temp, pid or time-based path."""
    from repro.common.compile_cache import compile_cache_dir

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    want = env_dir or os.path.join(ROOT, ".jax_cache")
    assert compile_cache_dir() == want == compile_cache_dir()


def test_chip_smoke_refuses_cpu():
    proc = subprocess.run([sys.executable, SCRIPT], env=_env(),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_pod_phase_matches_one_device_on_cpu(chip_smoke):
    """The four-chip comparison on 4 virtual CPU devices at the reduced
    Mamba2 config: the mesh program holds a collective-permute, every
    device holds only its client, each client receives exactly what its
    ring neighbour sent, and every step passes the comparison with one
    device."""
    code = (
        "import json, sys; sys.path.insert(0, sys.argv[1]);"
        "import jax, chip_smoke;"
        "from repro.configs import get_reduced;"
        "r = chip_smoke.run_pod(jax.devices()[:4], 2,"
        " get_reduced('mamba2-370m'));"
        "print(json.dumps(r))")
    proc = subprocess.run(
        [sys.executable, "-c", code, ROOT], capture_output=True, text=True,
        timeout=300,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert r["collective_permute"] and r["per_device_clients"]
    assert len(r["steps"]) == 2
    for c in r["steps"]:
        assert chip_smoke.pod_failures(c) == [], c
        assert c["exchange_exact"] == [True, True]
        # on the CPU both layouts round alike: nothing discrete differs
        assert c["flips"] == 0 and c["idx_swaps"] == 0, c

