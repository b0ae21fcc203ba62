"""Without a TPU the benchmark measures nothing and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import load_json, per_layer_for, run_cell  # noqa: E402


def _run(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_cpu_run_exits_without_a_result():
    proc = _run(ROOT, "--workload", "mix100.burst", "--seed", "3000000001",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_checkout_without_the_program_exits_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, "--workload", "mix100.burst", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_traced_cpu_run_reports_no_device_metric():
    """Below ``run.py``'s look for a chip the whole traced run goes through
    on the CPU at a tiny size, and the readers that need a device trace
    return nothing."""
    bench = load_json(ROOT / "BENCHMARK.json")
    wl = next(w for w in bench["workloads"] if w["name"] == "mix100.burst")
    out = run_cell(bench, wl, 5, 0.3, True, log=lambda m: None,
                   config_overrides={"n_devices": 40, "horizon_s": 3000.0},
                   traffic_overrides={"instances": 24, "warm_rows": [],
                                      "check_instances": 8},
                   compile_cache=False)
    device_metrics = {m["name"] for m in per_layer_for(bench, wl)
                      if m["source"] == "device_trace"}
    assert device_metrics
    assert not device_metrics & set(out["metrics"])
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["busy_s"] == 0.0
    assert out["correct"]
    json.dumps(out)
