"""The benchmark end to end on the CPU at 2^12 keys: every op type of the
mix vocabulary against the reference, the control and each planted fault
caught, the command's refusals, and a mix added as a data file alone."""
import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, run, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SEED = 2**33 + 12345


def small(conf_name="ycsb-2e25-1chip", records=1 << 12):
    with open(os.path.join(BENCH, "configs", conf_name + ".json")) as f:
        conf = json.load(f)
    conf["records"] = records
    conf["tree"]["headroom_keys"] = 1024
    return conf


def mix_of(name, lanes=64):
    mix = traffic.load_mix(os.path.join(BENCH, "traffic", name + ".json"))
    mix = copy.deepcopy(mix)
    for o in mix["ops"]:
        o["lanes"] = lanes
    return mix


ALL_OPS = {
    "ops": [{"op": t, "lanes": 32} for t in traffic.OP_TYPES],
    "request_distribution": {"kind": "zipfian", "theta": 0.99},
    "scan_length": {"min": 1, "max": 16}, "max_items": 16,
    "insert_pool": 1 << 14,
}


def test_every_op_type_matches_the_reference():
    out = harness.run_cell(small(), ALL_OPS, SEED, seconds=0.2)
    assert out["correct"], out["checks"]
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"ops_per_s", "p95_op_ms", "setup_s"} <= set(out["metrics"])


def test_latest_and_uniform_distributions():
    for dist in ({"kind": "latest", "theta": 0.99}, {"kind": "uniform"}):
        mix = dict(ALL_OPS, request_distribution=dist)
        out = harness.run_cell(small(), mix, SEED + 1, seconds=0.2)
        assert out["correct"], (dist, out["checks"])


@pytest.mark.parametrize("fault", ["control", "lost_writes", "half_batch",
                                   "altered_answer", "stale_reads"])
def test_planted_fault_is_not_correct(fault):
    out = harness.run_cell(small(), mix_of("ycsb-a"), SEED, seconds=0.3,
                           fault=fault)
    assert not out["correct"], out["checks"]


def test_control_fails_reads_of_ycsb_c():
    out = harness.run_cell(small(), mix_of("ycsb-c", 256), SEED, seconds=0.3,
                           fault="control")
    assert out["checks"]["read_mismatch"]["value"] > 0


def test_traced_run_on_cpu_reports_no_device_metric():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    out = harness.run_cell(small(), mix_of("ycsb-c"), SEED, seconds=0.3,
                           trace=True, per_layer=spec["per_layer"])
    assert out["correct"]
    # the CPU trace has no device plane: no idle share, program time or
    # roofline, and never a 0 in their place
    assert set(out["metrics"]) <= {"client_host_ms"}


def _command(cwd, env_extra=None, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ycsb-c", "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def test_command_refuses_a_cpu_backend():
    p = _command(ROOT, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_command_needs_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_a_mix_added_as_a_data_file_runs(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    with open(tmp_path / "bench" / "traffic" / "throwaway.json", "w") as f:
        json.dump({"ops": [{"op": "rmw", "lanes": 48},
                           {"op": "read", "lanes": 16}],
                   "request_distribution": {"kind": "uniform"}}, f)
    spec["workloads"].append({"name": "throwaway", "config": "ycsb-2e25-1chip",
                              "traffic": "throwaway", "chips": 1,
                              "why": "a rehearsal mix"})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    wl, conf, mix, e2e, per_layer = run.load_cell("throwaway",
                                                  root=str(tmp_path))
    conf["records"] = 1 << 12
    conf["tree"]["headroom_keys"] = 1024
    out = harness.run_cell(conf, mix, SEED, seconds=0.2)
    assert out["correct"], out["checks"]
    assert {m["name"] for m in e2e} >= {"ops_per_s", "setup_s"}
