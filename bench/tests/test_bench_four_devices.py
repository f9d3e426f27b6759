"""The range-sharded path of the benchmark on four forced CPU devices, in a
process of its own (the device count is fixed when JAX starts): correct
against the reference, and a read that skips the shard exchange caught."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FOUR = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from bench import harness, traffic
conf = json.load(open(sys.argv[1] + "/bench/configs/ycsb-2e27-range4.json"))
conf["records"] = 1 << 12
conf["tree"]["headroom_keys"] = 1024
mix = traffic.load_mix(sys.argv[1] + "/bench/traffic/ycsb-a.json")
for o in mix["ops"]:
    o["lanes"] = 32
res = {}
for fault in (None, "no_exchange"):
    out = harness.run_cell(conf, mix, 2**33 + 7, seconds=0.3, fault=fault)
    res[str(fault)] = [out["correct"], out["device"]["count"]]
print(json.dumps(res))
"""


def test_range_sharded_path_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR, ROOT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["None"] == [True, 4]
    assert res["no_exchange"][0] is False
