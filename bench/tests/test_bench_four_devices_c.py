"""The ``ycsb-c-range4`` cell's own mix, YCSB C, on the range-sharded path
over four forced CPU devices, in a process of its own (the device count
is fixed when JAX starts): correct against the reference, and a read that
skips the shard exchange caught."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FOUR = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from bench import harness, run
wl, conf, mix, _, _ = run.load_cell("ycsb-c-range4", root=sys.argv[1])
conf["records"] = 1 << 12
conf["tree"]["headroom_keys"] = 1024
for o in mix["ops"]:
    o["lanes"] = 32
res = {"chips": wl["chips"], "ops": [o["op"] for o in mix["ops"]]}
for fault in (None, "no_exchange"):
    out = harness.run_cell(conf, mix, 2**34 + 11, seconds=0.3, fault=fault)
    res[str(fault)] = [out["correct"], out["device"]["count"],
                       out["attempted"]]
print(json.dumps(res))
"""


def test_ycsb_c_range4_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR, ROOT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["chips"] == 4 and res["ops"] == ["read"]
    assert res["None"][:2] == [True, 4] and res["None"][2] > 0
    assert res["no_exchange"][0] is False
