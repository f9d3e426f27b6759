"""End-to-end behaviour: train loop with checkpoint/restart, sharded train
step on a local production-axis mesh, dry-run cell as a subprocess."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from repro.launch.train import train_loop


def test_train_loop_learns_and_restarts(tmp_path):
    cfg = get_config("yi-9b", smoke=True)
    out = train_loop(cfg, steps=30, batch=4, seq=32, ckpt_dir=str(tmp_path),
                     save_every=10, lr=3e-3, inject_failure=17, log_every=100)
    losses = sorted(out["losses"].items())
    assert len(out["restarts"]) == 1
    first = np.mean([l for _, l in losses[:5]])
    last = np.mean([l for _, l in losses[-5:]])
    assert last < first, (first, last)


def test_train_loop_microbatch_and_compression():
    cfg = get_config("yi-9b", smoke=True)
    out = train_loop(cfg, steps=6, batch=4, seq=32, n_micro=2, compress=True,
                     log_every=100)
    assert all(np.isfinite(l) for l in out["losses"].values())


def test_local_mesh_sharded_train_step():
    """The production train-step code path (shardings + constraints) on a
    1-device mesh with production axis names."""
    from repro.parallel import sharding as SH
    from repro.train import optim as O
    from repro.train.train_step import init_state, make_train_step
    cfg = get_config("qwen3-14b", smoke=True)
    mesh = make_local_mesh(("data", "model"))
    ocfg = O.OptConfig(lr=1e-3, warmup=1, total_steps=10)
    state = init_state(cfg, ocfg, jax.random.PRNGKey(0))
    step = make_train_step(cfg, ocfg, shard=SH.shard)
    with mesh, SH.ShardCtx(mesh):
        jstep = jax.jit(step)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab, jnp.int32)
        state, m = jstep(state, {"tokens": toks})
    assert np.isfinite(float(m["loss"]))


def test_dryrun_single_cell_smoke():
    """Lower+compile one production cell exactly as the launcher does (the
    512-virtual-device env only exists in the subprocess)."""
    code = (
        "from repro.launch.dryrun import run_cell; import json; "
        "r = run_cell('paligemma-3b', 'decode_32k', 'single'); "
        "print(json.dumps({'status': r['status'], "
        "'dom': r.get('roofline', {}).get('dominant')}))"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"     # the parent may hold the accelerator
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=560, env=env, cwd=".")
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["status"] == "ok"
