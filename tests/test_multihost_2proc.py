"""Real 2-process jax.distributed exercise.

Two CPU subprocesses (coordinator on localhost, 2 forced local devices
each -> 4 global) run initialize_from_env, assemble a global array from
per-host shards, reconcile counts with all_hosts_sum, and train a small
DP-sharded ALS whose factors must match the single-device oracle — the
degenerate single-process paths tested in test_multihost.py actually
crossing process boundaries here (SURVEY.md §7.9; the reference's
equivalent surface is Spark driver/executor, testable only in local
mode there)."""

import os
import socket
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = """
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from predictionio_tpu.parallel import multihost as mh
from predictionio_tpu.parallel.mesh import create_mesh

assert mh.initialize_from_env() is True, "distributed init did not engage"
assert jax.process_count() == 2
assert jax.device_count() == 4, jax.device_count()
assert jax.local_device_count() == 2

mesh = create_mesh({"data": 4})

# global_array: each host contributes its contiguous axis-0 shard
n = 16
sl = mh.host_shard_slice(n)
full = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
g = mh.global_array(full[sl], mesh, "data")
assert g.shape == (n, 3)
total = jax.jit(
    lambda a: a.sum(), out_shardings=NamedSharding(mesh, P())
)(g)
np.testing.assert_allclose(float(total), full.sum())

# all_hosts_sum: per-host counts reconcile across processes
counts = np.array([10.0 + mh.process_index(), 1.0])
summed = mh.all_hosts_sum(counts, mesh)
np.testing.assert_allclose(summed, [21.0, 2.0])   # (10+0) + (10+1), 1+1

# DP-sharded ALS across the 2-process mesh matches the 1-device oracle
from predictionio_tpu.ops.als import ALSConfig, als_train

rng = np.random.default_rng(3)
nnz, n_users, n_items = 400, 32, 16
coo = (rng.integers(0, n_users, nnz), rng.integers(0, n_items, nnz),
       (rng.random(nnz) * 4 + 1).astype(np.float32))
cfg = ALSConfig(rank=8, iterations=2, reg=0.1, block_size=8, seg_len=8,
                compute_dtype="float32", cg_dtype="float32")
sharded = als_train(coo, n_users, n_items, cfg, mesh=mesh)
oracle = als_train(coo, n_users, n_items, cfg, mesh=None)
np.testing.assert_allclose(
    sharded.user_factors, oracle.user_factors, rtol=2e-3, atol=2e-3
)
print(f"MULTIHOST2 OK p{mh.process_index()}")
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed(tmp_path):
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("PYTEST_CURRENT_TEST", None)
        env.update(
            {
                "PYTHONPATH": REPO_ROOT,
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "PIO_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
                "PIO_NUM_PROCESSES": "2",
                "PIO_PROCESS_ID": str(pid),
            }
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _WORKER], cwd=REPO_ROOT, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"MULTIHOST2 OK p{pid}" in out
