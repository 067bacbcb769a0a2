"""Multi-host path: REAL 2-process jax.distributed (Gloo over localhost)
on CPU, 2 local devices each → a 4-shard global mesh.

The reference simulates multi-node by multi-process mpirun on one machine
(reference: cpp/test/CMakeLists.txt:36-76 `mpirun --oversubscribe -np`);
the analog here is two coordinated JAX controller processes. Each child
process writes per-rank CSVs for its own shards, builds a
MultiHostConfig context, ingests via read_csv_per_rank, runs a
distributed join + groupby, and checks counts against a host-side pandas
computation of the same data.
"""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

# multi-process (slow spawn + compile): excluded from the quick tier
pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import os, sys
pid, nproc, port, tmp = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
# 2 virtual CPU devices per process: the config option overrides the
# 8-device XLA_FLAGS inherited from the parent pytest process
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
# cross-process collectives on the CPU backend need gloo
jax.config.update("jax_cpu_collectives_implementation", "gloo")
import numpy as np
import cylon_tpu as ct

ctx = ct.CylonContext.InitDistributed(ct.MultiHostConfig(
    coordinator_address=f"127.0.0.1:{port}", num_processes=nproc,
    process_id=pid))
assert jax.process_count() == nproc, jax.process_count()
world = ctx.get_world_size()
assert world == 2 * nproc, world
local = ctx.local_shard_indices()
assert len(local) == 2, local
assert ctx.get_rank() == local[0]
assert ctx.get_process_rank() == pid
nbrs = ctx.get_neighbours()
assert ctx.get_rank() not in nbrs and len(nbrs) == world - 1

# every process generates the SAME global data (seeded), writes only its
# own shards' files, and computes the expected answer host-side
rng = np.random.default_rng(42)
n_per, w = 500, world
lk = rng.integers(0, 400, n_per * w).astype(np.int64)
lv = rng.integers(0, 1000, n_per * w).astype(np.int64)
rk = rng.integers(0, 400, n_per * w).astype(np.int64)
rv = rng.integers(0, 1000, n_per * w).astype(np.int64)
import pandas as pd

exp_join = pd.merge(pd.DataFrame({"k": lk, "v": lv}),
                    pd.DataFrame({"k": rk, "w": rv}), on="k")

for i in local:
    pd.DataFrame({"k": lk[i*n_per:(i+1)*n_per],
                  "v": lv[i*n_per:(i+1)*n_per]}).to_csv(
        f"{tmp}/l_{i}.csv", index=False)
    pd.DataFrame({"k": rk[i*n_per:(i+1)*n_per],
                  "w": rv[i*n_per:(i+1)*n_per]}).to_csv(
        f"{tmp}/r_{i}.csv", index=False)

left = ct.read_csv_per_rank(ctx, tmp + "/l_{rank}.csv")
right = ct.read_csv_per_rank(ctx, tmp + "/r_{rank}.csv")
assert left.row_count == n_per * w, left.row_count

joined = left.distributed_join(right, "inner", on="k")
assert joined.row_count == len(exp_join), (joined.row_count, len(exp_join))

g = joined.groupby(0, [1], ["sum"])
exp_g = exp_join.groupby("k")["v"].sum()
assert g.row_count == len(exp_g), (g.row_count, len(exp_g))

ctx.barrier()
print(f"MHOK {pid}", flush=True)
"""


def test_two_process_multihost_join(tmp_path):
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the parent pytest process pins jax to its own platform config;
    # children boot fresh interpreters with their own 2-device CPU config
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD, str(pid), "2", str(port),
         str(tmp_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for pid in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"child {pid} failed:\n{out[-4000:]}"
        assert f"MHOK {pid}" in out


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port
