"""Distributed data engineering for ML: multi-process cylon_tpu ETL →
torch DistributedDataParallel training (reference:
cpp/src/tutorial/demo_pytorch_distributed.py:1-50 — per-MPI-rank pycylon
ETL feeding torch DDP over NCCL/gloo; python/examples/
cylon_sequential_mnist.py).

Two coordinated controller processes (the multi-host harness
tests/test_multihost.py uses) each own 2 shards of a 4-shard CPU mesh:

  1. per-rank ingest (`assemble_process_local` via in-memory tables),
  2. DISTRIBUTED ETL on the mesh — distributed_join + groupby,
  3. `Table.to_pydict_local()` hands each process exactly ITS shards'
     rows (no global gather),
  4. torch DDP (gloo) trains on the per-process feed; gradient
     all-reduce is torch's, data placement is ours.

Run: python examples/torch_ddp_demo.py          (spawns both workers)
     python examples/torch_ddp_demo.py <pid> <nproc> <jax_port> <torch_port>
"""
import os
import socket
import subprocess
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_local_tables(ctx, n_per_shard=4096):
    """Every process generates the SAME seeded global frame and keeps
    only its own shards' slices — the reference's per-rank CSV
    convention without the filesystem."""
    import cylon_tpu as ct

    world = ctx.get_world_size()
    rng = np.random.default_rng(7)
    n = n_per_shard * world
    uid = np.arange(n, dtype=np.int64)
    age = rng.integers(18, 80, n).astype(np.float32)
    spend_uid = rng.integers(0, n, n).astype(np.int64)
    spend = rng.exponential(20.0, n).astype(np.float32)

    def shard_tables(cols_by_name):
        out = []
        for s in ctx.local_shard_indices():
            lo, hi = s * n_per_shard, (s + 1) * n_per_shard
            out.append(ct.Table.from_pydict(
                ctx, {k: v[lo:hi] for k, v in cols_by_name.items()}))
        return out

    from cylon_tpu.parallel import shard as _shard

    users = _shard.assemble_process_local(
        shard_tables({"uid": uid, "age": age}), ctx)
    events = _shard.assemble_process_local(
        shard_tables({"uid": spend_uid, "spend": spend}), ctx)
    return users, events


def worker(pid: int, nproc: int, jax_port: str, torch_port: str) -> None:
    # 2 virtual CPU devices per process: the config option overrides a
    # launching pytest parent's 8-device XLA_FLAGS
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    # cross-process collectives on the CPU backend need gloo
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    import cylon_tpu as ct

    ctx = ct.CylonContext.InitDistributed(ct.MultiHostConfig(
        coordinator_address=f"127.0.0.1:{jax_port}", num_processes=nproc,
        process_id=pid))

    users, events = make_local_tables(ctx)
    # distributed ETL: total spend per user (hash-shuffled groupby),
    # joined back onto the user features across the mesh
    per_user = events.groupby(0, ["spend"], ["sum"])
    table = users.distributed_join(per_user, "inner", on="uid")

    feed = table.to_pydict_local()  # THIS process's shards only
    # join output names columns positionally (lt-*/rt-*, pycylon
    # parity): [uid, age, uid, spend_sum]
    vals = list(feed.values())
    age = np.asarray(vals[1], dtype=np.float32)
    spend = np.nan_to_num(np.asarray(vals[3], dtype=np.float32))
    x = np.stack([age, np.zeros_like(age)], axis=1)
    y = (spend > 100.0).astype(np.float32)

    import torch
    import torch.distributed as dist

    os.environ.setdefault("MASTER_ADDR", "127.0.0.1")
    os.environ.setdefault("MASTER_PORT", torch_port)
    dist.init_process_group("gloo", rank=pid, world_size=nproc)
    model = torch.nn.parallel.DistributedDataParallel(
        torch.nn.Sequential(torch.nn.Linear(2, 16), torch.nn.ReLU(),
                            torch.nn.Linear(16, 1)))
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    loss_fn = torch.nn.BCEWithLogitsLoss()
    ds = torch.utils.data.TensorDataset(torch.from_numpy(x),
                                        torch.from_numpy(y))
    dl = torch.utils.data.DataLoader(ds, batch_size=256, shuffle=True)
    for epoch in range(2):
        total = 0.0
        for xb, yb in dl:
            opt.zero_grad()
            loss = loss_fn(model(xb).squeeze(-1), yb)
            loss.backward()  # DDP all-reduces gradients here
            opt.step()
            total += float(loss.detach()) * len(xb)
        print(f"[rank {pid}] epoch {epoch}: loss {total / len(ds):.4f}"
              f" on {len(ds)} local rows", flush=True)
    dist.destroy_process_group()
    print(f"DDPOK {pid}", flush=True)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch(nproc: int = 2, timeout: int = 540) -> list:
    """Spawn the workers; returns their outputs (asserts success)."""
    jax_port, torch_port = str(_free_port()), str(_free_port())
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), str(pid), str(nproc),
         jax_port, torch_port],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for pid in range(nproc)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-4000:]}"
        assert f"DDPOK {pid}" in out, out[-2000:]
    return outs


if __name__ == "__main__":
    if len(sys.argv) >= 5:
        worker(int(sys.argv[1]), int(sys.argv[2]), sys.argv[3],
               sys.argv[4])
    else:
        for o in launch():
            print(o, end="")
