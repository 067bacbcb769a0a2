"""Phase profile of the streaming join path at bench shapes.

Phases are synced by device_get of one element of their outputs.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from cylon_tpu.ops import join as _join
from cylon_tpu.ops import tpu_kernels as tk


def sync(r):
    leaf = [x for x in jax.tree_util.tree_leaves(r)
            if hasattr(x, "ravel")][-1]
    jax.device_get(leaf.ravel()[:1])


def timeit(fn, iters=3):
    sync(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        sync(fn())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def main(n=1 << 24):
    rng = np.random.default_rng(0)
    lk = jnp.asarray(rng.integers(0, n, n).astype(np.int32))
    lv = jnp.asarray(rng.normal(size=n).astype(np.float32))
    rk = jnp.asarray(rng.integers(0, n, n).astype(np.int32))
    rv = jnp.asarray(rng.normal(size=n).astype(np.float32))
    ldat, lval = (lk, lv), (None, None)
    rdat, rval = (rk, rv), (None, None)
    jt = _join.JoinType.INNER
    a_desc, b_desc = _join.plan_lane_descs(ldat, lval, rdat, rval, jt)
    br = _join.stream_block_rows(n, n)

    def plan():
        return _join.plan_program_stream(
            (lk,), (None,), None, (rk,), (None,), None,
            ldat, lval, rdat, rval, (False,), jt,
            a_desc=a_desc, b_desc=b_desc, block_rows=br)

    t_plan = timeit(plan)
    counts, a_streams, b_streams = plan()
    n_primary = int(jax.device_get(counts)[0])
    cap_e = _join.stream_expand_capacity(n_primary, br)
    print(f"plan         {t_plan * 1e3:9.1f} ms   n_out={n_primary}")

    def mat():
        return _join.materialize_program_stream(
            counts, a_streams, b_streams, ldat, lval, rdat, rval,
            jt, cap_e, a_desc=a_desc, b_desc=b_desc, block_rows=br)

    print(f"materialize  {timeit(mat) * 1e3:9.1f} ms   cap_e={cap_e}")

    def expand():
        return tk.join_expand_stream(counts, a_streams, b_streams, cap_e,
                                     block_rows=br)

    print(f"  expand jit {timeit(jax.jit(expand)) * 1e3:9.1f} ms")


if __name__ == "__main__":
    main()
