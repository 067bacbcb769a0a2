"""Same-host competitor comparison (VERDICT r04 missing #4; reference
analog: cpp/src/experiments/dask_run.py + the published
Cylon-vs-Dask/Spark tables, docs/docs/arch.md:146-160).

One workload — inner join, groupby-aggregate (sum/count/mean), sort —
run at the same row count on the same machine by every engine present:

* cylon_tpu (this framework, whatever backend jax selects — the real
  chip under the driver, CPU elsewhere; forced CPU with --cpu),
* pandas (always baked in),
* pyarrow acero (Table.join / TableGroupBy / sort_by),
* duckdb / dask / polars when importable (gated, reported "absent"
  otherwise — none are in this image).

Engines time REAL execution: cylon_tpu closures end in a one-element
device_get; host engines are synchronous. Writes COMPARE.json at the repo root.

Usage: python scripts/compare_competitors.py [rows_log2=22] [--cpu]
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

if "--cpu" in sys.argv:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)


def best_of(f, iters=3):
    f()
    b = 1e9
    for _ in range(iters):
        t0 = time.perf_counter()
        f()
        b = min(b, time.perf_counter() - t0)
    return b


def make_data(n):
    rng = np.random.default_rng(0)
    return {
        "lk": rng.integers(0, n, n).astype(np.int32),
        "lv": rng.normal(size=n).astype(np.float32),
        "rk": rng.integers(0, n, n).astype(np.int32),
        "rv": rng.normal(size=n).astype(np.float32),
        "g": rng.integers(0, 1 << 20, n).astype(np.int32),
        "sk": rng.integers(0, 1 << 31, n).astype(np.int32),
    }


def run_cylon(d, iters):
    import jax

    import cylon_tpu as ct

    ctx = ct.CylonContext.Init()
    left = ct.Table.from_pydict(ctx, {"k": d["lk"], "v": d["lv"]})
    right = ct.Table.from_pydict(ctx, {"k": d["rk"], "w": d["rv"]})
    gt = ct.Table.from_pydict(ctx, {"g": d["g"], "x": d["lv"],
                                    "y": d["g"]})
    st = ct.Table.from_pydict(ctx, {"k": d["sk"], "v": d["lv"]})

    def sync(t):
        jax.device_get(t._columns[0].data[:1])

    out = {"backend": jax.devices()[0].platform}
    out["join_s"] = best_of(lambda: sync(left.join(right, "inner",
                                                   on="k")), iters)
    out["groupby_s"] = best_of(lambda: sync(gt.groupby(
        0, [1, 2, 1], ["sum", "count", "mean"])), iters)
    out["sort_s"] = best_of(lambda: sync(st.sort("k")), iters)
    return out


def run_pandas(d, iters):
    import pandas as pd

    ldf = pd.DataFrame({"k": d["lk"], "v": d["lv"]})
    rdf = pd.DataFrame({"k": d["rk"], "w": d["rv"]})
    gdf = pd.DataFrame({"g": d["g"], "x": d["lv"], "y": d["g"]})
    sdf = pd.DataFrame({"k": d["sk"], "v": d["lv"]})
    return {
        "join_s": best_of(lambda: ldf.merge(rdf, on="k"), iters),
        "groupby_s": best_of(lambda: gdf.groupby("g").agg(
            x_sum=("x", "sum"), y_count=("y", "count"),
            x_mean=("x", "mean")), iters),
        "sort_s": best_of(lambda: sdf.sort_values("k"), iters),
    }


def run_pyarrow(d, iters):
    import pyarrow as pa

    lt = pa.table({"k": d["lk"], "v": d["lv"]})
    rt = pa.table({"k": d["rk"], "w": d["rv"]})
    gt = pa.table({"g": d["g"], "x": d["lv"], "y": d["g"]})
    st = pa.table({"k": d["sk"], "v": d["lv"]})
    return {
        "join_s": best_of(lambda: lt.join(rt, "k", join_type="inner"),
                          iters),
        "groupby_s": best_of(lambda: gt.group_by("g").aggregate(
            [("x", "sum"), ("y", "count"), ("x", "mean")]), iters),
        "sort_s": best_of(lambda: st.sort_by("k"), iters),
    }


def run_duckdb(d, iters):  # pragma: no cover - not in this image
    import duckdb
    import pandas as pd

    con = duckdb.connect()
    con.register("l", pd.DataFrame({"k": d["lk"], "v": d["lv"]}))
    con.register("r", pd.DataFrame({"k": d["rk"], "w": d["rv"]}))
    con.register("g", pd.DataFrame({"g": d["g"], "x": d["lv"]}))
    con.register("s", pd.DataFrame({"k": d["sk"], "v": d["lv"]}))
    return {
        "join_s": best_of(lambda: con.execute(
            "SELECT count(*) FROM l JOIN r USING (k)").fetchall(), iters),
        "groupby_s": best_of(lambda: con.execute(
            "SELECT g, sum(x), count(x), avg(x) FROM g GROUP BY g"
        ).fetchall(), iters),
        "sort_s": best_of(lambda: con.execute(
            "SELECT * FROM s ORDER BY k").arrow(), iters),
    }


def run_dask(d, iters):  # pragma: no cover - not in this image
    import dask.dataframe as dd
    import pandas as pd

    ldf = dd.from_pandas(pd.DataFrame({"k": d["lk"], "v": d["lv"]}),
                         npartitions=8)
    rdf = dd.from_pandas(pd.DataFrame({"k": d["rk"], "w": d["rv"]}),
                         npartitions=8)
    return {"join_s": best_of(
        lambda: ldf.merge(rdf, on="k").shape[0].compute(), iters)}


ENGINES = {
    "cylon_tpu": run_cylon,
    "pandas": run_pandas,
    "pyarrow": run_pyarrow,
    "duckdb": run_duckdb,
    "dask": run_dask,
}


def main(log2n: int, iters: int = 3) -> dict:
    n = 1 << log2n
    d = make_data(n)
    res = {"n_rows": n, "engines": {}}
    for name, fn in ENGINES.items():
        try:
            r = fn(d, iters)
            res["engines"][name] = {
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in r.items()}
        except ImportError:
            res["engines"][name] = {"absent": True}
        except Exception as e:  # pragma: no cover - defensive
            res["engines"][name] = {
                "error": f"{type(e).__name__}: {e}"[:200]}
        print(name, json.dumps(res["engines"][name]), flush=True)
    cy = res["engines"].get("cylon_tpu", {})
    pdr = res["engines"].get("pandas", {})
    for op in ("join_s", "groupby_s", "sort_s"):
        if isinstance(cy.get(op), float) and isinstance(pdr.get(op), float):
            res.setdefault("speedup_vs_pandas", {})[op] = round(
                pdr[op] / cy[op], 2)
    return res


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--cpu"]
    out = main(int(args[0]) if args else 22)
    print(json.dumps(out))
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "COMPARE.json"), "w") as f:
        json.dump(out, f, indent=1)
