"""TPC-H Query 12 (Shipping Modes and Order Priority) through the lazy
plan, at a small size on the CPU, beside the benchmark's plain reference.

    python examples/tpch_q12_example.py

The query is the cell's own (`benchmarks/queries/tpch_q12.py`), written in
the specification's order: ORDERS joined to LINEITEM, THEN the five
predicates (two of them compare two columns), THEN two `case_when`
columns, GROUP BY and ORDER BY the ship mode. EXPLAIN shows what the
planner made of it: the five conjuncts under the join on LINEITEM's side,
the three dates pruned above them; EXPLAIN ANALYZE shows the filtered
table compacted on the device before the join sorts it.
"""
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _load(kind, name):
    path = os.path.join(ROOT, "benchmarks", kind, name)
    if name.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    spec = importlib.util.spec_from_file_location(
        "example_" + name[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(scale=0.002):
    import cylon_tpu as ct
    from cylon_tpu import plan

    config = _load("configs", "tpch-sf100-q12.json")
    traffic = _load("traffic", "tpch-q12.json")
    host = _load("generators", config["generator"] + ".py").generate(
        config, traffic, 1, scale, 12)["tables"]
    reference = _load("references", config["reference"] + ".py")
    ref = reference.reference(host, config, traffic)

    ctx = ct.CylonContext.Init()
    tables = {name: ct.Table.from_pydict(ctx, t) for name, t in host.items()}
    pipe = _load("queries", "tpch_q12.py").build(plan, tables, traffic)
    print(pipe.explain(analyze=True))
    out = pipe.execute()
    modes = out.get_column(0).dictionary
    print("\nl_shipmode  high_line_count  low_line_count")
    rows = out.to_pandas()
    for mode, high, low in rows.itertuples(index=False):
        print(f"{mode:<10}  {high:>15}  {low:>14}")
    print("reference: " + reference.describe(ref))
    got = [(str(m), int(h), int(low))
           for m, h, low in rows.itertuples(index=False)]
    want = [(ref["modes"][g], h, low) for g, h, low in
            zip(ref["groups"], ref["high"], ref["low"])]
    assert set(modes) >= {m for m, _h, _l in want}
    if got != want:
        print(f"MISMATCH: {got} != {want}")
        return 1
    print("matches the reference")
    return 0


if __name__ == "__main__":
    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.exit(main())
