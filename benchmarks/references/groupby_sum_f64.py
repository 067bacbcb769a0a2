"""Sums by one integer key with np.bincount in float64. The guarantee the
configuration states: every group present once, integer sums exact, and a
float32 sum within count_g * 2^-24 * sum|x|_g of the float64 sum of the
same float32 inputs — the worst-case bound of ANY order of float32
accumulation of count_g terms ((n-1)u for the additions, u for the final
rounding; Higham, Accuracy and Stability, eq. 4.4). At 100 rows a group
that is about 6e-6 relative; inputs or partial sums carried in bfloat16
(u = 2^-9) miss it by one to two orders of magnitude."""
import numpy as np

U32 = 2.0 ** -24


def _sums(table, traffic, cast=None):
    key = table[traffic["by"]]
    nk = int(key.max()) + 1
    count = np.bincount(key, minlength=nk)
    present = count > 0
    out = {"keys": np.flatnonzero(present), "count": count[present],
           "sums": [], "abs": [], "dtypes": [key.dtype]}
    for name in traffic["values"]:
        x = table[name]
        out["dtypes"].append(x.dtype)
        if cast is not None and x.dtype.kind == "f":
            x = x.astype(cast)
        is_float = x.dtype.kind == "f"
        x = x.astype(np.float64)
        out["sums"].append(np.bincount(key, weights=x, minlength=nk)[present])
        # sum|x| is only read for the float columns' error bound
        out["abs"].append(np.bincount(key, weights=np.abs(x), minlength=nk)
                          [present] if is_float else None)
    return out


def reference(tables, config, traffic):
    return _sums(tables[traffic["table"]], traffic)


def control(tables, config, traffic):
    """The same sums over float inputs rounded to bfloat16: the nearest
    precision below the float32 the configuration states."""
    import ml_dtypes

    table = tables[traffic["table"]]
    s = _sums(table, traffic, cast=ml_dtypes.bfloat16)
    cols = [s["keys"].astype(s["dtypes"][0])]
    cols += [x.astype(d) for x, d in zip(s["sums"], s["dtypes"][1:])]
    return {"names": [traffic["by"]] + list(traffic["values"]),
            "columns": cols, "nulls": 0}


def describe(ref):
    return (f"{len(ref['keys'])} groups over {int(ref['count'].sum())} rows, "
            f"{len(ref['sums'])} sums")


def rows_out(ref):
    return len(ref["keys"])


def compare(got, ref):
    cols = got["columns"]
    schema = int(len(cols) != len(ref["dtypes"])) + sum(
        c.dtype != d for c, d in zip(cols, ref["dtypes"]))
    numbers = [{"name": "schema_diff", "value": schema, "limit": 0},
               {"name": "nulls", "value": got["nulls"], "limit": 0}]
    keys = cols[0] if cols else np.zeros(0, np.int64)
    order = np.argsort(keys, kind="stable")
    same_keys = len(keys) == len(ref["keys"]) and \
        bool(np.array_equal(keys[order], ref["keys"]))
    groups_diff = 0 if same_keys else max(
        1, len(np.setxor1d(keys, ref["keys"]))
        + len(keys) - len(np.unique(keys)))
    numbers.append({"name": "groups_diff", "value": groups_diff, "limit": 0})
    if schema or not same_keys:
        return numbers
    for i, (s, a) in enumerate(zip(ref["sums"], ref["abs"])):
        x = cols[1 + i][order]
        name = got["names"][1 + i] if len(got["names"]) > 1 + i else str(i)
        if x.dtype.kind in "iu":
            numbers.append({"name": f"int_sum_mismatches.{name}",
                            "value": int((x.astype(np.float64) != s).sum()),
                            "limit": 0})
        else:
            bound = np.maximum(ref["count"] * U32 * a, np.finfo(float).tiny)
            err = np.abs(x.astype(np.float64) - s) / bound
            numbers.append({"name": f"f32_sum_err_over_bound.{name}",
                            "value": float(np.nan_to_num(
                                err, nan=np.inf).max()),
                            "limit": 1.0})
    return numbers
