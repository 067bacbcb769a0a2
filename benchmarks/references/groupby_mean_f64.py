"""Means by one integer key with np.bincount in float64: count and float64
sum a group, mean = sum / count. The guarantees the configuration states:
every group present once and none invented, no nulls, the key's dtype and
three float32 means; an INTEGER column's mean within 4 * 2^-24 relative of
the float64 one (its sum and its count are exact in float32 at this size,
so the mean is one float32 division, which a chip may round within two
ulps); a FLOAT column's mean within 8 * 2^-24 * (sum|x|_g / count_g) of
the float64 mean of the same float32 inputs (the result's rounding, the
division's, and the 1-2 units of a compensated or pairwise float32 sum).
Inputs rounded to bfloat16 (u = 2^-9) move a group's mean by about
0.097 / sqrt(count_g) on values in [0, 100]: tens of units of that bound at
1e6 rows a group, hundreds at a rehearsal's few hundred."""
import numpy as np

U32 = 2.0 ** -24
INT_UNITS, FLOAT_UNITS = 4.0, 8.0
MEAN_DTYPE = np.dtype(np.float32)


def _means(table, traffic, cast=None):
    key = table[traffic["by"]]
    nk = int(key.max()) + 1
    count = np.bincount(key, minlength=nk)
    present = count > 0
    out = {"keys": np.flatnonzero(present), "count": count[present],
           "means": [], "abs": [], "kinds": [],
           "dtypes": [key.dtype] + [MEAN_DTYPE] * len(traffic["values"])}
    for name in traffic["values"]:
        x = table[name]
        out["kinds"].append(x.dtype.kind)
        if cast is not None and x.dtype.kind == "f":
            x = x.astype(cast)
        x = x.astype(np.float64)
        out["means"].append(np.bincount(key, weights=x, minlength=nk)[present]
                            / out["count"])
        out["abs"].append(np.bincount(key, weights=np.abs(x), minlength=nk)
                          [present] / out["count"])
    return out


def reference(tables, config, traffic):
    return _means(tables[traffic["table"]], traffic)


def control(tables, config, traffic):
    """The same means over float inputs rounded to bfloat16: the nearest
    precision below the float32 the configuration states."""
    import ml_dtypes

    m = _means(tables[traffic["table"]], traffic, cast=ml_dtypes.bfloat16)
    cols = [m["keys"].astype(m["dtypes"][0])]
    cols += [x.astype(d) for x, d in zip(m["means"], m["dtypes"][1:])]
    return {"names": [traffic["by"]] + list(traffic["values"]),
            "columns": cols, "nulls": 0}


def describe(ref):
    return (f"{len(ref['keys'])} groups over {int(ref['count'].sum())} rows, "
            f"{len(ref['means'])} means")


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
    for i, (mean, a, kind) in enumerate(zip(ref["means"], ref["abs"],
                                            ref["kinds"])):
        x = cols[1 + i][order].astype(np.float64)
        name = got["names"][1 + i] if len(got["names"]) > 1 + i else str(i)
        label, units = ("f32", FLOAT_UNITS) if kind == "f" \
            else ("int", INT_UNITS)
        bound = np.maximum(units * U32 * a, np.finfo(float).tiny)
        err = np.abs(x - mean) / bound
        numbers.append({"name": f"{label}_mean_err_over_bound.{name}",
                        "value": float(np.nan_to_num(err, nan=np.inf).max()),
                        "limit": 1.0})
    return numbers
