"""h2oai/db-benchmark's groupby data set G1_<N>_<K>_0_0 (0% NA, unsorted),
after its generator (_data/groupby-datagen.R): only the columns the traffic
asks for are made, each from a stream of its own (seed, position in the
configuration's column list), so a column is the same whichever others a
query touches."""
import numpy as np


def _size(expr, n, k):
    return {"N": n, "K": k, "N/K": n // k}.get(expr, expr)


def generate(config, traffic, chips, scale, seed):
    n = max(int(config["N"] * scale), 1024)
    k = int(config["K"])
    names = list(config["columns"])
    table = {}
    for col in traffic["columns"]:
        spec = config["columns"][col]
        r = np.random.default_rng([seed, names.index(col)])
        dtype = np.dtype(spec["dtype"])
        if spec["kind"] == "int_uniform":      # R: sample(high, N, TRUE)
            table[col] = r.integers(1, int(_size(spec["high"], n, k)) + 1, n,
                                    dtype=dtype)
        elif spec["kind"] == "real_uniform":   # R: round(runif(N,max=m), p)
            x = np.round(r.uniform(0.0, spec["max"], n), spec["places"])
            table[col] = x.astype(dtype)
        else:
            raise ValueError(f"column kind {spec['kind']!r}")
    return {"tables": {"x": table}}
