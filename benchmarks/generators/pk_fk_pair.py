"""A primary-key table R(k, v) and a foreign-key table S(k, w), as the
multi-core join literature's workload generators make them (Balkesen et
al., ICDE 2013, workload B, after Kim et al., VLDB 2009): R's keys are the
``n`` dense values in a random order, each once; S's keys are drawn
uniformly from R's, so every row of S matches exactly one row of R. The
payloads are 4 bytes of float32, standard normal: the source fixes only
their width. ``rows_per_chip`` a side a chip."""
import numpy as np


def generate(config, traffic, chips, scale, seed):
    n = max(int(config["rows_per_chip"] * scale), 256) * chips
    r = np.random.default_rng(seed)
    key = np.dtype(config["schema"]["key_dtype"])
    val = np.dtype(config["schema"]["value_dtype"])
    lk, lv = config["schema"]["left"]
    rk, rv = config["schema"]["right"]
    return {"tables": {
        "left": {lk: r.permutation(n).astype(key),
                 lv: r.standard_normal(n, dtype=val)},
        "right": {rk: r.integers(0, n, n, dtype=key),
                  rv: r.standard_normal(n, dtype=val)},
    }}
