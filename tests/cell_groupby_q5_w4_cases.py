"""What the two halves of the cell `groupby-q5-w4`'s tests share
(`test_cell_groupby_q5_w4.py`: the cell at a test size, the keys off the
sorted lanes, the partial table's masks; `test_cell_groupby_q5_w4_pack.py`:
what the first per-shard sort is handed, PR 43): the benchmark's own
generator, reference, query and harness, the cell's table at a test size,
and how a test runs `_groupby_fn` as a TPU backend does. Two files, so that
`--dist loadfile` gives each a worker (PR 45).
"""
import copy
import importlib.util
import json
import os
import sys

import numpy as np

import jax.numpy as jnp

import cylon_tpu as ct
from cylon_tpu import plan, telemetry
from cylon_tpu.ops import groupby as G
from cylon_tpu.parallel import shard

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks")


def _code(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"q5w4_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    path = list(sys.path)
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path[:] = path   # run.py puts its own directory first
    return mod


def _json(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


GENERATOR = _code("generators", "h2o_g1")
REFERENCE = _code("references", "groupby_sum_f64")
QUERY = _code("queries", "groupby_agg")
RUN = _code("", "run")          # host_result, as the harness reads a result
TRAFFIC = _json("traffic", "q5-4chip")
CONFIG = _json("configs", "h2o-groupby-1e9-f32")
I32_MIN, I32_MAX = np.iinfo(np.int32).min, np.iinfo(np.int32).max
EXACT = ("schema_diff", "nulls", "groups_diff", "int_sum_mismatches.v1",
         "int_sum_mismatches.v2")
FLOAT = "f32_sum_err_over_bound.v3"


def _data(rows, key_range, seed):
    """The cell's table at a test size: the configuration as committed,
    fewer rows, id6 over a literal range wider than a shard's rows."""
    config = copy.deepcopy(CONFIG)
    config["N"] = rows
    config["columns"]["id6"]["high"] = key_range
    return config, GENERATOR.generate(config, TRAFFIC, 4, 1.0,
                                      seed)["tables"]


def _numbers(out, tables, config):
    ref = REFERENCE.reference(tables, config, TRAFFIC)
    assert int(out.row_count) == REFERENCE.rows_out(ref)
    return {n["name"]: n["value"] for n in REFERENCE.compare(
        RUN.host_result(out), ref)}


def _planned(ctx, cols, mask=None):
    t = shard.distribute(ct.Table.from_pydict(ctx, cols), ctx)
    if mask is not None:
        live = np.zeros(t.capacity, bool)
        live[:len(mask)] = mask
        t = ct.Table(list(t.columns()), ctx, shard.pin(jnp.asarray(live),
                                                       ctx))
    return QUERY.build(plan, {TRAFFIC["table"]: t}, TRAFFIC)


READBACK = ('cylon_groupby_key_readback_total{path="lanes"}',
            'cylon_groupby_key_readback_total{path="gather"}',
            "cylon_groupby_sort_operands_total")


def _readback():
    snap = telemetry.metrics_snapshot()
    return [snap.get(k, 0) for k in READBACK]


def _as_on_a_tpu(monkeypatch):
    """The host's decision (`G.sort_carries_index`, which says "index" on
    a CPU backend) and `_groupby_fn`'s reduce step as a TPU backend takes
    them, under the interpreter."""
    real_agg, real_index = G.sorted_segment_aggregate, G.sort_carries_index
    monkeypatch.setattr(
        G, "sorted_segment_aggregate",
        lambda *a, **k: real_agg(*a, **k, interpret=True))
    monkeypatch.setattr(
        G, "sort_carries_index",
        lambda *a, **k: real_index(*a, **k, interpret=True))


def _with_columns(ctx, t, validity=None, mask=None):
    """``t`` spread over the chips, column i under ``validity[i]`` (host
    bool arrays over the capacity), rows under ``mask``."""
    t = shard.distribute(t, ctx)

    def pinned(a):
        return shard.pin(jnp.asarray(a), ctx)

    cols = [c if i not in (validity or {}) else ct.Column(
        c.data, c.dtype, pinned(validity[i]), c.dictionary, c.name)
        for i, c in enumerate(t.columns())]
    return ct.Table(cols, ctx, None if mask is None else pinned(mask))


ROWS4 = 2048        # four shards of 512: a multiple of the row quantum


def _ints_of(rng, lo, hi, n=None):
    """int32 values over exactly [lo, hi]."""
    x = rng.integers(lo, hi + 1, n or ROWS4).astype(np.int32)
    x[:2] = (lo, hi)
    return x


def _q5_cols(rng, n=ROWS4, key=None, v1=None):
    """The cell's columns at a test size: id6 over a range wider than a
    shard's rows, v1 in [1, 5], v2 in [1, 15], v3 whole floats (their
    sums do not feel the order of a group's rows)."""
    return {"id6": _ints_of(rng, 1, 3000, n) if key is None else key,
            "v1": _ints_of(rng, 1, 5, n) if v1 is None else v1,
            "v2": _ints_of(rng, 1, 15, n),
            "v3": rng.integers(-64, 64, n).astype(np.float32)}


def _spread(ctx, cols, validity=None, mask=None):
    return _with_columns(ctx, ct.Table.from_pydict(ctx, cols), validity,
                         mask)


def _by_key(frame):
    return frame.sort_values(frame.columns[0], na_position="last"
                             ).reset_index(drop=True)
