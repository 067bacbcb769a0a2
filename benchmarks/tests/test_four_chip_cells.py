"""The two four-chip cells (``join-w4``, ``join-w4-zipf``) and what came
with them: the Zipf generator's promise that every seed is the same work,
the two new reducers on recorded counters and a hand-built four-chip
trace, every new metric through its own file, and a whole four-device
rehearsal of each cell at a test size."""
import argparse
import json
import os

import numpy as np
import pytest

import run as bench_run
import xplane
from test_query_spans import BENCH, metric, reducer   # the same loaders

WORLD = 4
CONFIG = "hashjoin-workload-b-zipf"


def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def gen():
    return bench_run.load_code("generators", "pk_fk_zipf")


@pytest.fixture(scope="module")
def ctx():
    import cylon_tpu as ct

    return ct.CylonContext.InitDistributed(ct.TPUConfig(world_size=WORLD))


def chip_of(ctx, keys):
    """The chip each key goes to, found by calling the program's own
    placement (its key hash over a key column), not by copying it."""
    import cylon_tpu as ct
    from cylon_tpu.parallel import dist_ops

    col = ct.Table.from_pydict(ctx, {"k": keys})._columns[0]
    return np.asarray(dist_ops._partition_targets_dist(ctx, [col]))


# -- the generator: every seed the same work --------------------------------

SCALE = 0.002    # 32,000 rows a chip, 128,000 a side
SEEDS = (1, 2147483659, 4294967311)


@pytest.fixture(scope="module")
def three_seeds(gen):
    return [gen.generate(config(), {}, WORLD, SCALE, s)["tables"]
            for s in SEEDS]


def test_per_key_counts_are_equal_on_every_seed(three_seeds):
    n = len(three_seeds[0]["right"]["k"])
    per_key = [np.bincount(t["right"]["k"], minlength=n)
               for t in three_seeds]
    assert all((p == per_key[0]).all() for p in per_key[1:])
    assert per_key[0].sum() == n
    # R: the dense keys, each once; only the ORDER is the seed's
    for t in three_seeds:
        assert (np.sort(t["left"]["k"]) == np.arange(n)).all()
    a, b = three_seeds[0], three_seeds[1]
    assert (a["right"]["k"] != b["right"]["k"]).any()
    assert (a["left"]["v"] != b["left"]["v"]).any()
    assert a["right"]["k"].dtype == np.int32
    assert a["right"]["w"].dtype == np.float32


def test_rows_a_chip_receives_are_equal_on_every_seed(three_seeds, ctx):
    """Under the program's own placement; only the split of a chip's rows
    over the chips that send them varies with the seed."""
    recv, pairs = [], []
    for t in three_seeds:
        for side in ("left", "right"):
            chips = chip_of(ctx, t[side]["k"])
            recv.append(np.bincount(chips, minlength=WORLD))
            per = len(chips) // WORLD
            pairs.append(np.stack(
                [np.bincount(chips[s * per:(s + 1) * per], minlength=WORLD)
                 for s in range(WORLD)]))
    for i in (2, 4):
        assert (recv[i] == recv[0]).all()          # R
        assert (recv[i + 1] == recv[1]).all()      # S
    s_recv = recv[1]
    assert s_recv.max() / s_recv.mean() > 1.15     # S is skewed,
    assert recv[0].max() / recv[0].mean() < 1.02   # R is not
    assert any((p != pairs[1]).any() for p in pairs[3::2])   # the split
    for p in pairs[3::2]:                          # ... by sampling only
        assert np.abs(p - pairs[1]).max() < 0.05 * pairs[1].max()


@pytest.mark.parametrize("n", [1024, 128000, 2000000])
def test_multiset_sums_to_n_and_rank_one_holds_its_share(gen, n):
    exponent = config()["zipf"]["exponent"]
    counts = gen.rank_counts(n, exponent)
    assert counts.sum() == n and counts.min() >= 0
    assert (np.diff(counts) <= 1).all()       # never rising by more than
    h = (np.arange(1, n + 1, dtype=np.float64) ** -exponent).sum()   # 1
    assert abs(counts[0] / n - 1 / h) < 1e-3 * (1 / h) + 1 / n
    assert (gen.rank_counts(n, 0.0) == 1).all()


@pytest.mark.parametrize("n, exponent", [
    (1024, 0.0), (1024, 1.05), (4099, 1.25), (128000, 1.05),
    (2000000, 1.05), (2000000, 1.25)])
def test_foreign_keys_are_the_multiset_rank_by_rank(gen, n, exponent):
    """``foreign_keys`` gives keys only to the ranks that occur; the plain
    form, every rank's key repeated by its count, is the same array."""
    m = config()["zipf"]["rank_to_key_multiplier"]
    plain = np.repeat(gen.rank_keys(n, m), gen.rank_counts(n, exponent))
    fk = gen.foreign_keys(n, exponent, m)
    assert fk.dtype == np.int64 and np.array_equal(fk, plain)


@pytest.mark.parametrize("scale", [0.0001, 0.002, 0.05, 1.0])
def test_rank_to_key_is_a_bijection_at_scale_sizes(gen, scale):
    cfg = config()
    n = max(int(cfg["rows_per_chip"] * scale), 256) * WORLD
    if scale == 1.0:     # 64M keys: the arithmetic, not the array
        import math

        assert math.gcd(cfg["zipf"]["rank_to_key_multiplier"], n) == 1
        return
    keys = gen.rank_keys(n, cfg["zipf"]["rank_to_key_multiplier"])
    assert (np.sort(keys) == np.arange(n)).all()
    assert keys[0] == 0 and keys.dtype == np.int64
    with pytest.raises(ValueError):
        gen.rank_keys(n, 2 * 3 * 5)


# -- the reducers -------------------------------------------------------------

def recorded_counters(padded_slots, live=128_000_000):
    """What three traced join-w4-zipf queries add to the program's
    counters, keyed as the program renders them."""
    from cylon_tpu.telemetry.metrics import format_series

    rows = "cylon_exchange_recv_rows_total"
    return {
        format_series(rows, (("stat", "max"),)): 3 * (19_252_936
                                                     + 16_002_713),
        format_series(rows, (("stat", "mean"),)): 3 * 32_000_000.0,
        "cylon_exchange_live_rows_total": 3 * live,
        "cylon_exchange_slots_total": 3 * padded_slots,
        "cylon_shuffle_bytes_total": 3 * 1_024_000_000,
        'cylon_join_algorithm_total{algo="shuffle"}': 3,
        'cylon_join_algorithm_total{algo="local"}': 0,
    }


def test_counter_ratio_reads_imbalance_and_padding():
    slots = 4 * ((1 << 24) + (1 << 25))
    run = {"traced_queries": 3, "counters": recorded_counters(slots)}
    imb, pad = (metric(m) for m in ("exchange_recv_imbalance",
                                    "exchange_padding_share"))
    red = reducer("counter_ratio")
    assert imb["reducer"] == pad["reducer"] == "counter_ratio"
    assert red.reduce(run, imb) == pytest.approx(35_255_649 / 32_000_000)
    assert red.reduce(run, pad) == pytest.approx(
        100 * (1 - 128_000_000 / slots))            # 36.4%
    # the padding share cannot pass 100% nor fall under 0: the exchange
    # allocates at least a slot a live row
    run["counters"] = recorded_counters(128_000_000)
    assert red.reduce(run, pad) == 0.0
    # a program without the counters (the parent), a one-chip cell, an
    # untraced run: nothing to read
    for gone in ("cylon_exchange_slots_total",
                 "cylon_exchange_live_rows_total"):
        c = recorded_counters(slots)
        del c[gone]
        assert red.reduce({"traced_queries": 3, "counters": c}, pad) is None
    c = {k: v for k, v in recorded_counters(slots).items()
         if "recv_rows" not in k}
    assert red.reduce({"traced_queries": 3, "counters": c}, imb) is None
    c = dict(recorded_counters(slots), cylon_exchange_slots_total=0)
    assert red.reduce({"traced_queries": 3, "counters": c}, pad) is None
    assert red.reduce({"traced_queries": 0, "counters": None}, imb) is None


def test_counter_metrics_of_the_new_cells_read_their_series():
    run = {"traced_queries": 3, "counters": recorded_counters(1 << 28)}
    red = reducer("counter_delta")
    assert red.reduce(run, metric("shuffle_bytes_per_query")) == 1.024e9
    assert red.reduce(run, metric("join_shuffle_algo_per_query")) == 1.0


def _ms(name, start_ms, dur_ms):
    return [name, int(start_ms * 1e6), int(dur_ms * 1e6)]


@pytest.fixture(scope="module")
def four_chip_run():
    """Two queries on four chips, every chip alike: the count program, two
    chunked exchanges (a first and a second chunk program a side), the two
    per-shard join programs; names as a v5e trace spells them (PERF.md
    section 3)."""
    planes, host = [], []
    for q in (0.0, 700.0):
        host.append(_ms("bench:query", q, 600.0))
    for chip in range(WORLD):
        modules, ops = [], []
        for q in (0.0, 700.0):
            modules += [
                _ms("jit_count2(11)", q + 1, 2.0),
                _ms("jit_exchange_chunk_first(12)", q + 10, 40.0),
                _ms("jit_exchange_chunk(13)", q + 50, 4.0),
                _ms("jit_exchange_chunk_first(14)", q + 60, 40.0),
                _ms("jit_exchange_chunk(13)", q + 100, 4.0),
                _ms("jit_join_plan_stream(15)", q + 150, 280.0),
                _ms("jit_join_mat_stream(16)", q + 440, 130.0),
                _ms("jit_copy(17)", q + 620, 1.0)]
            ops += [
                _ms("partition_hist.1 custom-call", q + 10, 1.0),
                _ms("partition_scatter.1 custom-call", q + 11, 32.0),
                _ms("all_to_all.3 all-to-all", q + 44, 1.0),
                _ms("all_to_all.4 all-to-all", q + 51, 1.0),
                _ms("partition_scatter.1 custom-call", q + 61, 32.0),
                _ms("all-to-all-start.1 all-to-all-start", q + 94, 0.5),
                _ms("all-to-all-done.1 all-to-all-done", q + 95, 0.5),
                _ms("sort.55 sort", q + 150, 250.0),
                _ms("join_stream_plan.1 custom-call", q + 400, 28.0),
                _ms("join_stream_expand.1 custom-call", q + 440, 129.0)]
        planes.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]})
    planes.append({"name": "/host:CPU",
                   "lines": [{"name": "python", "events": host}]})
    return {"trace": xplane.Trace({"planes": planes}), "chips": WORLD,
            "input_bytes": 2 * 64_000_000 * 8,
            "peaks": {"hbm_gbytes_per_s": 819}}


def test_trace_metrics_of_the_new_cells_match_their_programs(four_chip_run):
    run = four_chip_run
    assert run["trace"].n_queries == 2
    red = reducer("trace_line_ms")
    assert red.reduce(run, metric("exchange_device_ms_per_query")) \
        == pytest.approx(88.0)            # the four chunk programs alone
    assert red.reduce(run, metric("collective_ms_per_query")) \
        == pytest.approx(3.0)
    # the per-shard join: its sort and its two kernels, on the ops line
    assert red.reduce(run, metric("dist_join_device_ms_per_query")) \
        == pytest.approx(250.0 + 28.0 + 129.0)
    # ... and beside it the two programs by module: 3 ms of other ops more
    assert red.reduce(run, metric("dist_join_module_ms_per_query")) \
        == pytest.approx(410.0)
    assert red.reduce(run, metric("join_device_ms_per_query")) \
        == pytest.approx(410.0)           # join-w1's patterns: the same


def test_dist_join_ms_on_a_recorded_four_chip_trace():
    """``trace_zipf_4chip_cut.json``: the three traced queries of a
    ``join-w4-zipf`` run on four v5e chips (PR 28, seed 3961507771), cut
    to the module events and the ops of 1 ms and more. What a four-chip
    trace does to some runs of the plan program (PERF.md section 6): on
    chip 0 two of its three runs bear ANOTHER program's name,
    ``jit__multi_slice``, and every op inside is named ``region.<n>``.
    The reading by module loses those runs; the reading by ops keeps
    them."""
    with open(os.path.join(BENCH, "tests",
                           "trace_zipf_4chip_cut.json")) as f:
        run = {"trace": xplane.Trace(json.load(f))}
    trace = run["trace"]
    assert trace.n_queries == 3 and sorted(trace.devices) == [0, 1, 2, 3]
    wrong = [e for e in trace.devices[0]["XLA Modules"]
             if e[0].startswith("jit__multi_slice") and e[2] > 100e6]
    assert len(wrong) == 2
    for _name, start, dur in wrong:       # the sort and the plan kernel
        inside = [e for e in trace.devices[0]["XLA Ops"]
                  if start <= e[1] and e[1] + e[2] <= start + dur
                  and e[2] > 10e6]
        assert [e[0].split(".")[0] for e in inside] == ["region", "region"]
    red = reducer("trace_line_ms")
    by_ops = red.reduce(run, metric("dist_join_device_ms_per_query"))
    by_module = red.reduce(run, metric("dist_join_module_ms_per_query"))
    assert by_ops == pytest.approx(738.78, abs=0.01)
    assert by_module == pytest.approx(663.12, abs=0.01)
    # the gap: two runs of ~506 ms on one chip of four, over three queries,
    # less what the programs hold besides sort, plan kernel and expand
    lost = sum(e[2] for e in wrong) / 1e6 / WORLD / trace.n_queries
    assert 0 < lost - (by_ops - by_module) < 0.02 * by_module
    assert red.reduce(run, metric("exchange_device_ms_per_query")) \
        == pytest.approx(110.37, abs=0.01)


def test_dist_join_ms_on_a_recorded_one_chip_trace():
    """``trace_plan_probe.json``: three runs of the per-shard plan program
    at ``join-w4-zipf``'s shard shapes (2^24 + 2^25 slots) on ONE v5e chip
    (PR 28's probe), as ``xplane.load`` reduced it: the names a chip
    gives the sort and the plan kernel are the ones the patterns expect."""
    with open(os.path.join(BENCH, "tests", "trace_plan_probe.json")) as f:
        run = {"trace": xplane.Trace(json.load(f))}
    red = reducer("trace_line_ms")
    assert run["trace"].n_queries == 3
    assert red.reduce(run, metric("dist_join_device_ms_per_query")) \
        == pytest.approx(453.65 + 42.51, abs=0.01)   # sort + plan kernel
    assert red.reduce(run, metric("dist_join_module_ms_per_query")) \
        == pytest.approx(500.88, abs=0.01)           # the module around


def test_partition_scatter_hbm_share(four_chip_run):
    run = four_chip_run
    spec = metric("partition_scatter_hbm_share")
    red = reducer(spec["reducer"])
    # 2 x 1,024,000,000 bytes over four chips at 819 GB/s = 0.6252 ms,
    # over the kernel's 64 ms a query and chip
    want = 100 * (2 * 1_024_000_000 / 4 / 819e9) / 0.064
    assert red.reduce(run, spec) == pytest.approx(want)
    assert 0.9 < want < 1.1
    assert red.reduce(dict(run, trace=None), spec) is None
    assert red.reduce(dict(run, peaks=None), spec) is None
    none = dict(spec, patterns=["^no_such_kernel\\b"])
    assert red.reduce(run, none) is None       # a cell without the kernel


# -- whole rehearsals ---------------------------------------------------------

NEW_PER_LAYER = {
    "shuffle_bytes_per_query", "collective_ms_per_query",
    "exchange_device_ms_per_query", "dist_join_device_ms_per_query",
    "dist_join_module_ms_per_query", "join_shuffle_algo_per_query", "exchange_recv_imbalance",
    "exchange_padding_share", "partition_scatter_hbm_share"}


def args_for(cell, **kw):
    a = argparse.Namespace(workload=cell, seed=3000000019, seconds=0.5,
                           trace=0, scale=0.002, control=0)
    for k, v in kw.items():
        setattr(a, k, v)
    return a


@pytest.mark.parametrize("cell", ["join-w4", "join-w4-zipf"])
def test_rehearsal_on_four_devices(cell):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if cell in m.get("workloads", ())}
    assert listed == NEW_PER_LAYER
    assert bench_run.find_cell(bench, cell)["chips"] == WORLD

    result, code = bench_run.run(args_for(cell))
    assert code == 1                      # not a TPU: never a result line
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"query_p50_s", "query_p90_s",
                                      "setup_s"}

    result, _code = bench_run.run(args_for(cell, trace=1, seconds=1.0))
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert result["correct"] is True
    assert got["host_syncs_per_query"] == 2.0
    assert got["join_shuffle_algo_per_query"] == 1.0
    assert got["shuffle_bytes_per_query"] == 2 * 128000 * 8
    if cell == "join-w4":
        assert got["exchange_recv_imbalance"] < 1.02
        assert got["exchange_padding_share"] < 10
    else:
        assert 1.15 < got["exchange_recv_imbalance"] < 1.3
        assert 30 < got["exchange_padding_share"] < 40
    # device readings need a device: left out on the CPU, not raised
    assert not {"collective_ms_per_query", "partition_scatter_hbm_share",
                "dist_join_device_ms_per_query",
                "dist_join_module_ms_per_query"} & set(got)


def test_control_of_the_zipf_cell_is_not_correct():
    result, _code = bench_run.run(args_for("join-w4-zipf", control=1))
    assert result["correct"] is False
