"""The four-chip join on Zipf-skewed foreign keys (BENCHMARK.json's
``join-w4-zipf``), at a test size on a four-wide virtual mesh: the planned
distributed inner join over the benchmark generator's data against the
benchmark's plain reference, on the padded route (single pair program and
chunked pipeline) and on the compact rounds; the counters that the
exchange derives from its count matrix; the host fetches a query pays.

The generator and the reference are the benchmark's own files, loaded by
path: nothing of ``benchmarks/`` is a package."""
import importlib.util
import os

import numpy as np
import pytest

import cylon_tpu as ct
import forced_paths
from cylon_tpu import plan, telemetry
from cylon_tpu.parallel import dist_ops, shard
from cylon_tpu.util import capacity, pow2

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
WORLD = 4
ROWS_PER_CHIP = 2304          # 9,216 rows a side: no padding, and under
#                               a power of two (the routes depend on it)
MULTIPLIER = 2654435761       # the configuration's rank -> key constant


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


GEN = _load("generators", "pk_fk_zipf")
REF = _load("references", "inner_join_fingerprint")


def _config(exponent):
    return {"rows_per_chip": ROWS_PER_CHIP,
            "schema": {"left": ["k", "v"], "right": ["k", "w"],
                       "key_dtype": "int32", "value_dtype": "float32"},
            "zipf": {"exponent": exponent,
                     "rank_to_key_multiplier": MULTIPLIER}}


def _data(exponent, seed=20260928, cluster=None, ctx=None):
    """The generator's tables; ``cluster`` reorders S so that one (source,
    target) pair is hot: "key" sorts S by key (a hot key's rows then start
    on ONE chip), "chip" by the chip the key hashes to under ``ctx`` (a
    table that arrives grouped, on the wrong chips)."""
    tables = GEN.generate(_config(exponent), {}, WORLD, 1.0, seed)["tables"]
    if cluster is not None:
        by = tables["right"]["k"]
        if cluster == "chip":
            by = _targets(ctx, by)
        order = np.argsort(by, kind="stable")
        tables["right"] = {c: a[order] for c, a in tables["right"].items()}
    return tables


def _place(ctx, tables):
    return {name: shard.distribute(ct.Table.from_pydict(ctx, cols), ctx)
            for name, cols in tables.items()}


def _query(placed):
    return plan.scan(placed["left"]).join(plan.scan(placed["right"]),
                                          "inner", on="k")


def _host_columns(out):
    mask = np.asarray(out.emit_mask())
    return [np.asarray(c.data)[mask] for c in out.columns()]


def _assert_equals_reference(out, tables):
    ref = REF.reference(tables, None, None)
    cols = _host_columns(out)
    got = {"names": list(out.column_names), "columns": cols, "nulls": 0}
    assert ref["rows"] == len(tables["right"]["k"])   # PK-FK: one match
    assert {n["name"]: n["value"] for n in REF.compare(got, ref)} == {
        "schema_diff": 0, "rows_diff": 0, "nulls": 0, "fingerprint_diff": 0}


def _exchange_spans(cp):
    return [s for s in cp.spans if s.name.startswith("shuffle.exchange")]


def _targets(ctx, keys):
    """The chip of every key as the PROGRAM places it: its own key hash
    over the column, called and not copied."""
    col = ct.Table.from_pydict(ctx, {"k": keys})._columns[0]
    return np.asarray(dist_ops._partition_targets_dist(ctx, [col]))


def _count_matrix(ctx, keys):
    """counts[source, target] of one side: the sources are the row blocks
    of ``shard.distribute``."""
    targets = _targets(ctx, keys)
    per = len(keys) // WORLD
    assert per == ROWS_PER_CHIP == shard.shard_capacity(len(keys), WORLD)
    return np.stack([np.bincount(targets[s * per:(s + 1) * per],
                                 minlength=WORLD) for s in range(WORLD)])


def _exchange_counters():
    snap = telemetry.metrics_snapshot()
    return {k: snap.get(k, 0) for k in (
        'cylon_exchange_recv_rows_total{stat="max"}',
        'cylon_exchange_recv_rows_total{stat="mean"}',
        "cylon_exchange_live_rows_total", "cylon_exchange_slots_total")}


def _host_syncs():
    return {k: v for k, v in telemetry.metrics_snapshot().items()
            if k.startswith("cylon_host_syncs_total")}


# exponent, chunk bytes (None: the default, one fused pair program, the
# chip's route since PR 48; 4096: both sides through the chunked pipeline,
# forced as `forced_paths.chunked` does, the chip's route before)
PADDED = [(0.0, 4096), (1.05, 4096), (1.25, 4096), (1.05, None)]


@pytest.mark.parametrize("exponent,chunk_bytes", PADDED)
def test_planned_join_equals_reference_on_the_padded_route(
        dist_ctx, monkeypatch, exponent, chunk_bytes):
    if chunk_bytes is not None:
        forced_paths.chunked(monkeypatch, chunk_bytes)
    tables = _data(exponent)
    pipe = _query(_place(dist_ctx, tables))
    assert "Shuffle" in pipe.explain()
    with telemetry.collect_phases() as cp:
        out = pipe.execute()
    ex = _exchange_spans(cp)
    assert ex and all(s.attrs["mode"] == "padded" for s in ex)
    # a side crosses in blocks of its worst pair's rows on the
    # 16-an-octave grid (PR 52: on the octave before), so under skew no
    # more than a sixteenth of a block is padding the join then sorts
    worst = [int(_count_matrix(dist_ctx, tables[side]["k"]).max())
             for side in ("left", "right")]
    blocks = [capacity(w) for w in worst]
    assert all(w <= b <= w + w // 16 for w, b in zip(worst, blocks))
    assert [s.attrs["block"] for s in ex] == (
        [max(blocks)] if chunk_bytes is None else blocks)
    if exponent:
        assert any(b != pow2(b) for b in blocks)
    assert [s.name for s in ex] == (
        ["shuffle.exchange_pair"] if chunk_bytes is None
        else ["shuffle.exchange"] * 2)
    if chunk_bytes is not None:
        assert all(s.attrs["chunks"] > 1 for s in ex)
    _assert_equals_reference(out, tables)


@pytest.mark.parametrize("exponent,cluster", [(1.25, "key"), (1.05, "chip")])
def test_planned_join_is_exact_when_one_pair_is_hot(dist_ctx, exponent,
                                                    cluster):
    """One source sends one target most of its rows, so the padded
    layout (world blocks of the worst pair's capacity, a chip) would
    waste more than PADDED_WASTE_FACTOR and S's exchange falls to the
    compact rounds."""
    tables = _data(exponent, cluster=cluster, ctx=dist_ctx)
    with telemetry.collect_phases() as cp:
        out = _query(_place(dist_ctx, tables)).execute()
    modes = sorted(s.attrs["mode"] for s in _exchange_spans(cp))
    assert modes == ["compact", "padded"]    # S, R
    _assert_equals_reference(out, tables)


@pytest.mark.parametrize("exponent,cluster",
                         [(0.0, None), (1.05, None), (1.25, "key")])
def test_exchange_counters_move_by_what_the_count_matrix_says(
        dist_ctx, exponent, cluster):
    tables = _data(exponent, cluster=cluster)
    placed = _place(dist_ctx, tables)
    # each side is an exchange; its slots follow the route it took: world
    # * block a chip when padded, the pow2 of the worst chip's rows when
    # compact (S, where a pair is hot)
    want = dict.fromkeys(_exchange_counters(), 0)
    for side in ("left", "right"):
        counts = _count_matrix(dist_ctx, tables[side]["k"])
        recv = counts.sum(axis=0)
        compact = cluster is not None and side == "right"
        want['cylon_exchange_recv_rows_total{stat="max"}'] += recv.max()
        want['cylon_exchange_recv_rows_total{stat="mean"}'] += recv.mean()
        want["cylon_exchange_live_rows_total"] += counts.sum()
        want["cylon_exchange_slots_total"] += WORLD * (
            pow2(int(recv.max())) if compact
            else WORLD * capacity(int(counts.max())))
    before = _exchange_counters()
    with telemetry.collect_phases() as cp:
        _query(placed).execute()
    moved = {k: v - before[k] for k, v in _exchange_counters().items()}
    modes = sorted(s.attrs["mode"] for s in _exchange_spans(cp))
    assert modes == (["compact", "padded"] if cluster else ["padded"])
    assert moved == want
    n = 2 * ROWS_PER_CHIP * WORLD
    assert moved["cylon_exchange_live_rows_total"] == n
    assert moved["cylon_exchange_slots_total"] >= n   # no negative padding
    if exponent == 0.0:
        assert moved['cylon_exchange_recv_rows_total{stat="max"}'] \
            < 1.05 * n / WORLD
    else:
        assert moved['cylon_exchange_recv_rows_total{stat="max"}'] \
            > 1.05 * n / WORLD


def test_exchange_counters_stay_still_on_one_chip(local_ctx):
    tables = _data(1.05)
    placed = {name: ct.Table.from_pydict(local_ctx, cols)
              for name, cols in tables.items()}
    before = _exchange_counters()
    out = _query(placed).execute()
    assert _exchange_counters() == before
    _assert_equals_reference(out, tables)


@pytest.mark.parametrize("exponent", [0.0, 1.05])
def test_a_query_pays_two_host_fetches(dist_ctx, monkeypatch, exponent):
    """The count matrix and the join plan's counts, as at the parent: the
    skew counters ride the matrix that ``count_pair`` already fetched."""
    forced_paths.chunked(monkeypatch, 4096)
    placed = _place(dist_ctx, _data(exponent, seed=7))
    before = _host_syncs()
    _query(placed).execute()
    moved = {k: v - before.get(k, 0) for k, v in _host_syncs().items()
             if v != before.get(k, 0)}
    assert moved == {'cylon_host_syncs_total{site="shuffle.count_pair"}': 1,
                     'cylon_host_syncs_total{site="join.plan"}': 1}
