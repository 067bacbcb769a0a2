"""Every host statement of a distributed join runs under a leaf span that
says what the host does there (PR 36): which spans one `distributed_join`
opens on the four-wide CPU mesh, in which order, each under the span that
encloses it; that the one-chip branch opens none of them; and that
`shuffle()` opens the leaves it shares with the join through the exchange
helpers."""
import numpy as np
import pytest

import cylon_tpu as ct
import forced_paths
from cylon_tpu import telemetry
from cylon_tpu.parallel import dist_ops

JOIN_LEAVES = ("distributed_join.distribute", "distributed_join.targets",
               "shuffle.payload", "shuffle.route", "shuffle.unpack",
               "distributed_join.keybits", "distributed_join.finish")

# one join whose two sides both move, as the collector sees it: the
# spans in the order they open
FUSED = [
    "plan.shuffle.join",
    "distributed_join.distribute",
    "distributed_join.shuffle",
    "distributed_join.targets", "distributed_join.targets",
    "shuffle.count", "sync.shuffle.count_pair",
    "shuffle.payload", "shuffle.payload",
    "shuffle.route", "shuffle.exchange_pair",
    "shuffle.unpack", "shuffle.unpack",
    "distributed_join.keybits",
    "distributed_join.plan", "sync.join.plan",
    "distributed_join.materialize",
    "distributed_join.finish",
]
# where a side must chunk (no payload the budget admits since PR 48:
# forced, `forced_paths.chunked`) each side is a chunked exchange of its
# own: the pair's routing sends both on, and each routes for itself
CHUNKED = FUSED[:9] + [
    "shuffle.route",
    "shuffle.route", "shuffle.exchange",
    "shuffle.route", "shuffle.exchange",
] + FUSED[11:]

# the span that has to enclose each leaf (an ancestor, not always the
# parent)
UNDER = {
    "distributed_join.distribute": "plan.shuffle.join",
    "distributed_join.shuffle": "plan.shuffle.join",
    "distributed_join.targets": "distributed_join.shuffle",
    "shuffle.count": "distributed_join.shuffle",
    "sync.shuffle.count_pair": "shuffle.count",
    "shuffle.payload": "distributed_join.shuffle",
    "shuffle.route": "distributed_join.shuffle",
    "shuffle.exchange_pair": "distributed_join.shuffle",
    "shuffle.exchange": "distributed_join.shuffle",
    "shuffle.unpack": "distributed_join.shuffle",
    "distributed_join.keybits": "plan.shuffle.join",
    "distributed_join.plan": "plan.shuffle.join",
    "sync.join.plan": "distributed_join.plan",
    "distributed_join.materialize": "plan.shuffle.join",
    "distributed_join.finish": "plan.shuffle.join",
}


def _tables(ctx, n=256, seed=0):
    rng = np.random.default_rng(seed)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, 64, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, 64, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    return left, right


def _joined(ctx, seed, n=256):
    """The spans of one join under a root that stands for the executor's
    ``plan.shuffle.join``, and the result."""
    left, right = _tables(ctx, n=n, seed=seed)
    with telemetry.collect_phases() as cp:
        with telemetry.span("plan.shuffle.join"):
            out = left.distributed_join(right, "inner", on="k")
    return cp.spans, out


def _ancestors(spans):
    by_id = {s.span_id: s for s in spans}
    out = {}
    for s in spans:
        names, at = [], s
        while at.parent_id in by_id:
            at = by_id[at.parent_id]
            names.append(at.name)
        out[s.span_id] = names
    return out


@pytest.mark.parametrize("chunk_bytes,want", [(None, FUSED),
                                              (4096, CHUNKED)],
                         ids=["fused-pair", "chunked-each"])
def test_join_opens_every_leaf_in_order(dist_ctx, monkeypatch, chunk_bytes,
                                        want):
    if chunk_bytes:
        forced_paths.chunked(monkeypatch, chunk_bytes)
    # 4096 rows a side: ~256 a (source, target) pair, over the 128-row
    # chunk that 4096 bytes buy
    spans, out = _joined(dist_ctx, seed=1, n=4096 if chunk_bytes else 256)
    assert out.row_count > 0
    assert [s.name for s in spans] == want
    above = _ancestors(spans)
    for s in spans[1:]:
        assert UNDER[s.name] in above[s.span_id], (s.name,
                                                   above[s.span_id])
    # a leaf is a leaf: none of the new spans encloses another of them
    for s in spans:
        assert not (s.name in JOIN_LEAVES
                    and set(above[s.span_id]) & set(JOIN_LEAVES)), s.name


def test_leaf_attributes(dist_ctx):
    spans, out = _joined(dist_ctx, seed=3)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    dist, = by_name["distributed_join.distribute"]
    assert dist.attrs["world"] == 4
    assert dist.attrs["already_distributed"] == 0   # both staged on chip 0
    assert [s.attrs["side"] for s in by_name["distributed_join.targets"]] \
        == ["left", "right"]
    assert all(s.attrs["key_columns"] == 1
               for s in by_name["distributed_join.targets"])
    # k and v (or w), no validity mask: two leaves a side
    assert [s.attrs["lanes"] for s in by_name["shuffle.payload"]] == [2, 2]
    route, = by_name["shuffle.route"]
    assert (route.attrs["mode"], route.attrs["chunks"]) == ("pair", 1)
    assert route.attrs["block"] >= 1
    keybits, = by_name["distributed_join.keybits"]
    assert keybits.attrs["key_lanes"] == 1
    assert keybits.attrs["hash_mode"] is False
    finish, = by_name["distributed_join.finish"]
    assert finish.attrs["rows_out"] == out.row_count


def test_distribute_counts_the_sides_already_placed(dist_ctx):
    from cylon_tpu.parallel import shard

    left, right = _tables(dist_ctx, seed=4)
    left = shard.distribute(left, dist_ctx)
    with telemetry.collect_phases() as cp:
        left.distributed_join(right, "inner", on="k")
    dist, = [s for s in cp.spans if s.name == "distributed_join.distribute"]
    assert dist.attrs["already_distributed"] == 1


def test_one_chip_join_opens_no_leaf(local_ctx):
    left, right = _tables(local_ctx, seed=5)
    with telemetry.collect_phases() as cp:
        out = left.distributed_join(right, "inner", on="k")
    assert out.row_count > 0
    names = {s.name for s in cp.spans}
    assert not names & set(JOIN_LEAVES), names
    assert not any(n.startswith(("distributed_join.", "shuffle."))
                   for n in names), names


def test_shuffle_opens_the_shared_leaves(dist_ctx):
    """`shuffle()` reaches the exchange through the same helpers, so the
    payload, route and unpack leaves are there without a line of its
    own."""
    left, _right = _tables(dist_ctx, seed=6)
    with telemetry.collect_phases() as cp:
        out = dist_ops.shuffle(left, ["k"])
    assert out.row_count == 256
    assert [s.name for s in cp.spans] == [
        "shuffle.payload", "shuffle.count", "sync.shuffle.count",
        "shuffle.route", "shuffle.exchange", "shuffle.unpack"]
    route = cp.spans[3]
    assert (route.attrs["mode"], route.attrs["tables"]) == ("padded", 1)


def test_overlap_ratio_is_an_attribute_and_no_histogram(dist_ctx,
                                                       monkeypatch):
    """`cylon_exchange_overlap_ratio` said nothing its span attribute and
    `cylon_exchange_chunks_total` do not."""
    forced_paths.chunked(monkeypatch, 4096)
    before = telemetry.metrics_snapshot().get(
        "cylon_exchange_chunks_total", 0)
    left, _right = _tables(dist_ctx, n=4096, seed=7)
    with telemetry.collect_phases() as cp:
        dist_ops.shuffle(left, ["k"])
    ex, = [s for s in cp.spans if s.name == "shuffle.exchange"]
    assert ex.attrs["chunks"] > 1
    assert ex.attrs["overlap_ratio"] == round(
        (ex.attrs["chunks"] - 1) / ex.attrs["chunks"], 4)
    snap = telemetry.metrics_snapshot()
    assert snap["cylon_exchange_chunks_total"] - before \
        == ex.attrs["chunks"]
    assert not any(k.startswith("cylon_exchange_overlap_ratio")
                   for k in snap)


# ---------------------------------------------------------------------------
# what a join LAUNCHES, read off a profiler trace of the host (PR 39): the
# dispatches (`PjitFunction(...)` events of the dispatching thread, the
# outermost of each nest) by the innermost `cylon:` span open at their
# start, and the programs that ran by name (`hlo_module` of the CPU
# backend's operation events, the name `XLA Modules` carries on a chip)
# ---------------------------------------------------------------------------

def _host_trace(fn, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
        jax.block_until_ready([c.data for c in out.columns()])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans, calls, modules = [], [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                end = e.start_ns + e.duration_ns
                if e.name.startswith("cylon:"):
                    spans.append((e.name[len("cylon:"):].split("#")[0],
                                  e.start_ns, end))
                elif e.name.startswith("PjitFunction("):
                    calls.append((e.start_ns, end))
                else:
                    modules.update(str(v) for k, v in e.stats
                                   if k == "hlo_module")
    calls.sort(key=lambda c: (c[0], -c[1]))
    by_span, open_until = {}, -1
    for start, end in calls:
        if start < open_until:
            continue    # the inner event of one dispatch
        open_until = end
        inner = min((s for s in spans if s[1] <= start < s[2]),
                    key=lambda s: s[2] - s[1], default=(None,))
        by_span[inner[0]] = by_span.get(inner[0], 0) + 1
    return by_span, modules


def test_join_launches_one_key_program_a_side_a_stage(dist_ctx, tmp_path):
    """A four-way join of two int32-keyed tables, already distributed as
    a cell's are: ONE program a side under `distributed_join.targets`,
    one a side for the key bits, and in the whole join no program but
    those the engine names (`anonymous_programs_per_query`'s list): 27 +
    17 eager one-operation programs a side ran here before PR 39."""
    import json
    import os
    import re

    from cylon_tpu.parallel import shard

    def tables(seed):
        left, right = _tables(dist_ctx, n=4096, seed=seed)
        return (shard.distribute(left, dist_ctx),
                shard.distribute(right, dist_ctx))

    left, right = tables(7)
    left.distributed_join(right, "inner", on="k")    # compile everything
    left, right = tables(8)                          # fresh buffers
    by_span, modules = _host_trace(
        lambda: left.distributed_join(right, "inner", on="k"), tmp_path)
    assert by_span["distributed_join.targets"] == 2, by_span
    assert by_span["distributed_join.keybits"] == 2, by_span
    assert None not in by_span, by_span     # nothing outside a span
    assert {"jit_partition_targets_program",
            "jit_key_bits_program"} <= modules, modules
    spec = json.load(open(os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "metrics",
        "anonymous_programs_per_query.json")))
    anonymous = sorted(m for m in modules if not any(
        re.search(p, m) for p in spec["not_matching"]))
    assert anonymous == [], anonymous
