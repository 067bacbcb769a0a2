"""What a profiler trace reads: every ``counted_cache`` factory's program
is named after its factory (``XLA Modules``: ``jit_<program>(<hash>)``,
also under ``shard_map``), and every ``pallas_call`` carries a ``name``
that says what the kernel does (``XLA Ops``: ``<name>.<n> custom-call``).
The benchmark's metric files match these names; a factory added without
one would read ``jit_kernel`` and a kernel ``kernel``.

The factories and their abstract inputs come from the ``collectives``
analysis catalog, whose coverage sweep already fails on a factory it
does not hold: a new factory is a new case here without an edit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cylon_tpu.analysis.collectives import (_virtual_mesh,
                                            default_entry_points)
from cylon_tpu.ops import join as _join
from cylon_tpu.ops import tpu_kernels as tk
from cylon_tpu.parallel import dist_ops
from cylon_tpu.telemetry.metrics import program_name

ENTRIES = default_entry_points()
N = 4 * 4096   # rows over a 4-wide mesh


def _sds(dtype, n=N):
    return jax.ShapeDtypeStruct((n,), dtype)


def _stream_join(mesh):
    """The three Pallas stream factories (TPU-only in the catalog: they do
    not lower off a TPU, but they trace anywhere): each program with its
    abstract inputs."""
    k, w, e = _sds(jnp.int32), _sds(jnp.float32), _sds(jnp.bool_)
    cols = ((k,), (None,), (k, w), (None, None))
    a_desc, b_desc = _join.plan_lane_descs(*cols, _join.JoinType.INNER)
    br = _join.stream_block_rows(N // 4, N // 4)
    plan = dist_ops._join_plan_stream_fn(
        mesh, _join.JoinType.INNER, 1, a_desc, b_desc, br, False)
    plan_in = ((k,), None, e, (k,), None, e) + cols
    _rep, counts, a_streams, b_streams = jax.eval_shape(plan, *plan_in)
    mat = dist_ops._join_mat_stream_fn(
        mesh, _join.JoinType.INNER, 4096, a_desc, b_desc, br)
    # the semi join's ONE program: the left side's columns alone (PR 49)
    semi_desc, _ = _join.plan_lane_descs(
        (k, w), (None, None), (), (), _join.JoinType.SEMI, 0, None)
    semi = dist_ops._semi_plan_stream_fn(mesh, _join.JoinType.SEMI,
                                         semi_desc, br)
    return {"_join_plan_stream_fn": (plan, plan_in),
            "_join_mat_stream_fn": (mat, (counts, a_streams, b_streams)
                                    + cols),
            "_semi_plan_stream_fn": (semi, ((k,), e, e, (k,), e, e,
                                            (k, w), (None, None)))}


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


@pytest.mark.parametrize("entry", ENTRIES, ids=[e.name for e in ENTRIES])
def test_factory_program_is_named_after_its_factory(entry):
    mesh = _virtual_mesh(4)
    want = program_name(entry.factory)
    assert want and not want.startswith("_") and not want.endswith("_fn")
    if entry.tpu_only:
        with jax.enable_x64(False):
            prog, args = _stream_join(mesh)[entry.factory]
            closed = jax.make_jaxpr(prog)(*args)
        # what runs under shard_map keeps its kernel's name too
        assert _pallas_names(closed.jaxpr, []), entry.name
    else:
        prog, args = entry.build(mesh), entry.inputs(mesh)
        closed = jax.make_jaxpr(prog)(*args)
        head = prog.lower(*args).as_text().split("\n", 1)[0]
        assert head.startswith(f"module @jit_{want} "), head
    outer = closed.jaxpr.eqns[0]
    assert outer.primitive.name == "jit"
    assert outer.params["name"] == want


def test_program_name_strips_the_factory_decoration():
    assert program_name("_join_plan_stream_fn") == "join_plan_stream"
    assert program_name("_count_fn") == "count"
    assert program_name("plain") == "plain"


def _negate(x):
    return -x


def test_a_shared_function_keeps_its_own_name():
    """Only a closure the build created is renamed: a factory that jits
    a module-level function must not rename it for every other user."""
    from cylon_tpu.telemetry import counted_cache

    @counted_cache
    def _shared_probe_fn():
        return jax.jit(_negate)

    assert _shared_probe_fn().__wrapped__ is _negate
    assert _negate.__name__ == "_negate"


def _u32(n):
    return jnp.arange(n, dtype=jnp.uint32)


KERNELS = {
    "groupby_run_reduce": lambda: jax.make_jaxpr(
        lambda g, s: tk.groupby_run_reduce(g, g, [s], ["add"], 64,
                                           interpret=True))(
            jnp.ones(4096, bool), jnp.arange(4096, dtype=jnp.int32)),
    "groupby_dense_reduce": lambda: jax.make_jaxpr(
        lambda k, s: tk.groupby_dense_reduce(k, np.int32(0), np.int32(8),
                                             [s], ["int"], 8,
                                             interpret=True))(
            jnp.zeros(4096, jnp.int32), jnp.arange(4096, dtype=jnp.int32)),
    "stream_compact": lambda: jax.make_jaxpr(
        lambda m, s: tk.stream_compact(m, [s], interpret=True))(
            jnp.ones(4096, bool), _u32(4096)),
    "partition_hist": lambda: jax.make_jaxpr(
        lambda t: tk.partition_hist(t, 5, interpret=True))(
            jnp.zeros(4096, jnp.int32)),
    "partition_scatter": lambda: jax.make_jaxpr(
        lambda t, s: tk.partition_scatter(t, [s], 5, interpret=True))(
            jnp.zeros(4096, jnp.int32), _u32(4096)),
    "setop_stream": lambda: jax.make_jaxpr(
        lambda a, b, t, l: tk.setop_stream(a, b, t, [l], op=0,
                                           interpret=True))(
            _u32(4096), _u32(4096), _u32(4096), _u32(4096)),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_call_carries_its_name(name):
    assert _pallas_names(KERNELS[name]().jaxpr, []) == [name]


def test_groupby_reduce_keeps_its_program_and_names_its_kernel():
    """What a traced ``groupby-q5`` reads: the reduce step's program is
    still ``jit_sorted_segment_aggregate`` on ``XLA Modules`` (the
    benchmark's groupby metrics match it) and its one kernel reads
    ``groupby_run_reduce`` on ``XLA Ops``."""
    from cylon_tpu.ops import groupby as _groupby

    n = 4096
    args = (_sds(jnp.bool_, n), _sds(jnp.bool_, n), _sds(jnp.int32, n),
            (_sds(jnp.int32, n), _sds(jnp.float32, n)), (None, None))
    kw = dict(num_segments=64, ops=(_groupby.AggregationOp.SUM,) * 2,
              col_ids=(0, 1), all_valid=(True, True))
    head = _groupby.sorted_segment_aggregate_jit.lower(
        *args, **kw).as_text().split("\n", 1)[0]
    assert head.startswith("module @jit_sorted_segment_aggregate "), head
    closed = jax.make_jaxpr(lambda *a: _groupby.sorted_segment_aggregate(
        *a, interpret=True, **kw))(*args)
    assert _pallas_names(closed.jaxpr, []) == ["groupby_run_reduce"]


def test_dense_groupby_names_its_probe_its_program_and_its_kernel():
    """What a traced ``groupby-q4`` reads: the probe and the dense program
    on ``XLA Modules`` (both match the benchmark's pattern ``groupby``),
    the kernel ``groupby_dense_reduce`` on ``XLA Ops``."""
    from cylon_tpu.data import table as table_mod
    from cylon_tpu.ops import groupby as _groupby

    n = 4096
    probe = table_mod._groupby_key_range_fn()
    head = probe.lower(_sds(jnp.int32, n), None, None).as_text()
    assert head.startswith("module @jit_groupby_key_range "), head[:80]
    # the sort's packing probe (PR 35) matches the same pattern
    probe = table_mod._groupby_value_range_fn()
    head = probe.lower((_sds(jnp.int32, n), _sds(jnp.int8, n))).as_text()
    assert head.startswith("module @jit_groupby_value_range "), head[:80]
    MEAN = _groupby.AggregationOp.MEAN
    with jax.enable_x64(False):
        dense = table_mod._groupby_dense_fn(128, (MEAN, MEAN), (1, 2), True)
        args = (_sds(jnp.int32, n), None, None, _sds(jnp.int32, 2),
                (_sds(jnp.int32, n), _sds(jnp.float32, n)), (None, None))
        head = dense.lower(*args).as_text()
        closed = jax.make_jaxpr(dense)(*args)
    assert head.startswith("module @jit_groupby_dense "), head[:80]
    assert _pallas_names(closed.jaxpr, []) == ["groupby_dense_reduce"]


def test_distributed_groupby_names_its_packing_probe():
    """What a traced ``groupby-q5-w4`` reads since PR 43: the probe of
    the per-shard sort's packing is a NAMED program, one that
    `anonymous_programs_per_query`'s list takes (a name that ends in
    ``_program``) and that `dist_groupby_device_ms_per_query`'s pattern
    (``^jit_groupby\\b``: the per-shard sort + reduce program alone) does
    not count."""
    import json
    import os
    import re
    from cylon_tpu.data import table as table_mod

    n = 4096
    probe = table_mod._groupby_pack_ranges_program_fn()
    head = probe.lower(_sds(jnp.int32, n), None,
                       (_sds(jnp.int32, n), _sds(jnp.int32, n))).as_text()
    name = "jit_groupby_pack_ranges_program"
    assert head.startswith(f"module @{name} "), head[:80]
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "metrics")
    with open(os.path.join(metrics, "anonymous_programs_per_query.json")) as f:
        named = json.load(f)["not_matching"]
    assert any(re.search(p, name + "(123)") for p in named)
    with open(os.path.join(metrics,
                           "dist_groupby_device_ms_per_query.json")) as f:
        counted = json.load(f)["patterns"]
    assert not any(re.search(p, name + "(123)") for p in counted)


def test_join_kernels_carry_their_names():
    """The two join kernels, through the programs that the local join
    runs (``_plan_program_stream_impl`` and its materialize): the names
    that a traced ``join-w1`` reads on ``XLA Ops``."""
    k, w = _sds(jnp.int32, 4096), _sds(jnp.float32, 4096)
    cols = ((k,), (None,), (k, w), (None, None))
    a_desc, b_desc = _join.plan_lane_descs(*cols, _join.JoinType.INNER)
    kw = dict(join_type=_join.JoinType.INNER, a_desc=a_desc, b_desc=b_desc,
              block_rows=_join.stream_block_rows(4096, 4096),
              interpret=False)
    keys = ((k,), (None,), None, (k,), (None,), None)
    with jax.enable_x64(False):
        plan = jax.make_jaxpr(
            lambda *a: _join._plan_program_stream_impl(
                *a, str_flags=(False,), hash_mode=False, **kw))(
                    *keys, *cols)
        counts, a_streams, b_streams = jax.eval_shape(
            lambda *a: _join._plan_program_stream_impl(
                *a, str_flags=(False,), hash_mode=False, **kw),
            *keys, *cols)
        mat = jax.make_jaxpr(
            lambda *a: _join._materialize_program_stream_impl(
                *a, cap_e=4096, **kw))(counts, a_streams, b_streams, *cols)
    assert _pallas_names(plan.jaxpr, []) == ["join_stream_plan"]
    assert _pallas_names(mat.jaxpr, []) == ["join_stream_expand"]
