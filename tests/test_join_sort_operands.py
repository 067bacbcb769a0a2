"""What the stream join's fused plan sort carries, and that the answer
does not depend on it.

The sort is handed the key bits, the tag and one operand a payload slot
(``ops/join.plan_lane_descs``): no validity lane for a column without a
mask, no lane for THE join key where its bits are the column's own (kind
"k": the column is read back off the sorted bits), and it is not stable
(``(bits, tag)`` is a total order). The operand list is read off a
jaxpr; the answers are compared cell by cell, bit patterns included,
with a nested-loop reference over the host arrays, on the Pallas
interpreter (the same kernels compile to Mosaic on a TPU): the cases
and their helpers are `join_sort_operands_cases.py`, half of them run
from `test_join_sort_operands_outer.py`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import telemetry
from cylon_tpu.ops import join as _join
from cylon_tpu.parallel import dist_ops

import join_sort_operands_cases as cases

COUNTER = "cylon_join_sort_operands_total"
SWEEP = "cylon_join_expand_sweep_rows_total"
INNER = _join.JoinType.INNER


def _sorts(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            out.append((len(eqn.invars), eqn.params["is_stable"],
                        eqn.params["num_keys"]))
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _sorts(inner, out)
    return out


def _sds(dtype, n=4096):
    return jax.ShapeDtypeStruct((n,), dtype)


def _plan_sorts(keys, cols, a_desc, b_desc, hash_mode=False, wide=None):
    kw = dict(str_flags=(False,), join_type=INNER, a_desc=a_desc,
              b_desc=b_desc, block_rows=8, hash_mode=hash_mode,
              interpret=False, wide_key=wide)
    with jax.enable_x64(False):
        closed = jax.make_jaxpr(
            lambda *a: _join._plan_program_stream_impl(*a, **kw))(
                *keys, *cols)
    return _sorts(closed.jaxpr, [])


# workload B's schema: a 4-byte key and a 4-byte payload a side
K, W, M = _sds(jnp.int32), _sds(jnp.float32), _sds(jnp.bool_)
# upstream Cylon's: an int64 key and a float64 payload, on a backend
# without x64 each ONE array of two 32-bit word planes (data/column.py)
P = jax.ShapeDtypeStruct((2, 4096), jnp.uint32)


@pytest.mark.parametrize("case,cols,keycols,hash_mode,a_want,operands,wide", [
    # the local join's arguments (data/table._join_once)
    ("local", ((K, W), (None, None), (K, W), (None, None)), (0, 0), False,
     ((0, "k"), (1, "d")), 3, None),
    # a payload column with a real mask still carries its validity lane
    ("masked_payload", ((K, W), (None, None), (K, W), (None, M)), (0, 0),
     False, ((0, "k"), (1, "d")), 4, None),
    # a key with a mask keeps its lanes, on both sides: slot 0 is shared
    ("masked_key", ((K, W), (M, None), (K, W), (None, None)), (0, 0), False,
     ((0, "d"), (0, "v"), (1, "d")), 5, None),
    # a key the caller does not vouch for (promoted, dictionary codes)
    ("no_key_map", ((K, W), (None, None), (K, W), (None, None)),
     (None, None), False, ((0, "d"), (1, "d")), 4, None),
    ("float_key", ((W, K), (None, None), (W, K), (None, None)), (0, 0),
     False, ((0, "d"), (1, "d")), 4, None),
    # the hash path: two hashes, tag, one verify lane, two payload slots
    ("hash", ((K, W), (None, None), (K, W), (None, None)), (None, None),
     True, ((0, "d"), (1, "d")), 6, None),
    # (int64 key, float64 value) as word planes: the key rides once, as
    # its two ordered lanes (hi, lo, tag are the sort's three keys), the
    # payload as its two planes: no gather
    ("i64_f64", ((P, P), (None, None), (P, P), (None, None)), (0, 0), False,
     ((0, "kh"), (0, "kl"), (1, "dh"), (1, "dl")), 5, "int64"),
    ("u64_masked_payload", ((P, P), (None, None), (P, P), (None, M)), (0, 0),
     False, ((0, "kh"), (0, "kl"), (1, "dh"), (1, "dl")), 6, "uint64"),
    # a float64 key keeps its planes as lanes (-0.0 must come back)
    ("f64_key", ((P, P), (None, None), (P, P), (None, None)), (0, 0), False,
     ((0, "dh"), (0, "dl"), (1, "dh"), (1, "dl")), 7, "float64"),
])
def test_plan_sort_operands_read_off_the_jaxpr(case, cols, keycols,
                                               hash_mode, a_want, operands,
                                               wide):
    ldat, lval, rdat, rval = cols
    a_desc, b_desc = _join.plan_lane_descs(ldat, lval, rdat, rval, INNER,
                                           *keycols, wide)
    assert a_desc == a_want
    keys = ((ldat[0],), (lval[0],), None, (rdat[0],), (rval[0],), None)
    sorts = _plan_sorts(keys, cols, a_desc, b_desc, hash_mode, wide)
    lanes = 2 if wide else 1     # 32-bit key lanes the sort compares
    assert _join.plan_key_lane_count(keys[0], (False,)) == lanes
    assert sorts == [(operands, False, 3 if hash_mode else lanes + 1)]
    assert _join.plan_sort_operand_count(
        keys[0], (False,), a_desc, b_desc, hash_mode) == operands
    # the XLA plan (join_plan_keys): the key bit arrays and the tag
    assert _join.plan_sort_operand_count(keys[0], (False,)) == lanes + 1
    # every column rides a lane: none is gathered by index
    assert _join.plan_gathered_column_count(4, a_desc, b_desc) == 0
    assert _join.plan_gathered_column_count(4) == 4   # the XLA plan: all


def test_right_join_key_map_follows_the_swap():
    """RIGHT joins swap sides before the lanes are built: the key's
    column index goes with its side."""
    cols = ((W, K), (None, None), (K, W), (None, None))
    a_desc, b_desc = _join.plan_lane_descs(*cols, _join.JoinType.RIGHT, 1, 0)
    assert a_desc == ((0, "k"), (1, "d"))     # the right table probes
    assert b_desc == ((1, "k"), (0, "d"))


class _Recorded(Exception):
    pass


def test_distributed_join_hands_the_plan_no_mask_that_says_nothing(
        dist_ctx, monkeypatch):
    """What `distributed_join` builds for all-valid shuffled columns, as
    a TPU backend would run it (the Pallas factory is stood in for: it
    does not lower off a TPU): validity None stays None, the key rides
    once, and the per-shard plan sort has 3 operands, not stable."""
    rng = np.random.default_rng(1)
    n = 512
    tables = [ct.Table.from_pydict(dist_ctx, {
        "k": rng.integers(0, 64, n).astype(np.int32),
        name: rng.random(n).astype(np.float32)}) for name in ("v", "w")]
    seen = {}

    def factory(mesh, jt, nk, a_desc, b_desc, br, hash_mode):
        def run(lkb, lkv, lemit, rkb, rkv, remit, ldat, lval, rdat, rval):
            seen.update(a_desc=a_desc, b_desc=b_desc, hash_mode=hash_mode,
                        args=(lkb, lkv, lemit, rkb, rkv, remit,
                              ldat, lval, rdat, rval))
            raise _Recorded()
        return run

    monkeypatch.setattr(dist_ops, "_dist_stream_mode",
                        lambda lkb, rkb, jt, world: (False, 8))
    monkeypatch.setattr(dist_ops, "_join_plan_stream_fn", factory)
    before = telemetry.metrics_snapshot()
    with pytest.raises(_Recorded):
        tables[0].distributed_join(tables[1], "inner", on="k")
    after = telemetry.metrics_snapshot()
    assert after[COUNTER] - before.get(COUNTER, 0) == 3
    assert after[SWEEP] - before.get(SWEEP, 0) == 16
    assert seen["a_desc"] == seen["b_desc"] == ((0, "k"), (1, "d"))
    lkb, lkv, lemit, rkb, rkv, remit, ldat, lval, rdat, rval = seen["args"]
    assert lval == (None, None) and rval == (None, None)
    world = dist_ctx.get_world_size()
    shard = lambda x: None if x is None else _sds(x.dtype,
                                                  x.shape[0] // world)
    keys = jax.tree.map(shard, ((lkb, (lkv,), lemit, rkb, (rkv,), remit)))
    cols = (tuple(map(shard, ldat)), lval, tuple(map(shard, rdat)), rval)
    assert _plan_sorts(keys, cols, seen["a_desc"], seen["b_desc"]) == \
        [(3, False, 2)]


@pytest.mark.parametrize("block_rows,rows", [
    (None, 0),   # the XLA plan: no expand kernel, it gathers
    (8, 16),     # the block's window IS the slab's 16 rows
    (16, 16),
    (64, 16),    # 72 before PR 33: every output vreg swept the block's
])
def test_expand_sweep_rows_are_the_slabs_not_the_blocks(block_rows, rows):
    assert _join.expand_sweep_rows(block_rows) == rows


@pytest.mark.parametrize("stream,rows", [(True, 16), (False, 0)])
def test_expand_sweep_rows_counted_once_a_join(local_ctx, monkeypatch,
                                               stream, rows):
    """16 a join on the stream path, 0 (but counted: the family is
    there) on the XLA plan, which every CPU run takes."""
    rng = np.random.default_rng(3)
    left, right = (ct.Table.from_pydict(local_ctx, {
        "k": rng.integers(0, 40, n).astype(np.int32),
        name: rng.random(n).astype(np.float32)})
        for name, n in (("v", 300), ("w", 200)))
    monkeypatch.setattr(_join, "STREAM_PLAN", stream)
    before = telemetry.metrics_snapshot().get(SWEEP, 0)
    got = left.join(right, "inner", on=["k"])
    assert telemetry.metrics_snapshot()[SWEEP] - before == rows
    assert got.row_count > 300


# ---------------------------------------------------------------------------
# the answer, key columns included, bit for bit: INNER and LEFT here, RIGHT
# and FULL OUTER in test_join_sort_operands_outer.py
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("kind", cases.KINDS)
def test_stream_join_answer_bit_for_bit(local_ctx, monkeypatch, kind, how):
    cases.check_stream_join_answer(local_ctx, monkeypatch, kind, how)
