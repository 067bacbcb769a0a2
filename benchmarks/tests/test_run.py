"""A whole run on the CPU at a test size, past the harness's look for a
chip: sound, it comes out correct; with the timed path broken underneath (an
answer altered where the program produces it, from the third query on, so
that only the comparison AFTER the window can see it) it comes out
``correct: false``; so does the control (the reference in bfloat16 in the
program's place)."""
import argparse

import pytest

import run as bench_run

CELLS = [("join-w1", 0.002), ("groupby-q5", 0.001)]


def args_for(cell, scale, **kw):
    a = argparse.Namespace(workload=cell, seed=2147483659, seconds=0.5,
                           trace=0, scale=scale, control=0)
    for k, v in kw.items():
        setattr(a, k, v)
    return a


@pytest.mark.parametrize("cell,scale", CELLS)
def test_sound_run_is_correct(cell, scale):
    result, code = bench_run.run(args_for(cell, scale))
    assert code == 1                      # not a TPU: never a result line
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"query_p50_s", "query_p90_s",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_every_query_has_inputs_the_program_has_not_seen(monkeypatch):
    """The program remembers a join's counts by the identity of its input
    buffers (shuffle._count_cached). Every query of a run, the window's
    too, has to miss that memo and pay the count's host sync."""
    from cylon_tpu.parallel import shuffle

    real, computed = shuffle._count_cached, [0]

    def counting(ids_key, refs, compute):
        def counted():
            computed[0] += 1
            return compute()
        return real(ids_key, refs, counted)

    monkeypatch.setattr(shuffle, "_count_cached", counting)
    result, _code = bench_run.run(args_for("join-w1", 0.002))
    assert result["correct"] is True
    assert computed[0] == result["attempted"] + 2   # + set-up's two


@pytest.mark.parametrize("cell,scale", CELLS)
def test_altered_answer_in_the_window_is_not_correct(cell, scale,
                                                     monkeypatch):
    from cylon_tpu.data.column import Column
    from cylon_tpu.data.table import Table
    from cylon_tpu.plan.lazy import LazyTable

    real, calls = LazyTable.execute, [0]

    def broken(self, *a, **kw):
        out = real(self, *a, **kw)
        calls[0] += 1
        if calls[0] <= 2:                 # the two queries of set-up pass
            return out
        cols = list(out.columns())
        c = cols[-1]                      # the last payload / sum column
        cols[-1] = Column(c.data + 1, c.dtype, c.validity, c.dictionary,
                          c.name)
        return Table(cols, out.context, out.row_mask)

    monkeypatch.setattr(LazyTable, "execute", broken)
    result, _code = bench_run.run(args_for(cell, scale))
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["correct"] is False


@pytest.mark.parametrize("cell,scale", CELLS)
def test_dropped_rows_in_the_window_are_failed_queries(cell, scale,
                                                       monkeypatch):
    from cylon_tpu.data.table import Table
    from cylon_tpu.plan.lazy import LazyTable

    real, calls = LazyTable.execute, [0]

    def broken(self, *a, **kw):
        out = real(self, *a, **kw)
        calls[0] += 1
        if calls[0] <= 2:
            return out
        mask = out.emit_mask()
        return Table(list(out.columns()), out.context,
                     mask.at[int(mask.argmax())].set(False))

    monkeypatch.setattr(LazyTable, "execute", broken)
    result, _code = bench_run.run(args_for(cell, scale))
    assert result["failed"] == result["attempted"] >= 1
    assert result["correct"] is False


@pytest.mark.parametrize("cell,scale", CELLS)
def test_control_is_not_correct(cell, scale):
    result, _code = bench_run.run(args_for(cell, scale, control=1))
    assert result["correct"] is False


def test_off_a_tpu_without_scale_there_is_no_run():
    a = args_for("join-w1", 1.0)
    with pytest.raises(SystemExit) as e:
        bench_run.run(a)
    assert e.value.code not in (0, None)
