"""The stream join's answer bit for bit, RIGHT and FULL OUTER: the other
half of `tests/test_join_sort_operands.py`'s cases, in a file of its
own so that `--dist loadfile` gives each half a worker (PR 45; the
cases and their helpers are `tests/join_sort_operands_cases.py`)."""
import pytest

import join_sort_operands_cases as cases


@pytest.mark.parametrize("how", ["right", "outer"])
@pytest.mark.parametrize("kind", cases.KINDS)
def test_stream_join_answer_bit_for_bit(local_ctx, monkeypatch, kind, how):
    cases.check_stream_join_answer(local_ctx, monkeypatch, kind, how)
