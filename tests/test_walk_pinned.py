"""The (II, jitter) walk both mapping backends share maps every request
of the benchmark's ``paper_table`` mix exactly as the pinned record says
(`tests/_walk_pinned.py`): the portfolio's spans, their attributes and
counts in order, and the exact backend's verdicts and certificates."""

import pytest

from _walk_pinned import cases, exact_fields, load, portfolio_spans

CASES = cases()
PINNED = load()


def test_pinned_cases_match_the_mix():
    assert sorted(PINNED) == sorted(CASES)


@pytest.mark.parametrize("name", list(CASES))
def test_portfolio_spans_pinned(name):
    dfg, mode, engine = CASES[name]
    assert portfolio_spans(dfg, mode, engine) == PINNED[name]["portfolio"]


@pytest.mark.parametrize(
    "name", [n for n, (_, _, engine) in CASES.items() if engine == "numpy"])
def test_exact_result_pinned(name):
    dfg, mode, _ = CASES[name]
    assert exact_fields(dfg, mode) == PINNED[name]["exact"]
