"""Race semantics (`repro.exact.race`) and cooperative cancellation.

What the race driver promises, pinned:

- **Bounded loser shutdown** — a cancelled `PortfolioSBTS` stops
  within one iteration of the token being set (the prover's CSP polls
  every 64 nodes; the portfolio polls per super-iteration), and a
  pre-cancelled `map_dfg` / `exact_map_dfg` returns without claiming
  anything (no partial-range certificates masquerading as full UNSAT
  proofs).
- **Reproducible winners** — the winner is decided by *soundness*,
  not thread timing, whenever only one side can produce a sound
  answer: an UNSAT instance with portfolio certification off can only
  be won by the prover; a feasible instance with a starved prover
  budget can only be won by the portfolio.  Pinned seeds reproduce
  the same winner across repeats.
- **Degradation** — a crashed prover degrades the race to
  portfolio-only (and vice versa); the race only raises when both
  sides crash.
"""

import pytest

from repro.core import CancelToken, make_cnkm, map_dfg
from repro.core.cgra import CGRAConfig
from repro.core.conflict import build_conflict_graph
from repro.core.mis import PortfolioSBTS
from repro.core.schedule import schedule_dfg

CGRA = CGRAConfig()


class _CountingToken(CancelToken):
    """Cancels itself after ``after`` is_set() polls."""

    def __init__(self, after: int):
        super().__init__()
        self.after = after
        self.polls = 0

    def is_set(self) -> bool:
        self.polls += 1
        if self.polls >= self.after:
            self.cancel()
        return super().is_set()


# -------------------------------------------------- bounded shutdown
def _sbts():
    sched = schedule_dfg(make_cnkm(3, 6), CGRA, mode="busmap")
    cg = build_conflict_graph(sched, CGRA, bus_pressure=True)
    return PortfolioSBTS(cg.bits, [None] * 4, seed=0), cg


def test_portfolio_stops_immediately_on_preset_cancel():
    sbts, _ = _sbts()
    tok = CancelToken()
    tok.cancel()
    sbts.run(5000, cancel=tok)
    assert sbts.it == 0


def test_portfolio_stops_within_one_iteration_of_cancel():
    sbts, _ = _sbts()
    tok = _CountingToken(after=10)
    sbts.run(5000, cancel=tok)
    # Polled once per super-iteration: by poll 10 the token is set, so
    # at most 10 iterations ever ran (and no target was hit earlier).
    assert sbts.it <= 10


def test_portfolio_run_identical_with_inert_token():
    """An attached-but-never-set token must not perturb trajectories:
    cancel=None and an inert token produce identical best sets."""
    a, _ = _sbts()
    b, _ = _sbts()
    ra = a.run(300)
    rb = b.run(300, cancel=CancelToken())
    assert a.it == b.it
    assert (ra == rb).all()


@pytest.mark.parametrize("backend", ["portfolio", "exact"])
def test_map_dfg_preset_cancel_claims_nothing(backend):
    tok = CancelToken()
    tok.cancel()
    r = map_dfg(make_cnkm(5, 5), CGRA, mode="busmap", max_ii=2,
                backend=backend, cancel=tok)
    assert not r.ok
    # The crucial soundness property: a cancelled run covers only a
    # prefix of the (II, jitter) range, so it must not carry the
    # full-range UNSAT claim (which this instance would otherwise earn).
    assert not r.proved_infeasible


def test_cancelled_portfolio_never_fakes_certificate_fast_fail():
    """Cancel after the first few polls, mid-II-range: whatever prefix
    was certified must not surface as a sound attempts==0 fast-fail."""
    tok = _CountingToken(after=3)
    r = map_dfg(make_cnkm(5, 5), CGRA, mode="busmap", max_ii=2,
                cancel=tok)
    assert not r.ok and not r.proved_infeasible


def test_token_chaining_reaches_children():
    parent = CancelToken()
    child = CancelToken(parent=parent)
    assert not child.is_set()
    parent.cancel()
    assert child.is_set()
    solo = CancelToken(parent=None)
    solo.cancel()
    assert solo.is_set()


# ------------------------------------------------ reproducible winners
def test_exact_always_wins_unsat_race_without_portfolio_certificates():
    """Portfolio certification off => only the prover can be sound on
    an infeasible instance; the winner is forced, not timed."""
    dfg = make_cnkm(5, 5)
    for _ in range(3):
        r = map_dfg(dfg, CGRA, mode="busmap", max_ii=2, backend="race",
                    certify=False, seed=7)
        assert r.backend == "race:exact"
        assert not r.ok and r.proved_infeasible


def test_portfolio_always_wins_with_starved_prover():
    """A one-node prover budget can neither accept nor certify, so the
    portfolio's validated mapping is the only sound answer."""
    dfg = make_cnkm(3, 6)
    for _ in range(3):
        r = map_dfg(dfg, CGRA, mode="busmap", backend="race",
                    certify=False, certify_budget=1, seed=7)
        assert r.backend == "race:portfolio"
        assert r.ok


def test_race_winner_matches_solo_portfolio_result():
    """Same seed => the racing portfolio walks the same trajectories
    as a solo run; when it wins, it returns the same mapping."""
    dfg = make_cnkm(3, 6)
    solo = map_dfg(dfg, CGRA, mode="busmap", certify=False, seed=3)
    raced = map_dfg(dfg, CGRA, mode="busmap", backend="race",
                    certify=False, certify_budget=1, seed=3)
    assert raced.backend == "race:portfolio"
    assert (raced.ii, raced.placement) == (solo.ii, solo.placement)


def test_race_preset_cancel_returns_unsound_best_effort():
    tok = CancelToken()
    tok.cancel()
    r = map_dfg(make_cnkm(5, 5), CGRA, mode="busmap", max_ii=2,
                backend="race", cancel=tok)
    assert not r.ok and not r.proved_infeasible
    assert r.backend.startswith("race:")


# ------------------------------------------------------- degradation
def test_crashed_prover_degrades_to_portfolio(monkeypatch):
    import repro.exact.race as race_mod

    def boom(*a, **kw):
        raise RuntimeError("prover died")

    monkeypatch.setattr(race_mod, "exact_map_dfg", boom)
    from repro.obs.trace import Tracer
    tracer = Tracer()
    r = map_dfg(make_cnkm(2, 6), CGRA, mode="busmap", backend="race",
                tracer=tracer)
    assert r.ok
    assert r.backend == "race:portfolio"
    # The survivor wins, but the crash is on record, not swallowed.
    race = [s for s in tracer.finished if s.name == "race"]
    assert race[-1].attrs["side_errors"] == {
        "exact": "RuntimeError: prover died"}


def test_crashed_portfolio_degrades_to_prover(monkeypatch):
    import repro.core.bandmap as bandmap_mod

    real = bandmap_mod.map_dfg

    def boom(*a, **kw):
        if kw.get("cancel") is not None and kw.get("backend",
                                                   "portfolio") \
                == "portfolio":
            raise RuntimeError("portfolio died")
        return real(*a, **kw)

    monkeypatch.setattr(bandmap_mod, "map_dfg", boom)
    r = bandmap_mod.map_dfg(make_cnkm(2, 6), CGRA, mode="busmap",
                            backend="race")
    assert r.ok
    assert r.backend == "race:exact"
    assert r.optimal


def test_both_sides_crashed_raises(monkeypatch):
    import repro.core.bandmap as bandmap_mod
    import repro.exact.race as race_mod

    def boom(*a, **kw):
        raise RuntimeError("dead")

    monkeypatch.setattr(race_mod, "exact_map_dfg", boom)
    monkeypatch.setattr(bandmap_mod, "map_dfg", boom)
    from repro.exact import race_map_dfg
    with pytest.raises(RuntimeError):
        race_map_dfg(make_cnkm(2, 6), CGRA, mode="busmap")


def test_traced_race_bounds_loser_iterations_after_cancel():
    """The traced race records the cancel-request -> loser-exit latency
    and the loser's iterations after the cancel; the poll-at-top
    contract bounds the latter at <= 1 on the real engine.  Forced
    winner: certification off on an infeasible instance means only the
    prover can be sound, so the portfolio is always the loser."""
    from repro.obs import Tracer

    tr = Tracer()
    r = map_dfg(make_cnkm(5, 5), CGRA, mode="busmap", max_ii=2,
                backend="race", certify=False, seed=7, tracer=tr)
    assert r.backend == "race:exact" and not r.ok
    (race_rec,) = [s for s in tr.finished if s.name == "race"]
    assert race_rec.attrs["winner"] == "exact"
    assert race_rec.attrs["loser"] == "portfolio"
    assert race_rec.attrs["cancel_latency_s"] >= 0.0
    assert race_rec.attrs["loser_iters_after_cancel"] <= 1
    sides = {s.attrs["side"]: s for s in tr.finished
             if s.name == "race-side"}
    assert set(sides) == {"exact", "portfolio"}
    assert sides["exact"].attrs["ok"] is False
    # Both sides ran nested engine pipelines on the shared tracer.
    names = {s.name for s in tr.finished}
    assert "exact-csp" in names and "conflict-build" in names
