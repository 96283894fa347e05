"""Repo-invariant AST linter: every rule fires on a seeded violation,
stays quiet on the compliant twin, and the shipped tree is clean.

Each fixture is a minimal source string linted under a synthetic
repo-relative path (the rules are path-scoped: e.g. only canonical-path
modules may not read wall clocks, only the engine modules may build
``MappingResult(ok=True)``).
"""

from __future__ import annotations

import pytest

from repro.analysis.astlint import (RULE_NAMES, lint_paths, lint_source,
                                    main)

# (name, expected rule, source, synthetic rel path) — one violation each.
VIOLATIONS = [
    ("ok-constructor", "mapping-result-ok", """
def f(sched):
    return MappingResult(ok=True, mode="bandmap")
""", "src/repro/serve/rogue.py"),
    ("ok-in-exact-backend", "mapping-result-ok", """
def f(sched):
    return MappingResult(ok=True, mode="bandmap", backend="exact")
""", "src/repro/exact/backend.py"),
    ("ok-replace", "mapping-result-ok", """
import dataclasses
def f(res):
    return dataclasses.replace(res, ok=True)
""", "src/repro/comap/rogue.py"),
    ("cancel-param-unread", "cancel-poll", """
def run(self, max_iters, cancel=None):
    for _ in range(max_iters):
        pass
""", "src/repro/core/mis.py"),
    ("while-true-no-poll", "cancel-poll", """
def spin(cancel):
    if cancel.is_set():
        return
    while True:
        step()
""", "src/repro/exact/backend.py"),
    ("stale-fingerprint", "serial-version-pin", """
class MappingResult:
    ok: bool
    extra_field: int
    SERIAL_VERSION = 2
""", "src/repro/core/bandmap.py"),
    ("unlocked-mutation", "lock-guarded-state", """
class S:
    _lock_guarded = ("_hits",)
    def __init__(self):
        self._hits = 0
    def bump(self):
        self._hits += 1
    def good(self):
        with self._lock:
            self._hits += 1
""", "src/repro/serve/service.py"),
    ("wallclock-aliased", "no-wallclock-canonical", """
import time as _time
def canon(d):
    return _time.perf_counter()
""", "src/repro/serve/canon.py"),
    ("global-rng", "no-wallclock-canonical", """
import numpy as np
def sig(d):
    return np.random.permutation(3)
""", "src/repro/core/schedule.py"),
    ("tracer-non-none-default", "tracer-default-none", """
def map_it(dfg, tracer=NULL_TRACER):
    return run(dfg, tracer)
""", "src/repro/core/bandmap.py"),
    ("tracer-no-default", "tracer-default-none", """
def run(self, max_iters, *, tracer):
    return tracer
""", "src/repro/core/mis.py"),
    ("tracer-content-branch", "tracer-default-none", """
def f(tracer=None):
    if tracer is not None and tracer.counter_value("x") > 10:
        return early()
""", "src/repro/exact/race.py"),
    ("tracer-truthiness-branch", "tracer-default-none", """
def f(tracer=None):
    if tracer:
        tracer.count("x")
""", "src/repro/comap/comap.py"),
    ("recorder-non-none-default", "recorder-default-none", """
def map_it(dfg, record=NULL_RECORDER):
    return run(dfg, record)
""", "src/repro/core/bandmap.py"),
    ("recorder-boolop-branch", "recorder-default-none", """
def f(record=None):
    if record is not None and not res.ok:
        return record.dump()
""", "src/repro/exact/race.py"),
    ("knob-subscript", "options-single-source", """
def dispatch(req):
    return run(iters=req.options["mis_iters"])
""", "src/repro/serve/scheduler.py"),
    ("knob-dict-get", "options-single-source", """
def dispatch(opts):
    return run(mode=opts.get("mode", "bandmap"))
""", "src/repro/comap/comap.py"),
    ("knob-dict-pop", "options-single-source", """
def dispatch(opts):
    seed = opts.pop("seed", 0)
    return run(seed=seed)
""", "src/repro/exact/race.py"),
]

# Compliant twin under the SAME path scope: must produce no findings.
CLEAN = [
    ("ok-in-engine", """
def f(sched):
    return MappingResult(ok=True, mode="bandmap")
""", "src/repro/core/bandmap.py"),
    ("cancel-polled", """
def run(self, max_iters, cancel=None):
    for _ in range(max_iters):
        if cancel is not None and cancel.is_set():
            return
""", "src/repro/core/mis.py"),
    ("while-true-polls", """
def spin(cancel):
    while True:
        if cancel.is_set():
            return
        step()
""", "src/repro/exact/backend.py"),
    ("lock-held", """
class S:
    _lock_guarded = ("_hits",)
    def __init__(self):
        self._hits = 0
    def bump(self):
        with self._lock:
            self._hits += 1
""", "src/repro/serve/service.py"),
    ("wallclock-elsewhere", """
import time
def bench():
    return time.perf_counter()
""", "src/repro/benchmarks/run.py"),
    ("seeded-rng-ok", """
import numpy as np
def sig(d):
    return np.random.default_rng(0).permutation(3)
""", "src/repro/core/schedule.py"),
    ("tracer-identity-check-ok", """
def f(dfg, *, tracer=None):
    trc = live(tracer)
    if tracer is not None:
        trc.span("conflict-build")
    if tracer is None:
        return fast(dfg)
    return slow(dfg, trc)
""", "src/repro/core/conflict.py"),
    ("tracer-rule-scoped-to-engine", """
def plot(tracer):
    if tracer:
        draw(tracer.finished)
""", "src/repro/analysis/plots.py"),
    ("recorder-identity-check-ok", """
def f(dfg, *, record=None):
    rec = recording(record)
    rec.emit("attempt", ii=2)
    if record is not None:
        if not res.ok:
            return record.dump()
    return res
""", "src/repro/core/bandmap.py"),
    ("recorder-rule-scoped-to-engine", """
def replay(record):
    if record:
        draw(record.dump())
""", "src/repro/analysis/plots.py"),
    ("knob-membership-test-ok", """
def solo(req):
    eff = MapOptions.coerce(req.options)
    if "seed" not in req.options:
        eff = eff.replace(seed=7)
    return eff
""", "src/repro/serve/scheduler.py"),
    ("knob-attribute-read-ok", """
def dispatch(opts):
    return run(iters=opts.portfolio.iters, mode=opts.mode)
""", "src/repro/core/bandmap.py"),
    ("knob-nonknob-key-ok", """
def co(opts):
    raw = dict(opts)
    rounds = raw.pop("rounds", 4)
    return rounds
""", "src/repro/serve/scheduler.py"),
    ("knob-rule-scoped-to-engine", """
def plot(opts):
    return opts["mis_iters"]
""", "src/repro/analysis/plots.py"),
]


@pytest.mark.parametrize("name,rule,src,rel", VIOLATIONS,
                         ids=[v[0] for v in VIOLATIONS])
def test_seeded_violation_fires_once(name, rule, src, rel):
    findings = lint_source(src, rel)
    assert [f.rule for f in findings] == [rule], findings
    assert findings[0].path == rel
    assert findings[0].line > 0


@pytest.mark.parametrize("name,src,rel", CLEAN,
                         ids=[c[0] for c in CLEAN])
def test_compliant_twin_is_clean(name, src, rel):
    assert lint_source(src, rel) == []


def test_all_rules_covered():
    """The seeded-violation fixtures exercise every named rule."""
    assert len(RULE_NAMES) >= 8
    assert {v[1] for v in VIOLATIONS} == set(RULE_NAMES)


def test_knob_names_mirror_legacy_knobs():
    """astlint never imports the linted package, so it carries its own
    copy of the legacy knob names; the two sets must not drift."""
    from repro.analysis.astlint import _KNOB_NAMES
    from repro.core.options import LEGACY_KNOBS
    assert _KNOB_NAMES == frozenset(LEGACY_KNOBS)


def test_syntax_error_is_a_finding():
    findings = lint_source("def broken(:\n", "src/repro/core/x.py")
    assert [f.rule for f in findings] == ["syntax-error"]


def test_repo_tree_is_clean():
    """The gate CI enforces: the shipped source linted end-to-end."""
    findings, n_files = lint_paths(["src"])
    assert n_files > 50
    assert findings == [], [f"{f.path}:{f.line} {f.rule}" for f in findings]


def test_main_exit_codes(tmp_path, capsys):
    assert main(["src"]) == 0
    assert "clean" in capsys.readouterr().out

    rogue = tmp_path / "repro" / "serve" / "rogue.py"
    rogue.parent.mkdir(parents=True)
    rogue.write_text("def f():\n    return MappingResult(ok=True)\n")
    assert main([str(tmp_path)]) == 1
    assert "mapping-result-ok" in capsys.readouterr().out
