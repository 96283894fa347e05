"""The program's spans on the profiler's clock: under `jax.profiler`
every span of a traced `map_dfg` is a host event of the same name, and
it starts where `harness.attach_spans` puts it by offset arithmetic."""

import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from benchkit import harness, program, reference, traffic, xtrace  # noqa: E402,E501


def test_program_spans_are_host_events_where_attach_spans_puts_them(
        tmp_path):
    import jax
    from jax.profiler import ProfileData
    config = json.load(open(os.path.join(BENCH, "configs",
                                         "cnkm-4x4-bus.json")))
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      "paper_table.json")))
    # A loop kernel the device engine (interpret mode here) repairs.
    req = next(r for r in traffic.distinct(mix, "bandmap")
               if r.name == "loop4x4s128")
    req = dataclasses.replace(req, index=0)
    cgra = program.program_fabric(reference.Fabric(**config["fabric"]))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        rec = harness.send(req, cgra, dict(config["options"]), True)
    finally:
        jax.profiler.stop_trace()
    assert rec.answered
    names = {sp.name for sp in rec.spans}
    assert {"map-dfg", "certify", "portfolio-device", "repair",
            "validate"} <= names

    path = xtrace.latest_xplane(str(tmp_path))
    tr = xtrace.read_trace(path)
    harness.attach_spans(tr, [rec])
    placed: dict = {}
    for n, a, _ in tr.host:
        if n.startswith(f"{rec.name} "):
            placed.setdefault(n[len(rec.name) + 1:], []).append(a)
    recorded: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        recorded.setdefault(e.name, []).append(
                            e.start_ns * 1e-9)
    assert set(recorded) == names
    for name in names:
        a, b = sorted(placed[name]), sorted(recorded[name])
        assert len(a) == len(b), name
        assert max(abs(x - y) for x, y in zip(a, b)) < 1e-3, name
