"""One client in a closed loop through `map_dfg`: the next request is
sent when the previous verdict is back, in whole cycles of the mix, and
no cycle starts after the window's nominal length.  Whole cycles keep
the window's work the same from run to run, and a faster program never
reads slower for letting one more long request into the window.

Reads the cycle mix of `benchkit.traffic` (``cycle``, ``order``)."""

import time

from benchkit import harness, traffic


def check(mix):
    traffic.check_cycle(mix)


def distinct(mix, default_mode):
    return traffic.distinct(mix, default_mode)


def window(mix, seed, seconds, cgra, options, traced):
    stream = traffic.requests(mix, seed, options.get("mode", "bandmap"))
    cycle = len(mix["cycle"])
    records = []
    t0 = time.perf_counter()
    for req in stream:
        if req.index % cycle == 0 and time.perf_counter() - t0 >= seconds:
            break
        records.append(harness.send(req, cgra, options, traced))
    return t0, records
