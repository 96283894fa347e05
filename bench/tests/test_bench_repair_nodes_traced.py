"""The reader of ``repair.nodes`` in a traced cell run whose kernel still
repairs with the conflict graph's LRF-pressure edges: loop4x4s71 on the
numpy engine makes the same repair tries in every map."""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from benchkit import harness  # noqa: E402

sys.path.insert(0, HERE)
from checkout import make_checkout, without_chip  # noqa: E402

NAME = "repair_nodes_per_request"


def test_reader_reports_for_a_kernel_that_still_repairs(tmp_path, capsys,
                                                        monkeypatch):
    root = make_checkout(tmp_path, [{
        "name": "loop4x4s71", "family": "loop",
        "params": {"n_chains": 2, "chain_len": 4, "n_inputs": 3,
                   "n_outputs": 2, "n_carries": 2, "max_distance": 2,
                   "seed": 71},
        "mode": "bandmap", "expect": "binding"}])
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    for m in spec["per_layer"]:
        if m["name"] == NAME:
            m["workloads"] = m["workloads"] + ["tiny.mix"]
    with open(path, "w") as f:
        json.dump(spec, f)
    without_chip(monkeypatch, root)
    rc = harness.run_cell(root, "tiny.mix", 2 ** 31 + 13, 0.2, True,
                          t_process=time.perf_counter())
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    got = res["metrics"][NAME]
    assert got["unit"] == "nodes"
    assert got["value"] > 0 and got["value"] == int(got["value"])
