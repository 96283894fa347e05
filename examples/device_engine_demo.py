"""Accelerator-resident portfolio quickstart: `MapOptions` with
``engine="device"``.

The device engine (`repro.core.mis_device.DeviceSBTS`) runs the SBTS
local search as ONE vmapped Pallas kernel step over K independent
trajectories in lock step — counter-based RNG (`jax.random.fold_in`
streams keyed on (seed, trajectory, iteration)), so runs are
bit-reproducible and resume-safe.  On a TPU the kernel runs compiled
(`python chip_smoke.py` checks that path at full size); on the CPU it
runs in Pallas interpret mode.  `map_dfg` keeps the harvest loop
(dedupe -> repair -> validate) on the host — only the MIS search moves
on-device — so golden (II, routing-PE) results are unchanged.

  PYTHONPATH=src python examples/device_engine_demo.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import (MapOptions, PortfolioOptions,     # noqa: E402
                        make_cnkm, map_dfg)
from repro.core.cgra import CGRAConfig                    # noqa: E402
from repro.core.conflict import build_conflict_graph      # noqa: E402
from repro.core.mis_device import (DeviceSBTS,            # noqa: E402
                                   differential_vs_numpy)
from repro.core.schedule import schedule_dfg              # noqa: E402
from repro.compile_cache import enable_compile_cache      # noqa: E402

enable_compile_cache()

cgra = CGRAConfig()
dfg = make_cnkm(2, 6)

# --- end to end: the consolidated options object selects the engine ---
opts = MapOptions(
    mode="bandmap",
    portfolio=PortfolioOptions(engine="device", device_seeds=64,
                               iters=4000))
t0 = time.perf_counter()
res = map_dfg(dfg, cgra, opts)
print(f"map_dfg(engine=device): {res.summary()}")
print(f"  II={res.ii} (MII={res.mii}), routing PEs={res.n_routing_pes}, "
      f"wall={time.perf_counter() - t0:.2f}s")

# The same mapping through the numpy engine — identical (II, routing):
base = map_dfg(dfg, cgra, opts.replace(engine="numpy"))
print(f"map_dfg(engine=numpy) : II={base.ii}, "
      f"routing PEs={base.n_routing_pes}")
assert (res.ii, res.n_routing_pes) == (base.ii, base.n_routing_pes)

# --- engine level: differential harness against the numpy oracle -----
sched = schedule_dfg(dfg, cgra, ii=res.ii, max_ii=res.ii)
cg = build_conflict_graph(sched, cgra)
diff = differential_vs_numpy(cg.bits, iters=256, k=4, seed=0,
                             target=len(sched.dfg.ops))
print(f"\ndifferential on |V_C|={diff['n']} (k={diff['k']}, "
      f"iters={diff['iters']}):")
print(f"  device coverage {diff['device_cov']} vs "
      f"numpy {diff['numpy_cov']} "
      f"(independent sets: device={diff['device_independent']}, "
      f"numpy={diff['numpy_independent']})")

# --- reproducibility: counter RNG makes resume bit-identical ---------
split = DeviceSBTS(cg.bits, k=8, seed=7)
whole = DeviceSBTS(cg.bits, k=8, seed=7)
split.run(32)
split.run(64)
whole.run(96)
same = (split.best == whole.best).all() and \
    (split.in_s == whole.in_s).all()
print(f"\nrun(32)+run(64) == run(96) bit-identical: {same}")
print(f"best coverage per seed: {sorted(split.best_size.tolist())}")
