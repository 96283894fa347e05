"""The benchmark's frozen generators build the same graphs as the
program's generators, at every parameter the cells (and the served-path
cell kept for later) use."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]

from benchkit import dfggen  # noqa: E402
from repro.core import workloads  # noqa: E402
from repro.core.kernels_cnkm import (  # noqa: E402
    EXTRA_KERNELS, PAPER_KERNELS, make_cnkm)


def frozen_form(g):
    return ([(i, op.kind, op.name, op.latency, op.clone_of)
             for i, op in g.ops.items()], list(g.edges), g.next_id)


def program_form(d):
    return ([(i, op.kind.value, op.name, op.latency, op.clone_of)
             for i, op in d.ops.items()],
            [(e.src, e.dst, e.distance) for e in d.edges], d._next_id)


@pytest.mark.parametrize("n,m", PAPER_KERNELS + EXTRA_KERNELS)
def test_cnkm(n, m):
    assert frozen_form(dfggen.make_cnkm(n, m)) == \
        program_form(make_cnkm(n, m))


def test_loop_tail_kernels():
    """The 4x4 loop kernels of seeds 100-299 (n_carries = seed mod 3)."""
    for s in range(100, 300):
        p = dict(n_chains=2, chain_len=4, n_inputs=3, n_outputs=2,
                 n_carries=s % 3, max_distance=2, seed=s)
        assert frozen_form(dfggen.build("loop", p)) == \
            program_form(workloads.make_loop_kernel(**p)), s


@pytest.mark.parametrize("scale", ["4x4", "8x8", "16x16"])
def test_serve_catalog(scale):
    frozen = dfggen.serve_catalog(scale)
    prog = workloads.serve_catalog(scale)
    assert [(n, f, p) for n, f, p in frozen] == \
        [(s.name, s.family, s.params) for s in prog]
    for (_, fam, params), spec in zip(frozen, prog):
        assert frozen_form(dfggen.build(fam, params)) == \
            program_form(spec.build())


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_permute(seed):
    p = dict(n_chains=2, chain_len=4, n_inputs=3, n_outputs=2,
             n_carries=1, max_distance=2, seed=124)
    for g, d in [(dfggen.make_cnkm(5, 5), make_cnkm(5, 5)),
                 (dfggen.build("loop", p), workloads.make_loop_kernel(**p))]:
        assert frozen_form(dfggen.permute(g, seed=seed)) == \
            program_form(workloads.permute_dfg(d, seed=seed))


@pytest.mark.parametrize("seed", [0, 3, 2 ** 31 + 5])
def test_zipf_draws(seed):
    cat = workloads.serve_catalog("4x4")
    trace = workloads.make_request_trace(40, scale="4x4", zipf_s=1.1,
                                         seed=seed, permute=False)
    draws = dfggen.zipf_draws(len(cat), 40, s=1.1,
                              rng=np.random.default_rng(seed))
    assert [cat[k].name for k in draws] == [t.name for t in trace]
