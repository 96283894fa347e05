"""Accelerator-resident SBTS portfolio: K lock-step tabu trajectories
as one jitted jax program over packed adjacency rows.

`DeviceSBTS` is the ``engine="device"`` counterpart of
`mis.PortfolioSBTS` (which stays the oracle — see
`differential_vs_numpy` and tests/test_mis_device.py).  The numpy
engine advances ~20 seeds per core under the GIL; here the whole
``[K, n]`` state lives on the accelerator and a single compiled chunk
advances every trajectory:

- **Conflict-count evaluation** runs on packed uint32 adjacency rows
  (`BitsetGraph.rows_u32`) through the `kernels.sbts_step` Pallas
  kernel: one AND+popcount contraction yields |N(v) ∩ S_k| for every
  (trajectory, vertex) pair.  On a TPU the kernel runs compiled; on
  the CPU backend (the test suite) it runs in Pallas interpret mode.
  One jitted chunk program is built per engine shape and shared by
  every engine of that shape (`_build_chunk`).
- **The per-seed step** (`_seed_step` below) is a pure jittable
  function of one trajectory's slice — tabu-guarded add/swap selection
  and plateau perturbation — ``vmap``ped over the K seeds; steps are
  chained with `lax.fori_loop` into chunks so host round-trips happen
  every ``chunk`` iterations, not every iteration.
- **Counter-based RNG**: every random draw derives from
  ``fold_in(fold_in(fold_in(base_key, seed_idx), it), channel)`` — a
  pure function of (seed, trajectory, iteration), replacing the numpy
  engine's stateful per-seed `np.random` streams.  Trajectories are
  therefore reproducible run-to-run and resume-safe: advancing 30+34
  iterations equals advancing 64 (asserted in the tests).

Step semantics (one lock-step iteration, all seeds)
---------------------------------------------------
With ``conf[v] = |N(v) ∩ S|``:

1. *Add phase* (taken whenever any vertex is addable: ``conf == 0``,
   not selected, not tabu).  All "safe" addables (no addable
   neighbour at all) enter at once; the remaining clustered addables
   enter via a degree-aware Luby round — each samples itself with
   probability 1/(1+addable-degree) and the sampled vertices with no
   sampled neighbour enter together (provably independent: a safe
   vertex has no addable neighbour, a winner no sampled one, and
   every addable has ``conf == 0`` against S).  If both sets come up
   empty, the top-priority clustered addable enters alone, so an add
   phase always makes progress.
2. *Swap phase* (no addable vertex): the top-priority vertex with
   ``conf == 1`` and an expired tabu replaces its unique selected
   neighbour, which becomes tabu for ``tenure + U{0..3}`` iterations.
3. *Plateau perturbation*: a trajectory whose best has not improved
   for ``thresh`` iterations evicts a random ~10% slice of its
   selection (tabu'd on the way out) and re-draws ``thresh``.

`map_dfg(engine="device")` harvests the top-scoring device seeds into
the same dedupe → repair → validate loop the numpy engine feeds, under
a "portfolio-device" span (`repro.obs.PHASES`).
"""

from __future__ import annotations

import functools

import numpy as np

from .bitset import BitsetGraph

_LANE = 128          # pad n to a multiple of this (fewer jit shapes,
#                    # device-lane friendly); always a multiple of 32.
_PERTURB_FRAC = 0.1  # eviction probability per member on a plateau


def _pad_n(n: int) -> int:
    return max(_LANE, -(-n // _LANE) * _LANE)


@functools.lru_cache(maxsize=32)
def _build_chunk(n_pad: int, k: int, tenure: int, block_n: int,
                 block_k: int, interpret: bool):
    """The jitted chunk advancer for one engine shape,
    ``(rows32, state, base_key, n, it0, n_steps) -> state``.  ``state``
    is the tuple (in_s, tabu, stall, thresh, best, best_size) of device
    arrays; the RNG key, the live vertex count ``n`` and the step count
    are traced arguments, so every `DeviceSBTS` of one
    (n_pad, K, tenure, block sizes, mode) shares one compiled program
    whatever its seed — `map_dfg` builds a fresh engine, with a fresh
    seed, for every (II, jitter) attempt."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.sbts_step.kernel import selection_counts_pallas

    w = n_pad // 32
    bit_w = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))

    def pack(bits):
        """bool [K, n_pad] -> packed uint32 [K, W] (little-endian)."""
        return (bits.reshape(k, w, 32).astype(jnp.uint32) * bit_w).sum(
            axis=-1, dtype=jnp.uint32)

    def counts(rows32, bits):
        return selection_counts_pallas(
            rows32, pack(bits), block_n=block_n, block_k=block_k,
            interpret=interpret)

    def unpack_row(words):
        """uint32 [W] -> bool [n_pad]."""
        return ((words[:, None] >> jnp.arange(32, dtype=jnp.uint32))
                & jnp.uint32(1)).astype(bool).reshape(n_pad)

    def draws(base_key, it):
        """Counter-based per-(seed, iteration) randomness."""
        def one(sid):
            kit = jax.random.fold_in(
                jax.random.fold_in(base_key, sid), it)
            r1 = jax.random.uniform(jax.random.fold_in(kit, 0), (n_pad,))
            r2 = jax.random.uniform(jax.random.fold_in(kit, 1), (n_pad,))
            j4 = jax.random.randint(jax.random.fold_in(kit, 2), (), 0, 4)
            dth = jax.random.randint(
                jax.random.fold_in(kit, 3), (), 0, 24)
            return r1, r2, j4, dth
        return jax.vmap(one)(jnp.arange(k))

    def _seed_step(rows32, valid, it, in_s, tabu, stall, thresh, best,
                   best_size, conf, aconf, samp, sconf, r1, r2, j4,
                   dth):
        """One trajectory's add/swap/perturb update (vmapped over K)."""
        addable = valid & ~in_s & (conf == 0) & (tabu <= it)
        any_add = addable.any()
        # ---- add phase: safe set + Luby winners (+ forced fallback)
        safe = addable & (aconf == 0)
        winners = samp & (sconf == 0)
        clustered = addable & ~safe
        v_add = jnp.argmax(jnp.where(clustered, r1, -1.0))
        force = clustered.any() & ~safe.any() & ~winners.any()
        add_mask = safe | winners
        add_mask = add_mask.at[v_add].set(add_mask[v_add] | force)
        in_s_add = in_s | add_mask
        # ---- swap phase: conf==1 vertex in, its unique neighbour out
        swapable = valid & ~in_s & (conf == 1) & (tabu <= it)
        r_swap = jnp.where(swapable, r1, -1.0)
        v_swap = jnp.argmax(r_swap)
        has_swap = r_swap[v_swap] > 0.0
        row_v = unpack_row(rows32[v_swap])
        u_out = jnp.argmax(row_v & in_s)
        in_s_swap = jnp.where(
            has_swap, in_s.at[u_out].set(False).at[v_swap].set(True),
            in_s)
        tabu_swap = jnp.where(
            has_swap, tabu.at[u_out].set(it + tenure + j4), tabu)
        stall_swap = stall + jnp.where(has_swap, 1, 3)
        # ---- pick the phase, update the best
        in_s2 = jnp.where(any_add, in_s_add, in_s_swap)
        tabu2 = jnp.where(any_add, tabu, tabu_swap)
        stall2 = jnp.where(any_add, stall, stall_swap)
        size2 = in_s2.sum()
        better = size2 > best_size
        best2 = jnp.where(better, in_s2, best)
        bsz2 = jnp.maximum(best_size, size2)
        stall3 = jnp.where(better, 0, stall2)
        # ---- plateau perturbation
        pert = stall3 >= thresh
        evict = in_s2 & (r2 < _PERTURB_FRAC)
        evict = evict.at[jnp.argmax(jnp.where(in_s2, r2, -1.0))].set(
            in_s2.any())
        in_s3 = jnp.where(pert, in_s2 & ~evict, in_s2)
        tabu3 = jnp.where(pert,
                          jnp.where(evict, it + tenure + j4, tabu2),
                          tabu2)
        stall4 = jnp.where(pert, 0, stall3)
        thresh2 = jnp.where(pert, 60 + dth, thresh)
        return in_s3, tabu3, stall4, thresh2, best2, bsz2

    vstep = jax.vmap(
        _seed_step,
        in_axes=(None, None, None) + (0,) * 14)

    def lockstep(rows32, valid, base_key, state, it):
        in_s, tabu, stall, thresh, best, best_size = state
        r1, r2, j4, dth = draws(base_key, it)
        conf = counts(rows32, in_s)
        addable = valid[None] & ~in_s & (conf == 0) & (tabu <= it)
        aconf = counts(rows32, addable)
        samp = addable & (aconf > 0) \
            & (r1 < 1.0 / (1.0 + aconf.astype(jnp.float32)))
        sconf = counts(rows32, samp)
        return vstep(rows32, valid, it, in_s, tabu, stall, thresh, best,
                     best_size, conf, aconf, samp, sconf, r1, r2, j4,
                     dth)

    @jax.jit
    def chunk(rows32, state, base_key, n, it0, n_steps):
        valid = jnp.arange(n_pad) < n

        def body(i, st):
            return lockstep(rows32, valid, base_key, st, it0 + i)
        return jax.lax.fori_loop(0, n_steps, body, state)

    return chunk


class DeviceSBTS:
    """Device-resident drop-in for the `PortfolioSBTS` harvest-loop
    surface: ``run`` / ``best`` / ``best_size`` / ``it`` / ``rearm`` /
    ``reset_seed``.  ``interpret=None`` picks the mode from the backend
    (`repro.kernels.interpret_mode`: interpret on CPU, compiled Pallas
    on an accelerator); ``self.interpret`` records the mode that runs,
    and `map_dfg` copies it onto its "portfolio-device" spans.
    ``inits`` entries must be independent sets (e.g.
    `conflict.constructive_init` results); ``None`` entries and the
    seeds beyond ``len(inits)`` start cold — the add phase doubles as
    a randomized greedy construction, so cold seeds are cheap."""

    def __init__(self, g: BitsetGraph, inits=None, *, k: int = 1024,
                 tenure: int = 7, seed: int = 0,
                 interpret: bool | None = None, chunk: int = 64,
                 block_n: int = 1024, block_k: int = 8):
        if interpret is None:
            from repro.kernels import interpret_mode
            interpret = interpret_mode()
        self.interpret = bool(interpret)
        self.g = g
        n = g.n
        self.k = int(max(k, len(inits) if inits else 0))
        self.tenure = int(tenure)
        self.seed = int(seed)
        self.chunk_size = int(chunk)
        self.it = 0
        self._n_pad = _pad_n(n)
        self.in_s = np.zeros((self.k, self._n_pad), dtype=bool)
        for i, init in enumerate(inits or []):
            if init is not None:
                self.in_s[i, :n] = np.asarray(init, dtype=bool)
        self.tabu = np.zeros((self.k, self._n_pad), dtype=np.int32)
        self.stall = np.zeros(self.k, dtype=np.int32)
        self.thresh = (60 + np.arange(self.k) % 24).astype(np.int32)
        self._best = self.in_s.copy()
        self.best_size = self._best.sum(axis=1).astype(np.int32)
        if n and self.k:
            import jax
            import jax.numpy as jnp
            self._rows32 = jnp.asarray(g.rows_u32(self._n_pad))
            self._key = jax.random.PRNGKey(self.seed)
            self._chunk = _build_chunk(
                self._n_pad, self.k, self.tenure, int(block_n),
                int(block_k), self.interpret)
        else:
            self._rows32 = None
            self._chunk = None

    # ------------------------------------------------------- results
    @property
    def best(self) -> np.ndarray:
        """Per-seed best memberships ``bool [K, n]``."""
        return self._best[:, :self.g.n]

    # ----------------------------------------------------------- run
    def run(self, max_iters: int, target: int | None = None,
            cancel=None, tracer=None) -> np.ndarray:
        """Advance every trajectory up to ``max_iters`` lock-step
        iterations; early-exit (at chunk granularity) once any seed's
        best reaches ``target``.  ``cancel`` is polled between chunks.
        Returns per-seed best memberships ``bool [K, n]``."""
        from repro.obs.trace import live
        iters_counter = live(tracer).counter("portfolio.iters")
        if self.g.n == 0 or self.k == 0:
            return self.best
        if target is not None and (self.best_size >= target).any():
            return self.best
        import jax.numpy as jnp
        state = tuple(jnp.asarray(a) for a in (
            self.in_s, self.tabu, self.stall, self.thresh, self._best,
            self.best_size))
        done = 0
        while done < max_iters:
            if cancel is not None and cancel.is_set():
                break
            n_steps = min(self.chunk_size, max_iters - done)
            state = self._chunk(self._rows32, state, self._key,
                                self.g.n, self.it, n_steps)
            self.it += n_steps
            done += n_steps
            iters_counter.inc(n_steps)
            best_size = np.asarray(state[5])
            if target is not None and (best_size >= target).any():
                break
        # np.array (copy), not np.asarray: a zero-copy view of a jax
        # buffer is read-only, and rearm/reset_seed write this state.
        (self.in_s, self.tabu, self.stall, self.thresh, self._best,
         self.best_size) = (np.array(a) for a in state)
        return self.best

    # ------------------------------------------- harvest re-seeding
    def _rng(self, k: int) -> np.random.Generator:
        """Counter-based host RNG: a pure function of
        (seed, trajectory, iteration) — resume-safe like the device
        streams."""
        return np.random.default_rng((self.seed, k, self.it))

    def rearm(self, k: int, frac: float = 0.25) -> None:
        """Diversify seed ``k`` from its harvested best: evict a
        random slice, tabu it out, reset the best tracking (mirrors
        `PortfolioSBTS.rearm`)."""
        self.in_s[k] = self._best[k]
        members = np.flatnonzero(self.in_s[k])
        if members.size:
            rng = self._rng(k)
            evict = rng.choice(
                members, size=max(1, int(members.size * frac)),
                replace=False)
            self.in_s[k, evict] = False
            self.tabu[k, evict] = self.it + 3 * self.tenure + \
                rng.integers(0, 10)
        self._resync(k)

    def reset_seed(self, k: int, init: np.ndarray | None = None) -> None:
        """Fully restart trajectory ``k`` from ``init`` (or cold)."""
        self.in_s[k] = False
        if init is not None:
            self.in_s[k, :self.g.n] = np.asarray(init, dtype=bool)
        self.tabu[k] = 0
        self._resync(k)

    def _resync(self, k: int) -> None:
        self.stall[k] = 0
        self._best[k] = self.in_s[k]
        self.best_size[k] = int(self.in_s[k].sum())


def differential_vs_numpy(g: BitsetGraph, *, inits=None, iters: int = 512,
                          k: int = 8, seed: int = 0,
                          target: int | None = None) -> dict:
    """The device-vs-oracle harness: run `DeviceSBTS` and
    `mis.PortfolioSBTS` on the same graph at equal seed count and equal
    lock-step iteration budget, and check the shared invariants —
    every best an independent set on both engines, device coverage >=
    numpy coverage.  Returns the measured dict (tests and
    `benchmarks.bench_mis` both consume it)."""
    from .mis import PortfolioSBTS

    if inits is None:
        inits = [None] * k
    dev = DeviceSBTS(g, inits, k=k, seed=seed)
    ref = PortfolioSBTS(g, list(inits), seed=seed)
    dev_best = dev.run(iters, target=target)
    ref_best = ref.run(iters, target=target)
    dev_ok = all(not g.any_conflict(_pack(row)) for row in dev_best)
    ref_ok = all(not g.any_conflict(_pack(row)) for row in ref_best)
    return dict(
        n=g.n, k=k, iters=iters,
        device_cov=int(dev.best_size.max()) if dev.k else 0,
        numpy_cov=int(ref.best_size.max()) if ref.k else 0,
        device_independent=dev_ok, numpy_independent=ref_ok)


def _pack(row: np.ndarray) -> np.ndarray:
    from .bitset import pack_bool
    return pack_bool(row)
