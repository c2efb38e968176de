"""Continuous-batching projection server: LargeVis ``transform`` as a
serving loop.

The LM serving driver (``launch/serve.py``) holds a fixed-slot batch and
steps every active sequence in lockstep — admit into freed slots, decode
all slots at once, retire finished sequences.  Projection serving is the
same shape with "decode" replaced by the fused frozen-corpus edge step:

* **prefill** — a queued query gets its corpus neighborhood (one batched
  ``ops.topk_sqdist`` over the whole admit block), its perplexity-
  calibrated neighbor distribution, and its weighted-mean init spliced
  into a free slot row of the resident ``[corpus; slots]`` embedding.
* **decode** — ONE ``layout_engine.apply_edge_batch`` dispatch moves all
  slots: each slot contributes one positive edge (slot -> neighbor ∝ its
  own p) plus M negatives from the fitted noise sampler, with a
  **per-slot learning rate** at the slot's own schedule position (the
  (B,) lr form of the fused kernel) — freshly admitted and nearly-done
  queries share the same lockstep dispatch.  Corpus rows are frozen by
  the kernel's ``n_frozen`` masking, so the fitted embedding stays
  bit-identical no matter how much traffic flows through.
* **retire** — a slot that has taken ``steps`` updates completes its
  request with the slot row's coordinates and frees the slot.

Inactive slots loop their positive edge back onto themselves with all
negatives masked — an exactly-zero gradient — so the step shape never
depends on occupancy and the engine compiles twice (prefill + step),
total, regardless of traffic.

``benchmarks/serve_latency.py`` drives this engine at 1k-100k concurrent
requests and reports queries/sec and p50/p99 latency.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.largevis_default import LargeVisConfig
from repro.core import perplexity as perp_lib
from repro.core.layout_engine import apply_edge_batch
from repro.core.transform import sample_query_edges, uniform_node_sampler
from repro.runtime.fault_tolerance import InjectedFault


class QueueFullError(RuntimeError):
    """Admission backpressure: ``submit`` refused because the engine's
    queue is at ``max_queue``.  The caller sheds load or retries later —
    unbounded queueing would instead grow latency without bound."""


@dataclasses.dataclass
class ProjectRequest:
    rid: int
    x: np.ndarray                      # (d,) query point
    y: Optional[np.ndarray] = None     # (s,) result, set at retire
    t_submit: float = 0.0
    t_done: float = 0.0
    done: bool = False
    error: Optional[str] = None        # set when quarantined/retired-on-error

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit


@functools.partial(jax.jit, static_argnames=("k", "perplexity", "iters"))
def _prefill_block(xq, x, y, *, k: int, perplexity: float, iters: int):
    """Neighborhoods + init coords for one admit block (A, d).

    Returns (nn_idx (A, k), p_log (A, k), y0 (A, s))."""
    from repro.kernels import ops
    nn_idx, nn_dist = ops.topk_sqdist(xq, x, k)
    p = perp_lib.calibrate_p(nn_dist, perplexity, iters=iters)
    return nn_idx, jnp.log(p), jnp.einsum("qk,qks->qs", p, y[nn_idx])


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("n_negatives", "steps", "rho0",
                                    "prob_fn", "a", "gamma", "clip",
                                    "layout_step"))
def _lockstep_step(y_full, key, p_log, nn_idx, ages, active, neg_sampler, *,
                   n_negatives: int, steps: int, rho0: float, prob_fn: str,
                   a: float, gamma: float, clip: float, layout_step: str):
    """One lockstep transform step over all S slots (active or not).

    Slot s sits at schedule position ages[s]/steps -> its own lr (the
    fused kernel's per-edge (B,) lr mode).  Inactive slots are no-ops:
    positive edge looped onto the slot itself (zero attractive force)
    and negatives masked out.  ``y_full`` is donated — one resident
    (N+S, s) buffer across the engine's whole lifetime."""
    n_frozen = y_full.shape[0] - p_log.shape[0]
    s = p_log.shape[0]
    i = n_frozen + jnp.arange(s, dtype=jnp.int32)
    j, negs, neg_mask = sample_query_edges(
        key, p_log, nn_idx, neg_sampler, n_negatives)
    j = jnp.where(active, j, i)
    neg_mask = neg_mask * active[:, None].astype(jnp.float32)
    t_frac = ages.astype(jnp.float32) / steps
    lr = rho0 * jnp.maximum(1.0 - t_frac, 1e-4)
    y_full = apply_edge_batch(
        y_full, i, j, negs, neg_mask, lr, prob_fn=prob_fn, a=a, gamma=gamma,
        clip=clip, layout_step=layout_step, n_frozen=n_frozen)
    return y_full, ages + active.astype(jnp.int32)


class ProjectionEngine:
    """Fixed-slot continuous-batching engine over a fitted LargeVis model.

    ``model`` is anything with the fitted-carrier fields — a
    :class:`repro.core.largevis.LargeVisResult` or a fitted
    :class:`repro.LargeVis` estimator's ``result_``: ``x`` (N, d) corpus,
    ``y`` (N, s) frozen layout, optional ``neg_sampler``, ``cfg``.
    """

    def __init__(self, model, *, slots: int = 256,
                 cfg: LargeVisConfig | None = None, seed: int = 0,
                 max_queue: Optional[int] = None,
                 slot_step_budget: Optional[int] = None,
                 fault=None):
        cfg = cfg or getattr(model, "cfg", None) or LargeVisConfig()
        self.cfg = cfg
        self.slots = slots
        self.x = jnp.asarray(model.x)
        self.n = int(self.x.shape[0])
        self.k = min(cfg.n_neighbors, self.n)
        self.steps = int(cfg.transform_steps)
        self.neg_sampler = (getattr(model, "neg_sampler", None)
                            or uniform_node_sampler(self.n))
        y = jnp.asarray(model.y, jnp.float32)
        self.s_dim = int(y.shape[1])
        # resident [corpus; slots] embedding — corpus rows frozen forever
        self.y_full = jnp.concatenate(
            [y, jnp.zeros((slots, self.s_dim), jnp.float32)])
        self.p_log = jnp.full((slots, self.k), -jnp.inf, jnp.float32)
        # row 0 at p=1 so categorical on an inactive slot is well-defined
        self.p_log = self.p_log.at[:, 0].set(0.0)
        self.nn_idx = jnp.zeros((slots, self.k), jnp.int32)
        self.ages = jnp.zeros((slots,), jnp.int32)
        self.active = jnp.zeros((slots,), bool)
        # host mirror of ages (deterministic: +1 per step while occupied)
        # so retire checks never force a device sync
        self._host_ages = np.zeros((slots,), np.int64)
        self.key = jax.random.key(seed)
        self.step_no = 0
        self.queue: List[ProjectRequest] = []
        self.requests: List[Optional[ProjectRequest]] = [None] * slots
        self.completed: List[ProjectRequest] = []
        # robustness (PR 8): admission backpressure, per-slot step budget
        # (a stuck slot is force-retired with an error instead of pinning
        # its slot forever), the quarantine list for rejected/poisoned
        # requests, and the deterministic fault injector for chaos tests
        self.max_queue = max_queue
        self.slot_step_budget = (slot_step_budget if slot_step_budget
                                 else 4 * self.steps)
        self.fault = fault
        self.quarantined: List[ProjectRequest] = []
        self.faults_retried = 0
        # engine step at which each slot was admitted (budget clock)
        self._slot_born = np.zeros((slots,), np.int64)

    # ------------------------------------------------------------------
    def submit(self, req: ProjectRequest) -> bool:
        """Queue a request; returns False when it was quarantined instead.

        Validation happens HERE, not in the hot loop: a query row with
        the wrong dimensionality or any NaN/Inf never enters the queue
        (it completes immediately with ``req.error`` set and lands in
        ``self.quarantined``), so faulty traffic cannot perturb the slot
        assignment, key stream, or results of healthy requests — the
        healthy subset of a poisoned workload retires bitwise-equal to a
        fault-free run (tests/test_chaos_serving.py).  Raises
        :class:`QueueFullError` at ``max_queue`` (backpressure)."""
        req.t_submit = req.t_submit or time.time()
        if self.fault is not None:
            req = self.fault.fire("submit", req)
        xq = np.asarray(req.x, np.float32).reshape(-1)
        d = int(self.x.shape[1])
        if xq.shape[0] != d:
            req.error = (f"query dim {xq.shape[0]} != corpus dim {d}")
        elif not np.all(np.isfinite(xq)):
            req.error = "query contains NaN/Inf"
        if req.error is not None:
            req.done, req.t_done = True, time.time()
            self.quarantined.append(req)
            return False
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            raise QueueFullError(
                f"queue at max_queue={self.max_queue}; retry later")
        self.queue.append(req)
        return True

    def _admit(self):
        """Fill every free slot from the queue with ONE batched prefill.

        The admit block pads to the full slot count, so the prefill
        compiles once; padded rows are discarded."""
        free = [s for s in range(self.slots) if self.requests[s] is None]
        if not free or not self.queue:
            return
        n_adm = min(len(free), len(self.queue))
        batch = [self.queue.pop(0) for _ in range(n_adm)]
        xq = np.zeros((self.slots, self.x.shape[1]), np.float32)
        for b, req in enumerate(batch):
            xq[b] = req.x
        nn_idx, p_log, y0 = _prefill_block(
            jnp.asarray(xq), self.x, self.y_full[:self.n],
            k=self.k, perplexity=float(min(self.cfg.perplexity, self.k)),
            iters=self.cfg.perplexity_iters)
        if self.fault is not None:
            nn_idx, p_log, y0 = self.fault.fire("prefill",
                                                (nn_idx, p_log, y0))
        rows = jnp.asarray(free[:n_adm], jnp.int32)
        take = jnp.arange(n_adm)
        self.nn_idx = self.nn_idx.at[rows].set(nn_idx[take])
        self.p_log = self.p_log.at[rows].set(p_log[take])
        self.y_full = self.y_full.at[self.n + rows].set(y0[take])
        self.ages = self.ages.at[rows].set(0)
        self.active = self.active.at[rows].set(True)
        for b, req in enumerate(batch):
            self.requests[free[b]] = req
            self._host_ages[free[b]] = 0
            self._slot_born[free[b]] = self.step_no

    def _retire(self):
        """Complete finished slots; quarantine poisoned or stuck ones.

        Two error paths free a slot WITHOUT returning coordinates:
        a slot whose retired row contains NaN/Inf (corruption escaped
        into the embedding) and a slot still unfinished after
        ``slot_step_budget`` engine steps (stuck — e.g. its ages stopped
        advancing after a fault).  Both complete their request with
        ``req.error`` set into ``self.quarantined``; the engine keeps
        serving every other slot."""
        done_rows, stuck_rows = [], []
        for s in range(self.slots):
            if self.requests[s] is None:
                continue
            if self._host_ages[s] >= self.steps:
                done_rows.append(s)
            elif self.step_no - self._slot_born[s] >= self.slot_step_budget:
                stuck_rows.append(s)
        all_rows = done_rows + stuck_rows
        if not all_rows:
            return
        coords = np.asarray(self.y_full[self.n + jnp.asarray(all_rows)])
        if self.fault is not None:
            coords = self.fault.fire("retire", coords)
        now = time.time()
        rows = jnp.asarray(all_rows, jnp.int32)
        self.active = self.active.at[rows].set(False)
        self.ages = self.ages.at[rows].set(0)
        for c, s in enumerate(all_rows):
            req = self.requests[s]
            req.t_done, req.done = now, True
            if s in stuck_rows:
                req.error = (f"slot {s} exceeded its step budget "
                             f"({self.slot_step_budget} engine steps) "
                             f"before finishing; force-retired")
                self.quarantined.append(req)
            elif not np.all(np.isfinite(coords[c])):
                req.error = "projection diverged: non-finite coordinates"
                self.quarantined.append(req)
            else:
                req.y = coords[c]
                self.completed.append(req)
            self.requests[s] = None

    def step(self) -> bool:
        """Admit -> one lockstep fused transform step -> retire.

        Returns False when there is nothing left to do.  The ``step``
        fault site fires BEFORE the dispatch and before any engine state
        advances, so an injected exception here is retryable with zero
        drift: ``step_no``/ages move only on success, and the retried
        step replays the identical key -> bitwise the same trajectory as
        a fault-free run (``run`` does this automatically)."""
        self._admit()
        if not any(r is not None for r in self.requests):
            return False
        if self.fault is not None:
            self.y_full = self.fault.fire("step", self.y_full)
        rho0 = self.cfg.transform_rho0 or self.cfg.rho0
        self.y_full, self.ages = _lockstep_step(
            self.y_full, jax.random.fold_in(self.key, self.step_no),
            self.p_log, self.nn_idx, self.ages, self.active,
            self.neg_sampler, n_negatives=self.cfg.n_negatives,
            steps=self.steps, rho0=float(rho0), prob_fn=self.cfg.prob_fn,
            a=self.cfg.prob_a, gamma=self.cfg.gamma,
            clip=self.cfg.grad_clip,
            layout_step=self.cfg.routing.layout_step)
        self.step_no += 1
        for s in range(self.slots):
            if self.requests[s] is not None:
                self._host_ages[s] += 1
        self._retire()
        return True

    def run(self, max_steps: int = 10_000_000) -> int:
        """Drain the queue; returns the number of engine step attempts.

        An :class:`~repro.runtime.fault_tolerance.InjectedFault` raised
        at the ``step`` site is caught and the step retried (counted in
        ``faults_retried``); retries are bitwise-transparent because no
        engine state advanced (see :meth:`step`).  Real exceptions
        propagate."""
        n = 0
        while (self.queue or any(r is not None for r in self.requests)) \
                and n < max_steps:
            try:
                progressed = self.step()
            except InjectedFault:
                self.faults_retried += 1
                n += 1
                continue
            if not progressed:
                break
            n += 1
        jax.block_until_ready(self.y_full)
        return n
