"""Alias-method samplers: O(1) weighted edge sampling + noise-distribution
negative sampling (paper §3.2, Mikolov-style P_n(j) ∝ d_j^0.75).

Sampling on device is two gathers + a compare per draw, fully batched.
Edge sampling ∝ w_ij is the paper's variance fix: sampled edges are treated
as *binary*, so divergent edge weights never enter the gradient.

Table construction comes in two implementations, selected by ``impl=``
(``LargeVisConfig.sampler_impl`` at the pipeline level):

* ``"device"`` (the ``"auto"`` default) — :func:`build_alias_device`, a
  fully-jitted construction: stable-partition the scaled probabilities
  into smalls (< 1) and larges (>= 1) with cumsum ranks (no sort — the
  pairing below only needs the two groups in *some* fixed order, so the
  build is O(E) data movement plus O(E log E) binary searches), then
  resolve Vose's two-pointer pairing with prefix sums + ``searchsorted``
  — smalls alias to the first large whose cumulative surplus covers
  their cumulative deficit, and the boundary-straddling remainders flow
  between adjacent larges through a backward alias chain, which makes
  the per-slot marginals *exact* in exact arithmetic.  The cumulative
  arithmetic runs in f64 via a trace-scoped ``enable_x64`` on CPU/GPU
  (f32 prefix sums break down around E ~ 1e5 — see ``_alias_pairing``),
  falling back to f32 on TPU.  No per-edge Python iteration, no host
  round trip: stage-1 outputs stay device-resident all the way into the
  layout step.
* ``"host"`` — :func:`build_alias`, the classic numpy Vose loop.  O(E)
  but single-core Python (minutes at the paper's E = N*K = 150M); kept as
  the test oracle and debug path.

The produced (threshold, alias) tables differ between implementations —
any table with the right per-index marginals is a valid alias table — but
both are exact, and ``tests/test_sampler.py`` pins the device builder's
marginals against the Vose oracle via threshold/alias reconstruction.

:class:`EdgeSampler` / :class:`NodeSampler` are registered JAX pytrees, so
whole samplers thread through ``jit`` / ``lax.scan`` / ``shard_map`` as
single arguments (see ``core/layout_engine.py``).

Distributed mode (:func:`build_samplers_sharded`) builds **per-shard**
tables on the same 1-D "data" mesh the KNN ring and the perplexity
stages use: each shard runs :func:`_alias_pairing` over its own rows'
edges (local alias indices — a slab sliced out of a *global* table
would carry alias pointers outside the slab and be invalid), negative
degrees are completed with one ``psum`` of O(N) scatter partials, and a
tiny (P,)-entry shard-selection alias table over per-shard total masses
makes the two-level draw exactly proportional to the global
distribution: P(shard s) * P(e | s) = (T_s / T) * (w_e / T_s) = w_e / T.
:class:`ShardedEdgeSampler` / :class:`ShardedNodeSampler` expose the
same duck-typed ``.sample`` the layout engine consumes, so they flow
through every driver unchanged; at ``n_shards == 1`` they skip the
shard draw and reproduce the flat samplers' key streams bitwise.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map

from repro.core.flat import ravel_rows, row_major


def build_alias(probs: np.ndarray):
    """Vose's alias method on host.  probs: (n,) nonnegative, any scale.
    Returns (threshold (n,) f32, alias (n,) i32).

    Pure-Python O(n) loop — the oracle the jitted device builder is tested
    against, and the ``impl="host"`` debug path."""
    p = np.asarray(probs, np.float64)
    n = p.shape[0]
    assert n > 0 and (p >= 0).all()
    s = p.sum()
    assert s > 0, "all-zero probabilities"
    scaled = p * (n / s)
    threshold = np.ones(n, np.float64)
    alias = np.arange(n, dtype=np.int32)
    small = [i for i in range(n) if scaled[i] < 1.0]
    large = [i for i in range(n) if scaled[i] >= 1.0]
    while small and large:
        s_i = small.pop()
        l_i = large.pop()
        threshold[s_i] = scaled[s_i]
        alias[s_i] = l_i
        scaled[l_i] = scaled[l_i] - (1.0 - scaled[s_i])
        (small if scaled[l_i] < 1.0 else large).append(l_i)
    for rest in (small, large):
        for i in rest:
            threshold[i] = 1.0
    return threshold.astype(np.float32), alias


def _alias_pairing(probs: jax.Array, *, hi_dtype=jnp.float32):
    """Traced alias-table construction body.  probs: (n,) nonnegative, any
    scale (all-zero input falls back to uniform).  Returns
    (threshold (n,) f32, alias (n,) i32) with exact per-index marginals.

    Construction (fully vectorized — cumsum/searchsorted/scatter, zero
    host involvement): stable-partition the scaled probabilities so the
    smalls (s < 1, deficit d = 1-s) occupy a prefix and the larges
    (s >= 1, surplus e = s-1) a suffix.  The partition is cumsum ranks,
    NOT a sort — Vose's pairing works for the two groups in any fixed
    order, since the prefix arrays below are monotone by construction.
    The pairing becomes:

    * small i aliases the first large j whose cumulative surplus SE_j
      reaches the cumulative deficit D_i (one ``searchsorted``);
    * a small straddling a surplus boundary is charged wholly to the later
      large, so larges <= j under-collect by beta_{j+1} = SE_j - D_{last
      small with D <= SE_j}; large j+1 repays exactly that by keeping only
      threshold 1 - beta_{j+1} of its own slot and aliasing the remainder
      to large j (a backward chain over the partitioned larges).

    Telescoping the chain gives every index its exact target mass; the
    final boundary term is total-surplus - total-deficit = 0, so nothing
    is lost.  Ties, zero-surplus larges, zero probabilities, and n == 1
    all degenerate correctly (clamps only guard rounding).

    ``hi_dtype`` is the cumulative-arithmetic dtype.  The prefix sums
    reach magnitude ~n with sub-1.0 increments, and beta is a
    catastrophically-cancelling difference of two such prefixes — in f32
    the per-slot *relative* marginal error passes 100% around E ~ 1e5.
    :func:`_pairing_scope` therefore runs this in f64 wherever the
    backend supports it (CPU/GPU), keeping f32 only as the TPU fallback.
    """
    p = jnp.asarray(probs, jnp.float32).reshape(-1).astype(hi_dtype)
    n = p.shape[0]
    one = jnp.asarray(1.0, hi_dtype)
    zero = jnp.zeros((), hi_dtype)
    p = jnp.maximum(p, zero)
    total = jnp.sum(p)
    p = jnp.where(total > 0, p, jnp.ones_like(p))
    total = jnp.where(total > 0, total, jnp.asarray(n, hi_dtype))
    scaled = p * (n / total)

    # stable partition, smalls first: O(n) cumsum ranks + one scatter
    is_small = scaled < one
    m = jnp.sum(is_small.astype(jnp.int32))      # partition point / first
    rank_small = jnp.cumsum(is_small.astype(jnp.int32)) - 1       # large
    rank_large = m + jnp.cumsum((~is_small).astype(jnp.int32)) - 1
    dest = jnp.where(is_small, rank_small, rank_large)
    order = jnp.zeros(n, jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32))          # partitioned -> original
    ss = scaled[order]
    pos = jnp.arange(n, dtype=jnp.int32)
    small = pos < m
    d = jnp.where(small, one - ss, zero)         # deficits  (small prefix)
    e = jnp.where(small, zero, ss - one)         # surpluses (large suffix)
    D = jnp.cumsum(d)
    SE = jnp.cumsum(e)

    # smalls -> first large whose cumulative surplus covers their deficit
    tgt = jnp.clip(jnp.searchsorted(SE, D, side="left").astype(jnp.int32),
                   m, n - 1)
    # larges: beta_j = straddling deficit owed to earlier larges, repaid by
    # this slot's alias pointing at the previous large
    prev_se = SE - e                             # SE_{j-1}
    hi = jnp.searchsorted(D, prev_se, side="right").astype(jnp.int32) - 1
    covered = jnp.where(hi >= 0, D[jnp.clip(hi, 0, n - 1)], zero)
    beta = jnp.clip(prev_se - covered, 0.0, 1.0)

    thr_sorted = jnp.where(small, ss, one - beta).astype(jnp.float32)
    alias_sorted = jnp.where(small, order[tgt],
                             order[jnp.clip(pos - 1, m, n - 1)])
    threshold = jnp.zeros(n, jnp.float32).at[order].set(thr_sorted)
    alias = jnp.zeros(n, jnp.int32).at[order].set(
        alias_sorted.astype(jnp.int32))
    return threshold, alias


_alias_jit = jax.jit(_alias_pairing, static_argnames=("hi_dtype",))


def _pairing_scope():
    """(context manager, dtype) for the pairing's cumulative arithmetic.

    CPU/GPU: a trace-scoped ``enable_x64`` so the prefix sums run in f64
    (exact marginals at any E) without requiring global x64 mode.  TPU
    has no native f64, so it keeps the f32 construction — a KNOWN
    LIMITATION: per-slot relative marginal error grows with E (~65% at
    E=1e5, >100% at E>=1e6; past E ~ 1e7 the beta cancellation loses all
    precision), so large-E TPU runs should build tables on the host CPU
    platform (``sampler_impl="host"``, or a CPU-backed device build) until
    a compensated-summation f32 pairing lands.  Builders enter this scope
    at the top level and trace entirely under it; it must not nest inside
    an outer non-x64 jit trace."""
    if jax.default_backend() == "tpu":
        return contextlib.nullcontext(), jnp.float32
    return jax.enable_x64(True), jnp.float64


def build_alias_device(probs) -> tuple:
    """One jitted device computation: probs -> (threshold f32, alias i32).
    See :func:`_alias_pairing` for the construction and dtype policy."""
    scope, hi_dtype = _pairing_scope()
    probs = jnp.asarray(probs)
    with scope:
        return _alias_jit(probs, hi_dtype=hi_dtype)


def sample_alias(key, threshold: jax.Array, alias: jax.Array, shape):
    """Batched alias draws -> int32 indices of the given shape."""
    n = threshold.shape[0]
    k1, k2 = jax.random.split(key)
    idx = jax.random.randint(k1, shape, 0, n)
    u = jax.random.uniform(k2, shape)
    return jnp.where(u < threshold[idx], idx, alias[idx]).astype(jnp.int32)


def _register_pytree(cls, data_fields, meta_fields):
    """Dataclass -> pytree with array leaves and static metadata.

    Uses register_pytree_node directly (register_dataclass signatures
    drift across the supported jax range)."""
    def flatten(obj):
        return (tuple(getattr(obj, f) for f in data_fields),
                tuple(getattr(obj, f) for f in meta_fields))

    def unflatten(meta, data):
        return cls(*data, *meta)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


@dataclasses.dataclass
class EdgeSampler:
    """Directed edge list (src, dst) with alias table over edge weights.

    A registered pytree: ``src/dst/threshold/alias`` are leaves,
    ``n_edges`` is static metadata — pass whole samplers through
    ``jit``/``scan``/``shard_map``."""
    src: jax.Array          # (E,) int32
    dst: jax.Array          # (E,) int32
    threshold: jax.Array    # (E,) f32
    alias: jax.Array        # (E,) int32
    n_edges: int

    def sample(self, key, batch: int):
        e = sample_alias(key, self.threshold, self.alias, (batch,))
        return self.src[e], self.dst[e]


@dataclasses.dataclass
class NodeSampler:
    """Noise distribution over nodes, P_n(j) ∝ deg_j^power.  A registered
    pytree (``n_nodes`` static)."""
    threshold: jax.Array
    alias: jax.Array
    n_nodes: int

    def sample(self, key, shape):
        return sample_alias(key, self.threshold, self.alias, shape)


@dataclasses.dataclass
class ShardedEdgeSampler:
    """Per-shard edge alias tables with a shard-selection table on top.

    All per-shard leaves are stacked ``(P, E_loc)``; ``alias`` entries
    are LOCAL edge indices (each shard's table is closed over its own
    edges), ``src``/``dst`` hold GLOBAL node ids.  ``shard_threshold``/
    ``shard_alias`` is a (P,)-entry alias table over per-shard total
    edge masses, so a two-level draw is exactly ∝ the global w_ij.

    Registered pytree (``n_shards``/``n_edges`` static); duck-types
    :class:`EdgeSampler` for the layout engine.  At ``n_shards == 1``
    ``sample`` delegates to the flat sampler on table row 0 — the
    identical key stream, for bitwise trajectory parity."""
    src: jax.Array              # (P, E_loc) int32, global node ids
    dst: jax.Array              # (P, E_loc) int32
    threshold: jax.Array        # (P, E_loc) f32
    alias: jax.Array            # (P, E_loc) int32, LOCAL edge indices
    shard_threshold: jax.Array  # (P,) f32
    shard_alias: jax.Array      # (P,) int32
    n_shards: int
    n_edges: int                # total real (unpadded) directed edges

    def local(self, i: int = 0) -> EdgeSampler:
        """The flat per-shard sampler from stacked-table row ``i`` —
        what a shard_map body (leaves arriving as (1, E_loc) blocks)
        uses for stratified local sampling."""
        return EdgeSampler(self.src[i], self.dst[i], self.threshold[i],
                           self.alias[i], int(self.src.shape[1]))

    def sample(self, key, batch: int):
        if self.n_shards == 1:
            return self.local().sample(key, batch)
        k0, k1 = jax.random.split(key)
        s = sample_alias(k0, self.shard_threshold, self.shard_alias,
                         (batch,))
        e_loc = self.threshold.shape[1]
        k1a, k1b = jax.random.split(k1)
        idx = jax.random.randint(k1a, (batch,), 0, e_loc)
        u = jax.random.uniform(k1b, (batch,))
        e = jnp.where(u < self.threshold[s, idx], idx, self.alias[s, idx])
        return self.src[s, e], self.dst[s, e]


@dataclasses.dataclass
class ShardedNodeSampler:
    """Per-shard noise distribution P_n(j) ∝ deg_j^power over the
    contiguous-block row layout: local node ``l`` on shard ``s`` is
    global node ``s * n_loc + l`` (``runtime/sharding.py``).  Padded
    rows carry exactly-zero mass, so padded ids are never drawn."""
    threshold: jax.Array        # (P, n_loc) f32
    alias: jax.Array            # (P, n_loc) int32, LOCAL node indices
    shard_threshold: jax.Array  # (P,) f32
    shard_alias: jax.Array      # (P,) int32
    n_shards: int
    n_nodes: int                # real (unpadded) node count

    def sample(self, key, shape):
        if self.n_shards == 1:
            return sample_alias(key, self.threshold[0], self.alias[0],
                                shape)
        k0, k1 = jax.random.split(key)
        s = sample_alias(k0, self.shard_threshold, self.shard_alias, shape)
        n_loc = self.threshold.shape[1]
        k1a, k1b = jax.random.split(k1)
        idx = jax.random.randint(k1a, shape, 0, n_loc)
        u = jax.random.uniform(k1b, shape)
        l = jnp.where(u < self.threshold[s, idx], idx, self.alias[s, idx])
        return (s * n_loc + l).astype(jnp.int32)


_register_pytree(EdgeSampler, ("src", "dst", "threshold", "alias"),
                 ("n_edges",))
_register_pytree(NodeSampler, ("threshold", "alias"), ("n_nodes",))
_register_pytree(ShardedEdgeSampler,
                 ("src", "dst", "threshold", "alias", "shard_threshold",
                  "shard_alias"), ("n_shards", "n_edges"))
_register_pytree(ShardedNodeSampler,
                 ("threshold", "alias", "shard_threshold", "shard_alias"),
                 ("n_shards", "n_nodes"))


def _resolve_impl(impl: str) -> str:
    if impl not in ("auto", "device", "host"):
        raise ValueError(f"sampler impl must be auto|device|host: {impl!r}")
    return "device" if impl == "auto" else impl


@functools.partial(jax.jit, static_argnames=("hi_dtype",))
def _build_edge_sampler_device(knn_idx, weights, *,
                               hi_dtype=jnp.float32) -> EdgeSampler:
    N, K = knn_idx.shape
    src = row_major(N, K)[0]
    dst = ravel_rows(knn_idx).astype(jnp.int32)
    thr, alias = _alias_pairing(ravel_rows(weights), hi_dtype=hi_dtype)
    return EdgeSampler(src, dst, thr, alias, N * K)


@functools.partial(jax.jit, static_argnames=("power", "hi_dtype"))
def _build_negative_sampler_device(knn_idx, weights, *, power: float,
                                   hi_dtype=jnp.float32) -> NodeSampler:
    N, _ = knn_idx.shape
    w = jnp.maximum(weights.astype(jnp.float32), 0.0)
    deg = jnp.sum(w, axis=1)                              # out-degree
    deg = deg.at[ravel_rows(knn_idx)].add(ravel_rows(w))  # + in-degree
    thr, alias = _alias_pairing(jnp.maximum(deg, 1e-12) ** power,
                                hi_dtype=hi_dtype)
    return NodeSampler(thr, alias, N)


def build_edge_sampler(knn_idx, weights, *, impl: str = "auto") -> EdgeSampler:
    """knn_idx/weights: (N, K) directed graph -> flat edge sampler.

    ``impl="device"`` (the ``"auto"`` default) builds the alias table
    on device in one jitted computation — the (N, K) graph never touches
    the host.  ``impl="host"`` is the numpy Vose oracle."""
    if _resolve_impl(impl) == "device":
        knn_idx, weights = jnp.asarray(knn_idx), jnp.asarray(weights)
        scope, hi_dtype = _pairing_scope()
        with scope:
            return _build_edge_sampler_device(knn_idx, weights,
                                              hi_dtype=hi_dtype)
    N, K = knn_idx.shape
    src = np.repeat(np.arange(N, dtype=np.int32), K)
    dst = np.asarray(knn_idx, np.int32).reshape(-1)
    w = np.asarray(weights, np.float64).reshape(-1)
    w = np.maximum(w, 0.0)
    if w.sum() <= 0:
        w = np.ones_like(w)
    thr, alias = build_alias(w)
    return EdgeSampler(jnp.asarray(src), jnp.asarray(dst),
                       jnp.asarray(thr), jnp.asarray(alias), len(src))


def build_negative_sampler(knn_idx, weights, *, power: float = 0.75,
                           impl: str = "auto") -> NodeSampler:
    """Weighted degree d_j = sum_i w_ij (directed, in+out), then ^power.
    ``impl`` as in :func:`build_edge_sampler`."""
    if _resolve_impl(impl) == "device":
        knn_idx, weights = jnp.asarray(knn_idx), jnp.asarray(weights)
        scope, hi_dtype = _pairing_scope()
        with scope:
            return _build_negative_sampler_device(knn_idx, weights,
                                                  power=power,
                                                  hi_dtype=hi_dtype)
    N, K = knn_idx.shape
    w = np.asarray(weights, np.float64)
    deg = w.sum(axis=1)                                   # out-degree
    np.add.at(deg, np.asarray(knn_idx, np.int64).reshape(-1),
              w.reshape(-1))                              # + in-degree
    deg = np.maximum(deg, 1e-12) ** power
    thr, alias = build_alias(deg)
    return NodeSampler(jnp.asarray(thr), jnp.asarray(alias), N)


def alias_marginals(threshold, alias) -> np.ndarray:
    """The exact per-index draw probability an alias table encodes.

    ``P(i) = (threshold_i + sum_j 1[alias_j = i] (1 - threshold_j)) / n``
    — the uniform slot draw keeps index ``i`` with its own threshold and
    collects every other slot's aliased remainder.  f64 host arithmetic:
    this is the oracle the sampler tests compare table constructions
    with, not a hot path."""
    thr = np.asarray(threshold, np.float64)
    ali = np.asarray(alias, np.int64)
    m = thr.copy()
    np.add.at(m, ali, 1.0 - thr)
    return m / thr.shape[0]


def edge_marginals(sampler) -> np.ndarray:
    """Global per-directed-edge draw probabilities, row-major ``(E,)``.

    Works for both :class:`EdgeSampler` and :class:`ShardedEdgeSampler`
    — for the sharded two-level draw the shard-selection marginal
    multiplies each shard's local table marginal, and the contiguous
    row layout makes shard-order concatenation global row-major order
    (padding rows sit at the end and are sliced off).  Samplers built
    from the same (knn_idx, weights) on ANY mesh agree up to table-
    construction rounding (exactly ``w_e / W`` in exact arithmetic) —
    the elastic-resume tests assert this across shard counts, and
    bitwise equality for same-mesh rebuilds."""
    if isinstance(sampler, ShardedEdgeSampler):
        P = sampler.n_shards
        if P == 1:
            return alias_marginals(sampler.threshold[0],
                                   sampler.alias[0])[:sampler.n_edges]
        shard_p = alias_marginals(sampler.shard_threshold,
                                  sampler.shard_alias)
        per = [shard_p[s] * alias_marginals(sampler.threshold[s],
                                            sampler.alias[s])
               for s in range(P)]
        return np.concatenate(per)[:sampler.n_edges]
    return alias_marginals(sampler.threshold,
                           sampler.alias)[:sampler.n_edges]


# ---------------------------------------------------------------------------
# Sharded build (1-D "data" mesh — same row layout as the KNN ring)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _make_sharded_builder_fn(mesh, axis: str, n_real: int, power: float,
                             hi_dtype):
    """jit'd shard_map body building one shard's edge + negative tables.

    Each shard pairs its OWN flat edge weights (local alias indices —
    valid by construction, unlike a slab cut out of a global table) and
    its own rows' degree^power masses; in-degree contributions landing
    on other shards' rows travel through one O(N) ``psum``.  Per-shard
    total masses come back stacked for the host-side (P,) shard table."""
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]

    def body(idx_loc, w_loc, ids_loc):
        n_loc, K = idx_loc.shape
        w = jnp.maximum(w_loc.astype(jnp.float32), 0.0)
        flat_w = ravel_rows(w)

        # --- edge table over this shard's own edges --------------------
        src = ids_loc.astype(jnp.int32)[row_major(n_loc, K)[0]]
        dst = ravel_rows(idx_loc).astype(jnp.int32)
        ethr, eali = _alias_pairing(flat_w, hi_dtype=hi_dtype)
        t_edge = jnp.sum(flat_w.astype(hi_dtype))

        # --- negative table over this shard's own rows -----------------
        # deg_j = out_j + in_j; in-degree scatters land anywhere, so each
        # shard scatters into an O(N) partial and one psum completes it
        out_deg = jnp.sum(w, axis=1)
        part = jnp.zeros((n_loc * n_shards,), jnp.float32)
        part = part.at[dst].add(flat_w)
        in_deg = jax.lax.psum(part, axis)
        deg = out_deg + jax.lax.dynamic_slice_in_dim(in_deg, ids_loc[0],
                                                     n_loc)
        # exact zero for padded rows — a clamped epsilon^power would give
        # out-of-range node ids a small but nonzero draw probability
        mass = jnp.where(ids_loc < n_real,
                         jnp.maximum(deg, 1e-12) ** power, 0.0)
        nthr, nali = _alias_pairing(mass, hi_dtype=hi_dtype)
        t_node = jnp.sum(mass.astype(hi_dtype))
        return (src[None], dst[None], ethr[None], eali[None], t_edge[None],
                nthr[None], nali[None], t_node[None])

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis)),
        out_specs=(P(axis, None), P(axis, None), P(axis, None),
                   P(axis, None), P(axis), P(axis, None), P(axis, None),
                   P(axis)), check_vma=False)
    return jax.jit(fn)


def build_samplers_sharded(knn_idx, weights, *, power: float = 0.75,
                           mesh=None, axis: str = "data"):
    """(ShardedEdgeSampler, ShardedNodeSampler) built on the data mesh.

    Rows pad to a shard multiple with zero weight (padded edges/nodes
    get exactly-zero mass at every level, so they are never drawn); the
    graph never leaves the mesh — per-shard tables are built where the
    rows already live, and only the (P,) total-mass vectors reach the
    host-free top-level pairing for the shard-selection tables."""
    from repro.runtime import sharding as sh
    if mesh is None:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(0)
    n_shards = mesh.shape[axis]
    N, K = knn_idx.shape
    idx_p = sh.pad_rows(jnp.asarray(knn_idx, jnp.int32), n_shards)
    w_p = sh.pad_rows(jnp.asarray(weights, jnp.float32), n_shards)
    ids = jnp.arange(idx_p.shape[0], dtype=jnp.int32)
    scope, hi_dtype = _pairing_scope()
    with scope:
        fn = _make_sharded_builder_fn(mesh, axis, N, float(power), hi_dtype)
        (src, dst, ethr, eali, t_edge,
         nthr, nali, t_node) = fn(idx_p, w_p, ids)
        sthr_e, sali_e = _alias_jit(t_edge, hi_dtype=hi_dtype)
        sthr_n, sali_n = _alias_jit(t_node, hi_dtype=hi_dtype)
    edge_s = ShardedEdgeSampler(src, dst, ethr, eali, sthr_e, sali_e,
                                n_shards, N * K)
    node_s = ShardedNodeSampler(nthr, nali, sthr_n, sali_n, n_shards, N)
    return edge_s, node_s
