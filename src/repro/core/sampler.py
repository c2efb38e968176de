"""Alias-method samplers: O(1) weighted edge sampling + noise-distribution
negative sampling (paper §3.2, Mikolov-style P_n(j) ∝ d_j^0.75).

Sampling on device is two gathers + a compare per draw, fully batched.
Edge sampling ∝ w_ij is the paper's variance fix: sampled edges are treated
as *binary*, so divergent edge weights never enter the gradient.

Table construction comes in two implementations, selected by ``impl=``
(``LargeVisConfig.sampler_impl`` at the pipeline level):

* ``"device"`` (the ``"auto"`` default) — :func:`_alias_tables`, one
  jitted construction, the same on every backend.  Masses are quantized
  to fixed point, ``SLOT`` = 2^24 units a slot, and corrected to sum to
  exactly E slots; Vose's pairing is then resolved by prefix sums and
  binary searches in 64-bit integers (under a trace-scoped
  ``enable_x64``): smalls alias the first large whose cumulative surplus
  covers their cumulative deficit, and the boundary-straddling
  remainders flow between consecutive larges through a backward alias
  chain.  The integer pairing is exact, and a float32 threshold holds
  each slot's units exactly, so the table draws slot i with probability
  q_i / (E * 2^24): within 2^-24 of its weight's share, relative, and a
  unit or two absolute (total variation ~3e-8 at E ~ 1e6).  The build
  walks the graph in chunks of rows with carried chunk offsets, so
  beyond its (E,) outputs it holds one chunk's temporaries.  No host
  round trip: stage-1 outputs stay device-resident into the layout step.
  Each device build runs inside a host span, ``lv.sampler.edge``
  (``edges=``) or ``lv.sampler.node`` (``nodes=``), closed once its
  tables are ready; its device scopes are ``lv.alias.quantize``,
  ``lv.alias.scan`` (chunk offsets) and ``lv.alias.pair``.
* ``"host"`` — :func:`build_alias`, the classic numpy Vose loop.  O(E)
  but single-core Python (minutes at the paper's E = N*K = 150M); kept as
  the test oracle and debug path.

The produced (threshold, alias) tables differ between implementations —
any table with the right per-index marginals is a valid alias table —
and ``tests/test_sampler.py`` pins the device builder's marginals against
float64 targets and the Vose oracle via threshold/alias reconstruction.

:class:`EdgeSampler` / :class:`NodeSampler` are registered JAX pytrees, so
whole samplers thread through ``jit`` / ``lax.scan`` / ``shard_map`` as
single arguments (see ``core/layout_engine.py``).

Distributed mode (:func:`build_samplers_sharded`) builds **per-shard**
tables on the same 1-D "data" mesh the KNN ring and the perplexity
stages use: each shard runs :func:`_alias_tables` over its own rows'
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

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map

from repro.core.flat import row_major
from repro.runtime import spans


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


# fixed point of the device build: a slot holds 2**24 units of mass, so a
# threshold (units kept / 2**24) is exact in float32
SLOT = 1 << 24
# elements per chunk of the device build's loops
CHUNK = 1 << 20
# the first quantization aims this far below the total, so that it never
# passes it; the second fills the gap the same way
_MARGIN = 1.0 - 2.0 ** -16
_I64, _I32, _F32 = jnp.int64, jnp.int32, jnp.float32
_TOP = (1 << 63) - 1           # pads the search tree's rows
_QUERIES = 8192                # queries of the search tree read at once


def _scan(v, op, identity):
    """Inclusive prefix of the 1-D ``v`` under ``op`` (``jnp.add`` or
    ``jnp.maximum``): 128-lane rows by log-step shifts, rows by recursion.
    (The TPU compiler takes tens of seconds over a 1-D ``cumsum``.)"""
    n = v.shape[0]
    m = -(-n // 128)
    x = jnp.pad(v, (0, m * 128 - n), constant_values=identity)
    x = x.reshape(m, 128)
    s = 1
    while s < 128:
        x = op(x, jnp.pad(x[:, :-s], ((0, 0), (s, 0)),
                          constant_values=identity))
        s *= 2
    if m > 1:
        rows = _scan(x[:, -1], op, identity)
        x = op(x, jnp.concatenate([jnp.full((1,), identity, v.dtype),
                                   rows[:-1]])[:, None])
    return x.reshape(-1)[:n]


def _below(a, v):
    """For each query of ``v``: how many entries of the sorted 1-D ``a``
    lie below it, and the largest of them (-1 where none).

    A search tree of 128-wide rows (each level holds the last entry of
    each row below it): a query reads one row a level, compares it whole,
    and goes down to the first row that does not lie wholly below it.  Two
    or three row reads a query, where a binary search makes ~20 dependent
    reads of single entries, which the chip serves slowly."""
    levels = [a]
    while levels[-1].shape[0] > 128:
        x = levels[-1]
        m = -(-x.shape[0] // 128)
        x = jnp.pad(x, (0, m * 128 - x.shape[0]), constant_values=_TOP)
        levels[-1] = x.reshape(m, 128)
        levels.append(x.reshape(m, 128)[:, -1])
    top = levels.pop()
    top = jnp.pad(top, (0, 128 - top.shape[0]), constant_values=_TOP)

    def walk(v):
        below = top < v
        count = jnp.sum(below, dtype=_I32)
        best = jnp.max(jnp.where(below, top, -1))
        for rows in reversed(levels):
            at = jnp.minimum(count, rows.shape[0] - 1)
            row = rows[at]
            below = row < v
            count = at * 128 + jnp.sum(below, dtype=_I32)
            best = jnp.maximum(best, jnp.max(jnp.where(below, row, -1)))
        return count, best

    # queries in blocks: the rows a block reads stay a few MB
    return jax.lax.map(walk, v, batch_size=_QUERIES)


def _window(a_t, start, rows: int):
    """Rows ``start .. start + rows`` of a 2-D array, flattened row-major,
    from its transpose ``a_t``: the chip holds a narrow 2-D array
    column-major, and slicing rows would first copy all of it row-major."""
    slab = jax.lax.dynamic_slice_in_dim(a_t, start, rows, axis=1)
    r, c = row_major(rows, a_t.shape[0])
    return slab[c, r]


def _flatten(a):
    """``a.reshape(-1)`` of a narrow 2-D array, a block of rows at a time
    (:func:`_window`), so that no index array the size of ``a`` is held."""
    n_rows, k = a.shape
    rows = _chunk_rows(n_rows, k)
    a_t = a.T

    def block(c, out):
        start = jnp.minimum(c * rows, n_rows - rows)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _window(a_t, start, rows), start * k, 0)

    return jax.lax.fori_loop(0, -(-n_rows // rows), block,
                             jnp.zeros(n_rows * k, a.dtype))


def _in_degrees(knn_idx, w, size: int):
    """Sum of the weights ``max(w, 0)`` landing on each of ``size`` nodes
    by ``knn_idx``, added a block of rows at a time in row-major order."""
    n_rows, k = knn_idx.shape
    rows = _chunk_rows(n_rows, k)
    idx_t, w_t = knn_idx.T, w.astype(jnp.float32).T

    def block(c, deg):
        start = jnp.minimum(c * rows, n_rows - rows)
        own = start + jnp.arange(rows, dtype=jnp.int32) >= c * rows
        ws = jnp.where(jnp.repeat(own, k),
                       jnp.maximum(_window(w_t, start, rows), 0.0), 0.0)
        return deg.at[_window(idx_t, start, rows)].add(ws)

    return jax.lax.fori_loop(0, -(-n_rows // rows), block,
                             jnp.zeros((size,), jnp.float32))


def _chunk_rows(n_rows: int, k: int) -> int:
    """Rows of a block of about ``CHUNK`` entries."""
    return min(max(1, CHUNK // k), n_rows)


def _alias_tables(p: jax.Array):
    """Exact alias table over the 1-D masses ``p``.

    p: (n,) nonnegative, any scale (all zero: uniform).  Returns
    (threshold (n,) f32, alias (n,) i32, total f32 ~ sum(p)).  Traced
    under ``jax.enable_x64``: the sums are 64-bit integers.

    Quantize: slot i gets q_i units, q_i = floor(p_i s1) + floor(p_i s2)
    + an even share of what is left, so that the q sum to exactly
    n * SLOT.  s1 aims 2^-16 below the total, s2 fills that gap the same
    way, and the last residue (under about one unit a slot) goes in whole
    units to the nonzero slots in order.  The table draws slot i with
    probability q_i / (n SLOT): within 2^-24 of p_i / sum(p) relative, and
    a unit or two absolute.

    Pair (Vose by prefix sums, all in integers): smalls (q < SLOT) owe
    d = SLOT - q, larges have surplus e = q - SLOT, D and SE are their
    prefix sums in index order.  A small keeps its own q and aliases the
    first large whose SE reaches its D.  A large j keeps SLOT - beta_j and
    aliases the previous large, beta_j being the part of the small
    straddling SE_{j-1} that was charged to a later large: SE_{j-1} minus
    the last D at or below it.  The sums telescope to q_j for every large.

    Memory: beyond its inputs and outputs the build holds one chunk at a
    time.  Its loops run over chunks of ``CHUNK`` slots in index order
    (the last one's window shifted back to end at n); chunk-level starts
    of the prefix sums, of the nonzero count and of the last large carry
    the rest.  Each small's search runs over the chunks whose SE range
    holds its D, each large's over the chunks whose D range holds its
    SE_{j-1}: both monotone, so each pass visits under three chunks per
    chunk.
    """
    n = p.shape[0]
    if n >= 2 ** 31:
        raise ValueError(f"{n} entries: alias indices are int32")
    width = min(CHUNK, n)
    nc = -(-n // width)
    units = n * SLOT

    with spans.scope("alias.quantize"):
        p = p.astype(_F32)
        t0 = jnp.sum(jnp.maximum(p, 0.0))
        uniform = ~(t0 > 0)
        t0 = jnp.where(uniform, jnp.float32(n), t0)

        def clean(x):
            return jnp.where(uniform, 1.0, jnp.maximum(x, 0.0))

        def floor_sum(scale):
            return jnp.sum(jnp.floor(clean(p) * scale).astype(_I64))

        # q0 is exact, so it corrects any error of the float32 sum t0
        s0 = jnp.float32(units) / t0
        q0 = floor_sum(s0)
        s1 = s0 * (jnp.float32(units) / q0.astype(_F32)) * _MARGIN
        q1 = floor_sum(s1)
        s2 = s1 * ((units - q1).astype(_F32) / q1.astype(_F32)) * _MARGIN
        rest = units - q1 - floor_sum(s2)
        nnz = jnp.sum((clean(p) > 0).astype(_I64))
        base, extra = rest // nnz, rest % nnz
        total = q1.astype(_F32) / s1

    def start_of(c):
        return jnp.minimum(c * width, n - width)

    def quanta(c, nz_before):
        """Chunk c's window: units q, flat positions, smalls, larges and
        its count of nonzero slots."""
        with spans.scope("alias.quantize"):
            start = start_of(c)
            x = clean(jax.lax.dynamic_slice_in_dim(p, start, width))
            pos = start + jnp.arange(width, dtype=_I32)
            own = pos >= c * width
            nz = (own & (x > 0)).astype(_I64)
            rank = nz_before + _scan(nz, jnp.add, 0) - nz
            q = (jnp.floor(x * s1).astype(_I64)
                 + jnp.floor(x * s2).astype(_I64)
                 + nz * (base + (rank < extra)))
        return q, pos, own & (q < SLOT), own & (q >= SLOT), jnp.sum(nz)

    def deficits(q, small):
        return jnp.where(small, SLOT - q, 0)

    def surpluses(q, large):
        return jnp.where(large, q - SLOT, 0)

    # chunk starts: nonzero slots, D, SE, last large before the chunk
    def starts_of(c, carry):
        nz0, d0, e0, last, table = carry
        table = table.at[:, c].set(jnp.stack([nz0, d0, e0, last]))
        q, pos, small, large, nz = quanta(c, nz0)
        return (nz0 + nz, d0 + jnp.sum(deficits(q, small)),
                e0 + jnp.sum(surpluses(q, large)),
                jnp.maximum(last, jnp.max(jnp.where(large, pos, -1)
                                          ).astype(_I64)), table)

    zero = jnp.zeros((), _I64)
    with spans.scope("alias.scan"):
        *ends, table = jax.lax.fori_loop(
            _I32(0), _I32(nc), starts_of,
            (zero, zero, zero, zero - 1, jnp.zeros((4, nc + 1), _I64)))
        table = table.at[:, nc].set(jnp.stack(ends))
    nz_start, d_start, e_start, last_large = table
    d_end, e_end = d_start[1:], e_start[1:]

    def span_of(ends_, lo, hi, side):
        """Chunks whose range of ``ends_`` holds values lo..hi."""
        c0, c1 = (jnp.minimum(jnp.searchsorted(ends_, v, side=side), nc - 1)
                  for v in (lo, hi))
        return c0.astype(_I32), c1.astype(_I32)

    def write(tables, c, mask, thr, ali):
        out = []
        for arr, new in zip(tables, (thr, ali)):
            old = jax.lax.dynamic_slice_in_dim(arr, start_of(c), width)
            out.append(jax.lax.dynamic_update_slice_in_dim(
                arr, jnp.where(mask, new, old), start_of(c), 0))
        return tuple(out)

    def smalls(c, tables):
        """Chunk c's smalls: threshold q / SLOT, alias the first large
        whose SE reaches their D."""
        q, pos, small, _, _ = quanta(c, nz_start[c])
        dd = d_start[c] + _scan(deficits(q, small), jnp.add, 0)

        def find(cl, tgt):
            ql, _, _, large, _ = quanta(cl, nz_start[cl])
            se = e_start[cl] + _scan(surpluses(ql, large), jnp.add, 0)
            at, _ = _below(se, dd)
            mine = small & (dd > e_start[cl]) & (dd <= e_end[cl])
            return jnp.where(mine, start_of(cl) + at, tgt)

        c0, c1 = span_of(e_end, d_start[c] + 1, d_end[c], "left")
        tgt = jax.lax.fori_loop(c0, c1 + 1, find, pos)
        return write(tables, c, small, q.astype(_F32) / SLOT, tgt)

    def larges(c, tables):
        """Chunk c's larges: threshold 1 - beta / SLOT, alias the previous
        large (itself for the first)."""
        q, pos, _, large, _ = quanta(c, nz_start[c])
        ee = surpluses(q, large)
        prev_se = e_start[c] + _scan(ee, jnp.add, 0) - ee
        seen = _scan(jnp.where(large, pos, -1), jnp.maximum, -1)
        prev = jnp.maximum(last_large[c].astype(_I32),
                           jnp.concatenate([jnp.full((1,), -1, _I32),
                                            seen[:-1]]))

        def find(cs, beta):
            qs, _, small, _, _ = quanta(cs, nz_start[cs])
            dd = d_start[cs] + _scan(deficits(qs, small), jnp.add, 0)
            _, covered = _below(dd, prev_se + 1)     # the last D <= SE_{j-1}
            mine = (large & (prev_se >= d_start[cs])
                    & ((prev_se < d_end[cs]) | (cs == nc - 1)))
            return jnp.where(mine,
                             prev_se - jnp.maximum(covered, d_start[cs]),
                             beta)

        c0, c1 = span_of(d_end, e_start[c], e_end[c], "right")
        beta = jax.lax.fori_loop(c0, c1 + 1, find, jnp.zeros_like(q))
        thr = (SLOT - beta).astype(_F32) / SLOT
        return write(tables, c, large, thr, jnp.where(prev >= 0, prev, pos))

    with spans.scope("alias.pair"):
        tables = (jnp.zeros(n, _F32), jnp.zeros(n, _I32))
        tables = jax.lax.fori_loop(_I32(0), _I32(nc), smalls, tables)
        thr, alias = jax.lax.fori_loop(_I32(0), _I32(nc), larges, tables)
    return thr, alias, total


@jax.jit
def _alias_jit(p):
    thr, alias, _ = _alias_tables(p)
    return thr, alias


def build_alias_device(probs) -> tuple:
    """One jitted device computation: probs (n,) -> (threshold f32,
    alias i32), exact (see :func:`_alias_tables`)."""
    probs = jnp.asarray(probs, jnp.float32).reshape(-1)
    with jax.enable_x64(True):
        return _alias_jit(probs)


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


@jax.jit
def _build_edge_sampler_device(knn_idx, weights) -> EdgeSampler:
    N, K = knn_idx.shape
    thr, alias, _ = _alias_tables(_flatten(weights))
    src = row_major(N, K)[0]
    dst = _flatten(knn_idx).astype(jnp.int32)
    return EdgeSampler(src, dst, thr, alias, N * K)


@functools.partial(jax.jit, static_argnames=("power",))
def _build_negative_sampler_device(knn_idx, weights, *,
                                   power: float) -> NodeSampler:
    N, _ = knn_idx.shape
    deg = (jnp.sum(jnp.maximum(weights, 0.0), axis=1)         # out + in
           + _in_degrees(knn_idx, weights, N))
    thr, alias, _ = _alias_tables(jnp.maximum(deg, 1e-12) ** power)
    return NodeSampler(thr, alias, N)


def build_edge_sampler(knn_idx, weights, *, impl: str = "auto") -> EdgeSampler:
    """knn_idx/weights: (N, K) directed graph -> flat edge sampler.

    ``impl="device"`` (the ``"auto"`` default) builds the alias table
    on device in one jitted computation — the (N, K) graph never touches
    the host — inside the host span ``lv.sampler.edge`` (``edges=``),
    which closes once the tables are ready.  ``impl="host"`` is the numpy
    Vose oracle."""
    if _resolve_impl(impl) == "device":
        knn_idx, weights = jnp.asarray(knn_idx), jnp.asarray(weights)
        with spans.span("sampler.edge", edges=int(knn_idx.size)):
            with jax.enable_x64(True):
                es = _build_edge_sampler_device(knn_idx, weights)
            return jax.block_until_ready(es)
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
    ``impl`` as in :func:`build_edge_sampler`; the device build runs
    inside the host span ``lv.sampler.node`` (``nodes=``)."""
    if _resolve_impl(impl) == "device":
        knn_idx, weights = jnp.asarray(knn_idx), jnp.asarray(weights)
        with spans.span("sampler.node", nodes=int(knn_idx.shape[0])):
            with jax.enable_x64(True):
                ns = _build_negative_sampler_device(knn_idx, weights,
                                                    power=power)
            return jax.block_until_ready(ns)
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
def _make_sharded_builder_fn(mesh, axis: str, n_real: int, power: float):
    """jit'd shard_map body building one shard's edge + negative tables.

    Each shard pairs its OWN edge weights (local alias indices — valid by
    construction, unlike a slab cut out of a global table) and its own
    rows' degree^power masses; in-degree contributions landing on other
    shards' rows travel through one O(N) ``psum``.  Per-shard total
    masses come back stacked for the (P,) shard table.  Traced under
    ``jax.enable_x64``, as :func:`_alias_tables` asks."""
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]

    def body(idx_loc, w_loc, ids_loc):
        n_loc, K = idx_loc.shape
        w = jnp.maximum(w_loc.astype(jnp.float32), 0.0)

        # --- edge table over this shard's own edges --------------------
        src = ids_loc.astype(jnp.int32)[row_major(n_loc, K)[0]]
        dst = _flatten(idx_loc).astype(jnp.int32)
        ethr, eali, t_edge = _alias_tables(_flatten(w))

        # --- negative table over this shard's own rows -----------------
        # deg_j = out_j + in_j; in-degree scatters land anywhere, so each
        # shard scatters into an O(N) partial and one psum completes it
        out_deg = jnp.sum(w, axis=1)
        in_deg = jax.lax.psum(_in_degrees(idx_loc, w, n_loc * n_shards),
                              axis)
        deg = out_deg + jax.lax.dynamic_slice_in_dim(in_deg, ids_loc[0],
                                                     n_loc)
        # exact zero for padded rows — a clamped epsilon^power would give
        # out-of-range node ids a small but nonzero draw probability
        mass = jnp.where(ids_loc < n_real,
                         jnp.maximum(deg, 1e-12) ** power, 0.0)
        nthr, nali, t_node = _alias_tables(mass)
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
    with jax.enable_x64(True):
        fn = _make_sharded_builder_fn(mesh, axis, N, float(power))
        (src, dst, ethr, eali, t_edge,
         nthr, nali, t_node) = fn(idx_p, w_p, ids)
        sthr_e, sali_e = _alias_jit(t_edge)
        sthr_n, sali_n = _alias_jit(t_node)
    edge_s = ShardedEdgeSampler(src, dst, ethr, eali, sthr_e, sali_e,
                                n_shards, N * K)
    node_s = ShardedNodeSampler(nthr, nali, sthr_n, sali_n, n_shards, N)
    return edge_s, node_s
