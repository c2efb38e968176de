"""Plain references that decide ``correct``.

Nothing here imports the program.  Each reference is a straightforward
restatement of what one timed operation must produce from its inputs,
written with numpy and ``jax.numpy`` alone, and each takes a ``dtype``:
the configuration's float32, or the control's bfloat16, the precision
below it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BIG = np.float32(3.0e38)


# ---------------------------------------------------------------------------
# neighbor explore: one round over a block of rows
# ---------------------------------------------------------------------------

def reverse_lists(idx: np.ndarray, rows: np.ndarray, r_cap: int) -> np.ndarray:
    """For each row r of ``rows``, the sources i whose list holds r, in
    ascending i, the first ``r_cap`` of them, padded with r itself."""
    n = idx.shape[0]
    pos = np.full(n, -1, np.int64)
    pos[rows] = np.arange(rows.shape[0])
    hit = pos[idx] >= 0                              # (n, k): lists naming a row
    src, col = np.nonzero(hit)                       # row-major: ascending src
    dst = pos[idx[src, col]]
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    start = np.searchsorted(dst, np.arange(rows.shape[0]))
    rank = np.arange(dst.shape[0]) - start[dst]
    keep = rank < r_cap
    out = np.repeat(rows[:, None].astype(np.int64), r_cap, axis=1)
    out[dst[keep], rank[keep]] = src[keep]
    return out.astype(np.int32)


@functools.partial(jax.jit, static_argnames=("tile", "dtype"))
def _sq_dists(x, rows, cand, *, tile: int, dtype):
    """Squared distances of each row to its candidates, as differences
    summed in ``dtype``, over tiles of ``tile`` rows."""
    def one(args):
        r, c = args
        diff = x[c].astype(dtype) - x[r][:, None, :].astype(dtype)
        return jnp.sum(diff * diff, axis=-1).astype(jnp.float32)
    b, width = cand.shape
    return jax.lax.map(one, (rows.reshape(-1, tile),
                             cand.reshape(-1, tile, width))).reshape(b, width)


def _tile(b: int, width: int, d: int) -> int:
    """Rows per tile: the (tile, width, d) gather stays near 256 MB, and
    the tile divides ``b``."""
    t = max(1, min(b, (64 << 20) // max(1, width * d)))
    while b % t:
        t -= 1
    return t


def sq_dists(x, rows, cand, *, dtype=jnp.float32):
    """Squared distances (B, C) of ``rows`` to their candidates ``cand``."""
    b, width = cand.shape
    return _sq_dists(x, jnp.asarray(rows, jnp.int32),
                     jnp.asarray(cand, jnp.int32),
                     tile=_tile(b, width, x.shape[1]), dtype=dtype)


@functools.partial(jax.jit, static_argnames=("tile", "dtype"))
def _explore_rows(x, idx, dist, rows, rev, *, tile: int, dtype):
    b = rows.shape[0]
    k = idx.shape[1]
    nbrs = idx[rows]
    cand = jnp.concatenate([nbrs, idx[nbrs].reshape(b, -1), rev], axis=1)
    dd = _sq_dists(x, rows, cand, tile=tile, dtype=dtype)
    dd = dd.at[:, :k].set(dist[rows])
    dd = jnp.where(cand == rows[:, None], BIG, dd)
    order = jnp.argsort(cand, axis=1, stable=True)
    ids = jnp.take_along_axis(cand, order, axis=1)
    ds = jnp.take_along_axis(dd, order, axis=1)
    repeat = jnp.concatenate(
        [jnp.zeros((b, 1), bool), ids[:, 1:] == ids[:, :-1]], axis=1)
    ds = jnp.where(repeat, BIG, ds)
    # nearer first; among equal distances the lower id (ids are sorted)
    best = jnp.argsort(ds, axis=1, stable=True)[:, :k]
    return (jnp.take_along_axis(ids, best, axis=1),
            jnp.take_along_axis(ds, best, axis=1))


def explore_rows(x, idx, dist, rows: np.ndarray, *, r_cap: int = 0,
                 dtype=jnp.float32, idx_host: np.ndarray | None = None):
    """What one explore round must give ``rows`` of the graph (idx, dist).

    The candidates of a row are its own neighbours, its neighbours'
    neighbours and its first ``r_cap`` reverse neighbours.  The row
    keeps its own neighbours at their stored distances, every other
    candidate gets its distance from ``x``, the row itself and repeats
    drop out, and the K nearest remain, nearer first and the lower id
    first among equals.  Returns device (ids (B, K), dists (B, K))."""
    k = idx.shape[1]
    r_cap = r_cap or k
    idx_host = np.asarray(idx) if idx_host is None else idx_host
    rev = reverse_lists(idx_host, rows, r_cap)
    width = k + k * k + r_cap
    return _explore_rows(x, idx, dist, jnp.asarray(rows, jnp.int32),
                         jnp.asarray(rev), dtype=dtype,
                         tile=_tile(rows.shape[0], width, x.shape[1]))


def explore_compare(x, idx_in, dist_in, rows: np.ndarray, ids: np.ndarray,
                    ds: np.ndarray, *, r_cap: int = 0) -> dict:
    """Compare one explored block (``ids``, ``ds`` for ``rows``) with the
    reference from the graph (idx_in, dist_in) it started from.

    ``id_miss``: share of the block's ids that the reference's K do not
    hold.  ``dist_err``: worst relative gap between a reported distance
    and what it must be (the stored one for a kept neighbour, else the
    distance from ``x``).  ``bad_ids``: ids that name the row itself, a
    repeat, or no point."""
    idx_host = np.asarray(idx_in)
    n, k = idx_host.shape
    ref_ids, _ = explore_rows(x, idx_in, dist_in, rows, r_cap=r_cap,
                              idx_host=idx_host)
    ref_sorted = np.sort(np.asarray(ref_ids), axis=1)
    slot = np.clip(_rowwise_searchsorted(ref_sorted, ids), 0, k - 1)
    found = np.take_along_axis(ref_sorted, slot, axis=1) == ids
    # what each reported distance must be
    in_range = (ids >= 0) & (ids < n)
    safe = np.where(in_range, ids, rows[:, None])
    stored = idx_host[rows]
    kept = safe[:, :, None] == stored[:, None, :]
    kept_any = kept.any(-1)
    stored_d = np.asarray(dist_in[jnp.asarray(rows)])
    kept_d = np.where(kept, stored_d[:, None, :], 0).sum(-1)
    fresh_d = np.asarray(sq_dists(x, rows, safe))
    want = np.where(kept_any, kept_d, fresh_d)
    err = np.abs(ds - want) / np.maximum(np.abs(want), 1e-30)
    srt = np.sort(ids, axis=1)
    repeats = (srt[:, 1:] == srt[:, :-1]).sum()
    bad = int((~in_range).sum() + (ids == rows[:, None]).sum() + repeats)
    return {"id_miss": float(1.0 - found.mean()),
            "dist_err": float(np.where(in_range, err, 0).max()),
            "bad_ids": bad}


def _rowwise_searchsorted(sorted_rows: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Per-row ``searchsorted`` of ``q[r]`` in ``sorted_rows[r]``."""
    k = sorted_rows.shape[1]
    base = (np.arange(sorted_rows.shape[0], dtype=np.int64) * 2) << 32
    flat = (sorted_rows.astype(np.int64) + base[:, None]).ravel()
    pos = np.searchsorted(flat, (q.astype(np.int64) + base[:, None]).ravel())
    return (pos.reshape(q.shape) - (np.arange(q.shape[0]) * k)[:, None])


# ---------------------------------------------------------------------------
# exact neighbours, for recall
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def _exact_knn(x, q_rows, *, k: int):
    q = x[q_rows]
    with jax.default_matmul_precision("highest"):
        d = (jnp.sum(q * q, 1)[:, None] + jnp.sum(x * x, 1)[None, :]
             - 2.0 * q @ x.T)
    d = d.at[jnp.arange(q_rows.shape[0]), q_rows].set(jnp.inf)
    return jax.lax.top_k(-d, k)[1]


def recall(x, ids: np.ndarray, rows: np.ndarray, *, block: int = 256) -> float:
    """Mean share of each row's exact K nearest neighbours that ``ids``
    (its reported list) holds."""
    k = ids.shape[1]
    hits = 0
    for s in range(0, rows.shape[0], block):
        r = rows[s:s + block]
        true = np.asarray(_exact_knn(x, jnp.asarray(r, jnp.int32), k=k))
        got = ids[s:s + block]
        hits += int((got[:, :, None] == true[:, None, :]).any(-1).sum())
    return hits / (rows.shape[0] * k)


# ---------------------------------------------------------------------------
# layout: the edge-sampling SGD step
# ---------------------------------------------------------------------------

def alias_draw(key, threshold, alias, shape):
    """One alias-method draw per element of ``shape``: a uniform slot,
    kept with its threshold's probability, else its alias."""
    k_slot, k_keep = jax.random.split(key)
    slot = jax.random.randint(k_slot, shape, 0, threshold.shape[0])
    u = jax.random.uniform(k_keep, shape)
    return jnp.where(u < threshold[slot], slot, alias[slot]).astype(jnp.int32)


def edge_forces(yi, yj, yn, mask, *, a, gamma, eps, clip):
    """Gradients of the LargeVis edge loss (paper Eqn 6) with
    f(x) = 1/(1 + a x^2), clipped per coordinate.  yi, yj: (B, s);
    yn: (B, M, s); mask: (B, M).  Returns (gi, gj, gn)."""
    dij = yi - yj
    d2 = jnp.sum(dij * dij, -1, keepdims=True)
    gpos = 2.0 * a * dij / (1.0 + a * d2)
    din = yi[:, None, :] - yn
    dn2 = jnp.sum(din * din, -1, keepdims=True)
    gneg = -2.0 * gamma * din / ((eps + dn2) * (1.0 + a * dn2))
    gneg = gneg * mask[:, :, None]
    gi = jnp.clip(gpos + jnp.sum(gneg, 1), -clip, clip)
    return gi, jnp.clip(-gpos, -clip, clip), jnp.clip(-gneg, -clip, clip)


def layout_steps(y, base_key, step_ids, t_fracs, tables: dict, *,
                 batch: int, negatives: int, a: float, gamma: float,
                 clip: float, rho0: float, eps: float = 0.1,
                 dtype=jnp.float32):
    """The layout's SGD steps ``step_ids`` from ``y``, one at a time.

    Step t draws B edges ∝ their weight and B·M negatives ∝ degree^0.75
    from the alias ``tables`` with the key ``fold_in(base_key, t)``
    (split in three: edges, negatives, unused), masks negatives that hit
    the edge's ends, and adds -rho_t·g for every draw at once, with
    rho_t = rho0·max(1 - t/T, 1e-4)."""
    return _layout_steps(y, base_key, step_ids, t_fracs, tables,
                         batch=batch, negatives=negatives, a=a, gamma=gamma,
                         clip=clip, rho0=rho0, eps=eps, dtype=dtype)


@functools.partial(jax.jit, static_argnames=(
    "batch", "negatives", "a", "gamma", "clip", "rho0", "eps", "dtype"))
def _layout_steps(y, base_key, step_ids, t_fracs, tables, *, batch,
                  negatives, a, gamma, clip, rho0, eps, dtype):
    def step(y, st):
        sid, tf = st
        ke, kn, _ = jax.random.split(jax.random.fold_in(base_key, sid), 3)
        e = alias_draw(ke, tables["edge_threshold"], tables["edge_alias"],
                       (batch,))
        i, j = tables["src"][e], tables["dst"][e]
        negs = alias_draw(kn, tables["node_threshold"], tables["node_alias"],
                          (batch, negatives))
        mask = ((negs != i[:, None]) & (negs != j[:, None])).astype(dtype)
        yc = y.astype(dtype)
        gi, gj, gn = edge_forces(yc[i], yc[j], yc[negs], mask, a=a,
                                 gamma=gamma, eps=eps, clip=clip)
        lr = (rho0 * jnp.maximum(1.0 - tf, 1e-4)).astype(dtype)
        rows = jnp.concatenate([i[:, None], j[:, None], negs], 1)
        upd = jnp.concatenate([gi[:, None], gj[:, None], gn], 1)
        yc = yc.at[rows.reshape(-1)].add(
            (-lr * upd).reshape(-1, y.shape[1]).astype(dtype))
        return yc.astype(y.dtype), None
    return jax.lax.scan(step, y, (step_ids, t_fracs))[0]


def layout_compare(y0, y_prog, y_ref) -> dict:
    """``y_err``: the worst coordinate gap between program and reference
    over the largest move the reference makes.  ``move_gap``: how far
    the program's total move (Frobenius norm of y - y0) is from the
    reference's, as a share of it."""
    y0, y_prog, y_ref = (np.asarray(v, np.float64) for v in (y0, y_prog,
                                                             y_ref))
    move_ref = y_ref - y0
    scale = max(np.abs(move_ref).max(), 1e-30)
    return {"y_err": float(np.abs(y_prog - y_ref).max() / scale),
            "move_gap": float(abs(np.linalg.norm(y_prog - y0)
                                  / max(np.linalg.norm(move_ref), 1e-30)
                                  - 1.0))}


def table_marginals(threshold: np.ndarray, alias: np.ndarray) -> np.ndarray:
    """The probability with which an alias table draws each index: a
    uniform slot keeps itself with its threshold and hands the rest to
    its alias.  In float64."""
    thr = np.asarray(threshold, np.float64)
    got = thr + np.bincount(alias, weights=1.0 - thr,
                            minlength=thr.shape[0])
    return got / thr.shape[0]


def table_tv(idx: np.ndarray, w: np.ndarray, tables: dict, *,
             power: float) -> dict:
    """How far the alias ``tables`` draw from the distributions the
    graph (idx, w) asks for, in float64: edges ∝ their weight, and
    nodes ∝ (weighted out-degree + in-degree)^power.

    ``edge_tv``, ``node_tv``: total variation distance between what the
    tables draw and those distributions.  ``edge_worst_rel``: the worst
    relative gap of one edge slot, and ``edge_ends_bad``: edge slots
    whose (src, dst) is not the graph's row-major edge."""
    n, k = idx.shape
    w = np.maximum(np.asarray(w, np.float64), 0.0)
    p_edge = w.reshape(-1) / w.sum()
    deg = w.sum(axis=1) + np.bincount(idx.reshape(-1), weights=w.reshape(-1),
                                      minlength=n)
    p_node = np.maximum(deg, 1e-12) ** power
    p_node = p_node / p_node.sum()
    m_edge = table_marginals(tables["edge_threshold"], tables["edge_alias"])
    m_node = table_marginals(tables["node_threshold"], tables["node_alias"])
    live = p_edge > 0
    ends_bad = int((np.asarray(tables["src"]) != np.repeat(
        np.arange(n), k)).sum() + (np.asarray(tables["dst"])
                                   != idx.reshape(-1)).sum())
    return {"edge_tv": float(0.5 * np.abs(m_edge - p_edge).sum()),
            "node_tv": float(0.5 * np.abs(m_node - p_node).sum()),
            "edge_worst_rel": float(np.max(np.abs(m_edge[live] - p_edge[live])
                                           / p_edge[live])),
            "edge_ends_bad": ends_bad}
