"""Jit'd public wrappers over the Pallas kernels with ref fallbacks.

``impl`` resolution: "pallas" runs the kernel (interpret mode on CPU — this
container; compiled on TPU), "ref" runs the pure-jnp oracle, "auto" picks
pallas on TPU and ref on CPU (interpret-mode kernels are Python-slow, so CPU
production paths use the oracle, which is mathematically identical — the
kernel tests assert this).
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.knn_topk import pairwise_sqdist as _sqdist_pallas
from repro.kernels.knn_topk import topk_sqdist as _topk_pallas
from repro.kernels.largevis_grad import _round_up
from repro.kernels.largevis_grad import largevis_grads as _lvgrad_pallas
from repro.kernels.largevis_step import ROWS, vmem_capacity
from repro.kernels.largevis_step import fused_edge_step as _lvstep_pallas
from repro.runtime import autotune

# VMEM budget for the fused edge-step kernel's resident slab of y; None
# derives it per call as 3/4 of the core's VMEM, leaving the last quarter
# for the edge scratch (229 KB at B=4096), the mask and lr blocks and the
# compiler's own.  The slab is planar, (s, R/128, 128) f32, so it costs
# s*4 bytes per row with R rounded up to 1024 rows: v5e's 96 MiB holds
# 12,582,912 rows at s=2.  Not a support bound: past it the kernel tiles
# y into slabs (``y_tile``), each of which repeats the per-edge loops.
_FUSED_MAX_Y_BYTES: int | None = None


def _fused_y_budget() -> int:
    """Bytes of y the fused step's resident slab may hold."""
    return _FUSED_MAX_Y_BYTES or 3 * vmem_capacity() // 4


def _tuned(kernel: str, shape: dict, default: dict, kw: dict) -> dict:
    """Fill ``kw`` with autotuned tile parameters (explicit args win).

    ``default`` is the route's legacy hard-coded config — what
    ``AUTOTUNE=off`` (and a cold cache) reproduces bitwise — and also
    whitelists which keys a cached entry may contribute."""
    cfg = autotune.get(kernel, shape, default)
    for name, val in cfg.items():
        kw.setdefault(name, val)
    return kw


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    return impl


def fused_step_supported(n_nodes: int, out_dim: int) -> bool:
    """Whether ``largevis_edge_step`` may route to the fused step.

    True for ANY size on CPU and TPU: past the VMEM budget
    (``_fused_y_budget`` for the resident slab of y) the kernel tiles
    y into slabs, bitwise-equal to one slab, so size is a tiling decision
    (``_fused_y_tile``), not a rejection.  Any other backend (GPU) gets
    the split path: there the kernel's sequential per-row update loop
    would serialize B*(2+M) tiny updates per step, far slower than one
    parallel scatter-add.
    """
    del n_nodes, out_dim  # size no longer bounds support — tiling does
    return jax.default_backend() in ("cpu", "tpu")


def _fused_y_tile(n_nodes: int, out_dim: int) -> int:
    """Row-tile for the fused step's slabs of y (0 = one slab).

    One slab while the whole planar embedding fits the VMEM budget —
    s*4 bytes per row, rows rounded up to 1024; past it, the largest
    multiple of 1024 rows whose slab stays inside the budget."""
    budget = _fused_y_budget()
    if _round_up(n_nodes, ROWS) * out_dim * 4 <= budget:
        return 0
    return max(ROWS, budget // (4 * out_dim) // ROWS * ROWS)


def pairwise_sqdist(a, b, *, impl: str = "auto", **kw):
    if _resolve(impl) == "pallas":
        return _sqdist_pallas(a, b, interpret=not _on_tpu(), **kw)
    return ref.pairwise_sqdist_ref(a, b)


def topk_sqdist(a, b, k, *, impl: str = "auto", **kw):
    """Streaming fused distance->top-k (ids (M, k), sqdists (M, k)).

    impl:
      "fused" | "pallas" — the Pallas kernel (``knn_topk.topk_sqdist``):
        (bm, k) running state in VMEM, max-extraction merge, no sort.
        Compiled on TPU, interpret mode elsewhere.
      "ref"  — the streaming jnp oracle (``ref.topk_sqdist_ref``):
        identical fold as a lax.map over row tiles + lax.scan over column
        tiles with a lax.top_k merge.  Bit-identical to the kernel at
        equal (bm, bn).
      "auto" — the kernel on TPU, the oracle elsewhere (same contract as
        ``pairwise_sqdist``: the interpreter's per-grid-step Python loop
        is the slow path on CPU, and the oracle is the SAME streaming
        computation — no (M, N) buffer either way).

    Both paths accept the a_ids/b_ids/codes/init/dedup keywords; see
    ``ref.topk_sqdist_ref``.  Tile parameters (bm/bn/lane, plus merge on
    the oracle) resolve through the autotuner per (backend, route,
    shape-bucket) — ``AUTOTUNE=off`` reproduces each route's legacy
    hard-coded defaults bitwise; pass explicit tiles when bitwise
    cross-impl equality matters.
    """
    shape = dict(m=a.shape[0], n=b.shape[0], d=a.shape[1], k=int(k))
    if impl in ("fused", "pallas") or (impl == "auto" and _on_tpu()):
        kw.pop("merge", None)                 # oracle-only knob
        _tuned("topk_sqdist", shape, dict(bm=256, bn=512, lane=128), kw)
        return _topk_pallas(a, b, k, interpret=not _on_tpu(), **kw)
    if impl in ("ref", "auto"):
        _tuned("topk_sqdist", shape,
               dict(bm=2048, bn=None, lane=1, merge="auto"), kw)
        return ref.topk_sqdist_ref(a, b, k, **kw)
    raise ValueError(f"unknown impl {impl!r}; expected fused|pallas|ref|auto")


def largevis_grads(yi, yj, yneg, neg_mask, *, gamma=7.0, a=1.0, clip=5.0,
                   eps=0.1, impl: str = "auto", **kw):
    # the kernel pads any batch to whole edge blocks, so it is usable
    # inside the scanned layout engine at collision-capped odd batches
    if _resolve(impl) == "pallas":
        _tuned("largevis_grads",
               dict(b=yi.shape[0], m=yneg.shape[1], s=yi.shape[1]),
               dict(tile=2048), kw)
        return _lvgrad_pallas(yi, yj, yneg, neg_mask, gamma=gamma, a=a,
                              clip=clip, eps=eps,
                              interpret=not _on_tpu(), **kw)
    return ref.largevis_grads_ref(yi, yj, yneg, gamma=gamma, a=a, clip=clip,
                                  eps=eps, neg_mask=neg_mask)


def _edge_step_tiles(n: int, s: int, b: int, m: int, kw: dict) -> dict:
    """Fill ``kw`` with the fused step's edge ``tile`` and row ``y_tile``:
    autotuned, else the VMEM budget's (0 = one slab)."""
    _tuned("largevis_edge_step", dict(n=n, b=b, m=m, s=s),
           dict(tile=2048, y_tile=0), kw)
    if not kw.get("y_tile"):
        kw["y_tile"] = _fused_y_tile(n, s)
    return kw


def edge_step_slabs(n: int, s: int, b: int, m: int, *,
                    layout_step: str = "auto") -> int:
    """Row tiles (slabs of y) one edge step of an (n, s) embedding runs
    over, as ``core.layout_engine.apply_edge_batch`` routes it: the
    kernel's ``ceil(n / R)`` for slabs of R rows, and 1 for one slab or a
    path without the kernel."""
    kernel = layout_step == "fused" or (layout_step == "auto" and _on_tpu())
    if not (kernel and fused_step_supported(n, s)):
        return 1
    y_tile = _edge_step_tiles(n, s, b, m, {})["y_tile"]
    if not 0 < y_tile < n:
        return 1
    return -(-n // _round_up(y_tile, ROWS))


def largevis_edge_step(y, i, j, negs, neg_mask, lr, *, gamma=7.0, a=1.0,
                       clip=5.0, eps=0.1, impl: str = "auto",
                       n_frozen: int = 0, **kw):
    """One fused SGD edge-step update of the (N, s) embedding.

    ``n_frozen`` freezes rows below that index (they are never written):
    the out-of-sample transform / serving mode, where the fitted corpus
    embedding must stay bit-identical while appended query rows move.
    ``lr`` may be a scalar or a (B,) per-edge vector (heterogeneous
    serving slots).

    impl:
      "fused" | "pallas" — the fully-fused Pallas kernel
        (``largevis_step.fused_edge_step``: in-kernel gather + grad +
        sequential scatter-accumulate on a planar copy of y, aliased in
        place; the copy in and out costs two embedding-sized copies per
        call).  Compiled on TPU, interpret mode elsewhere.
      "ref"  — the pure-jnp oracle (``ref.fused_edge_step_ref``), bitwise
        equal to the kernel.
      "auto" — the kernel on TPU, the oracle elsewhere (the interpreter
        runs the kernel's per-row loops op by op: the slow path on CPU).

    Callers must check :func:`fused_step_supported` first;
    ``core.layout_engine.apply_edge_batch`` falls back to the split
    gather/grad/scatter path when it fails, and for autodiff
    ``prob_fn``s.  The edge ``tile`` and the row tile ``y_tile`` resolve
    through the autotuner; when neither the caller nor a tuned entry sets
    ``y_tile``, it is derived from the VMEM budget (0 = one slab).
    """
    if impl in ("fused", "pallas") or (impl == "auto" and _on_tpu()):
        _edge_step_tiles(y.shape[0], y.shape[1], i.shape[0], negs.shape[1],
                         kw)
        return _lvstep_pallas(y, i, j, negs, neg_mask, lr, gamma=gamma,
                              a=a, clip=clip, eps=eps, n_frozen=n_frozen,
                              interpret=not _on_tpu(), **kw)
    if impl in ("ref", "auto"):
        return ref.fused_edge_step_ref(y, i, j, negs, neg_mask, lr,
                                       gamma=gamma, a=a, clip=clip, eps=eps,
                                       n_frozen=n_frozen)
    raise ValueError(f"unknown impl {impl!r}; "
                     "expected fused|pallas|ref|auto")


def flash_attention(q, k, v, *, causal=True, impl: str = "auto", **kw):
    if _resolve(impl) == "pallas":
        return _flash_pallas(q, k, v, causal=causal,
                             interpret=not _on_tpu(), **kw)
    return ref.flash_attention_ref(q, k, v, causal=causal)
