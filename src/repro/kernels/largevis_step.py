"""Pallas kernel: fully-fused LargeVis edge step — gather, gradient and
scatter-update in one pass over the embedding.

The split layout step moves the edge batch through HBM ~5x: an XLA gather
materializes yi/yj/yneg, the gradient kernel reads and rewrites them, and the
driver concatenates a (B*(2+M), s) update buffer for a scatter-add back into
y.  This kernel takes the full embedding plus the pre-sampled edge batch and
does everything in place.

Layout.  y enters **planar**: (s, N/128, 128), coordinate c of row r at
``[c, r // 128, r % 128]``.  A resident slab of R rows costs s*R*4 bytes
of VMEM, not the R*512 bytes a row-major (R, s) block pads to (s on the
128-lane axis).  Edge operands are planar too (edges along lanes), and the
gathered rows and staged updates live in one (K*s, B/128, 128) scratch,
K = 2+M update slots per edge in the canonical per-edge order
``[i_e, j_e, negs_e,0..M-1]``.  Row ids are read as scalars from SMEM, one
(K, tile) block of edges per grid step.

Grid (2, n_row_tiles, n_edge_tiles), minor dimension fastest:

  phase 0: for every row tile, copy its (s, R/128, 128) slab of y into
      VMEM and gather, edge by edge, the rows that tile owns into the
      scratch (an exact lane pick: a masked max over one 128-lane row).
  phase 1: at the first step compute the forces for the whole batch
      (``ref.edge_forces``, the oracle's own function) and stage ``-lr*g``
      in place of the gathered rows; then for every row tile add, edge by
      edge in canonical order, the updates that land in its slab, and copy
      the slab back.

Every gather precedes every update — the split step's batch semantics —
and duplicate rows accumulate in the canonical order, the order XLA's
scatter-add applies ``ref.fused_edge_step_ref``'s interleaved update
stream in.  Updates are row-local, so restricting the stream to one row
tile keeps each row's order: any row tiling gives the same bits.

The planar copy of y is aliased input->output and stays in HBM; only one
slab is ever in VMEM.  The drivers carry y as (N, s), so the wrapper
pads and transposes y into that copy and back on every call: two
embedding-sized HBM copies per step around the in-place kernel.
``y_tile=R`` (rounded up to 1024 rows) picks the slab height;
``ops.largevis_edge_step`` keeps the whole embedding as one slab up to
3/4 of the core's VMEM (12,582,912 rows at s=2 on v5e) and tiles past it.
Each row tile repeats both per-edge loops over all of the step's ids, so
a slab count above 1 multiplies the kernel's time.  The wrapper asks the
compiler for the scoped VMEM the slab and the edge scratch need
(``vmem_limit_bytes``) only where that is above the compiler's default.

``n_frozen=`` is the out-of-sample transform mode: rows below ``n_frozen``
are gathered and contribute forces but are never written, so a fitted
corpus embedding stays BIT-identical while appended query rows optimize
against it.  ``lr`` may be per-edge (B,) so lockstep serving slots at
different schedule positions share one dispatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.largevis_grad import LANES, _resolve_interpret, _round_up

# y rows per (8, 128) f32 tile of one planar coordinate
ROWS = 8 * LANES

# The scoped VMEM a Mosaic kernel gets when it asks for none (v5e), and
# one v5e TensorCore's VMEM, for a backend that cannot report its own:
# the CPU, and ahead-of-time compiles for a described v5e.
DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024
_V5E_VMEM = 128 * 1024 * 1024
# Room in a requested limit beyond the kernel's own buffers, for what the
# compiler allocates itself.
_VMEM_MARGIN = 1024 * 1024


def vmem_capacity() -> int:
    """VMEM bytes of one TensorCore: the chip's on a TPU backend, v5e's
    elsewhere."""
    if jax.default_backend() == "tpu":
        return pltpu.get_tpu_info().vmem_capacity_bytes
    return _V5E_VMEM


def vmem_limit_bytes(s: int, rows: int, m: int, bp: int) -> int | None:
    """Scoped VMEM the kernel asks for: None (the compiler's default)
    while its buffers fit the default, else what they need, at most the
    core's VMEM.  ``rows``: the slab's rows; ``bp``: the padded batch."""
    need = 4 * (s * rows                  # the slab of y
                + (2 + m) * s * bp        # gathered rows / staged updates
                + 2 * (m + 1) * bp)       # mask and lr, double-buffered
    need += _VMEM_MARGIN
    if need <= DEFAULT_SCOPED_VMEM:
        return None
    return min(need, vmem_capacity())


def _kernel(ids_ref, mask_ref, lr_ref, y_in, y_ref, slab, g_ref, *,
            gamma: float, a: float, clip: float, eps: float, m: int, s: int,
            b: int, tile: int, rq: int, n_etiles: int, n_frozen: int):
    del y_in  # aliased with y_ref; all access goes through the output ref
    p, t, et = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    k_slots = 2 + m
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    off = t * (rq * LANES)                    # first y row of this slab

    @pl.when(et == 0)
    def _load():
        pltpu.sync_copy(y_ref.at[:, pl.ds(t * rq, rq), :], slab)

    def locate(k, e):
        """(sublane, lane, in-slab) of the row edge e sends to slot k."""
        r = ids_ref[k, e] - off
        inside = (r >= 0) & (r < rq * LANES)
        r = jnp.clip(r, 0, rq * LANES - 1)
        return r // LANES, r % LANES, inside

    def pick(row, ln):
        """row[ln] as a (1, 1) value — exact for every f32, -0.0 too."""
        return jnp.max(jnp.where(lane == ln, row, -jnp.inf), axis=1,
                       keepdims=True)

    def for_each_edge(body):
        # edges in groups of 128 (one scratch row per slot and coordinate)
        def group(g, carry):
            row = et * (tile // LANES) + g
            body(g, row)
            return carry
        jax.lax.fori_loop(0, tile // LANES, group, 0)

    @pl.when(p == 0)
    def _gather():
        def body(g, row):
            def one(el, acc):
                acc = list(acc)
                e = g * LANES + el
                for k in range(k_slots):
                    q, ln, inside = locate(k, e)
                    hit = (lane == el) & inside
                    for c in range(s):
                        v = pick(slab[c, pl.ds(q, 1), :], ln)
                        acc[k * s + c] = jnp.where(hit, v, acc[k * s + c])
                return tuple(acc)

            acc = jax.lax.fori_loop(0, LANES, one, tuple(
                g_ref[x, pl.ds(row, 1), :] for x in range(k_slots * s)))
            for x in range(k_slots * s):
                g_ref[x, pl.ds(row, 1), :] = acc[x]

        for_each_edge(body)

    @pl.when((p == 1) & (t == 0) & (et == 0))
    def _grads():
        y = [g_ref[x] for x in range(k_slots * s)]
        gi, gj, gn = ref.edge_forces(
            y[0:s], y[s:2 * s],
            [y[(2 + mm) * s:(3 + mm) * s] for mm in range(m)],
            [mask_ref[mm] for mm in range(m)],
            gamma=gamma, a=a, clip=clip, eps=eps)
        lr = lr_ref[...]
        for x, gx in enumerate(gi + gj + [v for gm in gn for v in gm]):
            g_ref[x] = -lr * gx

    @pl.when(p == 1)
    def _scatter():
        def body(g, row):
            u = [g_ref[x, pl.ds(row, 1), :] for x in range(k_slots * s)]

            def one(el, carry):
                e = g * LANES + el
                real = et * tile + e < b          # padded edges never land
                for k in range(k_slots):
                    q, ln, inside = locate(k, e)
                    ok = inside & real
                    if n_frozen:
                        ok = ok & (ids_ref[k, e] >= n_frozen)
                    hit = (lane == ln) & ok
                    for c in range(s):
                        v = pick(u[k * s + c], el)
                        cur = slab[c, pl.ds(q, 1), :]
                        slab[c, pl.ds(q, 1), :] = jnp.where(hit, cur + v, cur)
                return carry

            jax.lax.fori_loop(0, LANES, one, 0)

        for_each_edge(body)

        @pl.when(et == n_etiles - 1)
        def _store():
            pltpu.sync_copy(slab, y_ref.at[:, pl.ds(t * rq, rq), :])


@functools.partial(jax.jit, static_argnames=("gamma", "a", "clip", "eps",
                                             "tile", "interpret",
                                             "n_frozen", "y_tile"))
def fused_edge_step(y, i, j, negs, neg_mask, lr, *, gamma: float = 7.0,
                    a: float = 1.0, clip: float = 5.0, eps: float = 0.1,
                    tile: int = 2048, interpret: bool | None = None,
                    n_frozen: int = 0, y_tile: int = 0):
    """One in-place SGD update of ``y`` over a sampled edge batch.

    y: (N, s) f32; i/j: (B,) int32 edge endpoints; negs: (B, M) int32
    negative samples; neg_mask: (B, M) 1.0 valid / 0.0 collision;
    lr: scalar learning rate, or a (B,) per-edge vector (the serving
    engine's lockstep slots sit at different schedule positions — the
    scalar form is the same computation broadcast).  Returns the updated
    (N, s) embedding; the planar copy of y is donated to the kernel via
    input_output_aliases.

    ``n_frozen``: rows with index < n_frozen are never written — the
    out-of-sample transform mode: corpus rows frozen, query rows moving.

    Any B: the batch pads to whole edge tiles of ``tile`` edges (a
    multiple of 128); padded edges gather row 0 and are never applied.
    ``y_tile=R`` (0 < R < N) holds only an R-row slab of y in VMEM at a
    time (R rounds up to a multiple of 1024); bitwise equal to the
    untiled mode for any R (see module docstring).
    """
    interpret = _resolve_interpret(interpret)
    N, s = y.shape
    B = i.shape[0]
    M = negs.shape[1]
    R = _round_up(y_tile if 0 < y_tile < N else N, ROWS)
    n_rtiles = -(-N // R)
    n_pad = n_rtiles * R
    t = min(_round_up(tile, LANES), _round_up(B, LANES))
    bp = _round_up(B, t)
    yp = jnp.pad(y.astype(jnp.float32), ((0, n_pad - N), (0, 0)))
    yp = yp.T.reshape(s, n_pad // LANES, LANES)
    ids = jnp.concatenate([i[:, None], j[:, None], negs], axis=1)
    ids = jnp.pad(ids.astype(jnp.int32).T, ((0, 0), (0, bp - B)))
    mask = jnp.pad(neg_mask.astype(jnp.float32).T, ((0, 0), (0, bp - B)))
    lr = jnp.broadcast_to(jnp.asarray(lr, jnp.float32), (B,))
    lr = jnp.pad(lr, (0, bp - B))
    kern = functools.partial(_kernel, gamma=gamma, a=a, clip=clip, eps=eps,
                             m=M, s=s, b=B, tile=t, rq=R // LANES,
                             n_etiles=bp // t, n_frozen=n_frozen)
    limit = vmem_limit_bytes(s, R, M, bp)
    out = pl.pallas_call(
        kern,
        grid=(2, n_rtiles, bp // t),
        in_specs=[
            pl.BlockSpec((2 + M, t), lambda p, r, e: (0, e),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((M, bp // LANES, LANES), lambda p, r, e: (0, 0, 0)),
            pl.BlockSpec((bp // LANES, LANES), lambda p, r, e: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(yp.shape, jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((s, R // LANES, LANES), jnp.float32),   # y slab
            # gathered rows, then the staged -lr*g updates, per slot/coord
            pltpu.VMEM(((2 + M) * s, bp // LANES, LANES), jnp.float32),
        ],
        input_output_aliases={3: 0},
        interpret=interpret,
        compiler_params=(None if limit is None else
                         pltpu.CompilerParams(vmem_limit_bytes=limit)),
    )(ids, mask.reshape(M, bp // LANES, LANES),
      lr.reshape(bp // LANES, LANES), yp)
    return out.reshape(s, n_pad).T[:N]
