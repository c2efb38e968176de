"""The program's own spans, under one prefix (``lv.``).

* Device scopes: :func:`scope` is ``jax.named_scope("lv." + name)``.  It
  writes only HLO metadata (``op_name``), so the compiled code is the same
  and costs nothing at run time.  :func:`scope_table` maps the
  instructions of a jitted program to these scopes, which is how a device
  trace (ops by HLO instruction name) is split by scope.
* Host spans: :func:`span` opens a ``jax.profiler.TraceAnnotation`` of the
  same name, which lands in a profiler trace on the device's clock, and on
  exit appends ``(t0_ns, t1_ns, counts, retraces)`` to a bounded record
  per name (:func:`records`), timed with ``time.perf_counter_ns``.
* A retrace counter: ``retraces`` is the number of jaxpr traces and
  backend compiles that ``jax.monitoring`` reported while the span was
  open, counted by a listener registered once when this module is
  imported.
* Program signatures: :func:`note` keeps the abstract signature of each
  call of an instrumented jitted program, once per distinct signature,
  for :func:`scope_table` to compile again outside any timed region.

Scopes and spans:

=========================  ====================================================
``lv.explore.reverse``     the explored rows' reverse lists (``reverse_rows``)
``lv.explore.gather``      candidate ids, ``x[cand]``, squared distances, tile
                           padding and keys
``lv.explore.merge``       the argsort-dedup top-K (``merge_candidates``)
``lv.explore.writeback``   the explored rows' results written into the graph
``lv.layout.sample``       edge and negative alias draws, collision mask, lr
``lv.layout.update``       ``apply_edge_batch``: the edge-step kernel with its
                           planar copies of y, or the split path
``lv.alias.quantize``      the alias build's fixed-point masses
``lv.alias.scan``          the alias build's chunk offsets of its prefix sums
``lv.alias.pair``          the alias build's pairing of smalls and larges
``lv.explore.call``        host: one ``neighbor_explore`` call, ``rows=``
``lv.layout.dispatch``     host: one chunk of ``run_layout``'s scanned loop,
                           ``steps=``, ``slabs=`` (row tiles of y per step)
``lv.layout.sync``         host: the chunk's ``block_until_ready`` when the
                           loop is monitored
``lv.sampler.edge``        host: the device edge alias build, ``edges=``,
                           until its tables are ready
``lv.sampler.node``        host: the device noise alias build, ``nodes=``,
                           until its tables are ready
=========================  ====================================================
"""
from __future__ import annotations

import collections
import time

import jax

PREFIX = "lv."
MAX_RECORDS = 4096
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# jaxpr traces, backend compiles (jax.monitoring, the whole process)
_compiles = [0, 0]
_records: dict[str, collections.deque] = {}
# program -> signature key -> (jitted fn, treedef, abstract leaves)
_signatures: dict[str, dict] = collections.defaultdict(dict)
# (program, signature key) -> that signature's scope table
_tables: dict[tuple, dict] = {}


def _on_event(event: str, duration_secs: float, **kwargs) -> None:
    if event == TRACE_EVENT:
        _compiles[0] += 1
    elif event == COMPILE_EVENT:
        _compiles[1] += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)


def scope(name: str):
    """The device scope ``lv.<name>`` (a ``jax.named_scope``)."""
    return jax.named_scope(PREFIX + name)


class span:
    """Host span ``lv.<name>``: a profiler annotation, and a record of
    ``(t0_ns, t1_ns, counts, retraces)`` appended on exit.  The object
    keeps ``t0``/``t1`` (ns, ``perf_counter_ns``) for the caller."""

    __slots__ = ("name", "counts", "t0", "t1", "_ann", "_c0")

    def __init__(self, name: str, **counts):
        self.name = name
        self.counts = counts
        self.t0 = self.t1 = 0

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self._c0 = _compiles[0] + _compiles[1]
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter_ns()
        retraces = _compiles[0] + _compiles[1] - self._c0
        self._ann.__exit__(*exc)
        rec = _records.get(self.name)
        if rec is None:
            rec = _records[self.name] = collections.deque(maxlen=MAX_RECORDS)
        rec.append((self.t0, self.t1, self.counts, retraces))


def records(name: str) -> list:
    """The newest (at most ``MAX_RECORDS``) records of span ``name``,
    oldest first."""
    return list(_records.get(name, ()))


def _leaf_key(leaf):
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return type(leaf), leaf
    # an uncommitted array leaves its placement to jit, as a shape does
    return (shape, leaf.dtype, getattr(leaf, "weak_type", False),
            getattr(leaf, "committed", False))


def _abstract(leaf):
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        array = isinstance(leaf, jax.Array)
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype,
            sharding=leaf.sharding if array and leaf.committed else None,
            weak_type=array and getattr(leaf, "weak_type", False))
    return leaf


def note(program: str, fn, *args, **kwargs) -> None:
    """Record the abstract signature of the call ``fn(*args, **kwargs)``
    of the jitted ``fn`` as one of ``program``'s, once per distinct
    signature (shapes, dtypes, whether placed, the other arguments'
    values).  A committed array's signature keeps the sharding of the
    first call that noted it.  A call inside another trace (the caller
    jitted whole) is no dispatch of ``fn``'s own and is not noted."""
    leaves, tree = jax.tree.flatten((args, kwargs))
    if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
        return
    key = (tree, tuple(_leaf_key(leaf) for leaf in leaves))
    sigs = _signatures[program]
    if key not in sigs:
        sigs[key] = (fn, tree, [_abstract(leaf) for leaf in leaves])


def _compiled_text(fn, args, kwargs) -> str:
    """Optimised HLO of ``fn`` at an abstract signature, with its
    ``op_name`` metadata.

    The executable a call ran may lack it: JAX's persistent cache keys a
    program by its HLO without the metadata by default, so it can hand
    back an entry compiled from the same program with other (or no)
    scopes.  So this compiles a copy of its own, keyed with the metadata
    (a later run finds it in the cache), with a compiler option at its
    default value that keeps it apart from the in-memory executable.
    Metadata does not change what XLA compiles, so the instruction names
    are the ones the call's trace shows."""
    lowered = fn.lower(*args, **kwargs)
    was = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        compiled = lowered.compile(
            compiler_options={"xla_embed_ir_in_executable": False})
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          was)
    return compiled.as_text()


def scope_table(program: str) -> dict:
    """``{instruction: scope | "" | None}`` of every signature of
    ``program`` recorded by :func:`note`, merged: each is compiled again
    (:func:`_compiled_text`) and its optimised HLO read by
    ``launch.hlo_analysis.scope_table``.  ``""`` marks a leaf instruction
    outside every ``lv.`` scope, None a container (``while``, ...), whose
    trace events hold its children's time.  A name that two signatures
    map differently is a container if either says so, else unscoped.
    Empty when no signature was recorded."""
    from repro.launch import hlo_analysis
    merged: dict = {}
    for key, (fn, tree, leaves) in list(_signatures.get(program, {}).items()):
        table = _tables.get((program, key))
        if table is None:
            args, kwargs = jax.tree.unflatten(tree, leaves)
            text = _compiled_text(fn, args, kwargs)
            table = _tables[(program, key)] = hlo_analysis.scope_table(text)
        for name, sc in table.items():
            if name not in merged or merged[name] == sc:
                merged[name] = sc
            elif merged[name] is not None:
                merged[name] = None if sc is None else ""
    return merged
