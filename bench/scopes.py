"""What the readers of the program's own spans share.

The program (``repro.runtime.spans``) puts ``lv.`` scopes on the device
code of the explore round and the layout step, and host spans around its
dispatches, which it keeps in a bounded record per span.  A reader splits
a program's device time by scope with the program's scope table
(``spans.scope_table``: HLO instruction -> scope, "" for a leaf outside
every scope, None for a container such as a loop, whose events hold its
children's time), and takes the window's host records from the end of
the program's record.

A program without ``repro.runtime.spans`` has no such spans: every reader
then returns None.  So does a trace with no chip plane, as the device
metrics do.  Where the program has the spans, a reader raises where its
reading would be wrong: a scope or program missing from the table, leaf
operations outside every scope above ``UNSCOPED_LIMIT`` of the program's
leaf time, or fewer records than the window made.
"""
from __future__ import annotations

UNSCOPED_LIMIT = 0.05


def program_spans():
    """The program's ``repro.runtime.spans`` module, or None."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans


def scope_seconds(trace: dict, table: dict, scope: str) -> float:
    """Device seconds of ``scope`` in the program whose scope table is
    ``table``: the sum of the trace's ``ops`` over the table's leaf
    instructions mapped to it.  Only instruction names in the table
    count; containers (None) are left out."""
    leaves = {name: sc for name, sc in table.items() if sc is not None}
    if scope not in leaves.values():
        raise LookupError(f"no instruction of scope {scope!r} in the "
                          f"program's table; scopes: "
                          f"{sorted(set(leaves.values()))}")
    ops = trace["ops"]
    total = sum(ops.get(name, 0.0) for name in leaves)
    if total <= 0:
        raise LookupError(f"no leaf operation of the program ran in the "
                          f"window ({len(leaves)} in its table)")
    unscoped = sum(ops.get(name, 0.0) for name, sc in leaves.items()
                   if not sc)
    if unscoped > UNSCOPED_LIMIT * total:
        worst = sorted(((ops.get(n, 0.0), n) for n, sc in leaves.items()
                        if not sc), reverse=True)[:5]
        raise ValueError(f"leaf operations outside every scope take "
                         f"{unscoped / total:.2%} of the program's leaf "
                         f"time, over {UNSCOPED_LIMIT:.0%}: {worst}")
    return sum(ops.get(name, 0.0) for name, sc in leaves.items()
               if sc == scope)


def device_seconds(trace: dict, program: str, scope: str) -> float | None:
    """Device seconds of ``scope`` in ``program`` (``explore_rows_round``,
    ``layout_chunk``) over the window; None without a chip plane or
    without the program's spans."""
    spans = program_spans()
    if not trace["n_chips"] or spans is None:
        return None
    table = spans.scope_table(program)
    if not table:
        raise LookupError(f"the program noted no signature of {program!r}")
    return scope_seconds(trace, table, scope)


def last_records(records: list, *, calls: int | None = None,
                 steps: int | None = None) -> list:
    """The window's records, from the end of a span's record: the last
    ``calls``, or the last whose ``steps`` counts sum to ``steps``."""
    if calls is not None:
        if len(records) < calls:
            raise LookupError(f"{len(records)} records, the window made "
                              f"{calls} calls")
        return records[len(records) - calls:]
    done = 0
    for i in range(len(records) - 1, -1, -1):
        done += records[i][2]["steps"]
        if done == steps:
            return records[i:]
        if done > steps:
            break
    raise LookupError(f"no tail of {len(records)} records sums to the "
                      f"window's {steps} steps")


def window_records(trace: dict, name: str, **which) -> list | None:
    """The window's records of the program's span ``name`` (see
    :func:`last_records`); None without a chip plane or without the
    program's spans."""
    spans = program_spans()
    if not trace["n_chips"] or spans is None:
        return None
    return last_records(spans.records(name), **which)


def mean_ms(records: list) -> float:
    """Mean host duration of the records, in ms."""
    return sum(t1 - t0 for t0, t1, _, _ in records) / len(records) / 1e6


def retraces(records: list) -> int:
    """Traces and compiles the records saw while their spans were open."""
    return sum(r for _, _, _, r in records)
