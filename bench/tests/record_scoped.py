"""Record the traces that ``test_scopes.py`` reads, on a chip, with what
the readers of the program's own spans read beside them.

    python bench/tests/record_scoped.py [<directory>]

Runs ``record_traces.py`` into the directory (``bench/tests/data/scoped``
unless named), then writes, from the same process, the scope table of
each program the cells ran (``<program>.scopes.json``: HLO instruction ->
``lv.`` scope, "" or null), the program's host span records
(``spans.json``) and the counts each window driver handed the readers
(``counts.json``).
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PROGRAMS = ("explore_rows_round", "layout_chunk")
SPANS = ("explore.call", "layout.dispatch")


def main() -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), HERE]
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        HERE, "data", "scoped")
    from bench import run as bench_run
    import record_traces

    counts = {}
    load_module = bench_run.load_module

    def keep_counts(kind, name):
        mod = load_module(kind, name)
        if kind == "drivers":
            window = mod.window

            def counted(ctx, state):
                res = window(ctx, state)
                counts[name] = res["counts"]
                return res
            mod.window = counted
        return mod

    bench_run.load_module = keep_counts
    sys.argv[1:] = [out]
    rc = record_traces.main()
    if rc:
        return rc
    from repro.runtime import spans
    for program in PROGRAMS:
        with open(os.path.join(out, f"{program}.scopes.json"), "w") as f:
            json.dump(spans.scope_table(program), f, indent=0,
                      sort_keys=True)
    with open(os.path.join(out, "spans.json"), "w") as f:
        json.dump({name: spans.records(name) for name in SPANS}, f)
    with open(os.path.join(out, "counts.json"), "w") as f:
        json.dump(counts, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
