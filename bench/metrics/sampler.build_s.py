"""Host seconds of set-up's alias-table build on the device: the newest
record of the program's ``lv.sampler.edge`` span plus the newest of its
``lv.sampler.node`` span (each closes once its tables are ready), from
the program's record (``bench/scopes.py``).  None without a chip plane,
or where the program has no such spans."""
from bench import scopes


def read(trace: dict, run: dict) -> float | None:
    spans = scopes.program_spans()
    if not trace["n_chips"] or spans is None:
        return None
    newest = [spans.records(name)[-1:] for name in ("sampler.edge",
                                                     "sampler.node")]
    if not all(newest):
        return None
    return sum(t1 - t0 for (t0, t1, _, _), in newest) / 1e9
