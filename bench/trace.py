"""Reduce one profiler trace (``.xplane.pb``) to what the metrics read.

    python bench/trace.py <trace.xplane.pb | trace directory>

prints the reduction as JSON.  What it holds:

* ``window_s``: the length of the benchmark's window span
  (``bench.window``), and ``busy_s``: the union of the intervals in which
  an operation ran on a chip inside it, averaged over the chips;
* ``programs``: device seconds per jitted program, by its stable name
  (``jit_<function>``, without the compile id), from the program spans;
* ``ops``: device seconds per operation, by its HLO instruction name
  (an op that runs others, such as a loop, holds their time too),
  ``kernels``: device seconds per Pallas kernel, by the kernel's name
  (the instruction name without its ``.<n>``), and ``program_kernels``:
  the Pallas kernel seconds of each program, the one whose span holds
  the kernel's start;
* ``spans``: count and host seconds of each of the benchmark's own spans
  (names starting ``bench.``);
* ``idle_gaps``: idle device seconds inside the window, by the innermost
  benchmark span open on the host at the time (``window`` where none);
* ``device_ops``: the operations that took most device time.

Device operations are the events of a chip plane's ``XLA Ops`` line,
each named by its HLO text (``%name = shape op(...), ...``); programs
those of its ``XLA Modules`` line.  Every time is in seconds.
"""
from __future__ import annotations

import bisect
import collections
import glob
import itertools
import json
import os
import re
import sys

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_COMPILE_ID = re.compile(r"\(\d+\)$")


def find_trace(path: str) -> str:
    """The ``.xplane.pb`` file at ``path`` or under it."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_NUMBERED = re.compile(r"\.\d+$")


def op_name(event) -> str:
    """The HLO instruction name of an op event (``fusion.12``), from
    the HLO text it is named by."""
    head = event.name.split(" = ", 1)[0] if " = " in event.name else \
        event.name
    return head.strip().lstrip("%")


def kernel_name(event) -> str | None:
    """The Pallas kernel an op event ran, or None for other operations.

    A Mosaic kernel is a custom call whose target is ``tpu_custom_call``;
    the instruction is named after the kernel's function
    (``%fused_edge_step.7``), so the name without its ``.<n>`` is the
    kernel's."""
    if _KERNEL_TARGET not in event.name:
        return None
    return _NUMBERED.sub("", op_name(event))


def program_seconds(trace: dict, fragment: str) -> float | None:
    """Device seconds of the programs whose name holds ``fragment``:
    their spans on the ``XLA Modules`` line, inside the window.

    None for a trace with no chip plane (nothing to read); a chip's
    trace with no such program is an error, so that a wrong name cannot
    drop a metric unnoticed."""
    if not trace["n_chips"]:
        return None
    hit = [v for k, v in trace["programs"].items() if fragment in k]
    if hit:
        return sum(hit)
    raise LookupError(f"no program named *{fragment}* in the trace; "
                      f"programs: {sorted(trace['programs'])}")


def kernel_seconds(trace: dict, program: str = "") -> float | None:
    """Device seconds of the Pallas kernels that ran in the programs
    whose name holds ``program`` (every kernel of the window for the
    empty name).  None and errors as for :func:`program_seconds`."""
    if not trace["n_chips"]:
        return None
    if not program:
        hit = list(trace["kernels"].values())
    else:
        hit = [v for k, v in trace["program_kernels"].items() if program in k]
    if not hit:
        raise LookupError(f"no Pallas kernel in a program named "
                          f"*{program}* in the trace; kernels: "
                          f"{sorted(trace['kernels'])}, by program: "
                          f"{sorted(trace['program_kernels'])}")
    return sum(hit)


class _Labeler:
    """The innermost benchmark span open at a time.  The benchmark's
    spans come from one host thread, so they nest: the innermost span
    open at t is the latest-starting one that has not ended."""

    def __init__(self, spans):
        self.spans = sorted((s, e, n) for n, s, e in spans
                            if n != WINDOW_SPAN)
        self.starts = [s for s, _, _ in self.spans]
        # reach[i]: the latest end among spans[:i + 1]
        self.reach = list(itertools.accumulate(
            (e for _, e, _ in self.spans), max))
        self.edges = sorted({t for s, e, _ in self.spans for t in (s, e)})

    def split(self, g0, g1):
        """(label, seconds) of each piece of the gap g0..g1 between the
        starts and ends of spans."""
        lo = bisect.bisect_right(self.edges, g0)
        hi = bisect.bisect_left(self.edges, g1)
        cuts = [g0, *self.edges[lo:hi], g1]
        return [(self(a), (b - a) / 1e9) for a, b in zip(cuts, cuts[1:])]

    def __call__(self, t) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.reach[i] > t:
            s, e, name = self.spans[i]
            if e > t:
                return name
            i -= 1
        return "window"


def reduce_profile(profile) -> dict:
    """The reduction of a loaded ``jax.profiler.ProfileData``."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    chips = []
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            chips.append(plane)
    if windows:
        w0, w1 = windows[0]
    else:
        ends = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                for p in chips for ln in p.lines for ev in ln.events]
        w0 = min((s for s, _ in ends), default=0.0)
        w1 = max((e for _, e in ends), default=0.0)
    window_s = (w1 - w0) / 1e9

    ops = collections.Counter()
    kernels = collections.Counter()
    programs = collections.Counter()
    program_kernels = collections.Counter()
    busy_total = 0.0
    gaps = []
    for plane in chips:
        lines = {line.name: line for line in plane.lines}
        spans_of = []                        # (start, end, program)
        for ev in (lines["XLA Modules"].events if "XLA Modules" in lines
                   else ()):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            name = _COMPILE_ID.sub("", ev.name)
            spans_of.append((s, e, name))
            if e > w0 and s < w1:
                programs[name] += (min(e, w1) - max(s, w0)) / 1e9
        spans_of.sort()
        starts = [s for s, _, _ in spans_of]
        busy = []
        for ev in lines["XLA Ops"].events if "XLA Ops" in lines else ():
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            busy.append((s, e))
            ops[op_name(ev)] += (e - s) / 1e9
            kn = kernel_name(ev)
            if kn is None:
                continue
            kernels[kn] += (e - s) / 1e9
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            if k >= 0 and spans_of[k][1] >= ev.start_ns:
                program_kernels[spans_of[k][2]] += (e - s) / 1e9
        merged = _union(busy)
        busy_total += sum(e - s for s, e in merged) / 1e9
        t = w0
        for s, e in merged + [[w1, w1]]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
    n_chips = max(1, len(chips))

    idle = collections.Counter()
    label = _Labeler(spans)
    for g0, g1 in gaps:
        for name, seconds in label.split(g0, g1):
            idle[name] += seconds / n_chips
    span_totals = collections.defaultdict(lambda: [0, 0.0])
    for n, s, e in spans:
        span_totals[n][0] += 1
        span_totals[n][1] += (e - s) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_total / n_chips,
        "n_chips": len(chips),
        "programs": {k: v / n_chips for k, v in programs.items()},
        "program_kernels": {k: v / n_chips
                            for k, v in program_kernels.items()},
        "ops": {k: v / n_chips for k, v in ops.items()},
        "kernels": {k: v / n_chips for k, v in kernels.items()},
        "spans": {k: {"count": c, "total_s": t}
                  for k, (c, t) in span_totals.items()},
        "idle_gaps": [[k, v] for k, v in idle.most_common()],
        "device_ops": [[k, v / n_chips] for k, v in ops.most_common()],
    }


def idle_share(trace: dict) -> float | None:
    """Idle share of the chips in the window, in %; None without a chip
    plane (a trace from the CPU has none)."""
    if not trace["n_chips"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_trace(path)))


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
