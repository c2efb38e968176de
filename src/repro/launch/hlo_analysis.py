"""HLO post-processing for the roofline: collective bytes + cost extraction.

``cost_analysis()`` has no collective accounting, so collective traffic is
parsed from the (optimized, SPMD-partitioned) HLO text: every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute contributes
its largest-operand byte size.  Ops inside ``while`` bodies are multiplied
by the loop trip count when XLA annotates it (known_trip_count) — our layer
stacks are scans, so this matters.
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of 'f32[128,256]{...}' -> 131072; tuples summed."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# a computation's header: ``[ENTRY] %name (params) -> shape {``; the
# parameters may hold tuple shapes, so their parentheses nest
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\))?\s*->.*\{$")


def _computation_blocks(hlo: str):
    """Split HLO text into (name, body) computation blocks."""
    blocks = {}
    cur_name, cur_lines = None, []
    for line in hlo.splitlines():
        stripped = line.strip()
        m = _HEADER.match(stripped)
        if m:
            if cur_name is not None:
                blocks[cur_name] = cur_lines
            cur_name, cur_lines = m.group(1), []
        elif stripped == "}" and cur_name is not None:
            blocks[cur_name] = cur_lines
            cur_name, cur_lines = None, []
        elif cur_name is not None:
            cur_lines.append(stripped)
    if cur_name is not None:
        blocks[cur_name] = cur_lines
    return blocks


def _trip_counts(hlo: str, blocks) -> dict:
    """body-computation name -> known trip count (1 if unknown)."""
    trips = {}
    for line in hlo.splitlines():
        if " while(" in line or " = while(" in line or "while(" in line:
            if "body=" not in line:
                continue
            bm = re.search(r"body=%?([\w\.\-]+)", line)
            tm = re.search(r'known_trip_count=\{?"?n"?[:=]"?(\d+)', line)
            if not tm:
                tm = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', line)
            if bm:
                trips[bm.group(1)] = int(tm.group(1)) if tm else 1
    return trips


def collective_bytes(hlo: str) -> dict:
    """Sum collective operand bytes, trip-count aware.

    Returns {op_name: bytes, ..., 'total': bytes}.
    """
    blocks = _computation_blocks(hlo)
    trips = _trip_counts(hlo, blocks)
    out = {op: 0 for op in COLLECTIVE_OPS}

    def block_mult(name: str) -> int:
        return trips.get(name, 1)

    for name, lines in blocks.items():
        mult = block_mult(name)
        for line in lines:
            for op in COLLECTIVE_OPS:
                # match "= f32[...] all-gather(" etc.
                m = re.search(rf"=\s*([^=]*?)\s{re.escape(op)}(-start|-done)?\(",
                              line)
                if m and f" {op}" in line:
                    if m.group(2) == "-done":
                        continue        # counted at -start
                    out[op] += _shape_bytes(m.group(1)) * mult
                    break
    out["total"] = sum(out[o] for o in COLLECTIVE_OPS)
    out["while_trip_counts"] = trips
    return out


def memory_stats(compiled) -> dict:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    out = {}
    for k in keys:
        out[k] = int(getattr(ma, k, 0) or 0)
    return out


def cost_stats(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0))}


# ---------------------------------------------------------------------------
# scope table: HLO instruction -> the program's own ``lv.`` scope
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLEE = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAME_PARTS = re.compile(r"[/()]")
# instructions whose trace events hold their children's time
_CONTAINERS = ("while", "conditional", "call")
# bookkeeping that runs nothing on the device
_NOT_RUN = ("parameter", "constant", "get-tuple-element", "tuple",
            "bitcast")
_UNRESOLVED = object()


def _close(text: str, i: int) -> int:
    """Index just past the parenthesis that closes the one at ``i``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def _instructions(lines):
    """(name, opcode, operands, callees, op_name) of each instruction
    line of a computation block, in order (operands before their users)."""
    out = []
    for line in lines:
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        i = _close(rest, 0) if rest.startswith("(") else rest.find(" ")
        om = _OPCODE.match(rest, max(i, 0))
        if not om:
            continue
        j = _close(rest, om.end() - 1)
        attrs = rest[j:]
        callees = dict(_CALLEE.findall(attrs))
        branches = _BRANCHES.search(attrs)
        if branches:
            callees["branches"] = _OPERAND.findall(branches.group(1))
        op = _OP_NAME.search(attrs)
        out.append((name, om.group(1), _OPERAND.findall(rest[om.end():j]),
                    callees, op.group(1) if op else None))
    return out


def _scope_of_op_name(op_name: str, prefix: str) -> str:
    """The innermost ``prefix`` scope in an ``op_name``, or ""."""
    found = [s for s in _NAME_PARTS.split(op_name) if s.startswith(prefix)]
    return found[-1] if found else ""


def scope_table(hlo: str, prefix: str = "lv.") -> dict:
    """``{instruction: scope | "" | None}`` over the instructions that run
    as operations of an optimised HLO module (its entry computation and
    the bodies, conditions and branches of its control flow; not the
    computations inside fusions).

    An instruction's scope is the innermost ``named_scope`` starting with
    ``prefix`` in its ``metadata={op_name=...}``, and "" where its op_name
    names none.  A fusion takes the scope of its fused computation's root.
    An instruction that XLA added with no op_name (a layout copy, a
    relayout reshape) takes the scope of the instruction whose output it
    reads; one reading only its computation's parameters, the scope of the
    loop or call that runs the computation.  Containers (``while``,
    ``conditional``, ``call``) map to None: their trace events hold their
    children's time."""
    blocks = {name: _instructions(lines)
              for name, lines in _computation_blocks(hlo).items()}
    entry = next((_HEADER.match(ln.strip()).group(1)
                  for ln in hlo.splitlines() if ln.startswith("ENTRY")), None)
    memo: dict = {}

    def scopes(comp: str) -> dict:
        """name -> scope (or _UNRESOLVED) of each instruction of comp."""
        if comp in memo:
            return memo[comp]
        memo[comp] = got = {}
        for name, opcode, operands, callees, op_name in blocks.get(comp, ()):
            sc = _UNRESOLVED
            if opcode == "fusion" and callees.get("calls") in blocks:
                fused = blocks[callees["calls"]]       # its root is last
                if fused:
                    sc = scopes(callees["calls"]).get(fused[-1][0],
                                                      _UNRESOLVED)
            if sc is _UNRESOLVED and op_name is not None:
                sc = _scope_of_op_name(op_name, prefix)
            if sc is _UNRESOLVED:
                sc = next((got[o] for o in operands
                           if got.get(o, _UNRESOLVED) is not _UNRESOLVED),
                          _UNRESOLVED)
            got[name] = sc
        return got

    table: dict = {}
    # (computation, the scope of the instruction that runs it)
    todo, seen = [(entry, "")], {entry}
    while todo:
        comp, outer = todo.pop()
        got = scopes(comp)
        for name, opcode, _, callees, _ in blocks.get(comp, ()):
            sc = got[name]
            if sc is _UNRESOLVED:
                sc = outer
            run = [callees.get(k) for k in ("body", "condition",
                                            "true_computation",
                                            "false_computation")]
            run += callees.get("branches", [])
            if opcode == "call":
                run.append(callees.get("to_apply"))
            for c in run:
                if c in blocks and c not in seen:
                    seen.add(c)
                    todo.append((c, sc))
            if opcode in _CONTAINERS:
                table[name] = None
            elif opcode not in _NOT_RUN:
                table[name] = sc
    return table
