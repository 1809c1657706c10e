"""From the profiler's xplane file to numbers: the busy union, device time
by operation, program and scope, and the idle gaps named by what the host
was doing.

``load_events`` reads the file with ``jax.profiler.ProfileData`` into plain
lists; ``reduce_events`` works on those lists alone, so the test feeds it a
small recorded trace (``recorded_v5e_serve.json``: the first 0.16 s of a traced
window of ``serve-chat-steady`` with its 1,200 longest host events).
Times are nanoseconds on the profiler's clock. The traced window is the host
annotation ``WINDOW_MARK``, and device events are clipped to it.
"""

from __future__ import annotations

import bisect
import re

WINDOW_MARK = "benchmark_traced_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 20_000  # shorter gaps are launch overhead, not host work


def short_name(hlo_text: str) -> str:
    """An operation's event is named by its whole HLO line:
    ``%fusion.8 = bf16[...] fusion(...)`` -> ``fusion.8``."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def scopes_from_hlo(hlo_text: str) -> dict:
    """{instruction name: its ``op_name``} from a compiled program's text.
    The events carry no scope, the compiled text does: a ``jax.named_scope``
    is part of the ``op_name`` of every instruction traced under it."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"', hlo_text,
        re.MULTILINE)}


def scopes_by_program(hlo_texts: list) -> dict:
    """{"<program>/<instruction>": ``op_name``} over several compiled
    programs, for a window that runs more than one: two programs number
    their instructions alike. Where two texts of one program (a prefill at
    two bucket sizes) give one instruction different ``op_name``s, it keeps
    the path the two share."""
    out = {}
    for text in hlo_texts:
        program = re.match(r"HloModule ([\w.\-]+)", text).group(1)
        for name, scope in scopes_from_hlo(text).items():
            key = program + "/" + name
            if key in out and out[key] != scope:
                a, b = out[key].split("/"), scope.split("/")
                n = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                         min(len(a), len(b)))
                scope = "/".join(a[:n])
            out[key] = scope
    return out


def load_events(xplane_path: str) -> dict:
    """{"devices": [{"name", "ops": [[name, start, dur]], "modules":
    [[name, start, dur]]}], "host": [[thread, name, start, dur]]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        dev["ops"].append([short_name(ev.name),
                                           int(ev.start_ns),
                                           int(ev.duration_ns)])
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        dev["modules"].append([ev.name, int(ev.start_ns),
                                               int(ev.duration_ns)])
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append([line.name, ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)])
    return {"devices": devices, "host": host}


def _window(events: dict) -> tuple:
    for _, name, start, dur in events["host"]:
        if name == WINDOW_MARK:
            return start, start + dur
    starts = [e[1] for d in events["devices"] for e in d["ops"]]
    ends = [e[1] + e[2] for d in events["devices"] for e in d["ops"]]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def program_of(module_name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``."""
    return module_name.split("(", 1)[0]


def leaves(ops: list) -> list:
    """The operations that hold no other: a ``while`` or a ``call`` spans
    its body's operations on the same line and would count them twice."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    holds, stack = set(), []
    for i in order:
        start = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            holds.add(stack[-1])
        stack.append(i)
    return [ops[i] for i in order if i not in holds]


def reduce_events(events: dict, scopes: dict = None) -> dict:
    """``scopes`` is ``scopes_from_hlo`` of the traced program, or
    ``scopes_by_program`` of several, where a reader wants device time by
    named scope."""
    scopes = scopes or {}

    def scope_of(program, name):
        return scopes.get(program + "/" + name, scopes.get(name, ""))

    lo, hi = _window(events)
    window_s = (hi - lo) / 1e9
    busy, by_op, by_program, program_calls = [], {}, {}, {}
    op_scopes = {}
    scoped = []
    fullest = None
    for di, dev in enumerate(events["devices"]):
        modules = sorted((s, s + d, program_of(n))
                         for n, s, d in dev["modules"])
        starts = [m[0] for m in modules]
        def program_at(t):
            i = bisect.bisect_right(starts, t) - 1
            return modules[i][2] if i >= 0 and t < modules[i][1] else "?"

        # busy and scopes take every operation: a ``while`` is running for
        # as long as its event lasts, whether or not its body's operations
        # all left events. Time by operation takes the leaves alone.
        clipped = []
        for name, s, d in dev["ops"]:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                clipped.append((a, b))
                program = program_at(s)
                scoped.append((scope_of(program, name), program, a, b, di))
        for name, s, d in leaves(dev["ops"]):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                program = program_at(s)
                key = program + "/" + name
                by_op[key] = by_op.get(key, 0.0) + (b - a) / 1e9
                op_scopes[key] = scope_of(program, name)
        for s, e, program in modules:
            a, b = max(s, lo), min(e, hi)
            if b > a:
                by_program[program] = by_program.get(program, 0.0) \
                    + (b - a) / 1e9
                if s >= lo and e <= hi:
                    program_calls[program] = program_calls.get(program, 0) + 1
        union = _union(clipped)
        sec = sum(e - s for s, e in union) / 1e9
        busy.append(sec)
        if fullest is None or sec > fullest[0]:
            fullest = (sec, union)
    if not busy or max(busy) <= 0:
        raise ValueError("no operation ran on the device in the traced "
                         "window")
    n_dev = len(events["devices"])
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "busy_s_fullest": fullest[0],
        "devices": n_dev,
        "by_op": {k: v / n_dev for k, v in by_op.items()},
        "op_scopes": op_scopes,
        "by_program": {k: v / n_dev for k, v in by_program.items()},
        "program_calls": program_calls,
        "scoped": scoped,
        "idle_gaps": idle_gaps(fullest[1], lo, hi, events["host"]),
    }


def scope_seconds(summary: dict, needle: str, exposed: bool = False) -> float:
    """Device seconds (a chip) of operations whose scope holds ``needle``.
    With ``exposed``, only the part of them during which no operation
    outside the scope ran on that device."""
    total = 0
    for di in range(summary["devices"]):
        mine = [x for x in summary["scoped"] if x[4] == di]
        inside = _union([(a, b) for scope, _, a, b, _ in mine
                         if needle in scope])
        total += sum(b - a for a, b in inside)
        if exposed:
            outside = _union([(a, b) for scope, _, a, b, _ in mine
                              if needle not in scope])
            total -= sum(min(b, e) - max(a, s) for a, b in inside
                         for s, e in outside if s < b and e > a)
    return total / 1e9 / summary["devices"]


def idle_gaps(union: list, lo: int, hi: int, host: list) -> dict:
    """{host frame: idle seconds}: each gap of the fullest device goes to
    the innermost host event that covers its middle."""
    edges = [lo] + [x for s, e in union for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= MIN_GAP_NS]
    spans = sorted((s, s + d, name) for _, name, s, d in host
                   if name != WINDOW_MARK and d > 0)
    starts = [s[0] for s in spans]
    out = {}
    for a, b in gaps:
        mid = (a + b) // 2
        i = bisect.bisect_right(starts, mid)
        best = None
        # the innermost cover is the latest-starting span that reaches mid
        for s, e, name in reversed(spans[max(0, i - 200):i]):
            if e >= mid:
                best = name
                break
        key = best or "(no host event)"
        out[key] = out.get(key, 0.0) + (b - a) / 1e9
    return out


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(summary: dict) -> dict:
    """The ten longest operations and idle gaps. An operation whose scope
    is known carries it: ``jit_step/fusion.2 <while/body/lm_moe/dot_general>``
    (its ``op_name`` without the leading ``jit(...)``)."""
    def label(key):
        scope = summary["op_scopes"].get(key, "").partition("/")[2]
        return f"{key} <{scope}>" if scope else key

    return {"device_ops": [[label(k), v] for k, v in top(summary["by_op"])],
            "idle_gaps": top(summary["idle_gaps"])}
