"""Reduction of a profiler trace to device intervals, idle gaps and the
host work beside them.

A trace is kept as a plain list of events, each a dict with `plane`,
`line`, `name`, `start_ns` and `dur_ns` (one clock for host and device),
so that a small recorded trace can stand in for a live one in tests.
`load_xplane` makes that list from the profiler's `.xplane.pb`.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_MARK = "chipbench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
DEVICE_OPS_LINE = "XLA Ops"
ASYNC_OPS_LINE = "Async XLA Ops"
# an XLA collective's opcode or name: JAX names the instruction after its
# primitive (`%psum.3 = f32[..] all-reduce(..)`), and an asynchronous one
# may run under the generic `async-start` opcode with the name it was given
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|psum|pmax|pmin|ppermute|all_gather|psum_scatter|all_to_all)")
# control flow whose event spans the operations of its body
WRAPPER = re.compile(r"^(while|conditional|call)$")
OPCODE = re.compile(r"(?<![\w\-])([a-z][a-z0-9\-]*)\(")


def load_xplane(log_dir: str) -> list[dict]:
    """Every event of the newest trace under `log_dir`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for e in line.events:
                events.append({"plane": plane.name, "line": line.name,
                               "name": e.name, "start_ns": float(e.start_ns),
                               "dur_ns": float(e.duration_ns)})
    return events


def window(events: list[dict]) -> tuple[float, float]:
    """(start, end) ns of the benchmark's own window mark."""
    marks = [e for e in events if e["name"] == WINDOW_MARK]
    if not marks:
        raise ValueError(f"the trace holds no {WINDOW_MARK!r} span")
    m = max(marks, key=lambda e: e["dur_ns"])
    return m["start_ns"], m["start_ns"] + m["dur_ns"]


def device_ids(events: list[dict]) -> list[int]:
    ids = {int(m.group(1)) for e in events
           if (m := DEVICE_PLANE.match(e["plane"]))}
    return sorted(ids)


def short_name(name: str) -> str:
    """An XLA operation's own name: the device trace names each by its HLO
    text (`%fusion.459 = f32[...] fusion(...)`)."""
    return name.split(" = ", 1)[0].lstrip("%")


def opcode(name: str) -> str:
    """The operation's HLO opcode (`fusion`, `all-reduce`, `while`): the
    first lower-case word after ` = ` that opens a parenthesis (layouts
    such as `T(8,128)` are upper-case).  An event named without its HLO
    text gives its own name up to the first dot."""
    head = name.split(" = ", 1)
    if len(head) < 2:
        return short_name(name).split(".")[0]
    m = OPCODE.search(head[1])
    return m.group(1) if m else ""


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(opcode(name))
                or COLLECTIVE.search(short_name(name)))


def result_type(name: str) -> str:
    """The operation's result type without layouts (`f32[3,8192,3072]`, or
    a tuple of such)."""
    head = name.split(" = ", 1)
    if len(head) < 2:
        return ""
    rest = head[1]
    typ = (rest[:rest.find(") ") + 1] if rest.startswith("(")
           else rest.split(" ", 1)[0])
    return re.sub(r"\{[^}]*\}", "", typ)


def device_ops(events: list[dict], device: int, lo: float, hi: float,
               line: str = DEVICE_OPS_LINE) -> list[dict]:
    """The device's operations on `line`, clipped to [lo, hi]."""
    plane = f"/device:TPU:{device}"
    out = []
    for e in events:
        if e["plane"] != plane or e["line"] != line:
            continue
        s, t = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
        if t > s:
            out.append(dict(e, start_ns=s, dur_ns=t - s))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def length(intervals) -> float:
    return sum(t - s for s, t in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the merged intervals `a` that no merged interval of `b`
    covers."""
    out, j = [], 0
    for s, t in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < t:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < t:
            out.append((cur, t))
    return out


def busy_ns(ops: list[dict]) -> float:
    return length(union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                         for e in ops]))


def exposed_collective_ns(ops: list[dict], async_ops: list[dict] = ()
                          ) -> float | None:
    """Time in which a collective runs (synchronous, or an asynchronous one
    between its start and done) and no compute does; None where the device
    ran no collective.  Control-flow events are not compute: they span
    their bodies."""
    coll = [(e["start_ns"], e["start_ns"] + e["dur_ns"])
            for e in list(ops) + list(async_ops)
            if is_collective(e["name"])]
    if not coll:
        return None
    other = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops
             if not is_collective(e["name"])
             and not WRAPPER.search(opcode(e["name"]))]
    return length(subtract(union(coll), union(other)))


def top_ops(ops: list[dict], n: int = 10) -> list[list]:
    """The `n` operations (by name and result type) with the most device
    time; control flow, which spans its body, is left out."""
    total: dict[str, float] = {}
    for e in ops:
        if WRAPPER.search(opcode(e["name"])):
            continue
        k = f"{short_name(e['name'])} {result_type(e['name'])}".strip()
        total[k] = total.get(k, 0.0) + e["dur_ns"]
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[k, v * 1e-9] for k, v in ranked]


def idle_gaps(events: list[dict], ops: list[dict], lo: float, hi: float,
              n: int = 10) -> list[list]:
    """The `n` longest stretches of [lo, hi] in which the device ran
    nothing, each named by the host event that overlaps it most (the
    shortest such, where several tie)."""
    busy = union([(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops])
    gaps = subtract([(lo, hi)], busy)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [e for e in events if e["plane"].startswith("/host:")
            and e["name"] != WINDOW_MARK and e["dur_ns"] > 0]
    out = []
    for s, t in gaps:
        best, best_key = "no host event", None
        for e in host:
            ov = min(t, e["start_ns"] + e["dur_ns"]) - max(s, e["start_ns"])
            if ov <= 0:
                continue
            key = (-ov, e["dur_ns"])
            if best_key is None or key < best_key:
                best, best_key = e["name"], key
        out.append([best, (t - s) * 1e-9])
    return out
