"""The trainer's own spans and named scopes, read from the profiler trace.

The trainer wraps each phase of a loop iteration in a host span
`train.<phase>` (its step as the `step` argument), and the model's
attention, MLP and head in named scopes that the device trace's operations carry in their name stack.
The benchmark keeps those names as its own literals and imports nothing
from the program.

`load` reads the newest `.xplane.pb` again and keeps what
`trace.load_xplane` leaves out: each event's thread (its line's index in
its plane, since two threads may share a name) and its stats.  A device
operation's name stack (`tf_op`, the HLO instruction's `op_name`) is a
stat of the event's metadata, which `ProfileData` does not show, so
`name_stacks` reads it from the file's protobuf fields directly.  The rest
works on plain event lists, so hand-made events and a small recorded slice
stand in for a live trace in tests.
"""

from __future__ import annotations

import functools
import glob
import os
import re

from chipbench import trace as tr

LOOP_PREFIX = "train."
DISPATCH = "train.dispatch"
READBACK = "train.readback"
INPUT = ("train.batch", "train.put")
LOOP = ("train.batch", "train.lookup", "train.put", "train.lr",
        "train.dispatch", "train.readback", "train.fence",
        "train.controller", "train.warmup")
SCOPES = ("attention", "mlp", "head")
# the device operation's metadata stat that holds its name stack (the HLO
# instruction's `op_name`, e.g. `jit(step)/transpose(jvp(head))/dot:`)
NAME_STACK_STAT = "tf_op"


def _kept(plane: str, name: str) -> bool:
    if plane.startswith("/host:"):
        return name.startswith(LOOP_PREFIX)
    return bool(tr.DEVICE_PLANE.match(plane))


# ------------------------------------------------------- protobuf fields --

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a slice of `buf` otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _entry(buf) -> tuple:
    """A map entry's (key, value)."""
    kv = dict(_fields(buf))
    return kv.get(1, 0), kv.get(2, b"")


def name_stacks(path: str) -> dict:
    """{device plane: {operation name: name stack}} from an `.xplane.pb`
    (XSpace.planes = 1; XPlane.name = 2, event_metadata = 4, stat_metadata
    = 5; XEventMetadata.name = 2, stats = 5; XStatMetadata.name = 2;
    XStat.metadata_id = 1, str_value = 5, ref_value = 7)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(_entry(v)[1])
            elif g == 5:
                k, md = _entry(v)
                stat_names[k] = bytes(dict(_fields(md)).get(2, b"")).decode()
        if not tr.DEVICE_PLANE.match(name):
            continue
        stacks = out.setdefault(name, {})
        for md in events:
            op_name, stack = "", ""
            for g, v in _fields(md):
                if g == 2:
                    op_name = bytes(v).decode()
                elif g == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) != NAME_STACK_STAT:
                        continue
                    stack = (bytes(st[5]).decode() if 5 in st
                             else stat_names.get(st.get(7), ""))
            if stack:
                stacks.setdefault(op_name, stack)
    return out


# ------------------------------------------------------------------ load --

@functools.lru_cache(maxsize=1)
def _load_file(path: str, mtime_ns: int) -> tuple:
    from jax.profiler import ProfileData
    stacks = name_stacks(path)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if not _kept(plane.name, e.name):
                    continue
                stats = {k: v for k, v in e.stats}
                stack = stacks.get(plane.name, {}).get(e.name)
                if stack:
                    stats[NAME_STACK_STAT] = stack
                events.append({
                    "plane": plane.name, "line": line.name,
                    "thread": f"{plane.name}#{i}", "name": e.name,
                    "start_ns": float(e.start_ns),
                    "dur_ns": float(e.duration_ns), "stats": stats})
    return tuple(events)


def load(log_dir: str) -> list[dict]:
    """The loop's host spans and every device operation of the newest
    trace under `log_dir`, with thread and stats."""
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return list(_load_file(paths[-1], os.stat(paths[-1]).st_mtime_ns))


def traced() -> list[dict]:
    """`load` of the trace the harness wrote for this run."""
    from chipbench import harness
    return load(harness.TRACE_DIR)


# ------------------------------------------------------------ idle split --

def loop_thread(events: list[dict]) -> str | None:
    """The thread that dispatched the steps (the warm-up thread has none)."""
    counts: dict[str, int] = {}
    for e in events:
        if e["name"] == DISPATCH:
            counts[e["thread"]] = counts.get(e["thread"], 0) + 1
    return max(sorted(counts), key=counts.get) if counts else None


def innermost(spans: list[tuple]) -> list[tuple]:
    """(start, end, name) spans cut at every boundary into pieces that do
    not overlap, each named by the innermost span covering it (the latest
    to start; the first to end where two start together)."""
    pts = sorted({p for s, t, _ in spans for p in (s, t)})
    out = []
    for a, b in zip(pts, pts[1:]):
        cover = [(s, -t, n) for s, t, n in spans if s <= a and t >= b]
        if cover:
            out.append((a, b, max(cover)[2]))
    return out


def attribute(idle: list[tuple], pieces: list[tuple]) -> dict:
    """Nanoseconds of the merged `idle` intervals under each piece's name
    (None where no piece lies); both lists sorted and without overlap."""
    out: dict = {}
    j = 0
    for s, t in idle:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < t:
            a, b, name = pieces[k]
            ov = min(t, b) - max(s, a)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
                covered += ov
            k += 1
        out[None] = out.get(None, 0.0) + (t - s) - covered
    return out


def idle_split(events: list[dict], ops: list[dict], lo: float, hi: float
               ) -> dict | None:
    """Device idle time in [lo, hi] by what the loop's thread was doing,
    as shares (%) of the window: under `train.readback`, under
    `train.batch` or `train.put` (input), and the rest (other loop spans,
    or none).  The three add up to the device's idle share.  None where
    the trace holds no loop span or the window no device operation."""
    thread = loop_thread(events)
    if thread is None or not ops or hi <= lo:
        return None
    loop = [(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
            for e in events if e["thread"] == thread
            and e["name"].startswith(LOOP_PREFIX)]
    busy = tr.union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                     for e in ops])
    by = attribute(tr.subtract([(lo, hi)], busy), innermost(loop))
    readback = by.pop(READBACK, 0.0)
    inputs = sum(by.pop(n, 0.0) for n in INPUT)
    other = sum(by.values())
    return {"readback": 100.0 * readback / (hi - lo),
            "input": 100.0 * inputs / (hi - lo),
            "other": 100.0 * other / (hi - lo)}


def idle_split_of(ctx) -> dict | None:
    ops = ctx.device_ops.get(0)
    if not ops:
        return None
    return idle_split(traced(), ops, *ctx.window_ns)


# --------------------------------------------------------- scope shares --

def holds_scope(name_stack: str, scope: str) -> bool:
    """Whether a name stack holds `scope` as a component, bare or inside
    a transformation (`jvp(attention)`, `transpose(jvp(attention))`)."""
    return re.search(rf"(?:^|[/(]){re.escape(scope)}(?:[)/:]|$)",
                     name_stack) is not None


def scope_share(ops: list[dict], scope: str) -> float | None:
    """Share (%) of the device's busy time in operations whose name stack
    holds `scope`; control flow, which spans its body, is left out of the
    scope's operations.  None where no operation holds it."""
    mine = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops
            if not tr.WRAPPER.search(tr.opcode(e["name"]))
            and holds_scope(str(e.get("stats", {}).get(NAME_STACK_STAT, "")),
                            scope)]
    busy = tr.busy_ns(ops)
    if not mine or busy <= 0:
        return None
    return 100.0 * tr.length(tr.union(mine)) / busy


def scope_share_of(ctx, scope: str) -> float | None:
    lo, hi = ctx.window_ns
    return scope_share(tr.device_ops(traced(), 0, lo, hi), scope)


# ------------------------------------------------------------- rung wait --

def rung_wait(history: dict) -> float | None:
    """Seconds the engine's lookup blocked in the first step whose global
    batch differs from the one before (the crossing); None where the run
    has no crossing or the trainer keeps no `lookup_wait_s`."""
    waits, gb = history.get("lookup_wait_s"), history["global_batch"]
    if not waits:
        return None
    for i in range(1, len(gb)):
        if gb[i] != gb[i - 1]:
            return waits[i]
    return None
