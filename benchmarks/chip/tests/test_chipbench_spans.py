"""The readers of the trainer's own spans, scopes and wait counter: the
idle time split by the loop phase it fell in, the scope shares of busy
time and the crossing's wait, on hand-made events and on a small recorded
slice of a traced v5e run."""

import json
import os
import types

import chipbench_tiny
from chipbench import spans, spec, trace as tr

import pytest

FIXTURE = os.path.join(chipbench_tiny.HERE, "fixtures",
                       "v5e_trace_spans_slice.json")
LOOP_THREAD = "/host:CPU#0"
WARMUP_THREAD = "/host:CPU#1"
IDLE_METRICS = ("readback_idle_share", "input_idle_share",
                "loop_other_idle_share")
SCOPES = ("attention", "mlp", "head")
SCOPE_METRICS = tuple(f"{s}_share" for s in SCOPES)


def op(name, start, dur, stack=""):
    return {"plane": "/device:TPU:0", "line": "XLA Ops",
            "thread": "/device:TPU:0#0",
            "name": f"%{name} = f32[8]{{0}} {name.split('.')[0]}(f32[8]{{0}} %p)",
            "start_ns": start, "dur_ns": dur, "stats": {"tf_op": stack}}


def span(name, start, dur, step=1, thread=LOOP_THREAD):
    return {"plane": "/host:CPU", "line": "python", "thread": thread,
            "name": name, "start_ns": start, "dur_ns": dur,
            "stats": {"step": step}}


def ctx_for(events, history=None):
    lo, hi = tr.window(events)
    return types.SimpleNamespace(
        device_ops={0: tr.device_ops(events, 0, lo, hi)},
        window_ns=(lo, hi), window_s=(hi - lo) * 1e-9,
        history=history or {"global_batch": []})


def read_all(events, monkeypatch, names, history=None):
    monkeypatch.setattr(spans, "traced", lambda: events)
    ctx = ctx_for(events, history)
    return {n: spec.metric_reader(n)(ctx) for n in names}


# window [0, 1000): device busy [0, 100) and [400, 500) and [900, 950)
def synthetic():
    mark = {"plane": "/host:CPU", "line": "python", "thread": LOOP_THREAD,
            "name": tr.WINDOW_MARK, "start_ns": 0, "dur_ns": 1000,
            "stats": {}}
    return [mark,
            op("fusion.1", 0, 100, "jit(step)/jvp(attention)/dot_general"),
            op("fusion.2", 400, 100, "jit(step)/transpose(jvp(mlp))/dot"),
            op("fusion.3", 900, 50, "jit(step)/jvp(head)/reduce"),
            span("train.dispatch", 50, 20),
            span("train.readback", 70, 130),     # idle [100, 200)
            span("train.fence", 200, 50),        # idle [200, 250)
            span("train.batch", 250, 100, 2),    # idle [250, 350)
            span("train.put", 350, 30, 2),       # idle [350, 380)
            span("np.asarray(jax.Array)", 360, 10, 2),  # not a loop span
            span("train.lr", 380, 10, 2),        # idle [380, 390)
            # idle [390, 400) and [500, 560): under no span
            span("train.batch", 560, 300, 3),    # idle [560, 860)
            # the warm-up thread's span under [860, 900) is ignored
            span("train.batch", 850, 60, 9, WARMUP_THREAD),
            span("train.readback", 880, 5, 3),   # idle [880, 885)
            ]


def test_idle_split_partitions_the_idle_share(monkeypatch):
    events = synthetic()
    got = read_all(events, monkeypatch, IDLE_METRICS + ("device_idle_share",))
    # idle [100,400) + [500,900) + [950,1000) = 750 of 1000
    assert got["device_idle_share"] == pytest.approx(75.0)
    assert got["readback_idle_share"] == pytest.approx(10.5)
    assert got["input_idle_share"] == pytest.approx(43.0)
    # fence 50, lr 10, nothing 10 + 60 + 20 (860-880) + 15 (885-900) + 50
    assert got["loop_other_idle_share"] == pytest.approx(21.5)
    assert sum(got[n] for n in IDLE_METRICS) == pytest.approx(
        got["device_idle_share"])


def test_innermost_span_wins():
    pieces = spans.innermost([(0, 100, "outer"), (20, 40, "inner"),
                              (40, 60, "next")])
    assert pieces == [(0, 20, "outer"), (20, 40, "inner"),
                      (40, 60, "next"), (60, 100, "outer")]
    by = spans.attribute([(10, 30), (50, 120)], pieces)
    assert by == {"outer": 10 + 40, "inner": 10, "next": 10, None: 20}


def test_warmup_thread_is_not_the_loop():
    events = synthetic()
    events += [span("train.batch", 0, 5, 1, WARMUP_THREAD)]
    assert spans.loop_thread(events) == LOOP_THREAD


def test_readers_silent_without_spans(monkeypatch):
    """A trace of a trainer without spans or scopes (the parent commit)."""
    events = [e for e in synthetic() if not e["name"].startswith("train.")]
    for e in events:
        e["stats"] = {}
    got = read_all(events, monkeypatch, IDLE_METRICS + SCOPE_METRICS
                   + ("rung_wait_s",),
                   history={"global_batch": [8, 32, 32]})
    assert got == {n: None for n in got}


def test_scope_shares(monkeypatch):
    events = synthetic()
    events.append(op("while.9", 0, 1000, "jit(step)/attention/while"))
    got = read_all(events, monkeypatch, SCOPE_METRICS)
    # busy is the while's [0, 1000); the scopes' own operations, not it
    assert got == {"attention_share": pytest.approx(10.0),
                   "mlp_share": pytest.approx(10.0),
                   "head_share": pytest.approx(5.0)}
    assert spans.holds_scope("a/transpose(jvp(attention))/dot", "attention")
    assert not spans.holds_scope("a/attention_proj/dot", "attention")
    assert not spans.holds_scope("a/header/dot", "head")


def test_rung_wait_reads_the_crossing():
    hist = {"global_batch": [8, 32, 32, 32],
            "lookup_wait_s": [0.02, 3.1, 0.0, 0.0]}
    assert spans.rung_wait(hist) == 3.1
    assert spans.rung_wait({"global_batch": [8, 8],
                            "lookup_wait_s": [0.02, 0.0]}) is None
    assert spans.rung_wait({"global_batch": [8, 32]}) is None


def expand(fx: dict) -> list[dict]:
    """The recorded slice's compact rows as trace events."""
    lo, hi = fx["window"]
    events = [{"plane": "/host:CPU", "line": "python3", "thread": fx["thread"],
               "name": tr.WINDOW_MARK, "start_ns": float(lo),
               "dur_ns": float(hi - lo), "stats": {}}]
    for thread, name, start, dur, step in fx["host"]:
        events.append({"plane": thread.split("#")[0], "line": "python3",
                       "thread": thread, "name": name,
                       "start_ns": float(start), "dur_ns": float(dur),
                       "stats": {} if step is None else {"step": step}})
    for i, (start, dur, stack, code) in enumerate(fx["ops"]):
        stack, code = fx["stacks"][stack], fx["codes"][code]
        events.append({"plane": "/device:TPU:0", "line": "XLA Ops",
                       "thread": "/device:TPU:0#2",
                       "name": f"%{code}.{i} = f32[] {code}()",
                       "start_ns": float(start), "dur_ns": float(dur),
                       "stats": {"tf_op": stack} if stack else {}})
    return events


def merged(intervals):
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def by_hand(fx: dict) -> dict:
    """The slice's shares worked out directly from its rows: device idle
    time is the window less the merged operations, and each idle stretch
    goes to the loop span that overlaps it (the loop's spans follow each
    other without overlap, so no nesting rule is needed); a scope's
    operations are those whose cut name stack ends in the scope, control
    flow (`while`) left out."""
    lo, hi = fx["window"]
    ops = [(max(s, lo), min(s + d, hi), fx["stacks"][k], fx["codes"][c])
           for s, d, k, c in fx["ops"]]
    busy = merged([(s, t) for s, t, _, _ in ops])
    idle, cur = [], lo
    for s, t in busy:
        if s > cur:
            idle.append((cur, s))
        cur = max(cur, t)
    if cur < hi:
        idle.append((cur, hi))
    loop = sorted((s, s + d, name) for th, name, s, d, _ in fx["host"]
                  if th == fx["thread"] and name.startswith("train."))
    assert all(a[1] <= b[0] for a, b in zip(loop, loop[1:]))
    under: dict = {}
    for s, t in idle:
        left = t - s
        for a, b, name in loop:
            ov = min(t, b) - max(s, a)
            if ov > 0:
                under[name] = under.get(name, 0.0) + ov
                left -= ov
        under[None] = under.get(None, 0.0) + left
    width = hi - lo
    busy_ns = sum(t - s for s, t in busy)
    out = {"device_idle_share": 100.0 * (1 - busy_ns / width),
           "readback_idle_share": 100.0 * under.pop("train.readback") / width,
           "input_idle_share": 100.0 * (under.pop("train.batch")
                                        + under.pop("train.put")) / width}
    out["loop_other_idle_share"] = 100.0 * sum(under.values()) / width
    for scope in SCOPES:
        mine = merged([(s, t) for s, t, stack, code in ops
                       if code != "while"
                       and stack.rstrip(")").endswith(scope)])
        out[f"{scope}_share"] = 100.0 * sum(t - s for s, t in mine) / busy_ns
    return out


def test_recorded_v5e_slice(monkeypatch):
    """One loop iteration of a traced constant-base window on a TPU v5e
    (one chip, ACCUM-NORM), taken from the run
    `run.py --workload phi3-mini-1chip.constant-base --seed 2147483911
    --seconds 20 --trace 1`: device 0's operations as recorded (each
    name stack cut after its scope), and the loop's spans, from the end
    of step 17's device work to the start of step 19's.  The two idle
    gaps that open and close step 18 are the third and fourth of that
    run's logged `idle_gaps`, to the nanosecond; the readers' shares on
    the slice equal those worked out directly from its rows."""
    with open(FIXTURE) as f:
        fx = json.load(f)
    events = expand(fx)
    lo, hi = tr.window(events)
    ops = tr.device_ops(events, 0, lo, hi)
    logged = fx["logged"]["idle_gaps"]
    gaps = tr.idle_gaps(events, ops, lo, hi, n=2)
    assert [g[0] for g in gaps] == [logged[2][0], logged[3][0]]
    assert [g[1] for g in gaps] == pytest.approx(
        [logged[2][1], logged[3][1]], abs=1e-12)

    got = read_all(events, monkeypatch,
                   IDLE_METRICS + SCOPE_METRICS + ("device_idle_share",))
    want = by_hand(fx)
    for name, value in want.items():
        assert got[name] == pytest.approx(value, rel=1e-9, abs=1e-12), name
    assert sum(got[n] for n in IDLE_METRICS) == pytest.approx(
        got["device_idle_share"], abs=1e-9)
    assert got["input_idle_share"] > got["readback_idle_share"] > 0
    assert 30 < got["attention_share"] < 70

    steps: dict = {}
    for e in events:
        if e["name"].startswith("train."):
            steps.setdefault(e["stats"]["step"], []).append(e["name"])
    assert steps[18] == list(spans.LOOP[:7]) + ["train.warmup"]


XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion()"
    display_name: "fusion.1"
    stats { metadata_id: 10 str_value: "jit(step)/jvp(attention)/dot:" }
    stats { metadata_id: 12 uint64_value: 7 } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8]{0} fusion()"
    stats { metadata_id: 10 ref_value: 11 } } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "jit(step)/mlp/dot:" } }
  stat_metadata { key: 12 value { id: 12 name: "flops" } }
}
planes { id: 2 name: "/host:CPU" }
"""


def test_name_stacks_read_from_the_event_metadata(tmp_path):
    """The name stack is a stat of the operation's metadata, held as a
    string or as a reference to a stat's name."""
    from jax.profiler import ProfileData
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    path = run / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert spans.name_stacks(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]{0} fusion()": "jit(step)/jvp(attention)/dot:",
        "%fusion.2 = f32[8]{0} fusion()": "jit(step)/mlp/dot:"}}
    ops = spans.load(str(tmp_path))
    assert [(e["start_ns"], e["dur_ns"], e["stats"].get("tf_op"))
            for e in ops] == [(1000.0, 5000.0, "jit(step)/jvp(attention)/dot:"),
                              (7000.0, 1000.0, "jit(step)/mlp/dot:")]
    assert spans.scope_share(ops, "attention") == pytest.approx(500 / 6)
