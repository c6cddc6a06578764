"""The trace reduction: device busy and idle time, exposed collective
time, the busiest operations and the idle gaps named by the host, on
hand-made events and on a small trace recorded on a TPU v5e."""

import json
import os

import chipbench_tiny
from chipbench import spec, trace as tr

import pytest

FIXTURE = os.path.join(chipbench_tiny.HERE, "fixtures", "v5e_trace_slice.json")


def ev(name, start, dur, plane="/device:TPU:0", line="XLA Ops"):
    if plane.startswith("/device") and " = " not in name:
        # the device trace names an operation by its HLO text
        name = f"%{name} = f32[8]{{0}} {name.split('.')[0]}(f32[8]{{0}} %p)"
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def test_union_and_subtract():
    u = tr.union([(0, 10), (5, 20), (30, 40)])
    assert u == [(0, 20), (30, 40)]
    assert tr.length(u) == 30
    assert tr.subtract([(0, 50)], u) == [(20, 30), (40, 50)]
    assert tr.subtract([(0, 10)], [(0, 10)]) == []


def test_busy_clips_to_window_and_merges_overlap():
    events = [ev(tr.WINDOW_MARK, 100, 100, "/host:CPU", "python"),
              ev("fusion.1", 50, 100), ev("fusion.2", 120, 30),
              ev("convolution.3", 180, 50)]
    lo, hi = tr.window(events)
    ops = tr.device_ops(events, 0, lo, hi)
    # [100, 150) and [180, 200) inside [100, 200)
    assert tr.busy_ns(ops) == 70


def test_exposed_collective_is_what_no_compute_hides():
    ops = [ev("all-gather.1", 0, 100), ev("fusion.1", 20, 30),
           ev("all-reduce.4", 200, 10), ev("fusion.2", 150, 100),
           ev("while.3", 0, 400),
           ev("%psum.9 = f32[8]{0:T(1024)} all-reduce(f32[8]{0} %g), "
              "channel_id=3", 500, 40),
           ev("%while.5 = (s32[]{:T(128)}, f32[8]{0}) while((s32[], f32[8]) "
              "%t), condition=%c", 480, 100)]
    asyncs = [ev("all-reduce-start.7", 300, 50, line="Async XLA Ops"),
              ev("psum-start.8", 600, 20, line="Async XLA Ops")]
    # all-gather exposed on [0,20) and [50,100); all-reduce hidden by
    # fusion.2; the async all-reduce exposed on [300,350); the all-reduce
    # JAX named `psum` exposed on [500,540), the asynchronous one on
    # [600,620); the while loops span their bodies and hide nothing
    assert tr.exposed_collective_ns(ops, asyncs) == 180
    assert tr.exposed_collective_ns([ev("fusion.1", 0, 5)]) is None


def test_exposed_collective_share_is_the_mean_over_chips():
    """Four chips, a 1000-ns window: chip 0's all-gather is exposed for
    60 ns outside its fusion; chip 1's asynchronous all-reduce lies wholly
    under compute and is not counted; chip 2's reduce-scatter is exposed
    for 100 ns, half of it clipped by the window; chip 3 runs none."""
    from types import SimpleNamespace
    events = [ev(tr.WINDOW_MARK, 0, 1000, "/host:CPU", "python"),
              ev("all-gather.1", 100, 100), ev("fusion.1", 140, 40),
              ev("fusion.2", 100, 300, "/device:TPU:1"),
              ev("all-reduce-start.3", 150, 200, "/device:TPU:1",
                 "Async XLA Ops"),
              ev("reduce-scatter.4", 900, 200, "/device:TPU:2"),
              ev("fusion.5", 0, 50, "/device:TPU:3")]
    lo, hi = tr.window(events)
    ctx = SimpleNamespace(events=events, window_ns=(lo, hi), device_ops={
        i: tr.device_ops(events, i, lo, hi) for i in range(4)})
    read = spec.metric_reader("exposed_collective_share")
    assert read(ctx) == pytest.approx(100.0 * (60 + 0 + 100 + 0) / 4 / 1000)
    one_chip = SimpleNamespace(events=events, window_ns=(lo, hi),
                               device_ops={3: ctx.device_ops[3]})
    assert read(one_chip) is None


def test_top_ops_and_idle_gaps_are_named():
    events = [ev(tr.WINDOW_MARK, 0, 1000, "/host:CPU", "python"),
              ev("fusion.1", 0, 100), ev("fusion.7", 400, 100),
              ev("custom-call.2", 900, 50),
              ev("make_batch", 120, 250, "/host:CPU", "python"),
              ev("PjitFunction(step)", 560, 300, "/host:CPU", "python")]
    ops = tr.device_ops(events, 0, 0, 1000)
    events.append(ev("while.9", 0, 1000))
    ranked = tr.top_ops(tr.device_ops(events, 0, 0, 1000))
    assert ranked[:2] == [["fusion.1 f32[8]", pytest.approx(100e-9)],
                          ["fusion.7 f32[8]", pytest.approx(100e-9)]]
    assert all(not k.startswith("while") for k, _ in ranked)
    gaps = tr.idle_gaps(events, ops, 0, 1000)
    assert gaps == [["PjitFunction(step)", pytest.approx(400e-9)],
                    ["make_batch", pytest.approx(300e-9)],
                    ["no host event", pytest.approx(50e-9)]]


def test_recorded_v5e_trace():
    """A quarter second of the constant-base cell's traced window (one
    chip, ACCUM-NORM): the end of one step, with its Pallas AdamW tail,
    the host's gap and the start of the next."""
    with open(FIXTURE) as f:
        events = json.load(f)
    lo, hi = tr.window(events)
    ops = tr.device_ops(events, 0, lo, hi)
    busy = tr.busy_ns(ops)
    assert 0.5 * (hi - lo) < busy < hi - lo
    top = tr.top_ops(ops)
    assert len(top) == 10 and all("fusion" in k for k, _ in top[:3])
    assert sum(v for _, v in top) <= busy * 1e-9
    gaps = tr.idle_gaps(events, ops, lo, hi)
    assert gaps[0][1] > 0.005 and gaps[0][0] != "no host event"
    assert tr.exposed_collective_ns(ops) is None
    tail = spec.metric_reader("tail_roofline")
    fams = [tail.__globals__["family"](e["name"]) for e in ops]
    assert fams.count("adamw") > 0
