"""Multi-host warmup coordination (DESIGN §8.1): file-backed barriers /
agreement / failure broadcast, the engine's coordinated-rung-entry behavior,
the 2-process coordinated-warmup acceptance bar, and persistent compile-cache
reuse across an engine restart."""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from repro.core.schedule import parse_ladder
from repro.data.pipeline import MarkovTokens, make_batch
from repro.distributed import engine as engine_mod
from repro.distributed.coordination import (
    FileCoordinator, NoOpCoordinator, make_coordinator)
from repro.distributed.engine import BucketedEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


# ------------------------------------------------------ file coordinator ----

def _pair(tmp_path, **kw):
    d = str(tmp_path / "coord")
    return (FileCoordinator(d, 0, 2, **kw), FileCoordinator(d, 1, 2, **kw))


def test_barrier_meets_and_reports_wait(tmp_path):
    c0, c1 = _pair(tmp_path)
    waits = {}

    def late():
        time.sleep(0.15)
        waits[1] = c1.barrier("entry")

    t = threading.Thread(target=late)
    t.start()
    waits[0] = c0.barrier("entry")     # must wait ~0.15s for rank 1
    t.join()
    assert waits[0] >= 0.1             # the early host measured real waiting
    assert waits[1] < 5.0


def test_barrier_generations_allow_reentry(tmp_path):
    """The same barrier NAME crossed twice (rung re-entry after an
    oscillating controller) gets a fresh generation — the second crossing
    really synchronizes instead of sailing through stale rank files."""
    c0, c1 = _pair(tmp_path)
    for _ in range(2):
        t = threading.Thread(target=lambda: c1.barrier("rung-abc"))
        t.start()
        c0.barrier("rung-abc")
        t.join()
    # generation 2 was a real rendezvous: rank 1 alone at a THIRD crossing
    # times out instead of finding leftover files
    with pytest.raises(TimeoutError, match="1/2"):
        c1.barrier("rung-abc", timeout=0.2)


def test_barrier_timeout_names_the_missing_fleet(tmp_path):
    c0, _ = _pair(tmp_path, timeout=0.25)
    with pytest.raises(TimeoutError) as ei:
        c0.barrier("rung-dead")
    msg = str(ei.value)
    assert "rung-dead" in msg and "1/2" in msg


def test_agreement_leader_wins_and_is_write_once(tmp_path):
    c0, c1 = _pair(tmp_path)
    got = {}
    t = threading.Thread(target=lambda: got.update(f=c1.agree("warmup-1", "8x2")))
    t.start()
    got["l"] = c0.agree("warmup-1", "4x2")
    t.join()
    assert got == {"l": "4x2", "f": "4x2"}     # follower adopted the leader
    # a restarted leader re-publishing the topic must NOT clobber the
    # decision followers already consumed
    assert c0.agree("warmup-1", "16x1") == "4x2"


def test_agreement_follower_timeout(tmp_path):
    _, c1 = _pair(tmp_path, timeout=0.25)
    with pytest.raises(TimeoutError, match="warmup-9"):
        c1.agree("warmup-9", "4x2")


def test_failure_broadcast_is_fleet_visible_and_idempotent(tmp_path):
    c0, c1 = _pair(tmp_path)
    assert c1.poll_failures() == frozenset()
    c0.broadcast_failure("deadbeef")
    c0.broadcast_failure("deadbeef")           # idempotent re-broadcast
    assert c1.poll_failures() == frozenset({"deadbeef"})
    c1.broadcast_failure("cafe0001")
    assert c0.poll_failures() == frozenset({"deadbeef", "cafe0001"})


def test_noop_coordinator_is_free():
    c = NoOpCoordinator()
    assert c.barrier("x") == 0.0
    assert c.agree("t", "4x2") == "4x2"
    c.broadcast_failure("x")
    assert c.poll_failures() == frozenset()


def test_distributed_coordinator_world_of_one():
    """The jax.distributed-backed impl degenerates correctly on a single
    process: free barriers (the allgather spans one host), echo agreement,
    and the barrier's failure exchange keeps local failures visible."""
    c = make_coordinator("distributed")
    assert (c.rank, c.world) == (0, 1)
    assert c.barrier("rung-x") >= 0.0
    assert c.agree("t1", "4x2") == "4x2"
    c.broadcast_failure("aabbccdd")
    assert "aabbccdd" in c.poll_failures()
    c.barrier("rung-y")                    # failure exchange round-trips
    assert "aabbccdd" in c.poll_failures()


def test_make_coordinator_resolution(tmp_path, monkeypatch):
    assert make_coordinator("none") is None
    with pytest.raises(ValueError, match="coord-dir"):
        make_coordinator("file")
    with pytest.raises(ValueError, match="unknown"):
        make_coordinator("gossip", root=str(tmp_path))
    monkeypatch.setenv("REPRO_COORD_RANK", "1")
    monkeypatch.setenv("REPRO_COORD_WORLD", "3")
    c = make_coordinator("file", root=str(tmp_path / "c"))
    assert (c.rank, c.world) == (1, 3)
    explicit = make_coordinator("file", root=str(tmp_path / "c"), rank=0,
                                world=2)
    assert (explicit.rank, explicit.world) == (0, 2)
    # run_id namespaces the shared dir: a different job reusing the same
    # --coord-dir cannot replay this run's barriers/agreements
    a = make_coordinator("file", root=str(tmp_path / "c"), rank=0, world=1,
                         run_id="job-aaaa")
    b = make_coordinator("file", root=str(tmp_path / "c"), rank=0, world=1,
                         run_id="job-bbbb")
    assert a.root != b.root
    a.broadcast_failure("dead")
    assert b.poll_failures() == frozenset()     # isolated namespaces
    with pytest.raises(ValueError, match="geometry"):
        FileCoordinator(str(tmp_path / "c"), rank=5, world=2)


# ------------------------------------------- engine coordination hooks ----

def test_remote_failure_downgrades_queued_warmup(tmp_path):
    """A rung another host flagged as warmup-failed gets its queued-not-
    started local warmup cancelled at rung entry (the coherent synchronous
    downgrade), counted in `coord_downgrades`, and the step is built in the
    foreground — no warmup_failure is charged to THIS host."""
    coord_a = FileCoordinator(str(tmp_path / "c"), 0, 2)
    coord_b = FileCoordinator(str(tmp_path / "c"), 1, 2)
    ladder = parse_ladder("2:1,2:2,2:4", workers=1)
    gate = threading.Event()

    class FakeJitted:
        def __init__(self, block):
            self.block = block

        def lower(self, *a):
            if self.block:
                gate.wait(timeout=30)
            return self

        def compile(self):
            return lambda *a: None

    built = []

    def wrap(batch_like):
        shapes = tuple(v.shape for v in batch_like.values())
        built.append(shapes)
        # the FIRST background build (rung 2:2) blocks the one-worker pool
        # so the 2:4 warmup stays QUEUED
        return FakeJitted(block=len(built) == 1)

    eng = BucketedEngine(wrap, ladder, params_like={}, opt_like={},
                         aot_warmup=True, coordinator=coord_b)
    src = MarkovTokens(vocab_size=32, seed=0)
    batch0 = make_batch(src, 0, ladder[0], seq_len=4)
    eng.warmup(ladder[1], batch0)      # running (blocked on gate)
    eng.warmup(ladder[2], batch0)      # queued behind it
    batch2 = make_batch(src, 1, ladder[2], seq_len=4)
    tag = engine_mod._key_tag(engine_mod._batch_key(batch2))
    # host A's warmup of the 2:4 rung died and was broadcast
    coord_a.broadcast_failure(tag)
    t = threading.Thread(target=lambda: coord_a.barrier(f"rung-{tag}"))
    t.start()
    fn = eng.get_step(batch2)          # downgrade + barrier + foreground build
    t.join()
    assert fn is not None
    assert eng.stats.coord_downgrades == 1
    assert eng.stats.warmup_failures == 0      # the failure was REMOTE
    assert eng.stats.barriers == 1
    gate.set()
    eng.drain()                        # the blocked 2:2 warmup completes fine
    assert eng.stats.warmups == 1


def test_engine_broadcasts_own_warmup_failure_promptly(tmp_path):
    """A failing background compile broadcasts its rung tag BEFORE any local
    consumption of the future — other hosts can downgrade while this host is
    still mid-step."""
    coord = FileCoordinator(str(tmp_path / "c"), 0, 2)
    observer = FileCoordinator(str(tmp_path / "c"), 1, 2)
    ladder = parse_ladder("2:1,2:2", workers=1)

    class Exploding:
        def lower(self, *a):
            raise RuntimeError("boom")

    eng = BucketedEngine(lambda bl: Exploding(), ladder, params_like={},
                         opt_like={}, aot_warmup=True, coordinator=coord)
    src = MarkovTokens(vocab_size=32, seed=0)
    eng.warmup(ladder[1], make_batch(src, 0, ladder[0], seq_len=4))
    deadline = time.monotonic() + 10
    while not observer.poll_failures():
        assert time.monotonic() < deadline, "failure never broadcast"
        time.sleep(0.01)
    # local accounting still happens exactly once, at consumption
    assert eng.stats.warmup_failures == 0
    with pytest.raises(RuntimeError, match="warmup compile"):
        eng.drain()
    assert eng.stats.warmup_failures == 1


# ------------------------------------- 2-process acceptance + restarts ----

_TWO_PROC_ENGINE = """
import json, sys
import jax, jax.numpy as jnp
from repro.core.schedule import parse_ladder
from repro.data.pipeline import MarkovTokens, make_batch
from repro.distributed.coordination import FileCoordinator
from repro.distributed.engine import BucketedEngine

rank = int(sys.argv[1])
coord = FileCoordinator(sys.argv[2], rank, 2, timeout=90.0)

def wrap(batch_like):
    return jax.jit(lambda p, o, b, lr: (p, o, {"loss": sum(
        jnp.sum(v) for v in b.values())}))

ladder = parse_ladder("2:1,2:2", workers=1)
eng = BucketedEngine(wrap, ladder, params_like={}, opt_like={},
                     aot_warmup=True, coordinator=coord)
src = MarkovTokens(vocab_size=32, seed=0)
batch0 = make_batch(src, 0, ladder[0], seq_len=8)
fn0 = eng.get_step(batch0)                     # rung-entry barrier + compile
eng.observe(ladder[0], ladder[0])
agreed = eng.warmup_agreed(ladder[0], batch0)  # fleet agrees: warm 2:2
assert agreed == ladder[1], agreed
eng.drain()                                    # background compile lands
before = (eng.stats.hits, eng.stats.compiles)
batch1 = make_batch(src, 1, ladder[1], seq_len=8)
fn1 = eng.get_step(batch1)                     # the post-increase step
after = (eng.stats.hits, eng.stats.compiles)
print("STATS", json.dumps({"rank": rank, "before": before, "after": after,
                           "engine": eng.stats.as_dict()}))
"""


def _launch_ranks(code, args, n=2, timeout=180):
    # the cache directory is the test's own, not one set from outside
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen([sys.executable, "-c", code, str(r), *args],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True, env=env) for r in range(n)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=timeout)
        assert p.returncode == 0, f"rank failed:\n{out}\n{err}"
        outs.append(out)
    return outs


def test_two_process_coordinated_warmup_post_increase_is_cache_hit(tmp_path):
    """The acceptance bar: after a coordinated warmup of the next rung, the
    first post-increase step is a cache hit on BOTH processes — `hits` goes
    up, `compiles` does not — with zero desyncs and two rung-entry barriers
    crossed by each host."""
    outs = _launch_ranks(_TWO_PROC_ENGINE, [str(tmp_path / "coord")])
    for out in outs:
        line = next(l for l in out.splitlines() if l.startswith("STATS"))
        s = json.loads(line.split(" ", 1)[1])
        hits0, compiles0 = s["before"]
        hits1, compiles1 = s["after"]
        assert hits1 == hits0 + 1, s          # post-increase step: a hit...
        assert compiles1 == compiles0, s      # ...not a foreground compile
        eng = s["engine"]
        assert eng["warmups"] == 1 and eng["warmup_failures"] == 0
        assert eng["desyncs"] == 0
        assert eng["barriers"] == 2           # one entry per distinct rung
        assert eng["compiles"] == 2           # first rung + the AOT warmup


_TWO_PROC_TRAIN = """
import json, sys
from repro.launch.train import TrainJob, run_training
rank, coord_dir = int(sys.argv[1]), sys.argv[2]
job = TrainJob(arch="llama3.2-1b", schedule="stagewise",
               stages=((0.5, 4), (0.5, 8)), steps=12, total_samples=48,
               seq_len=16, base_global_batch=4, max_global_batch=8,
               base_micro_batch=2, max_micro_batch=2, base_accum=2,
               step_impl="accum_norm", eval_every=0, aot_warmup=True,
               coord="file", coord_dir=coord_dir, coord_rank=rank,
               coord_world=2, coord_timeout=120.0)
h = run_training(job)
print("HIST", json.dumps({"rank": rank, "loss": h["loss"],
                          "gb": h["global_batch"], "engine": h["engine"]}))
"""


def test_two_process_training_over_batch_increase(tmp_path):
    """End-to-end `run_training` on two file-coordinated processes across a
    stagewise 4→8 increase: zero foreground compiles after the first rung on
    BOTH hosts (every later step a hit — the warmup covered the increase),
    zero desyncs/warmup failures, and bit-identical loss histories (the
    determinism contract the crc32 seed fix protects)."""
    outs = _launch_ranks(_TWO_PROC_TRAIN, [str(tmp_path / "coord")],
                         timeout=420)
    hists = []
    for out in outs:
        line = next(l for l in out.splitlines() if l.startswith("HIST"))
        hists.append(json.loads(line.split(" ", 1)[1]))
    for h in hists:
        eng = h["engine"]
        assert max(h["gb"]) == 8 and min(h["gb"]) == 4   # increase happened
        assert eng["warmup_failures"] == 0 and eng["desyncs"] == 0
        # the ONLY foreground compile is the very first rung; the increase
        # rode the coordinated warmup on this host
        assert eng["compiles"] - eng["warmups"] == 1, eng
        assert eng["hits"] == eng["steps"] - 1, eng
        assert eng["barriers"] == 2, eng
    assert hists[0]["loss"] == hists[1]["loss"]          # bit-identical


_RESTART_CACHE = """
import json, sys
import jax, jax.numpy as jnp
from repro.core.schedule import parse_ladder
from repro.data.pipeline import MarkovTokens, make_batch
from repro.distributed.engine import BucketedEngine

def wrap(batch_like):
    return jax.jit(lambda p, o, b, lr: (p, o, {"loss": sum(
        jnp.sum(v) for v in b.values())}))

ladder = parse_ladder("2:1", workers=1)
eng = BucketedEngine(wrap, ladder, persistent_cache_dir=sys.argv[2])
src = MarkovTokens(vocab_size=32, seed=0)
batch = make_batch(src, 0, ladder[0], seq_len=8)
fn = eng.get_step(batch)
out = fn({}, {}, {k: jnp.asarray(v) for k, v in batch.items()},
         jnp.float32(0.0))                     # lazy compile happens HERE
jax.block_until_ready(out)
eng.drain()
print("STATS", json.dumps(eng.stats.as_dict()))
"""


def test_persistent_cache_reused_across_engine_restart(tmp_path):
    """A restarted worker (fresh process, same per-job cache dir) must
    deserialize the executable from disk instead of recompiling:
    `disk_cache_hits` is 0 on the cold run and positive after restart."""
    cache = str(tmp_path / "compile-cache")
    cold = _launch_ranks(_RESTART_CACHE, [cache], n=1)[0]
    warm = _launch_ranks(_RESTART_CACHE, [cache], n=1)[0]
    s_cold = json.loads(next(l for l in cold.splitlines()
                             if l.startswith("STATS")).split(" ", 1)[1])
    s_warm = json.loads(next(l for l in warm.splitlines()
                             if l.startswith("STATS")).split(" ", 1)[1])
    assert s_cold["disk_cache_hits"] == 0, s_cold
    assert s_warm["disk_cache_hits"] >= 1, s_warm
    assert s_warm["compiles"] == s_cold["compiles"] == 1   # 1 trace each run


@pytest.mark.parametrize("env,flag,want", [
    (None, "", "default"),        # neither: <checkout>/.jax_cache
    (None, "flag", "flag"),       # --compile-cache
    ("env", "flag", "env"),       # $JAX_COMPILATION_CACHE_DIR wins, verbatim
])
def test_compile_cache_dir_resolution(monkeypatch, tmp_path, env, flag,
                                      want):
    from repro.distributed.coordination import (
        CACHE_ENV, DEFAULT_CACHE_DIR, compile_cache_dir)
    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    if env:
        monkeypatch.setenv(CACHE_ENV, str(tmp_path / env))
    else:
        monkeypatch.delenv(CACHE_ENV, raising=False)
    got = compile_cache_dir(str(tmp_path / flag) if flag else "")
    assert got == (DEFAULT_CACHE_DIR if want == "default"
                   else str(tmp_path / want))


_CACHE_PLACEMENT = """
import json, os, sys
import jax, jax.numpy as jnp
from repro.distributed.coordination import enable_persistent_cache
path = enable_persistent_cache(sys.argv[1])
jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)))
print("CACHE", json.dumps({"path": path,
                           "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_entries_land_in_resolved_dir(tmp_path, from_env):
    """Entries land in exactly the resolved directory — no version or
    backend subdirectory — and with $JAX_COMPILATION_CACHE_DIR set the code
    sets no directory of its own."""
    target = tmp_path / "cache"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(target)
    flag = str(tmp_path / "unused") if from_env else str(target)
    res = subprocess.run([sys.executable, "-c", _CACHE_PLACEMENT, flag],
                         capture_output=True, text=True, env=env,
                         timeout=180)
    assert res.returncode == 0, res.stderr
    got = json.loads(next(l for l in res.stdout.splitlines()
                          if l.startswith("CACHE")).split(" ", 1)[1])
    assert got == {"path": str(target), "config": str(target)}
    entries = os.listdir(target)
    assert entries and all(not (target / e).is_dir() for e in entries)
    assert not (tmp_path / "unused").exists()


def test_coord_none_bit_identical_to_uncoordinated(tmp_path):
    """--coord=none must be byte-for-byte the PR 4 single-host engine: same
    losses, same engine stats, and a file-coordinated world-of-one run also
    matches (its barriers are real but free)."""
    from repro.launch.train import TrainJob, run_training
    base = dict(arch="llama3.2-1b", steps=6, seq_len=16, base_global_batch=4,
                max_global_batch=16, base_micro_batch=2, max_micro_batch=2,
                base_accum=2, eta=0.12, step_impl="accum_norm", eval_every=0,
                aot_warmup=True)
    h_none = run_training(TrainJob(**base))
    h_solo = run_training(TrainJob(coord="file",
                                   coord_dir=str(tmp_path / "c"),
                                   coord_rank=0, coord_world=1, **base))
    assert h_none["loss"] == h_solo["loss"]              # bit-identical
    e_none, e_solo = h_none["engine"], h_solo["engine"]
    for k in ("compiles", "hits", "warmups", "steps", "buckets_used"):
        assert e_none[k] == e_solo[k], k
    assert e_none["barriers"] == 0                       # no coordinator
    assert e_solo["desyncs"] == e_solo["coord_downgrades"] == 0


# -------------------------------------------------- liveness (§12) ----

def test_barrier_timeout_is_typed_with_missing_ranks(tmp_path):
    """Both ranks alive (fresh heartbeats), one never arrives: a plain
    timeout, but TYPED and naming the missing rank id, not just a count."""
    from repro.distributed.coordination import CoordinationError
    c0, c1 = _pair(tmp_path, timeout=0.25)
    with pytest.raises(CoordinationError) as ei:
        c0.barrier("rung-solo")
    assert ei.value.missing_ranks == (1,)
    assert ei.value.dead_ranks == ()            # its heartbeat is fresh
    assert "missing ranks: [1]" in str(ei.value)
    assert isinstance(ei.value, TimeoutError)   # pre-liveness contract
    c0.close(), c1.close()


def test_barrier_fails_fast_when_missing_rank_is_dead(tmp_path):
    """A rank whose heartbeat was seen then went stale is DEAD: the barrier
    raises immediately with the blame attached instead of burning the full
    timeout."""
    from repro.distributed.coordination import CoordinationError
    d = str(tmp_path / "coord")
    c1 = FileCoordinator(d, 1, 2, heartbeat_s=0.05, dead_after=0.3)
    c1.close()                                  # rank 1 "dies": beat stops
    c0 = FileCoordinator(d, 0, 2, heartbeat_s=0.05, dead_after=0.3,
                         timeout=60.0)
    time.sleep(0.45)                            # let the heartbeat go stale
    t0 = time.monotonic()
    with pytest.raises(CoordinationError) as ei:
        c0.barrier("rung-x")
    assert time.monotonic() - t0 < 10.0         # fail-fast, not 60s
    assert ei.value.dead_ranks == (1,)
    assert "dead ranks (stale heartbeat): [1]" in str(ei.value)
    c0.close()


def test_agree_fails_fast_when_leader_is_dead(tmp_path):
    from repro.distributed.coordination import CoordinationError
    d = str(tmp_path / "coord")
    c0 = FileCoordinator(d, 0, 2, heartbeat_s=0.05, dead_after=0.3)
    c0.close()                                  # leader dies pre-publication
    c1 = FileCoordinator(d, 1, 2, heartbeat_s=0.05, dead_after=0.3,
                         timeout=60.0)
    time.sleep(0.45)
    t0 = time.monotonic()
    with pytest.raises(CoordinationError, match="heartbeat is stale") as ei:
        c1.agree("warmup-3", "4x2")
    assert time.monotonic() - t0 < 10.0
    assert ei.value.dead_ranks == (0,)
    c1.close()


def test_live_rank_never_reads_as_dead(tmp_path):
    """The heartbeat thread keeps a healthy rank fresh well past dead_after;
    only after it stops does the rank turn stale."""
    # dead_after is 20 heartbeats: a heartbeat thread starved for a few
    # hundred ms by a loaded machine is still alive
    d = str(tmp_path / "coord")
    c0 = FileCoordinator(d, 0, 2, heartbeat_s=0.05, dead_after=1.0)
    c1 = FileCoordinator(d, 1, 2, heartbeat_s=0.05, dead_after=1.0)
    for _ in range(3):                 # past several dead_after windows
        time.sleep(0.5)
        assert c0.dead_ranks() == frozenset()
    c1.close()
    deadline = time.time() + 10.0
    while c0.dead_ranks() != frozenset({1}) and time.time() < deadline:
        time.sleep(0.1)
    assert c0.dead_ranks() == frozenset({1})
    # a never-seen rank is only MISSING (could still be launching), not dead
    solo = FileCoordinator(str(tmp_path / "c2"), 0, 3, heartbeat_s=0.05,
                           dead_after=1.0)
    time.sleep(1.5)
    assert solo.dead_ranks() == frozenset()
    solo.close(), c0.close()
