"""Multi-host warmup coordination + persistent compile cache (DESIGN §8.1).

The bucketed engine makes a batch increase a cache hit on ONE host; on a
multi-host mesh that is not enough — the paper's efficiency case collapses
unless the rung transition is a cache hit on EVERY host, at the SAME step.
Three failure modes motivate this module:

* hosts entering a new rung's executable at different times stall the whole
  fleet on the slowest compile (collectives block until everyone arrives);
* each host *guessing* its own warmup target can diverge (e.g. after a
  restart, or any nondeterminism on the controller inputs) — then some hosts
  warm the wrong rung and pay a foreground compile at the transition;
* one host's background warmup failing while the others succeed leaves the
  fleet split between an AOT executable and a synchronous build.

`Coordinator` is the small protocol the engine consumes:

* ``barrier(name)``      — rung-entry barrier: returns the seconds THIS host
                           waited for the fleet (``EngineStats.barrier_wait_s``).
* ``agree(topic, p)``    — warmup agreement: every host proposes its next
                           rung; the leader's (rank 0) proposal wins and is
                           returned to everyone.  A host whose local proposal
                           differs counts a desync and warms the agreed rung.
* ``broadcast_failure``  / ``poll_failures`` — one host's warmup failure
                           downgrades ALL hosts to the synchronous-build
                           fallback coherently (nobody keeps waiting on a
                           warmup that will never land elsewhere).

Implementations:

* `NoOpCoordinator`      — single host; every operation is free.
* `FileCoordinator`      — a shared directory (NFS on real clusters, tmpdir
                           under ``--xla_force_host_platform_device_count``
                           subprocess tests).  Barriers are rank files in a
                           per-(name, generation) directory; agreement is an
                           atomic write-once file from the leader; failures
                           are marker files.  Restart semantics: barrier
                           files persist, so a restarted worker re-running
                           the same deterministic step sequence sails
                           through barriers the fleet already passed and
                           catches up to the live one.
* `DistributedCoordinator` — `jax.distributed` runs: barriers double as the
                           failure exchange (one `process_allgather` carries
                           each host's failed-rung tags), agreement is
                           `broadcast_one_to_all`.

The **persistent compile cache** half (`enable_persistent_cache`) points
JAX's compilation cache at one fixed directory (`compile_cache_dir`), so
restarted or late-joining workers reuse the fleet's executables; XLA's
cache key already carries the JAX version and backend.  A process-wide monitoring listener counts disk-cache hits
(`/jax/compilation_cache/cache_hits`) so `EngineStats` can distinguish a
compile served from disk from a fresh XLA build.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
import zlib

import numpy as np

from repro.testing.faults import fault_point


class CoordinationError(TimeoutError):
    """A coordination operation failed with structured blame: which ranks
    never arrived, and which of those are provably DEAD (their liveness
    heartbeat went stale after having been seen).  Subclasses TimeoutError
    so pre-liveness callers that caught the bare timeout keep working.

    The train driver catches this to checkpoint-and-exit cleanly instead of
    hanging the surviving ranks (DESIGN §12)."""

    def __init__(self, message: str, *, missing=(), dead=()):
        super().__init__(message)
        self.missing_ranks = tuple(missing)
        self.dead_ranks = tuple(dead)


def _blame(missing, dead) -> str:
    parts = []
    if missing:
        parts.append(f"missing ranks: {sorted(missing)}")
    if dead:
        parts.append(f"dead ranks (stale heartbeat): {sorted(dead)}")
    return "; ".join(parts) if parts else "all ranks present"


# ------------------------------------------------------------ protocol ----

class Coordinator:
    """What the bucketed engine needs from a multi-host rendezvous layer."""

    rank: int = 0
    world: int = 1

    def barrier(self, name: str, timeout: float | None = None) -> float:
        """Block until all `world` hosts reach `name`; return seconds waited."""
        raise NotImplementedError

    def agree(self, topic: str, payload: str) -> str:
        """Return the leader's `payload` for `topic` on every host."""
        raise NotImplementedError

    def broadcast_failure(self, tag: str) -> None:
        """Mark `tag` (a rung key digest) as failed fleet-wide."""
        raise NotImplementedError

    def poll_failures(self) -> frozenset:
        """Tags any host has marked failed (non-blocking; may lag until the
        next synchronization point on collective-backed impls)."""
        raise NotImplementedError

    def close(self) -> None:
        pass


class NoOpCoordinator(Coordinator):
    """Single-host: barriers are free, agreement echoes the proposal."""

    def barrier(self, name, timeout=None):
        return 0.0

    def agree(self, topic, payload):
        return payload

    def broadcast_failure(self, tag):
        pass

    def poll_failures(self):
        return frozenset()


# ------------------------------------------------------ file coordinator ----

def _fs_safe(name: str) -> str:
    """Filesystem-safe, collision-free token for an arbitrary name."""
    stem = re.sub(r"[^A-Za-z0-9_.x-]", "_", name)[:48]
    return f"{stem}-{zlib.crc32(name.encode()) & 0xFFFFFFFF:08x}"


class FileCoordinator(Coordinator):
    """Shared-directory rendezvous for multi-process (one JAX process per
    host) runs: subprocess tests under `--xla_force_host_platform_device_count`
    and real fleets with a shared filesystem.

    Every operation is lock-free on the consumer side: writers create files
    atomically (`os.replace` from a rank-private temp), readers poll.  The
    directory is append-only during a run — barrier generations, agreement
    topics and failure markers all get fresh paths — so a slow host can
    never miss an event that faster hosts already consumed.

    `run_id` namespaces the directory per job (`root/<run_id>/...`): a
    DIFFERENT job pointed at a reused coordination dir lands in its own
    namespace instead of silently sailing through the previous run's
    barrier files and replaying its write-once agreement decisions.
    Within one run_id, persistence is the restart contract: a restarted
    worker re-running the same deterministic step sequence skips barriers
    the fleet already passed and catches up to the live one.  Re-running
    an IDENTICAL job from scratch should use a fresh root.

    Liveness (DESIGN §12): a daemon thread refreshes ``hb/<rank>`` every
    `heartbeat_s`; a rank whose heartbeat was seen but has gone stale by
    more than `dead_after` seconds is DEAD.  A barrier whose missing ranks
    are all dead fails fast with a `CoordinationError` naming them instead
    of burning the full timeout, and every timeout names the missing/dead
    ranks rather than just a count.  A rank that never wrote a heartbeat is
    only *missing* (it may still be launching), so slow joiners get the
    whole timeout.  Polling backs off exponentially from `poll_s` to
    `poll_max_s` so fleet-scale shared filesystems aren't hammered at 200
    stats/s per rank for long waits.
    """

    def __init__(self, root: str, rank: int, world: int, *,
                 timeout: float = 120.0, poll_s: float = 0.005,
                 poll_max_s: float = 0.05, heartbeat_s: float | None = None,
                 dead_after: float | None = None, run_id: str = ""):
        if world < 1 or not (0 <= rank < world):
            raise ValueError(f"bad coordinator geometry rank={rank} world={world}")
        self.root = os.path.abspath(
            os.path.join(root, _fs_safe(run_id)) if run_id else root)
        self.rank, self.world = rank, world
        self.timeout, self.poll_s = timeout, poll_s
        self.poll_max_s = max(poll_max_s, poll_s)
        self.heartbeat_s = (heartbeat_s if heartbeat_s is not None else float(
            os.environ.get("REPRO_COORD_HEARTBEAT_S", "1.0")))
        self.dead_after = (dead_after if dead_after is not None else float(
            os.environ.get("REPRO_COORD_DEAD_AFTER_S",
                           str(10.0 * self.heartbeat_s))))
        self._gens: dict[str, int] = {}     # per-name barrier generation
        self._hb_dir = os.path.join(self.root, "hb")
        os.makedirs(self._hb_dir, exist_ok=True)
        self._stop = threading.Event()
        self._beat()                         # visible before any barrier
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, name=f"coord-hb-{rank}", daemon=True)
        self._hb_thread.start()

    # ------------------------------------------------------------ liveness --

    def _beat(self) -> None:
        self._atomic_write(os.path.join(self._hb_dir, str(self.rank)),
                           repr(time.time()))

    def _heartbeat_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_s):
            try:
                self._beat()
            except OSError:          # transient FS hiccup: stale beats are
                continue             # what the next refresh repairs

    def dead_ranks(self) -> frozenset:
        """Ranks whose heartbeat was SEEN but is now stale by > dead_after
        (started, then died/hung).  Never-seen ranks are not here — they may
        still be launching."""
        now = time.time()
        dead = set()
        for r in range(self.world):
            if r == self.rank:
                continue
            p = os.path.join(self._hb_dir, str(r))
            try:
                if now - os.path.getmtime(p) > self.dead_after:
                    dead.add(r)
            except OSError:
                continue             # no heartbeat yet: unknown, not dead
        return frozenset(dead)

    def close(self) -> None:
        self._stop.set()
        if self._hb_thread.is_alive():
            self._hb_thread.join(timeout=2 * self.heartbeat_s + 1.0)

    # ---------------------------------------------------------- primitives --

    def _atomic_write(self, path: str, content: str) -> None:
        tmp = f"{path}.tmp{self.rank}"
        with open(tmp, "w") as f:
            f.write(content)
        os.replace(tmp, path)

    def _poll_wait(self, waited_polls: int) -> None:
        """Exponential backoff: 5 ms doubling to the 50 ms cap, so a long
        barrier wait costs ~20 stats/s per rank instead of 200."""
        time.sleep(min(self.poll_s * (2 ** min(waited_polls, 16)),
                       self.poll_max_s))

    def barrier(self, name, timeout=None):
        timeout = self.timeout if timeout is None else timeout
        fault_point("coord.barrier", name=name, rank=self.rank)
        gen = self._gens[name] = self._gens.get(name, 0) + 1
        d = os.path.join(self.root, "barrier", f"{_fs_safe(name)}.{gen}")
        os.makedirs(d, exist_ok=True)
        self._atomic_write(os.path.join(d, str(self.rank)), "")
        t0 = time.monotonic()
        polls = 0
        while True:
            present = set()
            for f in os.listdir(d):
                try:                 # skip in-flight .tmp<rank> writes
                    present.add(int(f))
                except ValueError:
                    continue
            if len(present) >= self.world:
                return time.monotonic() - t0
            missing = set(range(self.world)) - present
            dead = self.dead_ranks() & missing
            timed_out = time.monotonic() - t0 > timeout
            if timed_out or (missing and missing <= dead):
                # every missing rank provably died: fail fast — waiting the
                # rest of the timeout cannot change the outcome
                raise CoordinationError(
                    f"coordination barrier {name!r} (generation {gen}): "
                    f"{len(present)}/{self.world} hosts arrived"
                    + (f" within {timeout:.1f}s" if timed_out else
                       " and every missing rank's heartbeat is stale")
                    + f" — {_blame(missing, dead)}; coordination dir: "
                    f"{self.root}", missing=missing, dead=dead)
            self._poll_wait(polls)
            polls += 1

    def agree(self, topic, payload):
        d = os.path.join(self.root, "agree")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, _fs_safe(topic))
        if self.rank == 0:
            # write-once: a restarted leader must republish the SAME value
            # (the topic stream is deterministic), never clobber a decision
            # followers may have consumed
            if not os.path.exists(path):
                self._atomic_write(path, payload)
            with open(path) as f:
                return f.read()
        t0 = time.monotonic()
        polls = 0
        while not os.path.exists(path):
            leader_dead = 0 in self.dead_ranks()
            if time.monotonic() - t0 > self.timeout or leader_dead:
                raise CoordinationError(
                    f"warmup agreement {topic!r}: leader (rank 0) published "
                    "nothing"
                    + (" and its heartbeat is stale" if leader_dead else
                       f" within {self.timeout:.1f}s")
                    + f" (coordination dir: {self.root})",
                    missing=(0,), dead=((0,) if leader_dead else ()))
            self._poll_wait(polls)
            polls += 1
        with open(path) as f:
            return f.read()

    def broadcast_failure(self, tag):
        d = os.path.join(self.root, "fail")
        os.makedirs(d, exist_ok=True)
        self._atomic_write(os.path.join(d, _fs_safe(tag)), tag)

    def poll_failures(self):
        d = os.path.join(self.root, "fail")
        if not os.path.isdir(d):
            return frozenset()
        tags = set()
        for entry in os.listdir(d):
            if entry.endswith(f".tmp{self.rank}"):
                continue
            try:
                with open(os.path.join(d, entry)) as f:
                    tags.add(f.read())
            except OSError:      # another rank's temp file vanished mid-list
                continue
        return frozenset(tags)


# ----------------------------------------------- jax.distributed backend ----

_PAYLOAD_BYTES = 1024


def _pack_str(s: str, n: int = _PAYLOAD_BYTES) -> np.ndarray:
    b = s.encode()
    if len(b) > n:
        raise ValueError(f"coordination payload too large ({len(b)} > {n})")
    arr = np.zeros(n, np.uint8)
    arr[: len(b)] = np.frombuffer(b, np.uint8)
    return arr


def _unpack_str(arr) -> str:
    return bytes(np.asarray(arr, np.uint8)).rstrip(b"\0").decode()


class DistributedCoordinator(Coordinator):
    """`jax.distributed`-backed coordination: barriers are a
    `process_allgather` that doubles as the failure exchange (each host
    contributes its locally-failed rung tags, so by the time anyone crosses
    a rung-entry barrier the whole fleet shares one failure view), and
    agreement is `broadcast_one_to_all` from process 0.

    `poll_failures` is non-blocking by design: it returns the view as of the
    last barrier plus this host's own failures — exactly the point where the
    engine consumes it (failures are checked AT rung entry, right next to
    the barrier that refreshes them).

    Timeouts: unlike the file coordinator, the collectives here cannot take
    a per-call deadline — a dead host surfaces through the `jax.distributed`
    runtime's own collective/heartbeat timeouts (configured at
    `jax.distributed.initialize`), not through `--coord-timeout`, which this
    backend ignores."""

    def __init__(self, timeout: float = 120.0):
        import jax
        self.rank = jax.process_index()
        self.world = jax.process_count()
        del timeout   # accepted for factory symmetry; see class docstring
        self._local: set[str] = set()
        self._known: set[str] = set()

    def barrier(self, name, timeout=None):
        from jax.experimental import multihost_utils
        fault_point("coord.barrier", name=name, rank=self.rank)
        t0 = time.monotonic()
        try:
            rows = multihost_utils.process_allgather(
                _pack_str(json.dumps(sorted(self._local))))
        except Exception as e:
            # the runtime's collective/heartbeat machinery already decided a
            # peer is gone; re-raise TYPED so the train driver's
            # checkpoint-and-exit path triggers (it cannot name the rank —
            # the runtime's error text usually does)
            raise CoordinationError(
                f"distributed barrier {name!r} failed across "
                f"{self.world} processes (a peer likely died): {e}") from e
        for row in np.atleast_2d(rows):
            self._known.update(json.loads(_unpack_str(row) or "[]"))
        return time.monotonic() - t0

    def agree(self, topic, payload):
        from jax.experimental import multihost_utils
        try:
            out = multihost_utils.broadcast_one_to_all(_pack_str(payload))
        except Exception as e:
            raise CoordinationError(
                f"distributed agreement {topic!r} failed (leader or a peer "
                f"died mid-broadcast): {e}", missing=(0,)) from e
        return _unpack_str(out)

    def broadcast_failure(self, tag):
        self._local.add(tag)

    def poll_failures(self):
        return frozenset(self._known | self._local)


# -------------------------------------------------------------- factory ----

def make_coordinator(kind: str, *, root: str = "", rank: int = -1,
                     world: int = 0, timeout: float = 120.0,
                     run_id: str = ""):
    """Resolve `--coord={none,file,distributed}` into a Coordinator (or None
    for `none` — the engine's coordination hooks vanish entirely, bit-
    identical to the uncoordinated single-host engine).

    `file` geometry resolves from explicit args first, then the
    `REPRO_COORD_RANK` / `REPRO_COORD_WORLD` environment (how the subprocess
    tests and the CI smoke launch per-host processes); `run_id` namespaces
    the shared directory per job (see FileCoordinator)."""
    if kind in ("none", "", None):
        return None
    if kind == "file":
        if not root:
            raise ValueError("--coord=file needs --coord-dir (a directory "
                             "shared by every host)")
        rank = rank if rank >= 0 else int(os.environ.get("REPRO_COORD_RANK", "0"))
        world = world or int(os.environ.get("REPRO_COORD_WORLD", "1"))
        return FileCoordinator(root, rank, world, timeout=timeout,
                               run_id=run_id)
    if kind == "distributed":
        return DistributedCoordinator(timeout=timeout)
    raise ValueError(f"unknown coordinator kind {kind!r} "
                     "(expected none|file|distributed)")


# ------------------------------------------- persistent compile cache ----

_disk_hits = 0
_listener_lock = threading.Lock()
_listener_installed = False


def _install_hit_listener() -> None:
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        import jax

        def _on_event(name: str, **kw) -> None:
            global _disk_hits
            if name == "/jax/compilation_cache/cache_hits":
                with _listener_lock:
                    _disk_hits += 1

        jax.monitoring.register_event_listener(_on_event)
        _listener_installed = True


def disk_cache_hits() -> int:
    """Process-wide count of compiles served from the persistent disk cache
    (0 until `enable_persistent_cache` installs the monitoring listener)."""
    with _listener_lock:
        return _disk_hits


CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: fixed, so every run of this checkout finds the
# entries of the last one (the path is part of nothing but the lookup)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compile_cache_dir(flag: str = "") -> str:
    """The persistent compile cache's directory: `$JAX_COMPILATION_CACHE_DIR`
    verbatim when set (JAX reads it itself), else `flag` (`--compile-cache`)
    when given, else `<checkout>/.jax_cache`."""
    return os.environ.get(CACHE_ENV) or flag or DEFAULT_CACHE_DIR


def enable_persistent_cache(flag: str = "") -> str:
    """Turn on JAX's persistent compilation cache at `compile_cache_dir(flag)`.

    Restarted or late-joining workers of the same job resolve to the same
    directory and deserialize the fleet's executables instead of
    recompiling; XLA's cache key carries the JAX version, backend and HLO,
    so one directory serves every toolchain and platform safely.  With
    `$JAX_COMPILATION_CACHE_DIR` set, no directory is set in code.
    Thresholds are zeroed so even smoke-scale steps persist — the
    multi-host tests restart an engine and assert a disk hit.  Returns the
    directory."""
    import jax
    path = compile_cache_dir(flag)
    if not os.environ.get(CACHE_ENV) and \
            jax.config.jax_compilation_cache_dir != path:
        from jax.experimental.compilation_cache import compilation_cache
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        # the cache binds its directory once per process: rebind it
        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _install_hit_listener()
    return path


__all__ = [
    "CoordinationError", "Coordinator", "NoOpCoordinator", "FileCoordinator",
    "DistributedCoordinator", "make_coordinator",
    "compile_cache_dir", "enable_persistent_cache", "disk_cache_hits",
]
