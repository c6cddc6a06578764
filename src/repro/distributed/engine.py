"""Bucketed step-execution engine: recompile-free adaptive batch growth.

Algorithm 1 grows the global batch mid-training; under XLA every new
(M, micro_batch, seq) input shape retraces and recompiles the distributed
step — minutes of stall per increase at scale, defeating the efficiency
argument that motivates adaptive schedules.  This engine makes a
controller-driven batch increase a dictionary lookup (full design, padding
accounting, and cache-key scheme: DESIGN.md §8 "Bucketed step compilation"):

* a precomputed **ladder** of shape buckets (`core.schedule.bucket_ladder`,
  powers-of-two capacities consistent with `round_plan`);
* **quantization**: a requested `BatchPlan` maps to the smallest rung whose
  capacity covers it (never shrinking the request, clamped at `max_global`);
* **padding**: the real samples are laid into the rung's (M, B, seq) shape
  and the tail is filled with `labels = -1` slots, which the masked-mean,
  valid-token-weighted loss ignores exactly (`data.pipeline.pad_to_bucket`);
* a keyed **cache of compiled steps** — one trace per (rung, seq_len,
  extra-input) signature for the whole run;
* optional **ahead-of-time warmup** of the next-larger rung in a background
  thread, overlapped with training (XLA compilation releases the GIL), so
  the first step after an increase doesn't pay the compile either;
* optional **multi-host coordination** (DESIGN §8.1, `coordination.py`):
  rung-entry barriers so every host enters a new rung's executable together,
  leader-decided warmup agreement instead of per-host guessing, and a
  failure broadcast that downgrades the whole fleet to the synchronous-build
  fallback coherently when any host's warmup dies — plus the persistent
  compile cache so restarted / late-joining workers deserialize executables
  from disk instead of recompiling.

`EngineStats` (compile count, cache hits, padding-waste fraction, barrier
waits, desyncs, disk-cache hits) threads through `launch/train.py` history
into `benchmarks/run.py` rows so the recompile savings stay measurable.
"""

from __future__ import annotations

import contextlib
import threading
import time
import zlib
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from jax import set_mesh
from repro.core.schedule import BatchPlan, LadderShapeError, quantize_to_ladder
from repro.distributed.coordination import disk_cache_hits, enable_persistent_cache
from repro.testing.faults import fault_point


@dataclass
class EngineStats:
    """Counters proving the cache works (emitted into benchmark rows).

    `compiles`/`warmups` count COMPLETED builds only — a queued background
    warmup increments them when (and only when) its compile succeeds;
    failures land in `warmup_failures` and are re-raised by `drain()`."""
    compiles: int = 0          # distinct traces built (>= 1 per bucket used)
    hits: int = 0              # steps served from the cache
    warmups: int = 0           # buckets compiled ahead of time
    warmup_failures: int = 0   # background compiles that PERMANENTLY failed
    warmup_retries: int = 0    # transient warmup-compile attempts retried
    steps: int = 0
    real_samples: int = 0
    padded_samples: int = 0
    buckets_used: list = field(default_factory=list)
    # rung-transition accounting (DESIGN §14): a transition is a step whose
    # input signature differs from the previous step's; a transition HIT
    # found its executable already cached or pending from an AOT warmup
    # (a pending compile is still a hit — the step waits on the background
    # build instead of paying a fresh foreground trace).  Predictive warmup
    # targeting aims for transition_hits == transitions.
    transitions: int = 0
    transition_hits: int = 0
    # multi-host coordination (DESIGN §8.1; all zero without a coordinator)
    barriers: int = 0          # rung-entry barriers crossed
    barrier_wait_s: float = 0.0   # seconds THIS host waited for the fleet
    desyncs: int = 0           # local warmup proposal != fleet agreement
    coord_downgrades: int = 0  # queued warmups dropped on a remote failure
    # compiles served from the persistent disk cache — PROCESS-wide since
    # engine construction (the monitoring counter cannot attribute a hit to
    # a jit): sibling jits like train.py's eval fn count too, so read this
    # as "executables this job reused from disk", not an engine-only figure
    disk_cache_hits: int = 0
    # seconds `RungCache.lookup` blocked for an executable: a pending
    # warmup's result, a foreground build, or another caller's build
    lookup_wait_s: float = 0.0

    @property
    def hit_rate(self) -> float:
        return self.hits / self.steps if self.steps else 0.0

    @property
    def padding_waste(self) -> float:
        total = self.real_samples + self.padded_samples
        return self.padded_samples / total if total else 0.0

    def as_dict(self) -> dict:
        return {
            "compiles": self.compiles,
            "hits": self.hits,
            "warmups": self.warmups,
            "warmup_failures": self.warmup_failures,
            "warmup_retries": self.warmup_retries,
            "steps": self.steps,
            "hit_rate": round(self.hit_rate, 4),
            "padding_waste": round(self.padding_waste, 4),
            "buckets_used": list(self.buckets_used),
            "transitions": self.transitions,
            "transition_hits": self.transition_hits,
            "barriers": self.barriers,
            "barrier_wait_s": round(self.barrier_wait_s, 4),
            "desyncs": self.desyncs,
            "coord_downgrades": self.coord_downgrades,
            "disk_cache_hits": self.disk_cache_hits,
            "lookup_wait_s": self.lookup_wait_s,
        }


def _batch_key(batch_like) -> tuple:
    """Cache key: the full input signature (names x shapes x dtypes), so any
    shape-relevant change — rung, seq_len, extra frontend inputs — is a new
    entry and everything else is a guaranteed hit."""
    return tuple(sorted(
        (k, tuple(v.shape), str(v.dtype)) for k, v in batch_like.items()))


def _sds(batch):
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}


def _key_tag(key: tuple) -> str:
    """Short, deterministic, filesystem-safe digest of a cache key — the
    vocabulary the coordinator speaks (barrier names, failure tags)."""
    return f"{zlib.crc32(repr(key).encode()) & 0xFFFFFFFF:08x}"


def _plan_tag(plan: BatchPlan | None) -> str:
    """Warmup-agreement payload: a rung identity, or 'none' at the ladder top."""
    return "none" if plan is None else f"{plan.micro_batch}x{plan.accum_steps}"


class RungCache:
    """The shared rung-cache/warmup core (DESIGN §8/§11).

    A keyed cache of compiled executables with (a) per-key build rendezvous —
    concurrent callers of the same key produce exactly ONE trace — and (b) a
    single-worker background AOT-warmup pool with exactly-once failure
    accounting.  Training's `BucketedEngine` and serving's
    `distributed.serve_engine.ServeEngine` both subclass it; a subclass
    supplies `_build` (foreground trace for a key's build argument) and
    `_aot_build` (background build + lower + compile).

    Thread safety: every `_cache`/`_pending`/`_building` access happens
    under `_lock`; the blocking waits (a pending warmup's `result()`, the
    actual trace) happen OUTSIDE it.

    Transient-failure policy (DESIGN §12): a background warmup compile that
    raises is retried up to `warmup_retries` times with exponential backoff
    (`warmup_backoff_s`, doubling) before it is treated as PERMANENT —
    only then does `_on_warmup_build_failure` fire (on the coordinated
    engine that hook broadcasts the failure fleet-wide, so a one-off OOM
    or filesystem blip no longer downgrades every host for the rest of the
    run).  Retry attempts are counted in `stats.warmup_retries`."""

    def __init__(self, *, mesh=None, aot: bool = False, stats=None,
                 warmup_retries: int = 2, warmup_backoff_s: float = 0.05):
        self._mesh = mesh
        self._aot = bool(aot)
        self._cache: dict[tuple, object] = {}     # ALL access under _lock
        self._lock = threading.Lock()
        self._pool = ThreadPoolExecutor(max_workers=1) if self._aot else None
        self._pending: dict[tuple, object] = {}   # key -> warmup Future
        self._building: dict[tuple, Future] = {}  # key -> foreground build
        self._warmup_errors: list[Exception] = []
        self._warmup_retries = max(0, int(warmup_retries))
        self._warmup_backoff_s = warmup_backoff_s
        self.stats = stats if stats is not None else EngineStats()

    # ------------------------------------------------------------- hooks --

    def _mesh_ctx(self):
        return (set_mesh(self._mesh) if self._mesh is not None
                else contextlib.nullcontext())

    def _build(self, build_arg):
        """Foreground trace for one key (subclass hook)."""
        raise NotImplementedError

    def _aot_build(self, build_arg):
        """Background build + AOT lower/compile for one key (subclass
        hook); only called when the cache was constructed with aot=True."""
        raise NotImplementedError

    def _on_warmup_build_failure(self, key: tuple):
        """Called from the warmup worker the moment its compile raises
        (before the failure is consumed); coordination hook, default no-op."""

    # ------------------------------------------------------------- cache --

    def lookup(self, key: tuple, build_arg):
        """The compiled executable for `key`; traces at most once per key
        across the run, even with concurrent callers.  A background warmup
        that failed is recorded (surfaced later by `drain()`) and the call
        falls back to a synchronous build.

        Every `_cache` read/write happens under `_lock` (a finishing AOT
        warmup and a foreground build used to race the unlocked check,
        double-compiling and double-counting `stats.compiles`).  Concurrent
        foreground callers rendezvous on a per-key `Future` in `_building`,
        so exactly one traces and the rest wait for it."""
        with self._lock:
            fut = self._pending.pop(key, None)
        if fut is not None:
            try:
                with self._waiting():
                    fn = fut.result()  # warmup finished or finishes now
            except Exception as e:               # noqa: BLE001 — surfaced in drain()
                self._record_warmup_failure(e, key)
            else:
                with self._lock:
                    self._cache.setdefault(key, fn)
        while True:
            with self._lock:
                fn = self._cache.get(key)
                if fn is not None:
                    self.stats.hits += 1
                    return fn
                bfut = self._building.get(key)
                if bfut is None:
                    bfut = self._building[key] = Future()
                    mine = True
                else:
                    mine = False
            if mine:
                try:
                    fault_point("engine.compile", key=key)
                    with self._waiting():
                        fn = self._build(build_arg)
                except BaseException as e:
                    with self._lock:
                        self._building.pop(key, None)
                    bfut.set_exception(e)
                    raise
                with self._lock:
                    self._cache[key] = fn
                    self._building.pop(key, None)
                    self.stats.compiles += 1
                bfut.set_result(fn)
                return fn
            # another foreground caller owns the build: wait, then re-check
            # the cache (on its failure, loop around and build ourselves).
            # Only the BUILDER's propagated failure is absorbed — an
            # interrupt raised in THIS thread while blocked must escape, or
            # Ctrl-C during a compile wait would silently retry forever.
            try:
                with self._waiting():
                    bfut.result()
            except Exception:                  # noqa: BLE001 — builder raised
                pass

    @contextlib.contextmanager
    def _waiting(self):
        """Count the enclosed wait in `stats.lookup_wait_s`."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            waited = time.perf_counter() - t0
            with self._lock:
                self.stats.lookup_wait_s += waited

    def cached(self, key: tuple) -> bool:
        """True when `key`'s executable is already resident (no build or
        warmup-wait would be paid to use it)."""
        with self._lock:
            return key in self._cache

    # ------------------------------------------------------- AOT warmup --

    def submit_warmup(self, key: tuple, build_arg) -> bool:
        """Queue a background AOT compile of `key`; no-op (False) when
        warmup is disabled or the key is already cached/pending.

        Stats accounting happens on COMPLETION inside the worker: a queued
        compile that later fails contributes to `warmup_failures`, never to
        `warmups`/`compiles`."""
        if not self._aot:
            return False
        with self._lock:
            if key in self._cache or key in self._pending:
                return False
            self._pending[key] = self._pool.submit(self._warm, build_arg, key)
        return True

    def _warm(self, build_arg, key):
        attempt = 0
        while True:
            try:
                fault_point("engine.warmup_compile", key=key, attempt=attempt)
                compiled = self._aot_build(build_arg)
                break
            except Exception:
                # transient until proven otherwise: bounded retry-with-
                # backoff BEFORE the permanent-failure hook (which, under
                # coordination, broadcasts the downgrade fleet-wide)
                if attempt >= self._warmup_retries:
                    self._on_warmup_build_failure(key)
                    raise
                attempt += 1
                with self._lock:
                    self.stats.warmup_retries += 1
                time.sleep(self._warmup_backoff_s * (2 ** (attempt - 1)))
            except BaseException:
                # interrupts/exits are never retried; the hook still fires
                # IMMEDIATELY (not when the failed future is eventually
                # consumed) — local stats stay consumption-time, exactly
                # once, in lookup/drain
                self._on_warmup_build_failure(key)
                raise
        with self._lock:     # success: count the finished warmup
            self.stats.warmups += 1
            self.stats.compiles += 1
        return compiled

    def _record_warmup_failure(self, exc: Exception, key: tuple | None = None):
        with self._lock:
            self.stats.warmup_failures += 1
            self._warmup_errors.append(exc)

    def drain(self, raise_errors: bool = True):
        """Block until queued warmups land in the cache (tests/teardown).

        Warmup exceptions — both ones recorded earlier by `lookup`'s
        fallback and ones surfacing now — are re-raised here (first one,
        with the failure count) instead of being swallowed into cache
        entries.  Pass raise_errors=False to only record them in
        `stats.warmup_failures` (the training loop does this: a failed
        warmup already fell back to a synchronous compile).

        Accounting is per-future exactly-once: a future is CLAIMED by
        atomically popping its key from `_pending` under the lock, and only
        the claimant records its outcome.  (`drain` used to iterate a stale
        snapshot of `_pending` while `get_step` popped and recorded the same
        future's failure — the one exception inflated `warmup_failures` to 2
        and a handled error was re-raised.)"""
        while True:
            with self._lock:
                if not self._pending:
                    break
                key = next(iter(self._pending))
                fut = self._pending.pop(key)
            try:
                fn = fut.result()
            except Exception as e:               # noqa: BLE001
                self._record_warmup_failure(e, key)
            else:
                with self._lock:   # cache writes stay under the lock
                    self._cache.setdefault(key, fn)
        with self._lock:
            errors, count = list(self._warmup_errors), self.stats.warmup_failures
            self._warmup_errors = []
        if errors and raise_errors:
            raise RuntimeError(
                f"{count} AOT warmup compile(s) failed; first error follows"
            ) from errors[0]


class BucketedEngine(RungCache):
    """Keyed cache of compiled train steps over a bucket ladder.

    wrap        : the step builder from `make_fsdp_norm_step` /
                  `make_accum_norm_step` (batch_like -> jitted step).
    ladder      : tuple[BatchPlan] from `core.schedule.bucket_ladder`.
    mesh        : bound while building/compiling (background threads must
                  re-enter it; mesh contexts are thread-local).
    params_like / opt_like : abstract step operands, only needed for
                  `aot_warmup` (lower+compile needs the full signature).
    coordinator : a `coordination.Coordinator` for multi-host runs (None =
                  uncoordinated, bit-identical to the single-host engine):
                  rung-entry barriers, warmup agreement, failure broadcast.
    persistent_cache_dir : when set, wires JAX's persistent compilation
                  cache (`coordination.compile_cache_dir`) so restarted or
                  late-joining workers deserialize executables from disk;
                  `stats.disk_cache_hits` counts the reuses.
    """

    def __init__(self, wrap, ladder: tuple[BatchPlan, ...], *, mesh=None,
                 params_like=None, opt_like=None, aot_warmup: bool = False,
                 coordinator=None, persistent_cache_dir: str | None = None,
                 warmup_retries: int = 2, warmup_backoff_s: float = 0.05):
        if not ladder:
            raise ValueError("bucket ladder must have at least one rung")
        super().__init__(mesh=mesh,
                         aot=aot_warmup and params_like is not None,
                         warmup_retries=warmup_retries,
                         warmup_backoff_s=warmup_backoff_s)
        self._wrap = wrap
        # the builder's shared per-step-signature FlatLayout (None on the
        # pure tree path): pinned at construction so every rung this engine
        # compiles provably reuses ONE layout (DESIGN §9/§10)
        self._flat_layout = getattr(wrap, "flat_layout", None)
        self.ladder = tuple(sorted(ladder, key=lambda p: p.global_batch))
        self._params_like = params_like
        self._opt_like = opt_like
        self._coord = coordinator
        self._last_key = None         # last step signature (transition stats)
        self._agree_seq = 0           # monotone warmup-agreement topic id
        self._agreed_for = None       # (bucket, proposal) the last agreement
        self._agreed_target = None    # ...and the rung the fleet settled on
        if persistent_cache_dir:
            enable_persistent_cache(persistent_cache_dir)
        # disk hits are a process-wide monitoring counter; this engine
        # reports the delta since its construction (an engine restart with a
        # warm cache directory therefore starts back at 0 and counts reuses)
        self._disk_base = disk_cache_hits()

    # ------------------------------------------------------ quantization --

    def bucket_for(self, desired_global: int,
                   max_global: int | None = None) -> BatchPlan:
        return quantize_to_ladder(desired_global, self.ladder, max_global)

    def next_bucket(self, bucket: BatchPlan) -> BatchPlan | None:
        """The next-larger rung (the AOT warmup target), or None at the top."""
        for plan in self.ladder:
            if plan.global_batch > bucket.global_batch:
                return plan
        return None

    # ------------------------------------------------------------- cache --

    def _build(self, batch_like):
        with self._mesh_ctx():
            fn = self._wrap(batch_like)
        lay = getattr(self._wrap, "flat_layout", None)
        if lay is not self._flat_layout:
            raise RuntimeError(
                "step builder changed its FlatLayout across bucket "
                "signatures — the per-step-signature layout must be built "
                "once and reused for every ladder rung (DESIGN §9/§10), or "
                "flat-resident params/moments from one rung would not feed "
                "the step compiled for the next")
        return fn

    def trace_step(self, batch_like):
        """Trace-only jaxpr of the step at `batch_like`'s signature — the
        `repro.analysis` entry point.  Never executes, never compiles, and
        never touches the cache or stats: the closed jaxpr of the FULL
        jitted step (pjit eqn included, so marker eqns, shardings, and
        donation flags are all visible to the static checker).  Off-ladder
        shapes raise `LadderShapeError` exactly as `get_step` would."""
        if self._params_like is None or self._opt_like is None:
            raise ValueError(
                "trace_step needs params_like/opt_like (the full abstract "
                "step signature) — construct the engine with both")
        self.check_on_ladder(batch_like)
        fn = self._build(_sds(batch_like))
        with self._mesh_ctx():
            return jax.make_jaxpr(fn)(
                self._params_like, self._opt_like, _sds(batch_like),
                jax.ShapeDtypeStruct((), jnp.float32))

    def lower_step(self, batch_like):
        """Lowered-HLO handle of the step at `batch_like`'s signature —
        the layer-3 cost-model entry point (DESIGN §15).  Lowers but never
        compiles, and like `trace_step` never touches the cache or stats;
        the returned `jax.stages.Lowered` exposes `.as_text()` (donation
        aliasing, shardings) and `.cost_analysis()` without ever loading
        an executable.  Off-ladder shapes raise `LadderShapeError`."""
        if self._params_like is None or self._opt_like is None:
            raise ValueError(
                "lower_step needs params_like/opt_like (the full abstract "
                "step signature) — construct the engine with both")
        self.check_on_ladder(batch_like)
        fn = self._build(_sds(batch_like))
        with self._mesh_ctx():
            return fn.lower(
                self._params_like, self._opt_like, _sds(batch_like),
                jax.ShapeDtypeStruct((), jnp.float32))

    def check_on_ladder(self, batch_like):
        """Reject a batch whose leading (M, B) dims match no ladder rung —
        BEFORE the cache is keyed or anything traces, so an off-ladder
        shape costs zero fresh lowerings instead of a silent one-off
        compile.  Leaves with fewer than two dims (scalars, per-step
        side inputs) carry no rung identity and are skipped."""
        rungs = sorted({(p.accum_steps, p.workers * p.micro_batch)
                        for p in self.ladder})
        for name in sorted(batch_like):
            v = batch_like[name]
            if len(getattr(v, "shape", ())) < 2:
                continue
            lead = tuple(v.shape[:2])
            if lead not in rungs:
                raise LadderShapeError(
                    f"batch leaf {name!r} has leading (M, B) dims {lead}, "
                    f"matching no ladder rung {rungs}; quantize the plan "
                    f"with bucket_for() and pad with pad_to_bucket() before "
                    f"stepping")

    def get_step(self, batch):
        """The compiled step for this (padded) batch's signature; traces at
        most once per signature across the run, even with concurrent
        callers (`RungCache.lookup`).  Off-ladder shapes are rejected up
        front with `LadderShapeError` (zero fresh lowerings).

        With a coordinator, stepping into a DIFFERENT signature than the
        last step is a rung transition: remote warmup failures are polled
        (a rung any host flagged gets its queued-not-started warmup dropped
        — the coherent downgrade to the synchronous path) and the rung-entry
        barrier holds this host until the whole fleet is ready to enter the
        new executable together."""
        self.check_on_ladder(batch)
        key = _batch_key(batch)
        if key != self._last_key:
            if self._last_key is not None:
                # a rung transition: count whether AOT warmup covered it
                # (cached, or pending — waiting on a background compile is
                # the warmed path, not a fresh foreground trace)
                with self._lock:
                    self.stats.transitions += 1
                    if key in self._cache or key in self._pending:
                        self.stats.transition_hits += 1
            if self._coord is not None:
                self._enter_rung(key)
            self._last_key = key
        return self.lookup(key, _sds(batch))

    def _enter_rung(self, key: tuple):
        """Multi-host rung transition (DESIGN §8.1): coherent-downgrade check
        + entry barrier.  Called once per change of step signature."""
        tag = _key_tag(key)
        if tag in self._coord.poll_failures():
            # some host's warmup of THIS rung died: nobody may depend on a
            # background compile landing.  A queued-not-started warmup is
            # cancelled (foreground build instead); one already running is
            # left in place — blocking on an in-flight compile IS the
            # synchronous fallback, and cancelling it could not stop it.
            with self._lock:
                fut = self._pending.get(key)
                if fut is not None and fut.cancel():
                    self._pending.pop(key, None)
                    self.stats.coord_downgrades += 1
        wait = self._coord.barrier(f"rung-{tag}")
        with self._lock:
            self.stats.barriers += 1
            self.stats.barrier_wait_s += wait

    def _record_warmup_failure(self, exc: Exception, key: tuple | None = None):
        super()._record_warmup_failure(exc, key)
        if self._coord is not None and key is not None:
            # fleet-wide coherence: every other host downgrades this rung to
            # the synchronous-build fallback instead of waiting on a warmup
            self._coord.broadcast_failure(_key_tag(key))

    def observe(self, plan: BatchPlan, bucket: BatchPlan):
        """Record one executed step's padding accounting."""
        self.stats.steps += 1
        self.stats.real_samples += plan.global_batch
        self.stats.padded_samples += bucket.global_batch - plan.global_batch
        tag = f"{bucket.micro_batch}x{bucket.accum_steps}"
        if tag not in self.stats.buckets_used:
            self.stats.buckets_used.append(tag)
        self._refresh_disk_hits()

    def _refresh_disk_hits(self):
        """Fold the process-wide persistent-cache hit counter into stats.

        Foreground compiles are lazy (XLA builds at the step's first CALL,
        after `get_step` returned), so the delta is refreshed at the two
        points that straddle them: each `observe` and `drain`."""
        hits = disk_cache_hits() - self._disk_base
        if hits > self.stats.disk_cache_hits:
            self.stats.disk_cache_hits = hits

    # ------------------------------------------------------- AOT warmup --

    def warmup(self, bucket: BatchPlan, batch_example: dict):
        """Queue an ahead-of-time compile of `bucket` shaped like
        `batch_example` (tail dims reused; leading dims replaced by the
        rung's (M, B)).  No-op unless aot_warmup was enabled.

        Stats accounting happens on COMPLETION inside the worker: a queued
        compile that later fails contributes to `warmup_failures`, never to
        `warmups`/`compiles`."""
        if not self._aot or bucket is None:
            return
        batch_like = {
            k: jax.ShapeDtypeStruct(
                (bucket.accum_steps, bucket.workers * bucket.micro_batch)
                + tuple(v.shape[2:]), v.dtype)
            for k, v in batch_example.items()}
        self.submit_warmup(_batch_key(batch_like), batch_like)

    def warmup_agreed(self, bucket: BatchPlan, batch_example: dict,
                      proposal: BatchPlan | None = None):
        """Coordinated AOT warmup: the fleet agrees on ONE rung to
        background-compile instead of each host guessing (DESIGN §8.1).

        `proposal` is the rung to warm — the caller's predicted target rung
        (DESIGN §14) or, when None, the next-larger rung (the pre-predictor
        behavior).  Every host submits its proposal; the leader's wins.  A
        host whose proposal differs (controller state drifted, restart
        mid-ladder) counts a `desync` and warms the agreed rung anyway, so
        the eventual rung transition is a cache hit everywhere.  Returns
        the rung actually queued (None at the ladder top).

        One agreement per (bucket, proposal) CHANGE, not per step:
        re-agreeing every step would add a per-step fleet rendezvous (and,
        on the file coordinator, a file per step) to the hot loop for an
        answer that cannot change.  Topic ids are a per-engine monotone
        counter, and both the bucket sequence and the caller's proposal are
        pure functions of globally-reduced controller state, so hosts
        trigger re-agreement at the same steps and consume the same topic
        stream; a host whose local state drifted still converges on the
        leader's answer via the desync path.

        Uncoordinated (or world-of-one) engines skip the agreement and
        behave exactly like `warmup(proposal or next_bucket(bucket), ...)`."""
        if proposal is None:
            proposal = self.next_bucket(bucket)
        if (not self._aot or self._coord is None
                or getattr(self._coord, "world", 1) == 1):
            self.warmup(proposal, batch_example)
            return proposal
        cur = (_plan_tag(bucket), _plan_tag(proposal))
        if cur != self._agreed_for:
            self._agree_seq += 1
            prop_tag = _plan_tag(proposal)
            agreed = self._coord.agree(f"warmup-{self._agree_seq}", prop_tag)
            target = proposal
            if agreed != prop_tag:
                with self._lock:
                    self.stats.desyncs += 1
                target = next(
                    (p for p in self.ladder if _plan_tag(p) == agreed), None)
            self._agreed_for, self._agreed_target = cur, target
        if self._agreed_target is not None:
            self.warmup(self._agreed_target, batch_example)
        return self._agreed_target

    def _aot_build(self, batch_like):
        fn = self._build(batch_like)
        with self._mesh_ctx():
            return fn.lower(
                self._params_like, self._opt_like, batch_like,
                jax.ShapeDtypeStruct((), jnp.float32)).compile()

    def _on_warmup_build_failure(self, key: tuple):
        # broadcast IMMEDIATELY (not when this host eventually consumes
        # the failed future): hosts polling at rung entry downgrade to
        # the synchronous build instead of counting on a warmup that
        # already died.  Local stats stay consumption-time — exactly
        # once, in get_step/drain — and the broadcast is idempotent.
        if self._coord is not None:
            self._coord.broadcast_failure(_key_tag(key))

    def drain(self, raise_errors: bool = True):
        try:
            super().drain(raise_errors)
        finally:
            self._refresh_disk_hits()


__all__ = ["BucketedEngine", "EngineStats", "LadderShapeError", "RungCache"]
