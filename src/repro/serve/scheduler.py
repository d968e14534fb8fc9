"""Continuous-batching serving: paged KV-cache pool, persistent
sessions, streaming delivery, concurrent multi-tenant front-end.

The bucketed ``Engine`` holds every request of an equal-length batch
until the WHOLE batch finishes — one long generation stalls the bucket
and throughput collapses under mixed-length traffic.  The ``Scheduler``
instead owns a fixed pool of ``max_slots`` decode slots and runs ONE
jitted decode program per step over all slots.

Since the paged-pool PR the cache is no longer a monolithic per-slot
region but a **paged pool** (vLLM-style): attention K/V lives in shared
fixed-size pages (``lm.init_paged_pool``), each slot holds a block
table of page ids, and the decode program reads/writes THROUGH the
block table (``lm.decode_step_paged``).  SSM state stays per-slot —
it is O(1) in sequence length, so there is nothing to page.  On top of
paging:

  * **shared-prefix reuse** — prompts are hashed at page granularity
    with a rolling chain (``serve.paging.PagePool``); a new request
    whose prefix pages are resident refcounts them and prefills only
    its tail, attending to the reused pages as context
    (``lm.prefill_paged``).  Retired requests' prefix pages stay cached
    (refcount 0, still indexed) until allocation pressure evicts them,
    so reuse works across sequential requests, not just concurrent
    ones.  Reuse auto-disables when it cannot be token-exact: configs
    with SSM layers (recurrent state is not per-position shareable) or
    a lossy ``cache_dtype`` (reused pages would round the context the
    reference prefill saw at compute precision).
  * **batched burst prefill** — all requests admitted at one step
    prefill together in one padded ``(B, bucket)`` program instead of
    one at a time; programs are keyed by (prompt-tail bucket,
    power-of-two batch width), keeping the compile budget bounded.

All serve-loop *state* lives in a long-lived :class:`ServeSession`: the
device cache pool, the ``PagePool`` prefix index, the slot allocator
and the per-slot host arrays are built ONCE and survive across an
arbitrary sequence of ``submit()`` / ``step()`` / ``serve()`` calls.
A system-prompt prefix filled by one trace is therefore a *hit* in the
next (``PageStats.cross_trace_hits``) instead of the cold miss the old
per-``serve()`` pool rebuild forced.  ``submit()`` returns a
:class:`StreamHandle` whose tokens are observable as they are produced
(``on_token`` per-step callback, iterator-style ``stream()`` drain);
``Scheduler.serve()`` is a thin batch wrapper over the scheduler's
persistent default session.

**Multi-tenant front-end** (this layer is what makes the session safe
under real concurrent traffic — docs/serving.md "Multi-tenant
serving"):

  * **thread safety** — every session entry point takes one re-entrant
    lock (a ``threading.Condition``); producers on any number of
    threads may ``submit()``/``stream()``/``wait()`` concurrently.
  * **single pump** — ``start()`` launches ONE background pump thread
    that owns ``step()``; while it runs, ``step()`` from any other
    thread raises (double-stepping a tick from two threads was the
    historical ``stream()`` race) and blocking observers wait on the
    condition instead of pumping.  Without a driver the session stays
    cooperatively pumped exactly as before, now under the lock.
  * **priority / fairness** — ``Request.priority`` (weight >= 1,
    higher = more slot share) selects admissions by stride scheduling:
    each class accumulates virtual time ``1/priority`` per admission
    and the eligible class with the least virtual time admits next
    (FIFO within a class; a lone class reduces to plain FIFO).
  * **admission control / shedding** — ``max_queue`` bounds the
    pending queue; an overloaded ``submit()``/``serve()`` raises the
    shared ``ValueError`` contract (``engine.check_queue_capacity``)
    and the session stays untouched, so callers can retry/back off.
  * **preemption** — under slot/page pressure a strictly
    higher-priority arrival evicts the lowest-priority occupant: its
    pages are released (registered prefix pages stay CACHED in the
    ``PagePool`` chain index) and the victim re-queues; on re-admission
    it re-prefills ``prompt + generated[:-1]`` — hitting its own
    still-cached pages — and resumes decoding at the exact position it
    left, so its token stream is unchanged.
  * **chunked prefill** — with ``prefill_chunk=C`` a long prompt tail
    fills C tokens per scheduler tick (one batched program over all
    chunking slots) instead of monopolizing a tick with one huge
    prefill, so co-tenant decode steps interleave with the fill.

Both paging features are ``Scheduler`` options that default ON;
``paged=False`` reproduces the pre-paging monolithic per-slot behavior
exactly (that path still runs ``lm.prefill`` + ``lm.insert_cache_slot``,
through the same persistent session machinery).  Preemption and chunked
prefill share prefix reuse's exactness gate (attention-only cache at
compute precision): re-prefilled K/V is bitwise what decode wrote, for
the same reason reused prefix pages are — masked lanes are arithmetic
zeros under XLA's order-preserving reductions.

Scheduling never changes numerics: for greedy decoding the served
tokens are *token-exact* against ``Engine.generate`` run per request
(tests/test_serve_scheduler.py, tests/test_serve_session.py,
tests/test_serve_concurrent.py), with paging, prefix reuse, burst
prefill, session persistence, priorities, preemption and chunked
prefill all enabled — regardless of tenant interleaving.  Admission
control raises the shared ``ValueError`` capacity contract
(``serve.check_capacity`` + per-pool ``paging.check_page_capacity`` +
``serve.check_unique_rids`` + ``serve.check_queue_capacity``).  See
docs/serving.md for the full design.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import threading
import time
from collections import Counter, deque
from functools import partial
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Set, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.registry import Built, Replay, register_contract
from repro.dist import sharding as shd
from repro.models import lm
from repro.models.config import LMConfig

from .engine import (
    check_capacity,
    check_queue_capacity,
    check_unique_rids,
    derive_request_keys,
    numerics_ctx,
    sample_tokens,
)
from .paging import PagePool, check_page_capacity, pages_needed


@dataclasses.dataclass
class Request:
    """One generation request for the continuous-batching scheduler."""
    prompt: np.ndarray                 # (P,) int32 token ids
    n_tokens: int = 32
    temperature: float = 0.0
    rid: Optional[int] = None          # defaults to submission index
    arrival: int = 0                   # earliest scheduler step it may join
                                       # (relative to the current trace)
    priority: int = 1                  # fairness weight (>= 1, higher = more
                                       # slot share; may preempt lower classes)
    tenant: str = "default"            # reporting label, carried to the result

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: np.ndarray                 # (P + generated,) prompt included
    prompt_len: int
    arrival: int
    admitted_step: int                 # FIRST admission (preemptions keep it)
    finished_step: int
    finished_wall_s: float             # seconds since the trace started
    prefix_hit_tokens: int = 0         # prompt tokens served from cached pages
    priority: int = 1
    tenant: str = "default"
    preemptions: int = 0               # times this request was evicted+resumed

    @property
    def generated(self) -> np.ndarray:
        return self.tokens[self.prompt_len:]


@dataclasses.dataclass
class ServeStats:
    steps: int                         # scheduler ticks, idle ones included
    decode_steps: int
    prefills: int                      # requests prefilled
    max_slots: int
    generated_tokens: int
    wall_s: float
    occupancy: float                   # mean fraction of slots active per decode step
    prefill_batches: int = 0           # prefill programs launched (== prefills
                                       # without burst batching)
    prefix_reuse_active: bool = False
    paging: Optional[dict] = None      # per-trace PageStats delta in paged mode
                                       # (cross_trace_* fields count hits on
                                       # pages filled by EARLIER traces)
    trace_index: int = 0               # which trace of the session this was
    pool_bytes: int = 0                # device cache-pool footprint (persists
                                       # across traces)
    preemptions: int = 0               # occupants evicted for a higher class
    prefill_chunks: int = 0            # chunked-prefill rows advanced
    shed: int = 0                      # submissions rejected by max_queue


class SlotAllocator:
    """Fixed pool of decode slot ids with LIFO reuse.

    LIFO keeps a just-retired slot's cache region hot: it is overwritten
    by the very next admission instead of cycling through the pool."""

    def __init__(self, n_slots: int):
        if n_slots < 1:
            raise ValueError(f"need at least one slot, got {n_slots}")
        self.n_slots = n_slots
        self._free: List[int] = list(reversed(range(n_slots)))
        self._busy: set = set()

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def busy(self) -> frozenset:
        return frozenset(self._busy)

    def acquire(self) -> int:
        if not self._free:
            raise RuntimeError("no free slot")
        slot = self._free.pop()
        self._busy.add(slot)
        return slot

    def release(self, slot: int) -> None:
        if slot not in self._busy:
            raise ValueError(f"slot {slot} is not in use")
        self._busy.discard(slot)
        self._free.append(slot)


def default_prefill_buckets(max_len: int) -> List[int]:
    """Powers of two up to max_len (max_len always included): a bounded
    set of compiled prefill shapes serves every admissible prompt."""
    buckets = []
    b = 2
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return buckets


def _prefill_fn(params, pool, tokens, valid_len, slot, key, temp, *,
                cfg: LMConfig, max_len: int):
    """Legacy (paged=False) prefill, jitted once per prompt bucket:
    prefill one request (right-padded to the bucket), overwrite slot
    ``slot`` of the monolithic pool with its cache, sample its first
    token at per-request step 0."""
    caches, logits = lm.prefill(
        params, {"tokens": tokens}, cfg, max_len=max_len, valid_len=valid_len
    )
    pool = lm.insert_cache_slot(pool, caches, slot)
    tok0 = sample_tokens(
        logits[:, -1], key[None], jnp.zeros((1,), jnp.int32), temp
    )[0]
    return shd.constrain_pool(pool), tok0


def _decode_fn(params, pool, cur, pos, active, keys, steps, temps, *,
               cfg: LMConfig):
    """Legacy (paged=False) decode, jitted exactly once: one step over
    ALL slots.  ``pos`` is the per-slot length vector; inactive slots are
    clamped to position 0 so their (discarded) writes stay in bounds, and
    their sampled token is masked to -1 so host code can never mistake it
    for output."""
    pos_eff = jnp.where(active, pos, 0)
    logits, pool = lm.decode_step(
        params, {"tokens": cur[:, None]}, pos_eff, pool, cfg
    )
    nxt = sample_tokens(logits[:, -1], keys, steps, temps)
    # Pin the returned (donated) pool's layout to the committed input
    # layout, so sharded serving never recompiles on pool rebinding.
    return shd.constrain_pool(pool), jnp.where(active, nxt, -1)


def _decode_paged_fn(params, pool, cur, pos, active, block_tables, keys,
                     steps, temps, *, cfg: LMConfig):
    """Jitted exactly once: one decode step over ALL slots, reading the
    paged pool through the block tables.  Inactive slots clamp to
    position 0 AND carry an all-garbage block table row, so their
    discarded writes land in the reserved garbage page — never in a
    page another request owns."""
    pos_eff = jnp.where(active, pos, 0)
    logits, pool = lm.decode_step_paged(
        params, {"tokens": cur[:, None]}, pos_eff, pool, block_tables, cfg
    )
    nxt = sample_tokens(logits[:, -1], keys, steps, temps)
    return shd.constrain_pool(pool), jnp.where(active, nxt, -1)


def _burst_prefill_fn(params, pool, tokens, block_tables, slots, ctx_len,
                      tail_valid, keys, temps, *, cfg: LMConfig,
                      page_size: int, use_context: bool):
    """Jitted once per (tail bucket, burst width): prefill a whole
    admission burst (or one chunked-prefill advance over all chunking
    slots) into the paged pool and sample each member's first token at
    per-request step 0.  Padding rows carry tail_valid == 0, the
    garbage slot and an all-garbage block table; their sampled token is
    junk the host ignores (as is every non-final chunk row's).
    ``use_context`` is False when neither prefix reuse nor chunked
    prefill can produce a nonzero ctx_len — the compiled program then
    skips the context gather entirely."""
    pool, logits = lm.prefill_paged(
        params, {"tokens": tokens}, cfg, pool, block_tables, slots,
        ctx_len, tail_valid, page_size, use_context,
    )
    toks = sample_tokens(
        logits[:, -1], keys, jnp.zeros((tokens.shape[0],), jnp.int32), temps
    )
    return shd.constrain_pool(pool), toks


class StreamHandle:
    """Observable handle for one submitted request.

    Tokens land on the handle as the session produces them — the first
    token at admission (sampled by the prefill program), one more per
    decode step until retirement (EOS or ``n_tokens``).  Three ways to
    observe them:

      * ``on_token(handle, token)`` — called synchronously for every
        produced token, from inside :meth:`ServeSession.step`, after
        that step's slot bookkeeping has completed (so a raising
        callback interrupts the caller but never corrupts the session;
        callbacks it pre-empted fire on the next ``step()``).  With a
        background driver, delivery is pinned to the pump thread —
        never to whichever thread happens to observe.
      * ``stream()`` — an iterator yielding tokens as they are
        produced.  On a driven session it *blocks* on delivered tokens
        (the single-pump invariant: it never steps a session a driver
        owns); on an undriven session it pumps ``step()`` cooperatively
        under the session lock, as it always did.
      * ``wait(timeout=None)`` — block until the request retires and
        return its :class:`RequestResult`.

    ``result`` is the final :class:`RequestResult` (``None`` until the
    request retires); ``generated`` is the tokens produced *so far*."""

    def __init__(self, session: "ServeSession", request: Request,
                 key: np.ndarray,
                 on_token: Optional[Callable[["StreamHandle", int], None]] = None):
        self.session = session
        self.request = request
        self.rid = request.rid
        self.key = np.asarray(key)
        self.on_token = on_token
        self.result: Optional[RequestResult] = None
        self._tokens: List[int] = []
        self._seq = -1                   # session submission order
        self._admitted: Optional[int] = None   # first admission step
        self._hit_tokens0 = 0            # prefix hits at first admission
        self._preempt_count = 0
        self._blocked_at: Optional[int] = None  # step first resource-blocked

    @property
    def done(self) -> bool:
        return self.result is not None

    @property
    def n_generated(self) -> int:
        return len(self._tokens)

    @property
    def generated(self) -> np.ndarray:
        with self.session._cv:
            return np.asarray(self._tokens, np.int32)

    def wait(self, timeout: Optional[float] = None) -> RequestResult:
        """Block until this request retires; returns its result.  On a
        driven session this waits on the pump; otherwise it pumps the
        session cooperatively.  Raises ``TimeoutError`` if ``timeout``
        (seconds) elapses first, and re-raises a pump failure."""
        sess = self.session
        deadline = None if timeout is None else time.monotonic() + timeout
        with sess._cv:
            while not self.done:
                sess._raise_pump_error()
                if sess._driven_elsewhere():
                    if not sess._cv_wait(deadline):
                        raise TimeoutError(
                            f"request {self.rid} not done after {timeout}s"
                        )
                else:
                    sess._step_locked()
            return self.result

    def stream(self) -> Iterator[int]:
        """Yield this request's generated tokens in order.  Never holds
        the session lock across a ``yield`` — consumers may block
        arbitrarily.  With a background driver this blocks on delivered
        tokens; without one it drives ``step()`` itself (other
        concurrently-submitted requests make progress too — their
        handles fill while this one streams)."""
        i = 0
        sess = self.session
        while True:
            with sess._cv:
                while not self._tokens[i:] and not self.done:
                    sess._raise_pump_error()
                    if sess._driven_elsewhere():
                        sess._cv.wait()
                    else:
                        sess._step_locked()
                batch = self._tokens[i:]
                finished = self.done
            if not batch and finished:
                return
            for tok in batch:
                yield tok
            i += len(batch)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        state = "done" if self.done else "live"
        return f"StreamHandle(rid={self.rid}, {state}, {self.n_generated} tokens)"


class ServeSession:
    """Long-lived serving state over one :class:`Scheduler`'s compiled
    programs.

    The device cache pool (paged or monolithic), the ``PagePool`` host
    index, the slot allocator and the per-slot host arrays are built
    once, here, and survive across traces: a *trace* is one busy period
    — it begins when a request is submitted to an idle session and ends
    when the last live request retires.  Step numbers (``arrival``,
    ``admitted_step``, ``finished_step``) are relative to the current
    trace, so back-to-back ``serve()`` calls see the same schedule they
    always did — but prefix pages cached by an earlier trace are HITS
    (``ServeStats.paging["cross_trace_hits"]``), not cold misses, and
    no device allocation or jit compile happens between traces.

    ``submit()`` enqueues one request and returns its
    :class:`StreamHandle`; ``step()`` runs one scheduler tick
    (admissions, chunked-prefill advance, then one decode step over all
    slots); ``drain()`` steps (or, driven, waits) until idle;
    ``serve()`` is submit-all + drain with batch-level validation,
    returning results in submission order.

    **Threading model** (docs/architecture.md): ONE re-entrant lock —
    the condition ``_cv`` — guards all session state; every public
    method takes it, so any number of producer threads may submit,
    stream and wait concurrently.  ``start()`` spawns the single
    background pump thread that then exclusively owns ``step()`` (the
    single-pump invariant); ``stop()`` joins it.  ``on_token``
    callbacks run on whichever thread executes the step — the pump
    thread, when driven — while holding the session lock, so a callback
    may re-enter ``submit()`` directly; a callback must NOT block
    waiting for another thread's session call (that thread needs this
    lock), and threads a callback signals may safely call ``submit()``
    — they simply serialize behind the running step."""

    def __init__(self, sched: "Scheduler"):
        self.s = sched
        S = sched.max_slots
        if sched.paged:
            self.pool = lm.init_paged_pool(
                sched.cfg, S, sched.n_pages, sched.page_size,
                mesh=sched.mesh,
            )
            self.ppool: Optional[PagePool] = PagePool(
                sched.n_pages, sched.page_size
            )
            self.btables = np.zeros((S, sched.pages_per_slot), np.int32)
        else:
            self.pool = lm.init_cache(sched.cfg, S, sched.max_len)
            self.ppool = None
            self.btables = None
        self.pool_bytes = lm.pool_nbytes(self.pool)
        self.alloc = SlotAllocator(S)
        self.pos = np.zeros(S, np.int32)
        self.active = np.zeros(S, bool)
        self.cur = np.zeros(S, np.int32)
        self.keys = np.zeros((S, 2), np.uint32)
        self.steps = np.zeros(S, np.int32)     # tokens sampled per occupant
        self.temps = np.zeros(S, np.float32)
        self.occupant: List[Optional[dict]] = [None] * S

        # Pending admissions, sorted by (arrival, submission seq) — FIFO
        # within an arrival step.  A plain list: fairness selection and
        # preemption re-queueing remove/insert at arbitrary positions.
        self.queue: List[StreamHandle] = []
        self._seq = 0                          # submission order counter
        # Stride-scheduling state: virtual time per priority class and
        # the floor newly-active classes start from (so a newcomer class
        # neither monopolizes nor starves).
        self._vt: Dict[int, float] = {}
        self._vt_floor = 0.0
        # Slots whose prompt is still being chunk-prefilled (inactive
        # for decode, but busy in the allocator).
        self._chunk_slots: Set[int] = set()
        # Tokens recorded but whose on_token callbacks have not fired
        # yet: callbacks run AFTER a step's slot bookkeeping completes,
        # so a raising callback can never leave the session half-updated
        # (undelivered callbacks fire on the next step()/drain()).
        self._events: "deque[Tuple[StreamHandle, int]]" = deque()
        self._live_rids: Set[int] = set()
        self._next_rid = 0                     # submit() auto-id counter
        self.trace_index = -1                  # bumped at each trace start
        self._in_trace = False
        self.last_stats: Optional[ServeStats] = None

        # Concurrency: one condition (re-entrant lock) guards ALL of the
        # state above; the optional background pump is the only thread
        # allowed to step while it runs.
        # Session-lifetime totals (never reset by trace boundaries; the
        # per-trace values land on ServeStats).  A multi-trace driver —
        # e.g. a bursty producer pool that lets the session idle
        # mid-burst — reads deltas of these instead of stitching
        # last_stats together.
        self.total_preemptions = 0
        self.total_prefill_chunks = 0
        self.total_shed = 0
        self._cv = threading.Condition(threading.RLock())
        self._driver: Optional[threading.Thread] = None
        self._driver_ident: Optional[int] = None
        self._stop_flag = False
        self._pump_error: Optional[BaseException] = None
        self._in_step = False
        self._reset_trace_counters()

    # --------------------------- trace lifecycle -----------------------------
    def _reset_trace_counters(self) -> None:
        self.step_idx = 0
        self.decode_steps = 0
        self.prefills = 0
        self.prefill_batches = 0
        self.active_slot_steps = 0
        self.gen_tokens = 0
        self.preemptions = 0
        self.prefill_chunks = 0
        self.shed = 0
        self._t0 = time.perf_counter()
        self._pg0 = self.ppool.stats.snapshot() if self.ppool else None

    def _ensure_trace(self) -> None:
        if self._in_trace:
            return
        self.trace_index += 1
        self._in_trace = True
        if self.ppool is not None:
            self.ppool.begin_trace()
        self._reset_trace_counters()

    def _finalize_trace(self) -> None:
        self._in_trace = False
        stats = ServeStats(
            steps=self.step_idx,
            decode_steps=self.decode_steps,
            prefills=self.prefills,
            max_slots=self.s.max_slots,
            generated_tokens=self.gen_tokens,
            wall_s=time.perf_counter() - self._t0,
            occupancy=(
                self.active_slot_steps / (self.decode_steps * self.s.max_slots)
                if self.decode_steps else 0.0
            ),
            prefill_batches=self.prefill_batches,
            prefix_reuse_active=self.s.prefix_reuse_active,
            paging=(
                self.ppool.stats.delta(self._pg0)
                if self.ppool is not None else None
            ),
            trace_index=self.trace_index,
            pool_bytes=self.pool_bytes,
            preemptions=self.preemptions,
            prefill_chunks=self.prefill_chunks,
            shed=self.shed,
        )
        self.last_stats = stats
        self.s.last_stats = stats

    @property
    def idle(self) -> bool:
        """No queued, no decoding and no chunk-prefilling requests."""
        return (not self.queue and not self.active.any()
                and not self._chunk_slots)

    # ------------------------------- driver ----------------------------------
    def _driven_elsewhere(self) -> bool:
        """A background pump owns stepping and this is not its thread."""
        return (self._driver is not None
                and threading.get_ident() != self._driver_ident)

    def _raise_pump_error(self) -> None:
        """Re-raise (once) an exception that killed the pump — typically
        a raising ``on_token`` callback.  Mirrors cooperative semantics:
        the raise interrupts one observer; the session itself stays
        consistent and resumable."""
        err, self._pump_error = self._pump_error, None
        if err is not None:
            raise err

    def _cv_wait(self, deadline: Optional[float]) -> bool:
        """Wait on the condition until notified; False on deadline."""
        if deadline is None:
            self._cv.wait()
            return True
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            return False
        self._cv.wait(remaining)
        return True

    def start(self) -> "ServeSession":
        """Launch the background pump thread.  While it runs it is the
        ONLY thread allowed to call ``step()`` — producers submit and
        block on handles/``wait_idle()`` instead.  Idempotent errors:
        raises if a driver is already attached."""
        with self._cv:
            if self._driver is not None:
                raise RuntimeError("session already has a background driver")
            self._pump_error = None
            self._stop_flag = False
            t = threading.Thread(
                target=self._pump, name="serve-session-pump", daemon=True
            )
            self._driver = t
            self._driver_ident = None    # set by the pump itself, under _cv
            t.start()
        return self

    def stop(self) -> None:
        """Stop and join the background pump.  Re-raises (once) an error
        that killed the pump, so a raising callback is never silently
        swallowed by a ``driving()`` exit."""
        with self._cv:
            t = self._driver
            self._stop_flag = True
            self._cv.notify_all()
        if t is not None:
            t.join()
        with self._cv:
            self._driver = None
            self._driver_ident = None
            self._raise_pump_error()

    @contextlib.contextmanager
    def driving(self):
        """``with session.driving():`` — pump in the background for the
        block's duration (``start()``/``stop()`` bracket)."""
        self.start()
        try:
            yield self
        finally:
            self.stop()

    def _pump(self) -> None:
        with self._cv:
            self._driver_ident = threading.get_ident()
            self._cv.notify_all()
            while True:
                while not self._stop_flag and self.idle:
                    self._cv.wait()
                if self._stop_flag:
                    return
                try:
                    self._step_locked()
                except BaseException as e:    # stash for observers, die
                    self._pump_error = e
                    self._driver = None
                    self._driver_ident = None
                    self._cv.notify_all()
                    return

    def wait_idle(self, timeout: Optional[float] = None) -> None:
        """Block until every queued and live request has retired.  On a
        driven session waits on the pump; otherwise pumps cooperatively
        (== ``drain()``).  Raises ``TimeoutError`` on expiry."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while True:
                self._raise_pump_error()
                if self._driven_elsewhere():
                    if self.idle and not self._events:
                        return
                    if not self._cv_wait(deadline):
                        raise TimeoutError(f"session not idle after {timeout}s")
                    continue
                while not self.idle:
                    self._step_locked()
                self._emit_events()
                return

    # --------------------------- token delivery ------------------------------
    def _record_token(self, handle: StreamHandle, tok: int) -> None:
        """Record a produced token on its handle; the on_token callback
        is deferred to the end of the current step so user code runs
        only against consistent session state."""
        handle._tokens.append(int(tok))
        self.gen_tokens += 1
        if handle.on_token is not None:
            self._events.append((handle, int(tok)))

    def _emit_events(self) -> None:
        """Deliver deferred on_token callbacks.  Only ever called from
        the stepping thread — the pump, when a driver is attached — so
        callback delivery is pinned to one thread regardless of how many
        observers are blocked on the session."""
        while self._events:
            handle, tok = self._events.popleft()
            handle.on_token(handle, tok)

    # ----------------------------- submission --------------------------------
    def _validate(self, req: Request) -> None:
        if req.n_tokens < 1:
            raise ValueError(f"request {req.rid}: n_tokens must be >= 1")
        if req.prompt.size < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.priority < 1:
            raise ValueError(
                f"request {req.rid}: priority must be >= 1, got {req.priority}"
            )
        check_capacity(req.prompt.size, req.n_tokens, self.s.max_len)
        if self.s.paged:
            check_page_capacity(
                req.prompt.size, req.n_tokens, self.s.page_size,
                self.s.n_pages - 1,
            )
        if req.rid in self._live_rids:
            # Results are keyed (and PRNG streams derived) by rid: a
            # collision with a LIVE request would overwrite its output
            # and share its sampling stream.
            raise ValueError(
                f"duplicate request id {req.rid}: a request with this id "
                f"is still queued or decoding in this session"
            )

    def _check_queue_room(self, incoming: int) -> None:
        """Overload shedding: reject (ValueError) submissions that would
        overflow ``max_queue``.  Counted per trace in ``ServeStats.shed``;
        preemption re-queues are exempt (they were already admitted)."""
        try:
            check_queue_capacity(len(self.queue), incoming, self.s.max_queue)
        except ValueError:
            self.shed += incoming
            self.total_shed += incoming
            raise

    def _auto_rid(self) -> int:
        while self._next_rid in self._live_rids:
            self._next_rid += 1
        rid = self._next_rid
        self._next_rid += 1
        return rid

    @staticmethod
    def _qkey(handle: StreamHandle) -> Tuple[int, int]:
        return (handle.request.arrival, handle._seq)

    def _insert_sorted(self, handle: StreamHandle) -> None:
        keys = [self._qkey(h) for h in self.queue]
        self.queue.insert(bisect.bisect_right(keys, self._qkey(handle)), handle)

    def _enqueue(self, req: Request, seed: Optional[int],
                 on_token=None, sorted_insert: bool = True) -> StreamHandle:
        """Post-validation enqueue shared by ``submit`` and ``serve``."""
        seed = self.s.seed if seed is None else seed
        # np.asarray BEFORE the [0]: indexing the device array with a
        # Python int would transfer the index constant implicitly (the
        # transfers lint runs submit/step under a disallow guard).
        key = np.asarray(derive_request_keys(seed, [req.rid]))[0]
        self._ensure_trace()
        handle = StreamHandle(self, req, key, on_token=on_token)
        handle._seq = self._seq
        self._seq += 1
        self._live_rids.add(req.rid)
        if sorted_insert:
            self._insert_sorted(handle)
        else:
            self.queue.append(handle)   # caller re-sorts the batch once
        return handle

    def _requeue(self, handle: StreamHandle) -> None:
        """Re-queue a preempted (already admitted) request; bypasses
        validation and shedding — its rid stays live, its delivered
        tokens stay delivered."""
        self._insert_sorted(handle)

    def submit(
        self,
        request: Union[Request, np.ndarray, list],
        seed: Optional[int] = None,
        on_token: Optional[Callable[[StreamHandle, int], None]] = None,
    ) -> StreamHandle:
        """Enqueue one request (validated now — the shared ``ValueError``
        capacity/rid/queue contracts — but admitted by a later
        ``step()``).  Thread-safe: any producer thread may call this,
        including an ``on_token`` callback (it already holds the session
        lock).  Safe to call mid-trace: the request joins the current
        trace with ``arrival`` relative to its step counter.  A failed
        validation leaves the session untouched and reusable."""
        with self._cv:
            req = request if isinstance(request, Request) else Request(prompt=request)
            if req.rid is None:
                req = dataclasses.replace(req, rid=self._auto_rid())
            self._validate(req)
            self._check_queue_room(1)
            handle = self._enqueue(req, seed, on_token=on_token)
            self._cv.notify_all()       # wake the pump / blocked observers
            return handle

    def serve(
        self,
        requests: Sequence[Union[Request, np.ndarray, list]],
        seed: Optional[int] = None,
    ) -> List[RequestResult]:
        """Submit a whole arrival trace and drain it to completion;
        results come back in submission order and the trace's
        ``ServeStats`` lands on ``last_stats`` (and on the scheduler).
        The WHOLE batch is validated before any request is enqueued, so
        a rejected trace leaves the session state untouched.  On a
        driven session this blocks until the batch's handles are done
        (the pump does the stepping).  Default rids count up from 0
        (the historical submission-index ids) but skip ids still live
        in the session, so serving a batch alongside in-flight
        ``submit()`` handles cannot spuriously collide."""
        with self._cv:
            reqs: List[Request] = []
            taken = set(self._live_rids)
            for i, r in enumerate(requests):
                if not isinstance(r, Request):
                    r = Request(prompt=r)
                if r.rid is None:
                    rid = i                 # historical submission-index default
                    while rid in taken:     # ...unless a live/assigned id holds it
                        rid += 1
                    r = dataclasses.replace(r, rid=rid)
                    taken.add(rid)
                reqs.append(r)
            check_unique_rids([r.rid for r in reqs])
            for r in reqs:
                self._validate(r)
            if not reqs:
                # On an idle session an empty serve() still lands fresh
                # stats: an empty trace begins and finalizes immediately
                # (all-zero counters) instead of leaving a previous trace's
                # numbers up.  Mid-trace (live submit() handles) it must NOT
                # finalize — that would publish partial stats and reset the
                # running trace's counters under its in-flight requests.
                if self.idle:
                    self._ensure_trace()
                    self._finalize_trace()
                return []
            self._check_queue_room(len(reqs))
            handles = [self._enqueue(r, seed, sorted_insert=False) for r in reqs]
            # One stable sort for the whole batch: equal (arrival, seq)
            # cannot occur, so submission order is preserved exactly.
            self.queue.sort(key=self._qkey)
            self._cv.notify_all()
            if self._driven_elsewhere():
                while not all(h.done for h in handles):
                    self._raise_pump_error()
                    if not self._driven_elsewhere():
                        break           # driver stopped: finish cooperatively
                    self._cv.wait()
                if not all(h.done for h in handles):
                    self.drain()
            else:
                self.drain()
            return [h.result for h in handles]

    # ------------------------------ stepping ---------------------------------
    def drain(self) -> None:
        """Until the session is idle: step it (cooperative) or wait on
        the pump (driven), then flush any deferred on_token callbacks —
        so a drain() after a raising callback always delivers what the
        raise pre-empted, even when the session is already idle."""
        with self._cv:
            while True:
                self._raise_pump_error()
                if self._driven_elsewhere():
                    if self.idle and not self._events:
                        return
                    self._cv.wait()
                    continue
                if self.idle:
                    self._emit_events()
                    return
                self._step_locked()

    def step(self) -> int:
        """One scheduler tick: admit every queued request that fits
        (fairness-ordered, preempting lower classes under pressure),
        advance chunked prefills, then run one decode step over the
        active slots.  Returns the number of tokens delivered to handles
        this tick (admission first-tokens included).  On an idle session
        this is a no-op returning 0.  While a background driver runs,
        only the pump thread may call this — any other thread gets a
        ``RuntimeError`` (the single-pump invariant)."""
        with self._cv:
            if self._driven_elsewhere():
                raise RuntimeError(
                    "a background pump owns this session (start() was "
                    "called): step() from another thread would double-pump "
                    "a tick; wait on handles / stream() instead, or stop() "
                    "the driver first"
                )
            return self._step_locked()

    def _step_locked(self) -> int:
        """Step body; caller holds ``_cv``."""
        try:
            if self.idle:
                self._emit_events()      # callbacks a raising peer pre-empted
                return 0
            before = self.gen_tokens
            self._in_step = True
            with self.s._numerics():
                if self.s.paged:
                    self._admit_all_paged()
                    self._advance_chunks()
                else:
                    self._admit_legacy()
                if not self.active.any():
                    if self._chunk_slots:
                        # Chunk-only tick: prefill progressed, nothing
                        # decodes yet.
                        self.step_idx += 1
                    elif (self.queue
                          and self.queue[0].request.arrival <= self.step_idx):
                        # An eligible request exists, nothing is running
                        # and nothing is chunk-filling: no live request
                        # holds pages, so available() must cover any
                        # admissible request (check_page_capacity passed
                        # at submission).  Transient waits — pages pinned
                        # by live/chunking occupants — never reach here.
                        raise RuntimeError(
                            "admission stalled with an idle pool — page "
                            "accounting bug: no live request holds pages, "
                            "yet an eligible request cannot be admitted"
                        )
                    elif self.queue:
                        # Nothing running: jump straight to the next arrival
                        # instead of ticking through the gap.
                        self.step_idx = max(
                            self.step_idx + 1, self.queue[0].request.arrival
                        )
                    else:
                        self._finalize_trace()
                    # Snapshot before callbacks run: a callback may submit()
                    # a follow-up request, beginning a new trace that resets
                    # the counters this return value is computed from.
                    produced = self.gen_tokens - before
                    self._emit_events()
                    return produced
                self._decode_once()
            if self.idle:
                self._finalize_trace()
            produced = self.gen_tokens - before
            self._emit_events()
            return produced
        finally:
            self._in_step = False
            self._cv.notify_all()

    def _decode_once(self) -> None:
        if self.s.paged:
            # Chunk-prefilling slots are inactive for decode but their
            # block tables hold REAL pages; mask them to all-garbage so
            # the inactive slots' clamped writes land in the garbage
            # page, not in a page mid-fill.
            bt = self.btables
            if self._chunk_slots:
                bt = np.where(self.active[:, None], bt, 0)
            self.pool, nxt = self.s._decode(
                self.s.params, self.pool, jnp.asarray(self.cur),
                jnp.asarray(self.pos), jnp.asarray(self.active),
                jnp.asarray(bt), jnp.asarray(self.keys),
                jnp.asarray(self.steps), jnp.asarray(self.temps),
            )
        else:
            self.pool, nxt = self.s._decode(
                self.s.params, self.pool, jnp.asarray(self.cur),
                jnp.asarray(self.pos), jnp.asarray(self.active),
                jnp.asarray(self.keys), jnp.asarray(self.steps),
                jnp.asarray(self.temps),
            )
        nxt = np.asarray(nxt)
        self.decode_steps += 1
        self.active_slot_steps += int(self.active.sum())
        self.step_idx += 1
        self.pos[self.active] += 1
        self.steps[self.active] += 1
        for slot in np.flatnonzero(self.active):
            tok = int(nxt[slot])
            st = self.occupant[slot]
            self._record_token(st["handle"], tok)
            st["remaining"] -= 1
            self.cur[slot] = tok
            if st["remaining"] == 0 or tok == self.s.eos_id:
                self._finish(slot)

    # --------------------------- slot bookkeeping ----------------------------
    def _finish(self, slot: int) -> None:
        st = self.occupant[slot]
        handle: StreamHandle = st["handle"]
        req = handle.request
        handle.result = RequestResult(
            rid=req.rid,
            tokens=np.concatenate(
                [req.prompt, np.asarray(handle._tokens, np.int32)]
            ),
            prompt_len=req.prompt.size,
            arrival=req.arrival,
            admitted_step=st["admitted"],
            finished_step=self.step_idx,
            finished_wall_s=time.perf_counter() - self._t0,
            prefix_hit_tokens=st["prefix_hit_tokens"],
            priority=req.priority,
            tenant=req.tenant,
            preemptions=handle._preempt_count,
        )
        self._live_rids.discard(req.rid)
        if self.s.paged:
            self.ppool.release(st["pages"])
            # An inactive slot's clamped decode write must land in
            # the garbage page, never in a (possibly reallocated)
            # page of the retired occupant.
            self.btables[slot, :] = 0
        self.occupant[slot] = None
        self.active[slot] = False
        self.alloc.release(slot)

    def _seat(self, slot: int, handle: StreamHandle, tok0: int,
              admitted: int, pages: List[int], hit_tokens: int) -> None:
        """Common post-prefill bookkeeping for both modes.  A handle
        with tokens already delivered is a preemption RESUME: its
        re-prefill covered ``prompt + generated[:-1]``, its sampled
        ``tok0`` is discarded (the original sample was already
        delivered) and decode continues mid-stream."""
        req = handle.request
        k = handle.n_generated
        if handle._admitted is None:
            handle._admitted = admitted
            handle._hit_tokens0 = min(hit_tokens, req.prompt.size)
        if k:
            # Resume: k tokens were sampled before eviction, the last
            # one has not been decoded yet.  pos = P + k - 1 restores
            # the decode-entry invariant pos = prompt_len + steps - 1.
            self.occupant[slot] = {
                "handle": handle, "remaining": req.n_tokens - k,
                "admitted": handle._admitted, "pages": pages,
                "prefix_hit_tokens": handle._hit_tokens0,
            }
            self.pos[slot] = req.prompt.size + k - 1
            self.active[slot] = True
            self.cur[slot] = handle._tokens[-1]
            self.keys[slot] = handle.key
            self.steps[slot] = k
            self.temps[slot] = req.temperature
            return
        self.occupant[slot] = {
            "handle": handle, "remaining": req.n_tokens - 1,
            "admitted": handle._admitted, "pages": pages,
            "prefix_hit_tokens": handle._hit_tokens0,
        }
        self.pos[slot] = req.prompt.size
        self.active[slot] = True
        self.cur[slot] = tok0
        self.keys[slot] = handle.key
        self.steps[slot] = 1
        self.temps[slot] = req.temperature
        self._record_token(handle, tok0)
        if self.occupant[slot]["remaining"] == 0 or tok0 == self.s.eos_id:
            self._finish(slot)

    # ----------------------------- fairness ----------------------------------
    def _effective_prompt(self, handle: StreamHandle) -> np.ndarray:
        """What admission must prefill: the prompt, plus — for a
        preemption resume — every generated token except the last (the
        last was sampled but its K/V not yet written by decode)."""
        k = handle.n_generated
        if not k:
            return handle.request.prompt
        return np.concatenate(
            [handle.request.prompt, np.asarray(handle._tokens[:-1], np.int32)]
        )

    def _select_candidate(self, blocked: Set[int]) -> Optional[StreamHandle]:
        """Stride scheduling over priority classes: among classes with
        an eligible (arrival reached, class not ``blocked``) queued
        request, pick the one with the least virtual time — ties to the
        higher priority — and return its FIFO head.  A single class
        reduces to plain arrival-order FIFO."""
        best: Optional[StreamHandle] = None
        best_key: Optional[Tuple[float, int]] = None
        seen: Set[int] = set()
        for h in self.queue:                  # sorted by (arrival, seq)
            if h.request.arrival > self.step_idx:
                break
            p = h.request.priority
            if p in blocked or p in seen:
                continue
            seen.add(p)
            vt = max(self._vt.get(p, self._vt_floor), self._vt_floor)
            key = (vt, -p)
            if best_key is None or key < best_key:
                best, best_key = h, key
        return best

    def _charge(self, priority: int) -> None:
        """Advance a class's virtual time by its stride (1/priority) on
        admission; the floor tracks the last admitted pass so a newly
        active class starts level with the field."""
        vt = max(self._vt.get(priority, self._vt_floor), self._vt_floor)
        self._vt_floor = vt
        self._vt[priority] = vt + 1.0 / priority

    # ---------------------------- preemption ---------------------------------
    def _preempt_one(self, for_handle: StreamHandle) -> bool:
        """Evict ONE occupant of strictly lower priority than
        ``for_handle`` (lowest class first, least progress within it —
        the cheapest resume).  Returns False when no such victim exists;
        the caller retries admission after each eviction, so no more
        occupants are evicted than the admission needs.

        Fires only under SUSTAINED pressure: the candidate must have
        been resource-blocked since an earlier step.  A merely backlogged
        higher class never evicts the admission the stride scheduler
        just seated (seat-then-evict thrash would waste every victim's
        prefill), and short-occupancy traffic keeps its weighted share —
        slots that free every step satisfy the higher class without any
        preemption at all."""
        if not self.s.preempt_active:
            return False
        if (for_handle._blocked_at is None
                or for_handle._blocked_at >= self.step_idx):
            return False
        p = for_handle.request.priority
        victims = [
            s for s in range(self.s.max_slots)
            if self.occupant[s] is not None
            and self.occupant[s]["handle"].request.priority < p
        ]
        if not victims:
            return False

        def cost(s: int):
            h = self.occupant[s]["handle"]
            return (h.request.priority, h.n_generated, -s)

        self._preempt_slot(min(victims, key=cost))
        return True

    def _preempt_slot(self, slot: int) -> None:
        """Evict ``slot``'s occupant: release its pages (registered
        prefix pages become CACHED — the resume's ``match_prefix`` hits
        them) and re-queue the handle.  Delivered tokens stay delivered;
        the resume path re-prefills the rest bitwise-identically."""
        st = self.occupant[slot]
        handle: StreamHandle = st["handle"]
        self.ppool.release(st["pages"])
        self.btables[slot, :] = 0
        self._chunk_slots.discard(slot)
        self.occupant[slot] = None
        self.active[slot] = False
        self.alloc.release(slot)
        handle._preempt_count += 1
        self.preemptions += 1
        self.total_preemptions += 1
        self._requeue(handle)

    # --------------------------- legacy admission ----------------------------
    def _admit_legacy(self) -> None:
        while self.alloc.free_count:
            handle = self._select_candidate(set())
            if handle is None:
                return
            self.queue.remove(handle)
            self._charge(handle.request.priority)
            req = handle.request
            slot = self.alloc.acquire()
            P = req.prompt.size
            bucket = self.s._bucket_for(P)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :P] = req.prompt
            # Explicit conversions only: a raw np scalar as a jit arg is
            # an implicit host->device transfer (the transfers lint runs
            # this path under jax.transfer_guard("disallow")).
            self.pool, tok0 = self.s._prefill_jit(bucket)(
                self.s.params, self.pool, jnp.asarray(padded),
                jnp.asarray(np.int32(P)), jnp.asarray(np.int32(slot)),
                jnp.asarray(handle.key),
                jnp.asarray(np.float32(req.temperature)),
            )
            self.prefills += 1
            self.prefill_batches += 1
            self._seat(slot, handle, int(tok0), self.step_idx, [], 0)

    # ---------------------------- paged admission ----------------------------
    def _try_admit_paged(self, handle: StreamHandle, pending: Set[int]):
        """Reserve a slot + pages for ``handle``'s request.  Returns an
        admission dict, None (cannot admit now: no slot / not enough
        pages), or "conflict" (its prefix pages are pending fill in the
        current burst group — flush the group first).  A chunked
        admission (tail longer than ``prefill_chunk``) reserves its slot
        and ALL its pages but defers the fill to ``_advance_chunks``;
        pending pages are no conflict for it — they are filled before
        its first chunk runs."""
        if not self.alloc.free_count:
            return None
        req = handle.request
        ppool = self.ppool
        prompt_eff = self._effective_prompt(handle)
        need = pages_needed(req.prompt.size, req.n_tokens, self.s.page_size)
        if self.s.prefix_reuse_active:
            matched, hashes = ppool.match_prefix(prompt_eff)
        else:
            matched, hashes = [], []
        ctx = len(matched) * self.s.page_size
        tail = prompt_eff[ctx:]
        chunked = (self.s.chunk_active
                   and tail.size > self.s.prefill_chunk)
        if not chunked and pending.intersection(matched):
            return "conflict"
        ppool.ref(matched)          # pin before allocation can evict
        fresh_needed = need - len(matched)
        if fresh_needed > ppool.available():
            ppool.unref(matched)    # roll back the pin (and its stats)
            return None
        fresh = ppool.allocate(fresh_needed)
        pages = matched + fresh
        registered = len(matched)
        if (self.s.prefix_reuse_active and not chunked
                and len(hashes) > len(matched)):
            # Non-chunked: the whole tail fills this step, so every
            # covered page can be indexed now.  Chunked admissions
            # register incrementally as chunks fill (_advance_chunks) —
            # indexing an unfilled page would let a concurrent match
            # attend garbage.
            ppool.register_prefix(
                hashes[len(matched):], pages[len(matched):len(hashes)],
                parent=hashes[len(matched) - 1] if matched else None,
            )
            registered = len(hashes)
        slot = self.alloc.acquire()
        self.btables[slot, :need] = pages
        self.btables[slot, need:] = 0
        return {
            "handle": handle, "slot": slot, "pages": pages, "ctx_len": ctx,
            "tail": tail, "fresh": fresh, "chunked": chunked,
            "hashes": hashes, "registered": registered,
            "prompt_eff": prompt_eff,
        }

    def _run_group(self, group: List[dict]) -> None:
        S = self.s.max_slots
        Bg = len(group)
        Bpad = 1 << (Bg - 1).bit_length()
        bucket = self.s._bucket_for(max(len(g["tail"]) for g in group))
        tokens = np.zeros((Bpad, bucket), np.int32)
        bt = np.zeros((Bpad, self.s.pages_per_slot), np.int32)
        slots_arr = np.full(Bpad, S, np.int32)      # garbage slot default
        ctx = np.zeros(Bpad, np.int32)
        tv = np.zeros(Bpad, np.int32)
        temps_g = np.zeros(Bpad, np.float32)
        keys_g = np.zeros((Bpad, 2), np.uint32)
        for i, g in enumerate(group):
            T = len(g["tail"])
            tokens[i, :T] = g["tail"]
            bt[i] = self.btables[g["slot"]]
            slots_arr[i] = g["slot"]
            ctx[i] = g["ctx_len"]
            tv[i] = T
            temps_g[i] = g["handle"].request.temperature
            keys_g[i] = g["handle"].key
        self.pool, toks = self.s._prefill_jit((bucket, Bpad))(
            self.s.params, self.pool, jnp.asarray(tokens), jnp.asarray(bt),
            jnp.asarray(slots_arr), jnp.asarray(ctx), jnp.asarray(tv),
            jnp.asarray(keys_g), jnp.asarray(temps_g),
        )
        toks = np.asarray(toks)
        self.prefills += Bg
        self.prefill_batches += 1
        for i, g in enumerate(group):
            self._seat(g["slot"], g["handle"], int(toks[i]), self.step_idx,
                       g["pages"], g["ctx_len"])

    def _seat_chunking(self, adm: dict) -> None:
        """Seat a chunked admission: slot and pages are reserved, the
        slot stays decode-inactive while ``_advance_chunks`` fills its
        tail ``prefill_chunk`` tokens per tick."""
        slot, handle = adm["slot"], adm["handle"]
        if handle._admitted is None:
            handle._admitted = self.step_idx
            handle._hit_tokens0 = min(adm["ctx_len"],
                                      handle.request.prompt.size)
        self.occupant[slot] = {
            "handle": handle, "remaining": None,   # set at activation
            "admitted": handle._admitted, "pages": adm["pages"],
            "prefix_hit_tokens": handle._hit_tokens0,
            "chunk": {
                "prompt_eff": adm["prompt_eff"], "filled": adm["ctx_len"],
                "hashes": adm["hashes"], "registered": adm["registered"],
            },
        }
        self._chunk_slots.add(slot)

    def _register_chunk_pages(self, slot: int, ck: dict) -> None:
        """Index the prefix pages a chunk fill just completed (never
        ahead of the fill: a concurrent match on an unfilled page would
        attend garbage).  Hashes another request registered first are
        skipped by ``register_prefix`` — our copy stays private."""
        if not self.s.prefix_reuse_active:
            return
        hashes = ck["hashes"]
        reg = ck["registered"]
        cover = min(ck["filled"] // self.s.page_size, len(hashes))
        if cover > reg:
            pages = self.occupant[slot]["pages"]
            self.ppool.register_prefix(
                hashes[reg:cover], pages[reg:cover],
                parent=hashes[reg - 1] if reg else None,
            )
            ck["registered"] = cover

    def _advance_chunks(self) -> None:
        """One chunked-prefill advance: every chunking slot fills its
        next ``prefill_chunk`` tokens in ONE batched prefill program
        (same (bucket, width) key space as burst prefill), so co-tenant
        decode steps interleave with a long prompt's fill instead of
        stalling behind it.  A slot whose tail completes activates for
        decode with its first token sampled from the final chunk's
        logits."""
        if not self._chunk_slots:
            return
        rows = sorted(self._chunk_slots)
        C = self.s.prefill_chunk
        S = self.s.max_slots
        plan = []
        for slot in rows:
            ck = self.occupant[slot]["chunk"]
            take = min(C, ck["prompt_eff"].size - ck["filled"])
            plan.append((slot, ck, take))
        Bg = len(plan)
        Bpad = 1 << (Bg - 1).bit_length()
        bucket = self.s._bucket_for(max(take for _, _, take in plan))
        tokens = np.zeros((Bpad, bucket), np.int32)
        bt = np.zeros((Bpad, self.s.pages_per_slot), np.int32)
        slots_arr = np.full(Bpad, S, np.int32)
        ctx = np.zeros(Bpad, np.int32)
        tv = np.zeros(Bpad, np.int32)
        temps_g = np.zeros(Bpad, np.float32)
        keys_g = np.zeros((Bpad, 2), np.uint32)
        for i, (slot, ck, take) in enumerate(plan):
            handle = self.occupant[slot]["handle"]
            filled = ck["filled"]
            tokens[i, :take] = ck["prompt_eff"][filled:filled + take]
            bt[i] = self.btables[slot]
            slots_arr[i] = slot
            ctx[i] = filled
            tv[i] = take
            temps_g[i] = handle.request.temperature
            keys_g[i] = handle.key
        self.pool, toks = self.s._prefill_jit((bucket, Bpad))(
            self.s.params, self.pool, jnp.asarray(tokens), jnp.asarray(bt),
            jnp.asarray(slots_arr), jnp.asarray(ctx), jnp.asarray(tv),
            jnp.asarray(keys_g), jnp.asarray(temps_g),
        )
        toks = np.asarray(toks)
        self.prefill_batches += 1
        for i, (slot, ck, take) in enumerate(plan):
            ck["filled"] += take
            self.prefill_chunks += 1
            self.total_prefill_chunks += 1
            self._register_chunk_pages(slot, ck)
            if ck["filled"] == ck["prompt_eff"].size:
                self._chunk_slots.discard(slot)
                st = self.occupant[slot]
                self.prefills += 1
                # Activate for decode; _seat rebuilds the occupant (the
                # slot stays acquired) and handles EOS/n_tokens==1 —
                # resume handles keep their delivered stream.
                self.occupant[slot] = None
                self._seat(slot, st["handle"], int(toks[i]), st["admitted"],
                           st["pages"], st["prefix_hit_tokens"])

    def _admit_round_paged(self) -> bool:
        """One admission round: build and run one burst group in
        fairness order.  A candidate that cannot admit blocks its class
        for the round (other classes may still fit); the round's FIRST
        candidate may preempt strictly-lower-priority occupants.
        Returns True when anything was admitted or a conflict flushed —
        both mean another round may make progress."""
        group: List[dict] = []
        chunk_seats: List[dict] = []
        pending: Set[int] = set()
        blocked: Set[int] = set()
        conflict = False
        head = True      # only the round's FIRST candidate has head rights
        while True:
            handle = self._select_candidate(blocked)
            if handle is None:
                break
            adm = self._try_admit_paged(handle, pending)
            if adm is None and head:
                # Head of the round under sustained pressure: evict one
                # victim at a time until it fits or no lower class
                # remains.  Losing as round HEAD (nothing admitted ahead
                # of it this round) is the pressure signal — a candidate
                # that merely queued behind this round's admissions is
                # not blocked, it is just not next.
                while adm is None and self._preempt_one(handle):
                    adm = self._try_admit_paged(handle, pending)
                if adm is None and handle._blocked_at is None:
                    handle._blocked_at = self.step_idx
            head = False
            if adm == "conflict":
                conflict = True     # flush the group; retry next round
                break
            if adm is None:
                blocked.add(handle.request.priority)
                continue
            self.queue.remove(handle)
            handle._blocked_at = None
            if handle._admitted is None:
                # A preemption resume was already charged at its first
                # admission — its class does not pay twice for one
                # request's slot share.
                self._charge(handle.request.priority)
            if adm.pop("chunked"):
                chunk_seats.append(adm)
            else:
                group.append(adm)
                pending.update(adm["fresh"])
                if not self.s.burst_prefill:
                    break
        for adm in chunk_seats:
            self._seat_chunking(adm)
        if group:
            self._run_group(group)  # may finish slots -> keep admitting
        return bool(group) or bool(chunk_seats) or conflict

    def _admit_all_paged(self) -> None:
        """Admit as many eligible requests as fit, in fairness order, in
        burst groups; a group flushes when a member's prefix pages are
        still pending fill by the group itself (its context gather must
        see them filled), or when burst batching is disabled."""
        while self._admit_round_paged():
            pass


def init_params(cfg: LMConfig, key, mesh=None):
    """``lm.init`` as one jitted program whose outputs land where
    serving keeps them: laid out by ``serve_param_sharding_tree`` over
    a tensor-parallel ``mesh``, or on the default device without one.
    A model larger than one chip is thus never whole on any device, as
    it would be if built eagerly and then placed; and no device holds
    the per-layer arrays beside their stacked copy, as eager
    ``lm.init`` does while it stacks them."""
    init = partial(lm.init, cfg=cfg)
    if mesh is None:
        return jax.jit(init)(key)
    shapes = jax.eval_shape(init, key)
    return jax.jit(
        init, out_shardings=shd.serve_param_sharding_tree(shapes, mesh)
    )(key)


def _bytes_per_device(tree) -> int:
    """Bytes of ``tree``'s arrays on the device that holds the most."""
    per = Counter()
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            per[shard.device] += shard.data.nbytes
    return max(per.values(), default=0)


class Scheduler:
    """Continuous-batching engine over a paged KV-cache pool.

    The scheduler owns the *compiled programs* and their configuration;
    all serve-loop state lives in a persistent :class:`ServeSession`
    (``session()``), created lazily on first use and shared by every
    ``serve()`` / ``submit()`` / ``step()`` call — so the device pool,
    the prefix cache and the jit caches survive across traces.

    Multi-tenant options: ``max_queue`` sheds overload at submission
    (``ValueError``), ``preempt`` lets strictly-higher-priority arrivals
    evict lower-class occupants (resumed bitwise-exactly from their
    still-cached pages), ``prefill_chunk`` caps how many prompt tokens
    one tick may prefill so long prompts cannot stall co-tenant decode.
    Preemption and chunked prefill need the same exactness conditions as
    prefix reuse (no SSM layers, lossless cache dtype) and auto-disable
    otherwise.

    Compiled-program budget across ANY trace — and across every trace
    of a session — is one decode program plus, in paged mode, one
    prefill program per (tail bucket, power-of-two burst width) pair
    actually used (chunked-prefill advances draw from the SAME keyed
    program set); with ``paged=False`` one prefill program per prompt
    bucket.  ``compile_counts`` exposes the jit cache sizes so tests
    assert this instead of eyeballing."""

    def __init__(
        self,
        cfg: LMConfig,
        params,
        max_slots: int = 4,
        max_len: int = 512,
        prefill_buckets: Optional[Sequence[int]] = None,
        eos_id: Optional[int] = None,
        seed: int = 0,
        dcim_sim=None,
        paged: bool = True,
        page_size: int = 16,
        n_pages: Optional[int] = None,
        prefix_reuse: bool = True,
        burst_prefill: bool = True,
        attn_backend: Optional[str] = None,
        max_queue: Optional[int] = None,
        preempt: bool = True,
        prefill_chunk: Optional[int] = None,
        mesh=None,
        tp: Optional[int] = None,
    ):
        if attn_backend is not None:
            # Thread the paged-attention backend (kernels.ops.AttnBackend)
            # through every jitted program via the config — zero call-site
            # churn; None keeps cfg's own setting (default "auto").
            cfg = dataclasses.replace(cfg, attn_backend=attn_backend).validate()
        # Tensor/expert-parallel serving mesh.  Like attn_backend this is
        # pure plumbing with zero call-site churn: params and the paged
        # pool are laid out by the serving rules
        # (dist.sharding.serve_param_sharding_tree /
        # serve_pool_sharding_tree) and every trace/call runs inside
        # _numerics()'s use_mesh, so the SAME jitted programs partition
        # over the mesh (row-parallel projections end in an all-reduce;
        # tokens agree with one device's within rounding, not bitwise).
        if tp is not None:
            if mesh is not None:
                raise ValueError("pass either mesh= or tp=, not both")
            tp = int(tp)
            if tp < 1:
                raise ValueError(f"tp must be >= 1, got {tp}")
            if tp > jax.device_count():
                raise ValueError(
                    f"tp={tp} exceeds {jax.device_count()} visible device(s)"
                )
            mesh = shd.make_mesh((tp,), ("model",))
        self.mesh = mesh
        self.mesh_ctx = None if mesh is None else shd.serving_context(mesh)
        if self.mesh is not None:
            params = jax.device_put(
                params, shd.serve_param_sharding_tree(params, self.mesh)
            )
        self.cfg = cfg
        self.params = params
        # Counter: parameter bytes the fullest device holds, read from
        # the placed shards.
        self.param_bytes_per_chip = _bytes_per_device(params)
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.seed = seed
        self.dcim_sim = dcim_sim
        if prefill_buckets is None:
            prefill_buckets = default_prefill_buckets(self.max_len)
        buckets = sorted(set(int(b) for b in prefill_buckets))
        if not buckets or buckets[0] < 1 or buckets[-1] > self.max_len:
            raise ValueError(f"bad prefill buckets {buckets} for max_len {self.max_len}")
        if buckets[-1] != self.max_len:
            buckets.append(self.max_len)   # every admissible prompt fits somewhere
        self.prefill_buckets = buckets
        if max_slots < 1:
            raise ValueError(f"need at least one slot, got {max_slots}")

        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.burst_prefill = bool(burst_prefill) and self.paged
        if self.paged:
            if self.max_len % self.page_size:
                raise ValueError(
                    f"max_len {self.max_len} must be a multiple of "
                    f"page_size {self.page_size}"
                )
            self.pages_per_slot = self.max_len // self.page_size
            if n_pages is None:
                # Every slot can hold a full max_len sequence even with
                # zero sharing, plus the garbage page.
                n_pages = self.max_slots * self.pages_per_slot + 1
            self.n_pages = int(n_pages)
            if self.n_pages < 2:
                raise ValueError(f"need >= 2 pages, got {self.n_pages}")
        else:
            self.pages_per_slot = 0
            self.n_pages = 0
        # Prefix reuse must be token-exact against full recompute:
        #  * SSM layers carry recurrent state — a page's K/V analogue
        #    does not exist, and skipping prefix prefill would skip the
        #    state the tail depends on;
        #  * a lossy cache dtype would hand the tail prefill ROUNDED
        #    context where the reference prefill attends compute-dtype
        #    values.
        # Preemption-resume and chunked prefill re-prefill positions the
        # reference computed in one pass (decode-written ones included),
        # attending earlier pages as context — exact under precisely the
        # same conditions, so they share the gate.
        period = cfg.scan_period()
        has_ssm = any(cfg.mixer_kind(i) == "mamba" for i in range(period))
        self._ctx_exact = (
            not has_ssm and cfg.cache_dtype == cfg.compute_dtype
        )
        self.prefix_reuse = bool(prefix_reuse) and self.paged
        self.prefix_reuse_active = self.prefix_reuse and self._ctx_exact
        self.max_queue = None if max_queue is None else int(max_queue)
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.preempt_active = bool(preempt) and self.paged and self._ctx_exact
        self.prefill_chunk = (
            None if prefill_chunk is None else int(prefill_chunk)
        )
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        self.chunk_active = (
            self.prefill_chunk is not None and self.paged and self._ctx_exact
        )
        # The prefill program's context gather compiles in only when
        # some admission can carry ctx_len > 0.
        self._use_context = self.prefix_reuse_active or self.chunk_active

        # The cache pool is donated: every program call rebinds the
        # session's pool to the returned value, and aliasing lets XLA
        # update the biggest buffer of the hot loop in place instead of
        # copying it per step.
        decode = _decode_paged_fn if self.paged else _decode_fn
        self._decode = jax.jit(partial(decode, cfg=cfg), donate_argnums=(1,))
        self._prefills: Dict[Union[int, Tuple[int, int]], "jax.stages.Wrapped"] = {}
        self.last_stats: Optional[ServeStats] = None
        self._session: Optional[ServeSession] = None

    # ----------------------------- plumbing ---------------------------------
    def _numerics(self):
        """The context every program trace/call runs under.  All jit
        entry points funnel through ``ServeSession._step_locked`` (and
        the contract replays), which wraps its whole body in this — so
        installing the serving mesh here shards every program with zero
        call-site churn."""
        if self.mesh_ctx is None:
            return numerics_ctx(self.dcim_sim)
        stack = contextlib.ExitStack()
        stack.enter_context(numerics_ctx(self.dcim_sim))
        stack.enter_context(shd.use_mesh(self.mesh_ctx))
        return stack

    def _bucket_for(self, prompt_len: int) -> int:
        for b in self.prefill_buckets:
            if b >= prompt_len:
                return b
        raise ValueError(        # unreachable: buckets end at max_len
            f"prompt length {prompt_len} exceeds every bucket"
        )

    def _prefill_jit(self, key):
        """Legacy mode keys by prompt bucket; paged mode by (tail
        bucket, burst width)."""
        fn = self._prefills.get(key)
        if fn is None:
            if self.paged:
                fn = jax.jit(
                    partial(_burst_prefill_fn, cfg=self.cfg,
                            page_size=self.page_size,
                            use_context=self._use_context),
                    donate_argnums=(1,),    # pool rebinding, as in _decode
                )
            else:
                fn = jax.jit(
                    partial(_prefill_fn, cfg=self.cfg, max_len=self.max_len),
                    donate_argnums=(1,),
                )
            self._prefills[key] = fn
        return fn

    def compile_counts(self) -> Dict[str, int]:
        """Jit-cache sizes: the scheduler's whole compiled-program
        budget, shared by every session and every trace."""
        counts = {
            "decode": int(self._decode._cache_size()),
            "prefill": {k: int(f._cache_size()) for k, f in self._prefills.items()},
        }
        counts["total"] = counts["decode"] + sum(counts["prefill"].values())
        return counts

    # ----------------------------- sessions ----------------------------------
    def session(self, fresh: bool = False) -> ServeSession:
        """The scheduler's persistent :class:`ServeSession` (created on
        first use).  ``fresh=True`` builds an independent session with
        its own device pool and prefix cache — compiled programs are
        still shared through this scheduler."""
        if fresh:
            return ServeSession(self)
        if self._session is None:
            self._session = ServeSession(self)
        return self._session

    def submit(self, request, seed: Optional[int] = None,
               on_token=None) -> StreamHandle:
        """Submit one request to the persistent session (see
        :meth:`ServeSession.submit`)."""
        return self.session().submit(request, seed=seed, on_token=on_token)

    def step(self) -> int:
        """One tick of the persistent session."""
        return self.session().step()

    def drain(self) -> None:
        self.session().drain()

    def serve(
        self,
        requests: Sequence[Union[Request, np.ndarray, list]],
        seed: Optional[int] = None,
    ) -> List[RequestResult]:
        """Serve an arrival trace to completion through the persistent
        session; results come back in submission order and the trace's
        ``ServeStats`` lands on ``self.last_stats``.  Unlike the
        pre-session scheduler this does NOT rebuild the device pool:
        prefix pages cached by an earlier ``serve()`` call are warm."""
        return self.session().serve(requests, seed=seed)


# ------------------------------ lint contract --------------------------------
@register_contract(
    "serve.scheduler",
    checks=("donation", "transfers", "recompile", "precision"),
    description="paged continuous-batching serve loop at a smoke config "
                "with the concurrent multi-tenant driver features on "
                "(priorities, preemption, chunked prefill, bounded queue): "
                "the pool donation must alias, the ServeSession.step() hot "
                "path must not transfer implicitly, a replayed mixed "
                "trace must stay within the one-decode + "
                "one-prefill-per-(bucket,width) compile budget, and the "
                "traced decode/prefill programs must satisfy the "
                "precision policy — including the exactness gates "
                "re-derived from the actual pool leaf dtypes",
)
def _build_serve_contract() -> Built:
    from repro import configs
    from repro.analysis.jaxpr_tools import (
        canonical_signature,
        compile_unit,
        pytree_leaf_specs,
    )
    from repro.analysis.registry import ExactnessGate, PrecisionPolicy

    # Lossless cache (cache_dtype == compute_dtype): the exactness gates
    # — prefix reuse, preemption-resume, chunked prefill — are ON, and
    # the precision check re-derives that from the traced pool leaves.
    cfg = configs.get_smoke_config("qwen2.5-3b")
    cfg = dataclasses.replace(cfg, cache_dtype=cfg.compute_dtype)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    # Multi-tenant knobs ON: the replayed trace exercises priority
    # admission, chunked prefill and the preemption path through the
    # same jitted programs the plain scheduler uses.  The transfer-guard
    # hot() stays single-threaded — jax.transfer_guard is thread-local,
    # so a background pump would escape it; the pump runs the very same
    # _step_locked() body this drives cooperatively.
    sched = Scheduler(cfg, params, max_slots=3, max_len=32, page_size=8,
                      max_queue=64, prefill_chunk=8)
    session = sched.session()

    # --- replay a mixed-length trace, recording abstract signatures ---
    signatures: List[Tuple[str, str]] = []
    orig_decode, orig_prefill_jit = sched._decode, sched._prefill_jit

    def spy_decode(*args):
        signatures.append(("decode", canonical_signature(args)))
        return orig_decode(*args)

    def spy_prefill_jit(key):
        fn = orig_prefill_jit(key)

        def wrapped(*args):
            signatures.append(("prefill", canonical_signature(args)))
            return fn(*args)

        return wrapped

    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(1, 64, p).astype(np.int32),
                n_tokens=t, rid=i, arrival=a, priority=pr,
                tenant=f"t{pr}")
        for i, (p, t, a, pr) in enumerate(
            [(3, 2, 0, 1), (5, 3, 0, 2), (9, 2, 0, 1), (3, 4, 1, 3),
             (17, 2, 2, 1), (6, 3, 2, 2)]
        )
    ]
    sched._decode, sched._prefill_jit = spy_decode, spy_prefill_jit
    try:
        session.serve(reqs)
    finally:
        sched._decode, sched._prefill_jit = orig_decode, orig_prefill_jit

    counts = sched.compile_counts()
    replay = Replay(
        signatures=signatures,
        # one decode signature ever; prefill signatures may differ only
        # as much as the (bucket, width) program keys actually used
        max_programs={"decode": 1, "prefill": len(sched._prefills)},
        live_counts={
            "decode": counts["decode"],
            "prefill": sum(counts["prefill"].values()),
        },
        live_budget={"decode": 1, "prefill": len(sched._prefills)},
    )

    # --- compiled units for the donation check ---
    S = sched.max_slots
    decode_args = (
        params, session.pool, jnp.asarray(session.cur),
        jnp.asarray(session.pos), jnp.asarray(session.active),
        jnp.asarray(session.btables), jnp.asarray(session.keys),
        jnp.asarray(session.steps), jnp.asarray(session.temps),
    )
    units = [compile_unit(
        "decode", sched._decode, decode_args, donate_argnums=(1,)
    )]
    if sched._prefills:
        bucket, width = sorted(
            k for k in sched._prefills if isinstance(k, tuple)
        )[0]
        prefill_args = (
            params, session.pool,
            jnp.zeros((width, bucket), jnp.int32),
            jnp.zeros((width, sched.pages_per_slot), jnp.int32),
            jnp.full((width,), S, jnp.int32),
            jnp.zeros((width,), jnp.int32),
            jnp.zeros((width,), jnp.int32),
            jnp.zeros((width, 2), jnp.uint32),
            jnp.zeros((width,), jnp.float32),
        )
        units.append(compile_unit(
            f"prefill[{bucket},{width}]", sched._prefill_jit((bucket, width)),
            prefill_args, donate_argnums=(1,),
        ))

    # --- hot path for the transfers check ---
    def hot():
        handle = session.submit(
            Request(prompt=rng.integers(1, 64, 7).astype(np.int32),
                    n_tokens=3, rid=9001, priority=2)
        )
        while not session.idle:
            session.step()
        return handle.result

    decode_jaxpr = jax.make_jaxpr(
        partial(_decode_paged_fn, cfg=cfg)
    )(*decode_args)
    hot_jaxprs = [("decode", decode_jaxpr)]
    pool_leaves = pytree_leaf_specs(session.pool)
    gates = [
        ExactnessGate("prefix_reuse", sched.prefix_reuse_active,
                      "decode", pool_leaves),
        ExactnessGate("preempt", sched.preempt_active, "decode",
                      pool_leaves),
    ]
    if sched._prefills:
        prefill_jaxpr = jax.make_jaxpr(partial(
            _burst_prefill_fn, cfg=cfg, page_size=sched.page_size,
            use_context=sched._use_context,
        ))(params, session.pool, *prefill_args[2:])
        hot_jaxprs.append(("prefill", prefill_jaxpr))
        gates.append(ExactnessGate(
            "chunked_prefill", sched.chunk_active, "prefill", pool_leaves
        ))

    return Built(
        compiled=units,
        hot=hot,
        hot_label="ServeSession.step()",
        hot_jaxprs=hot_jaxprs,
        replay=replay,
        precision=PrecisionPolicy(
            compute_dtype=cfg.compute_dtype, gates=gates
        ),
    )


@register_contract(
    "serve.scheduler_tp",
    checks=("donation", "recompile", "collectives", "precision"),
    description="tensor-parallel paged serve loop on a tp=<n_devices> "
                "('model',) mesh at a smoke config: the sharded pool "
                "donation must still alias, a replayed trace must stay "
                "within the single-device compile budget (sharding adds "
                "no programs), and the partitioned programs' collectives "
                "must stay within budget — each all-reduce at most one "
                "(tokens, d_model) partial sum of a row-parallel "
                "projection, the logits all-gather bounded, all-to-all "
                "forbidden for this non-MoE family",
)
def _build_serve_tp_contract() -> Built:
    from repro.analysis.jaxpr_tools import (
        canonical_signature,
        compile_unit,
        pytree_leaf_specs,
    )
    from repro.analysis.registry import (
        ContractSkip,
        ExactnessGate,
        PrecisionPolicy,
    )
    from repro import configs

    n_dev = jax.device_count()
    if n_dev < 2:
        raise ContractSkip(
            "tp serve contract needs >= 2 devices; run via "
            "`python -m repro.analysis.lint` (forces 8 host devices)"
        )

    cfg = configs.get_smoke_config("qwen2.5-3b")
    params = lm.init(jax.random.PRNGKey(0), cfg)
    sched = Scheduler(cfg, params, max_slots=3, max_len=32, page_size=8,
                      max_queue=64, prefill_chunk=8, tp=n_dev)
    session = sched.session()

    signatures: List[Tuple[str, str]] = []
    orig_decode, orig_prefill_jit = sched._decode, sched._prefill_jit

    def spy_decode(*args):
        signatures.append(("decode", canonical_signature(args)))
        return orig_decode(*args)

    def spy_prefill_jit(key):
        fn = orig_prefill_jit(key)

        def wrapped(*args):
            signatures.append(("prefill", canonical_signature(args)))
            return fn(*args)

        return wrapped

    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(1, 64, p).astype(np.int32),
                n_tokens=t, rid=i, arrival=a, priority=pr)
        for i, (p, t, a, pr) in enumerate(
            [(3, 2, 0, 1), (9, 3, 0, 2), (17, 2, 1, 1), (6, 3, 1, 2)]
        )
    ]
    sched._decode, sched._prefill_jit = spy_decode, spy_prefill_jit
    try:
        session.serve(reqs)
    finally:
        sched._decode, sched._prefill_jit = orig_decode, orig_prefill_jit

    counts = sched.compile_counts()
    replay = Replay(
        signatures=signatures,
        max_programs={"decode": 1, "prefill": len(sched._prefills)},
        live_counts={
            "decode": counts["decode"],
            "prefill": sum(counts["prefill"].values()),
        },
        live_budget={"decode": 1, "prefill": len(sched._prefills)},
    )

    # Per-device budgets.  The largest all-reduce of a program adds the
    # partial sums of one row-parallel projection (wo, w_down) over the
    # call's tokens, in the compute dtype: at most tokens * d_model * 4
    # bytes, as is the embedding's all-reduce of the looked-up rows.
    # Anything larger is a weight or a pool being reduced.  The biggest all-gather
    # is the logits gather for sampling, vocab * n_slots * 4 B; an order
    # of magnitude of headroom, far below a gathered weight.  All-to-all
    # at 0 is the teeth for a non-MoE family.
    def budget(tokens):
        return {"all-reduce": tokens * cfg.d_model * 4,
                "all-gather": 1 << 20, "all-to-all": 0}

    S = sched.max_slots
    decode_args = (
        sched.params, session.pool, jnp.asarray(session.cur),
        jnp.asarray(session.pos), jnp.asarray(session.active),
        jnp.asarray(session.btables), jnp.asarray(session.keys),
        jnp.asarray(session.steps), jnp.asarray(session.temps),
    )
    with shd.use_mesh(sched.mesh_ctx):
        units = [compile_unit(
            "decode_tp", sched._decode, decode_args, donate_argnums=(1,),
            shard_divisors=(1, n_dev), collective_budget=budget(S),
        )]
        if sched._prefills:
            bucket, width = sorted(
                k for k in sched._prefills if isinstance(k, tuple)
            )[0]
            prefill_args = (
                sched.params, session.pool,
                jnp.zeros((width, bucket), jnp.int32),
                jnp.zeros((width, sched.pages_per_slot), jnp.int32),
                jnp.full((width,), S, jnp.int32),
                jnp.zeros((width,), jnp.int32),
                jnp.zeros((width,), jnp.int32),
                jnp.zeros((width, 2), jnp.uint32),
                jnp.zeros((width,), jnp.float32),
            )
            units.append(compile_unit(
                f"prefill_tp[{bucket},{width}]",
                sched._prefill_jit((bucket, width)), prefill_args,
                donate_argnums=(1,), shard_divisors=(1, n_dev),
                collective_budget=budget(width * bucket),
            ))
        decode_jaxpr = jax.make_jaxpr(
            partial(_decode_paged_fn, cfg=cfg)
        )(*decode_args)

    # Stock smoke config: lossy bf16 cache under f32 compute, so the
    # exactness gates must come out DISABLED — the precision check
    # re-derives that from the traced pool leaves.
    gates = [
        ExactnessGate("prefix_reuse", sched.prefix_reuse_active,
                      "decode_tp", pytree_leaf_specs(session.pool)),
    ]
    return Built(
        compiled=units, replay=replay,
        hot_jaxprs=[("decode_tp", decode_jaxpr)],
        precision=PrecisionPolicy(
            compute_dtype=cfg.compute_dtype, gates=gates
        ),
    )
