"""A serving cell: one model configuration under one traffic mix, offered
open-loop to ``ServeSession.submit`` while the session's background pump
(``start()``) serves it.

Set-up makes the weights from the seed on the device, builds the
scheduler from the configuration file and warms exactly the programs
the mix can reach: the decode program and one prefill program per
prompt bucket up to the mix's longest prompt.  The window then offers
the mix's requests at their due times (``Window``); each request's
latency counts from when it was due.  After the window
the run waits for every request due in it, frees the program's state and
checks a seeded sample of finished requests against the float32
reference (``bench/reference.py``)."""
from __future__ import annotations

import collections
import dataclasses
import gc
import heapq
import itertools
import sys
import threading
import time

import jax
import numpy as np

from bench import gen, harness, reference, weights

GRACE_S = 60.0          # how long after the window a due request may finish
TRACE_S = 5.0           # traced span, centred in the window
POLL_S = 0.002          # how often a waiting generator looks for an idle session


def program_config(conf: dict, overrides: dict | None = None):
    """The program's config for the configuration file, with its sizes
    checked against the file's, which are the ones run."""
    from repro import configs

    p = conf["program"]
    get = configs.get_smoke_config if p.get("smoke") else configs.get_config
    cfg = get(p["arch"])
    cfg = dataclasses.replace(cfg, **p.get("overrides", {}), **(overrides or {}))
    c = conf["config"]
    have = {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv,
            "head_dim": cfg.hd, "num_hidden_layers": cfg.n_layers,
            "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": cfg.tie_embeddings,
            "attention_bias": cfg.qkv_bias}
    for k, v in have.items():
        if c[k] != v:
            raise ValueError(f"{conf['name']}: program {k}={v}, file says {c[k]}")
    return cfg.validate()


@dataclasses.dataclass
class Record:
    ask: gen.Ask
    due: float                      # perf_counter seconds
    rid: int = -1
    called: float = 0.0             # submit() entered
    accepted: float = 0.0           # submit() returned
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    hit: int = 0
    n_generated: int = 0
    done: bool = False
    handle: object = None


class Window:
    """The open-loop generator.  Each ask is handed to the session at its
    due time, and a session's next ask falls due ``think_s`` after the
    previous one's last token.

    While the pump waits for work the generator thread submits itself.
    While it steps, the ask waits in ``inbox`` for the end of the step in
    progress, where the first ``on_token`` callback submits it (the
    re-entrant path ``ServeSession`` documents for callbacks): the pump
    holds the session's lock for as long as anything is in flight, so a
    ``submit()`` from another thread would wait until every request in
    flight had finished.  Latency counts from the due time either way."""

    def __init__(self, session, sessions, t0: float, seconds: float):
        from repro.serve import Request

        self.Request = Request
        self.session = session
        self.t0, self.end = t0, t0 + seconds
        self.cv = threading.Condition()
        self.heap = []
        self.inbox = collections.deque()
        self.seq = itertools.count()
        self.rids = itertools.count()
        self.records: list[Record] = []
        for asks in sessions:
            heapq.heappush(self.heap, (t0 + asks[0].due_s, next(self.seq), asks, 0))

    def _on_token(self, rec: Record, asks, j):
        def cb(handle, tok):
            now = time.perf_counter()
            rec.times.append(now)
            rec.tokens.append(tok)
            if len(rec.tokens) == rec.ask.n_tokens:
                rec.done = True
                if j + 1 < len(asks):
                    with self.cv:
                        heapq.heappush(self.heap, (now + asks[j].think_s,
                                                   next(self.seq), asks, j + 1))
                        self.cv.notify_all()
            self._drain()
        return cb

    def _drain(self):
        """Submit every ask waiting in the inbox, in due order (from a
        callback, inside the pump's step)."""
        while self._submit_next():
            pass

    def _submit_next(self) -> bool:
        try:
            due, asks, j = self.inbox.popleft()
        except IndexError:
            return False
        ask = asks[j]
        rec = Record(ask=ask, due=due, rid=next(self.rids))
        req = self.Request(prompt=ask.prompt, n_tokens=ask.n_tokens,
                           temperature=ask.temperature, rid=rec.rid)
        rec.called = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.submit"):
            rec.handle = self.session.submit(req, on_token=self._on_token(rec, asks, j))
        rec.accepted = time.perf_counter()
        self.records.append(rec)
        return True

    def _pump_waiting(self) -> bool:
        """Whether the pump is waiting for work, holding the session's
        lock for the caller when it is.  ``session.idle`` alone is not
        enough: it reads true while the pump is still inside a step that
        has taken the last queued request but not yet seated it, and a
        ``submit()`` then would wait for everything in flight."""
        if not self.session._cv.acquire(blocking=False):
            return False
        if self.session.idle:
            return True
        self.session._cv.release()
        return False

    def run(self):
        """Offer the window's asks; returns once every ask due in the
        window has been submitted."""
        while True:
            now = time.perf_counter()
            with self.cv:
                while self.heap and self.heap[0][0] <= now:
                    due, _, asks, j = heapq.heappop(self.heap)
                    if due < self.end:            # due after the window: not sent
                        self.inbox.append((due, asks, j))
                nxt = self.heap[0][0] if self.heap else self.end
            if self.inbox and self._pump_waiting():
                try:
                    # One ask: it wakes the pump, and the rest wait for
                    # the end of its first step rather than for it to drain.
                    self._submit_next()
                finally:
                    self.session._cv.release()
            if now >= self.end and not self.inbox:
                return
            with self.cv:
                wait = min(nxt, self.end) - time.perf_counter()
                if self.inbox:
                    wait = min(wait, POLL_S)
                if wait > 0:
                    self.cv.wait(wait)

    def finish(self, grace: float) -> int:
        """Wait for every submitted request; returns how many never
        finished within ``grace`` seconds after the window."""
        unfinished = 0
        for rec in self.records:
            left = self.end + grace - time.perf_counter()
            try:
                res = rec.handle.wait(timeout=max(left, 0.001))
                rec.hit = res.prefix_hit_tokens
                rec.n_generated = int(res.generated.size)
            except TimeoutError:
                unfinished += 1
        return unfinished


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else float("nan")


def setup(conf: dict, mix: dict, seed: int, seconds: float):
    """Weights, scheduler and warmed programs; returns (sched, session,
    sessions of the window)."""
    from functools import partial

    from repro.models import lm
    from repro.serve import Request, Scheduler

    cfg = program_config(conf)
    shapes = jax.eval_shape(partial(lm.init, cfg=cfg), jax.random.PRNGKey(0))
    params = weights.program_params(conf["config"], shapes, seed)
    jax.block_until_ready(params)
    sched = Scheduler(cfg, params, **conf["serve"])
    session = sched.session()
    sessions = gen.generate(mix, seed, seconds, cfg.vocab_size)
    longest_prompt = max(len(a.prompt) for s in sessions for a in s)
    top = sched._bucket_for(longest_prompt)
    rng = np.random.default_rng([seed, 0xA11])
    for i, b in enumerate(x for x in sched.prefill_buckets if x <= top):
        n = b - 2                            # still in bucket b, and fits max_len
        session.serve([Request(prompt=rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                               n_tokens=2, rid=10 ** 9 + i)])
    jax.block_until_ready(session.pool)
    return sched, session, sessions


def run(bench: dict, cell: dict, conf: dict, mix: dict, seed: int,
        seconds: float, trace: bool, dev: dict) -> tuple[dict, dict]:
    clock = harness.CompileClock()
    t_setup = time.perf_counter()
    sched, session, sessions = setup(conf, mix, seed, seconds)
    setup_s = time.perf_counter() - t_setup
    compiles_before = clock.events

    prof: dict = {}
    t0 = time.perf_counter() + 0.05
    win = Window(session, sessions, t0, seconds)
    tracer = None
    if trace:
        span = min(TRACE_S, seconds)
        start = t0 + (seconds - span) / 2

        def traced():
            time.sleep(max(0.0, start - time.perf_counter()))
            with harness.profiled(prof, "serve"):
                prof["t_start"] = time.perf_counter()
                time.sleep(span)
            prof["t_stop"] = prof["t_start"] + prof["window_s"]

        tracer = threading.Thread(target=traced, name="bench-trace")
    session.start()
    time.sleep(max(0.0, t0 - time.perf_counter()))
    if tracer is not None:
        tracer.start()
    win.run()
    unfinished = win.finish(GRACE_S)
    drain_s = time.perf_counter() - win.end
    session.stop()
    if tracer is not None:
        tracer.join()
    compiles_in_window = clock.events - compiles_before
    mem = harness.peak_bytes()

    recs = win.records
    ttft = [r.times[0] - r.due for r in recs if r.times]
    itl = [b - a for r in recs for a, b in zip(r.times, r.times[1:])]
    in_window = sum(1 for r in recs for t in r.times if t0 <= t <= win.end)
    lag = [r.called - r.due for r in recs]
    blocked = [r.accepted - r.called for r in recs]
    print(f"requests {len(recs)} unfinished {unfinished} tokens_in_window {in_window} "
          f"ttft_p50_ms {_pct(ttft, 50) * 1e3:.1f} ttft_p95_ms {_pct(ttft, 95) * 1e3:.1f} "
          f"itl_p50_ms {_pct(itl, 50) * 1e3:.2f} "
          f"generator_lag_p50_ms {_pct(lag, 50) * 1e3:.2f} "
          f"generator_lag_max_ms {max(lag, default=0) * 1e3:.1f} "
          f"submit_blocked_p50_ms {_pct(blocked, 50) * 1e3:.2f} "
          f"submit_blocked_max_ms {max(blocked, default=0) * 1e3:.1f} "
          f"drain_s {drain_s:.1f} compiles_in_window {compiles_in_window}",
          file=sys.stderr, flush=True)
    if compiles_in_window:
        raise RuntimeError(f"{compiles_in_window} compile events inside the window")

    result = {"attempted": len(recs), "failed": unfinished,
              "device": {**dev, "memory_peak_bytes": mem}}
    if trace:
        from bench import trace as tr

        reduced = tr.reduce(prof["xplane"], prof["window_s"], marks=("bench.",))
        ctx = {"kind": "serve", "records": recs, "trace": reduced,
               "t_start": prof["t_start"], "t_stop": prof["t_stop"],
               "config": conf["config"], "serve": conf["serve"],
               "peaks": harness.peaks(dev["kind"])}
        result["metrics"] = harness.per_layer_metrics(bench, cell["name"], ctx)
        result["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
    else:
        result["metrics"] = harness.end_to_end_metrics(bench, cell["name"], {
            "ttft_p95_ms": {"value": _pct(ttft, 95) * 1e3, "unit": "ms"},
            "itl_p95_ms": {"value": _pct(itl, 95) * 1e3, "unit": "ms"},
            "output_tokens_per_s": {"value": in_window / seconds, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        })

    # Free the program's state before the reference runs.
    del session, sched, win
    for r in recs:
        r.handle = None
    gc.collect()
    t_check = time.perf_counter()
    compared = check(conf, recs, seed, unfinished)
    print(f"check_s {time.perf_counter() - t_check:.1f}", file=sys.stderr, flush=True)
    result["correct"] = all(c["value"] <= c["limit"] for c in compared.values())
    return result, compared


def sample(recs, seed: int, n: int):
    """The longest finished greedy request and n - 1 others, by seed."""
    ok = [r for r in recs if r.done and r.ask.temperature == 0.0]
    if not ok:
        return []
    ok.sort(key=lambda r: (-len(r.tokens), r.rid))
    rng = np.random.default_rng([seed, 0xC4EC])
    rest = ok[1:]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [ok[0]] + [rest[i] for i in sorted(pick)]


def check(conf: dict, recs, seed: int, unfinished: int) -> dict:
    """Every request due in the window finished, with as many tokens as
    it asked for, and the widest gap of a sampled served token's
    reference logit below the reference's best is within the limit."""
    wrong_length = sum(1 for r in recs
                       if r.n_generated and r.n_generated != r.ask.n_tokens)
    picked = sample(recs, seed, conf["check"]["sample"])
    gaps = reference.served_gaps(
        conf["config"], seed, [r.ask.prompt for r in picked],
        [np.asarray(r.tokens, np.int32) for r in picked]) if picked else []
    widest = max((float(g.max()) for g in gaps), default=float("inf"))
    print(f"checked {len(picked)} requests, {sum(len(g) for g in gaps)} served "
          f"tokens, {sum(1 for r in picked if r.hit)} of them prefilled over a cached "
          f"prefix", file=sys.stderr, flush=True)
    return {
        "unfinished_requests": {"value": unfinished, "limit": 0},
        "wrong_length_streams": {"value": wrong_length, "limit": 0},
        "max_logit_gap": {"value": widest,
                          "limit": conf["limits"]["max_logit_gap"]},
    }
