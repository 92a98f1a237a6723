"""Continuous-batching serving engine with chunked GSPN prefill.

Architecture (DESIGN.md §9).  The engine is a slot-based scheduler over a
:class:`~repro.serve.cache.StateCachePool`: requests move through

    QUEUED --admit--> PREFILL(chunk k/N) --commit--> DECODE --> FINISHED

``tick()`` is the scheduling quantum: it admits waiting requests into free
pool slots (``scheduler="fcfs"`` or ``"sjf"``), advances the in-flight
prefill by at most ONE chunk, and runs ONE batched decode step for every
active slot — so a long prompt never stalls the decode batch by more than
one ``prefill_chunk`` of work.  Chunks run through the fused GSPN scan via
``lm_prefill_chunk`` (offset-aware attention KV writes + boundary-seeded
GSPN grid resume); prompts no longer than one chunk, and architectures
without an incremental prefill path (SSM/xLSTM mixers, encoder-decoder),
take the one-shot ``lm_prefill`` fast path inside the admission tick.

Slot/cache lifecycle contract: a slot id is claimed from the pool at
admission, receives exactly one committed prefill state, is decoded as one
batch row until retirement (EOS or token budget), and returns to the pool
— reuse must be clean because ``commit`` rewrites every cache leaf's slot
row.  The decode step for the whole batch is one jitted function, so
throughput is independent of request mix; works for every architecture
family (KV for attention, SSM states for Mamba/xLSTM, the O(√L) row cache
for the GSPN mixer).

Observability (DESIGN.md §13): per-request TTFT / queue delay /
inter-token latencies and a streaming ``stream(uid, token)`` callback;
engine-level counters and latency histograms in the process-global
``repro.obs`` registry (``serve_*`` metrics), with ``ServeEngine.metrics``
kept as a per-engine compat view (the historical dict keys plus a derived
``queue_depth_mean``).  With tracing enabled the engine emits the request
lifecycle as spans: one async ``request`` span per uid
(queued → admitted → finished) enclosing the engine thread's spans.  Each
``serve.tick`` holds one span per phase (``serve.admit``,
``serve.cache_init``, ``serve.prefill``, ``serve.first_token``,
``serve.cache_commit``, ``serve.prefill_chunk`` with its
``serve.prefill_sync``, ``serve.decode_step`` with its
``serve.token_read`` and ``serve.emit``), never one per slot; under a
``jax.profiler`` trace they land beside the device's ops.

Submission surface: ``submit`` returns a :class:`RequestHandle`
(uid / status / ``result()`` accessor) — the one object a caller, the
router tier (serve.router), and a failure re-route all share.  Batch
drivers may still collect ``run()``'s results dict; the pre-handle
``on_finish`` callback survives as a deprecated shim.  When the engine
serves behind a router, a shared ``prefix_cache``
(serve.cache.PrefixStateCache) lets chunked prefill resume from a cached
fold-boundary state instead of recomputing a shared prompt prefix
(DESIGN.md §15).
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import lm as lm_mod
from repro.serve.cache import (StateCachePool, narrow_state,
                               update_cache_slots)  # noqa: F401
# update_cache_slots is re-exported: it moved to serve.cache (the pool owns
# the scatter) but long-standing callers import it from here.


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 32


def _serve_metrics():
    """Engine-level metrics in the process-global registry (get-or-create
    per access, so a test-time registry reset can never strand the
    engine on dead metric objects)."""
    return {
        "ticks": obs.counter("serve_ticks_total", "scheduler quanta run"),
        "decode": obs.counter("serve_decode_steps_total",
                              "batched decode steps"),
        "chunks": obs.counter("serve_prefill_chunks_total",
                              "prefill chunks advanced"),
        "submitted": obs.counter("serve_requests_submitted_total",
                                 "requests accepted by submit()"),
        "finished": obs.counter("serve_requests_finished_total",
                                "requests retired (eos or length)"),
        "qdepth": obs.gauge("serve_queue_depth",
                            "admission-queue depth after the last admit"),
        "ttft": obs.histogram("serve_ttft_seconds",
                              help="submit -> first token"),
        "qdelay": obs.histogram("serve_queue_delay_seconds",
                                help="submit -> admission"),
        "itl": obs.histogram("serve_itl_seconds",
                             help="inter-token latency"),
        "qdepth_hist": obs.histogram("serve_queue_depth_ticks",
                                     buckets=obs.DEPTH_BUCKETS,
                                     help="queue depth sampled per tick"),
        "chunk_s": obs.histogram("serve_prefill_chunk_seconds",
                                 help="wall seconds per prefill chunk "
                                      "(the TTFT predictor's cost model)"),
    }


def sample_tokens(logits, rng, temperature: float, top_k: int):
    """The engine-wide logits -> token policy (greedy when temperature<=0,
    else temperature + optional top-k).  logits (B, V) -> (B,) int32.
    One definition serves both the jitted batched decode step and the
    host-side first-token draw, so the two can never drift."""
    logits = logits.astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k:
        vals, _ = jax.lax.top_k(logits, top_k)
        logits = jnp.where(logits < vals[:, -1:], -1e30, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def drive(engine, requests, arrivals, *, idle_sleep: float = 0.002):
    """Open-loop arrival driver shared by examples and benchmarks: submit
    each request at its arrival time (seconds relative to the call), tick
    the engine in between, and return ``(elapsed_seconds, handles)`` once
    the engine drains — ``handles`` parallel to ``requests``, each
    finished, so callers read results through the handle API.  Works
    against a single :class:`ServeEngine` or a router (anything with
    ``submit``/``tick``/``idle``).  Open-loop means arrivals never wait
    for completions — queueing shows up in the metrics instead of being
    hidden."""
    t0 = obs.monotonic()
    nxt = 0
    handles = []
    while nxt < len(requests) or not engine.idle:
        now = obs.monotonic() - t0
        while nxt < len(requests) and arrivals[nxt] <= now:
            handles.append(engine.submit(requests[nxt]))
            nxt += 1
        if engine.idle and nxt < len(requests):
            time.sleep(min(arrivals[nxt] - now, idle_sleep))
            continue
        engine.tick()
    return obs.monotonic() - t0, handles


@dataclasses.dataclass
class Result:
    uid: int
    tokens: list
    ttft: float = 0.0               # submit -> first token (s)
    queue_delay: float = 0.0        # submit -> admission (s)
    itl: list = dataclasses.field(default_factory=list)  # inter-token (s)
    prefill_chunks: int = 0         # 0 == one-shot prefill
    finish_reason: str = ""         # "eos" | "length"
    t_submit: float = 0.0           # obs.monotonic() at submit
    t_finish: float = 0.0           # obs.monotonic() at retirement
    cached_tokens: int = 0          # prompt tokens resumed from the
    #                                 prefix cache instead of recomputed


@dataclasses.dataclass
class RequestHandle:
    """What :meth:`ServeEngine.submit` returns: the caller's view of one
    request's lifecycle.  ``status`` moves queued → running → finished;
    ``result()`` is the accessor for the finished :class:`Result` (raises
    until then — poll ``done`` or drive the engine first).  The routing
    tier reuses ONE handle across re-submissions (replica failure drains
    a queue back through the router), so the object a caller holds stays
    valid wherever the request lands; ``replica`` records the current
    placement."""

    uid: int
    status: str = "queued"          # "queued" | "running" | "finished"
    replica: Optional[int] = None   # owning replica id (router tier)
    _result: Optional[Result] = dataclasses.field(default=None, repr=False)

    @property
    def done(self) -> bool:
        return self.status == "finished"

    def result(self) -> Result:
        if self._result is None:
            raise RuntimeError(f"request {self.uid} is {self.status}; "
                               "result() is only available once finished")
        return self._result

    def _finish(self, res: Result):
        self._result = res
        self.status = "finished"


# Warn-once latch for the legacy ``on_finish`` callback surface.
_on_finish_warned = False


class ServeEngine:
    def __init__(self, params, cfg, *, batch_size: int = 4,
                 max_len: int = 512, temperature: float = 0.0,
                 top_k: int = 0, eos_id: Optional[int] = None,
                 seed: int = 0, ctx=None, prefill_chunk: int = 0,
                 scheduler: str = "fcfs", state_dtype=None,
                 stream: Optional[Callable[[int, int], None]] = None,
                 on_finish: Optional[Callable[[Result], None]] = None,
                 prefix_cache=None):
        if scheduler not in ("fcfs", "sjf"):
            raise ValueError(f"unknown scheduler {scheduler!r}")
        self.params = params
        self.cfg = cfg
        self.bs = batch_size
        self.max_len = max_len
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        self.ctx = ctx or lm_mod.Ctx()
        self.scheduler = scheduler
        # At-rest dtype of the pooled propagation state (DESIGN.md §10):
        # bf16 halves pool bytes → ~2× decode batch at fixed memory.
        self.state_dtype = (None if state_dtype is None
                            else jnp.dtype(state_dtype))
        self.stream = stream
        # Internal finish hook for the routing tier (Replica installs it);
        # distinct from the DEPRECATED user-facing ``on_finish`` so the
        # two can never shadow each other.
        self._finish_hook: Optional[Callable[[Result], None]] = None
        self._on_finish = None
        self.on_finish = on_finish       # property: warns once if not None
        # Shared prefix/state cache (serve.cache.PrefixStateCache); None
        # disables the probe.  Router tiers pass ONE cache to every
        # replica so a prefix prefilled anywhere is reusable everywhere.
        self.prefix_cache = prefix_cache
        self.rng = jax.random.PRNGKey(seed)
        self._seed = seed

        # Chunked prefill is only engaged when the architecture has an
        # incremental prefill path; chunk sizes snap to the GSPN fold
        # width so chunks start at grid-row boundaries (lm.py contract).
        if prefill_chunk > 0 and lm_mod.supports_chunked_prefill(cfg):
            align = lm_mod.prefill_chunk_alignment(cfg)
            self.prefill_chunk = max(align, (prefill_chunk // align) * align)
        else:
            self.prefill_chunk = 0

        self.pool = StateCachePool(cfg, batch_size, max_len,
                                   state_dtype=self.state_dtype)
        self._reset_state()

        self._prefill = jax.jit(
            lambda p, toks: lm_mod.lm_prefill(p, cfg, toks, max_len,
                                              ctx=self.ctx)[:2])
        self._prefill_chunk_fn = jax.jit(
            lambda p, toks, caches, off, with_logits: lm_mod.lm_prefill_chunk(
                p, cfg, toks, caches, off, ctx=self.ctx,
                with_logits=with_logits),
            static_argnums=4)
        self._decode = jax.jit(self._decode_fn)

    @property
    def on_finish(self):
        """DEPRECATED side-channel result delivery — ``submit`` returns a
        :class:`RequestHandle` now; read results through it.  Kept as a
        shim (warns once per process) for pre-handle callers."""
        return self._on_finish

    @on_finish.setter
    def on_finish(self, fn):
        global _on_finish_warned
        if fn is not None and not _on_finish_warned:
            _on_finish_warned = True
            import warnings
            warnings.warn(
                "ServeEngine(on_finish=...) is deprecated; submit() "
                "returns a RequestHandle — read results through it",
                DeprecationWarning, stacklevel=3)
        self._on_finish = fn

    def _reset_state(self):
        self.waiting: list = []              # [(Request, t_submit)]
        self._handles: dict = {}             # uid -> unfinished handle
        self._inflight = None                # chunked prefill in progress
        self.slot_req = [None] * self.bs
        self._slot_res: list = [None] * self.bs
        self._slot_t_last = [0.0] * self.bs
        self.last_token = jnp.zeros((self.bs, 1), jnp.int32)
        self.active = np.zeros((self.bs,), bool)
        self.results: dict = {}
        self._m = {"ticks": 0, "decode_steps": 0, "prefill_chunks": 0,
                   "queue_depth_max": 0, "queue_depth_sum": 0,
                   "depth_samples": 0,
                   # bounded: a long-running server must not grow a
                   # per-request list without limit
                   "admission_order": collections.deque(maxlen=1024)}

    @property
    def metrics(self) -> dict:
        """Per-engine compat view of the historical counter dict, plus
        ``queue_depth_mean`` derived ONCE here at snapshot time (callers
        used to recompute ``queue_depth_sum / depth_samples`` by hand).
        The same counters also feed the process-global ``repro.obs``
        registry (``serve_*``) for JSON/Prometheus export."""
        m = dict(self._m)
        m["queue_depth_mean"] = (m["queue_depth_sum"] / m["depth_samples"]
                                 if m["depth_samples"] else 0.0)
        return m

    def reset(self):
        """Clear all scheduling state (fresh pool pages included) but keep
        the compiled functions (benchmark rungs reuse one engine to avoid
        re-jitting)."""
        self.pool = StateCachePool(self.cfg, self.bs, self.max_len,
                                   state_dtype=self.state_dtype)
        self.rng = jax.random.PRNGKey(self._seed)
        self._reset_state()

    # -- jitted decode+sample --------------------------------------------
    def _decode_fn(self, params, token, caches, rng):
        logits, new_caches = lm_mod.lm_decode_step(params, self.cfg, token,
                                                   caches, ctx=self.ctx)
        # narrow inside the jitted step so the cast fuses with the cache
        # writes instead of costing a separate device pass
        new_caches = narrow_state(new_caches, self.state_dtype)
        nxt = sample_tokens(logits[:, 0], rng, self.temperature, self.top_k)
        return nxt, new_caches

    # -- request management -------------------------------------------------
    def check_fits(self, req: Request):
        """Reject oversized requests at the door: past max_len the chunked
        prefill would silently clamp its KV writes and the decode step
        silently drops K/V (the one_hot blend writes nothing) — wrong
        tokens, no error.  Decode writes cache rows up to
        prompt + max_new − 2 (the final token is never written).  Pure
        check (thread-safe) so the router can validate before handing the
        request to a replica worker thread."""
        need = len(req.prompt) + max(req.max_new_tokens, 1) - 1
        if need > self.max_len:
            raise ValueError(
                f"prompt ({len(req.prompt)}) + max_new_tokens "
                f"({req.max_new_tokens}) needs {need} cache rows, exceeding "
                f"the per-slot capacity max_len={self.max_len}")

    def submit(self, req: Request, *,
               handle: Optional[RequestHandle] = None) -> RequestHandle:
        """Queue a request; returns its :class:`RequestHandle`.  ``handle``
        lets the routing tier re-submit a drained request under the handle
        the caller already holds (replica-failure path)."""
        self.check_fits(req)
        if handle is None:
            handle = RequestHandle(uid=req.uid)
        handle.status = "queued"
        self._handles[req.uid] = handle
        self.waiting.append((req, obs.monotonic()))
        _serve_metrics()["submitted"].inc()
        obs.async_begin("request", req.uid, prompt_tokens=len(req.prompt),
                        max_new_tokens=req.max_new_tokens)
        obs.event("request.queued", uid=req.uid)
        return handle

    def _pop_next(self):
        if self.scheduler == "sjf":
            i = min(range(len(self.waiting)),
                    key=lambda i: len(self.waiting[i][0].prompt))
        else:
            i = 0
        return self.waiting.pop(i)

    def _sample_first(self, logits):
        """Draw a request's first token from the last position of its
        prefill logits (1, S, V) under the SAME policy as decode
        (sample_tokens), and read it to the host."""
        with obs.trace("serve.first_token"):
            if self.temperature <= 0.0:
                sub = self.rng               # unused; keep the stream fixed
            else:
                self.rng, sub = jax.random.split(self.rng)
            return int(sample_tokens(logits[:, -1], sub,
                                     self.temperature, self.top_k)[0])

    @property
    def idle(self) -> bool:
        """True when nothing is queued, prefilling, or decoding."""
        return (not self.waiting and self._inflight is None
                and not self.active.any())

    @property
    def queue_depth(self) -> int:
        """Admission-queue depth: requests waiting for a slot.  The
        in-flight chunked prefill is already admitted (its queue_delay
        has ended) and is deliberately NOT counted — this is the
        backpressure signal, not an occupancy count."""
        return len(self.waiting)

    @property
    def pending_chunks(self) -> int:
        """Prefill chunks of work queued ahead of a new arrival: the
        in-flight request's remaining chunks plus an estimate for every
        waiting prompt.  The TTFT-predictive router policy multiplies
        this by the measured per-chunk latency (DESIGN.md §15)."""
        n = 0
        if self._inflight is not None:
            st = self._inflight
            left = len(st["toks"]) - st["off"]
            n += -(-left // self.prefill_chunk)
        if self.prefill_chunk:
            for req, _t in self.waiting:
                n += max(-(-len(req.prompt) // self.prefill_chunk), 1)
        else:
            n += len(self.waiting)
        return n

    def drain(self) -> list:
        """Evacuate every unfinished request — the replica-failure path.
        Returns ``[(Request, RequestHandle), ...]`` (admitted requests
        first, then the in-flight prefill, then the waiting queue) with
        each handle reset to ``queued`` so the router can re-submit it to
        a survivor under the SAME handle the caller holds.  Partial decode
        progress is discarded (restart semantics); all scheduling state is
        reset, compiled functions kept."""
        reqs = [self.slot_req[s] for s in range(self.bs) if self.active[s]]
        if self._inflight is not None:
            reqs.append(self._inflight["req"])
        reqs.extend(r for r, _t in self.waiting)
        out = []
        for req in reqs:
            h = self._handles.pop(req.uid, None)
            if h is None:
                h = RequestHandle(uid=req.uid)
            h.status = "queued"
            h._result = None
            obs.async_end("request", req.uid, finish_reason="evacuated")
            out.append((req, h))
        self.reset()
        return out

    # -- prefill ------------------------------------------------------------
    def _admit(self):
        while self.waiting:
            if self._inflight is not None:
                break                        # one chunked prefill at a time
            slot = self.pool.alloc()
            if slot is None:
                break                        # backpressure: batch is full
            req, t_submit = self._pop_next()
            t_admit = obs.monotonic()
            self._m["admission_order"].append(req.uid)
            obs.event("request.admitted", uid=req.uid, slot=slot)
            if self.prefill_chunk and len(req.prompt) > self.prefill_chunk:
                toks = np.asarray(req.prompt, np.int32)
                # Prefix/state probe (DESIGN.md §15): a hit hands back the
                # full boundary-state cache at a chunk-aligned offset k —
                # prefill resumes at k via the same chunk_resume path a
                # cold chunk chain uses, so reuse is a lookup, not a new
                # numeric mode.
                off, cache = 0, None
                if self.prefix_cache is not None:
                    hit = self.prefix_cache.lookup(toks, self.prefill_chunk)
                    if hit is not None:
                        off, cache = hit
                        obs.event("request.prefix_hit", uid=req.uid,
                                  cached_tokens=off)
                if cache is None:
                    # A fresh zeroed batch-1 cache per admission (once per
                    # request, not per chunk).  Reusing a persistent
                    # scratch would need leaf-selective resets — a stale
                    # GSPN prev_row corrupts the seeded scan — for one
                    # saved zero-fill; not worth the foot-gun.
                    with obs.trace("serve.cache_init"):
                        cache = lm_mod.init_lm_cache(self.cfg, 1,
                                                     self.max_len)
                self._inflight = {
                    "req": req, "slot": slot, "off": off, "chunks": 0,
                    "cached": off, "toks": toks, "cache": cache,
                    "t_submit": t_submit, "t_admit": t_admit,
                }
            else:
                with obs.trace("serve.prefill", uid=req.uid,
                               prompt_tokens=len(req.prompt)):
                    prompt = jnp.asarray(req.prompt, jnp.int32)[None]
                    logits, new_caches = self._prefill(self.params, prompt)
                first = self._sample_first(logits)
                self._activate(req, slot, first, new_caches, t_submit,
                               t_admit, 0)

    def _advance_prefill(self):
        """Run at most one prompt chunk of the in-flight prefill."""
        st = self._inflight
        if st is None:
            return
        off = st["off"]
        end = min(off + self.prefill_chunk, len(st["toks"]))
        last = end == len(st["toks"])
        t0 = obs.monotonic()
        with obs.trace("serve.prefill_chunk", uid=st["req"].uid,
                       index=st["chunks"], offset=off, tokens=end - off):
            chunk = jnp.asarray(st["toks"][off:end], jnp.int32)[None]
            # only the final chunk's logits feed sampling; intermediate
            # chunks skip the vocab-head projection entirely
            logits, st["cache"] = self._prefill_chunk_fn(
                self.params, chunk, st["cache"], jnp.asarray(off, jnp.int32),
                last)
            # Block so the chunk histogram measures device time, not
            # dispatch: the per-chunk latency is the TTFT predictor's
            # cost model (DESIGN.md §15), and the very next tick would
            # block on this state anyway.
            with obs.trace("serve.prefill_sync"):
                jax.block_until_ready(st["cache"])
        _serve_metrics()["chunk_s"].observe(obs.monotonic() - t0)
        st["off"] = end
        st["chunks"] += 1
        self._m["prefill_chunks"] += 1
        _serve_metrics()["chunks"].inc()
        if (self.prefix_cache is not None
                and end % self.prefill_chunk == 0 and end > st["cached"]):
            # Every freshly computed chunk boundary is a reusable prefix
            # state: chunk offsets are alignment-snapped, so `end` sits on
            # a GSPN fold-row boundary (the resumable-state contract).
            self.prefix_cache.insert(st["toks"][:end], st["cache"])
        if last:
            first = self._sample_first(logits)
            self._activate(st["req"], st["slot"], first, st["cache"],
                           st["t_submit"], st["t_admit"], st["chunks"],
                           cached=st["cached"])
            self._inflight = None

    def _activate(self, req, slot, first, cache, t_submit, t_admit, chunks,
                  cached: int = 0):
        """Commit a finished prefill's ``cache`` to ``slot`` and start
        decoding the request from its ``first`` token."""
        with obs.trace("serve.cache_commit"):
            self.pool.commit(slot, cache)
            self.last_token = self.last_token.at[slot, 0].set(first)
        now = obs.monotonic()
        res = Result(uid=req.uid, tokens=[first], ttft=now - t_submit,
                     queue_delay=t_admit - t_submit, prefill_chunks=chunks,
                     t_submit=t_submit, cached_tokens=cached)
        h = self._handles.get(req.uid)
        if h is not None:
            h.status = "running"
        sm = _serve_metrics()
        sm["ttft"].observe(res.ttft)
        sm["qdelay"].observe(res.queue_delay)
        obs.event("request.first_token", uid=req.uid,
                  ttft_ms=round(res.ttft * 1e3, 3))
        self.slot_req[slot] = req
        self._slot_res[slot] = res
        self._slot_t_last[slot] = now
        self.active[slot] = True
        if self.stream:
            self.stream(req.uid, first)
        if self.eos_id is not None and first == self.eos_id:
            self._retire(slot, "eos")
        elif req.max_new_tokens <= 1:
            self._retire(slot, "length")

    # -- decode / retirement ------------------------------------------------
    def _retire(self, slot, reason: str):
        res = self._slot_res[slot]
        res.finish_reason = reason
        res.t_finish = obs.monotonic()
        _serve_metrics()["finished"].inc()
        obs.async_end("request", res.uid, finish_reason=reason,
                      tokens=len(res.tokens))
        h = self._handles.pop(res.uid, None)
        if h is not None:
            h._finish(res)
        if self._finish_hook is not None:
            # routing tier: the replica/router observes the finish; the
            # handle already carries the result, so nothing is retained
            # engine-side and state stays bounded
            self._finish_hook(res)
        elif self.on_finish is not None:
            # deprecated front-end callback (pre-handle shim); nothing is
            # retained engine-side
            self.on_finish(res)
        else:
            self.results[res.uid] = res
        self.slot_req[slot] = None
        self._slot_res[slot] = None
        self.active[slot] = False
        self.pool.free(slot)

    def _decode_step(self):
        """One decode step for the whole batch."""
        sm = _serve_metrics()
        with obs.trace("serve.decode_step", batch=int(self.active.sum())):
            self.rng, sub = jax.random.split(self.rng)
            nxt, new_caches = self._decode(self.params, self.last_token,
                                           self.pool.caches, sub)
            self.pool.update(new_caches)
            self._m["decode_steps"] += 1
            sm["decode"].inc()
            with obs.trace("serve.token_read"):
                nxt_host = np.asarray(nxt)
            self.last_token = nxt[:, None]
            now = obs.monotonic()
            with obs.trace("serve.emit"):
                for slot in range(self.bs):
                    if not self.active[slot]:
                        continue
                    tok = int(nxt_host[slot])
                    res = self._slot_res[slot]
                    res.tokens.append(tok)
                    res.itl.append(now - self._slot_t_last[slot])
                    sm["itl"].observe(now - self._slot_t_last[slot])
                    self._slot_t_last[slot] = now
                    if self.stream:
                        self.stream(res.uid, tok)
                    req = self.slot_req[slot]
                    if self.eos_id is not None and tok == self.eos_id:
                        self._retire(slot, "eos")
                    elif len(res.tokens) >= req.max_new_tokens:
                        self._retire(slot, "length")

    # -- main loop ----------------------------------------------------------
    def tick(self):
        """One scheduling quantum: admit, one prefill chunk, one decode
        step.  Drivers interleave ``submit``/``tick`` to model arrivals."""
        with obs.trace("serve.tick"):
            sm = _serve_metrics()
            self._m["ticks"] += 1
            sm["ticks"].inc()
            with obs.trace("serve.admit"):
                self._admit()
            # Depth is sampled AFTER admission: requests that found a free
            # slot this very tick never waited it out, so counting them
            # (the old pre-admit sample) double-counted depth on every
            # tick that retired a request and admitted its replacement.
            # What remains in `waiting` here is true backpressure.
            depth = self.queue_depth
            self._m["queue_depth_max"] = max(
                self._m["queue_depth_max"], depth)
            self._m["queue_depth_sum"] += depth
            self._m["depth_samples"] += 1
            sm["qdepth"].set(depth)
            sm["qdepth_hist"].observe(depth)
            self._advance_prefill()
            if self.active.any():
                self._decode_step()

    # kept as an alias of the scheduling quantum for older callers
    step = tick

    def run(self):
        """Run until all submitted requests complete.  Returns results."""
        while not self.idle:
            self.tick()
        return self.results
