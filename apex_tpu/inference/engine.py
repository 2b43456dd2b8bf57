"""Continuous-batching serving engine for GPT decode.

Orchestration is host-side and simple by design; the device work is two
jitted programs — one prefill per prompt bucket and ONE batched
``decode_step`` whose batch dimension is the cache slot table:

* admission — while slots are free and requests are queued, each request
  gets one prefill (prompt padded to a power-of-two bucket: causal
  masking makes the pad rows inert) whose K/V lands in its slot and
  whose last-position logits yield the first token (TTFT ends here).
* decode — every step runs ALL slots through ``decode_step``; inactive
  slots compute garbage that is never read (their writes land at stale
  positions that the next prefill overwrites before any valid length
  reaches them).  New requests admit between steps as slots free — no
  batch drain, which is the point of continuous batching.
* completion — eos / ``max_new_tokens`` / cache exhaustion free the
  slot; a request past its ``deadline`` is EVICTED mid-flight with
  whatever it has generated; a request past its per-request ``timeout``
  (a budget relative to submission, distinct from the absolute
  deadline) finishes with ``reason="timeout"``.

Resilience (ISSUE 4): the engine loop must survive its inputs.
``submit`` validates every ``Request`` field it can check statically and
applies bounded-queue backpressure (:class:`QueueFull`); whatever
validation can't catch — a sampling config that only detonates at
decode time, a seed of the wrong type — is QUARANTINED: the per-request
sampling/prefill work is wrapped so a poison request finishes with
``reason="error"`` and frees its slot instead of raising out of
``step()`` and killing every other request in flight.

Determinism: each decode row depends only on its own slot's cache and
token (attention masks by per-row length, norms/linears are per-token),
so greedy decode of a request inside any batch mix is token-identical to
running it alone — asserted by the engine tests.

Where a token is picked: the tick's program returns, beside its logits,
the arg-max of every row (``int32[slots]``: :func:`_picking`), and an
admission asks one small program for the arg-max of its prompt's last row
(:func:`first_token_id`); those ids are all that crosses to the host.
``_sample`` is still the one place where a row of logits becomes a token:
it is handed the row where it lies (:class:`_DeviceRow`), returns the id
the device picked for a greedy request, and for any other request brings
that one row home and samples it from the stream keyed by ``(seed, token
index)``, as it always did.  The same float32 logits, the same
first-maximum rule as ``np.argmax``, the same tokens.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.inference.kv_cache import KVCache
from apex_tpu.inference.sampling import SamplingParams, sample
from apex_tpu.observability.fleetobs import TraceContext
from apex_tpu.observability.request_trace import RequestTracer
from apex_tpu.observability.spans import span
from apex_tpu.utils.profiling import ServingMetrics


class QueueFull(RuntimeError):
    """``submit`` refused a request: the bounded queue is at capacity.
    Explicit backpressure — callers shed load or retry, instead of the
    queue growing without bound until the host OOMs."""


@dataclasses.dataclass
class Request:
    """One generation request.

    ``deadline`` is an absolute value of the engine's ``clock`` (default
    ``time.monotonic``); a request still running past it is evicted.
    ``timeout`` is a RELATIVE budget in clock units from submission —
    queued or decoding, a request over budget finishes with
    ``reason="timeout"`` (deadline eviction answers "the result is no
    longer wanted"; timeout answers "this request used up its share").
    ``seed`` feeds the per-request sampling stream (stochastic modes
    only) — streams are keyed by (seed, token index), never by batch
    composition.  ``trace`` is the fleet-wide causal identity
    (:class:`~apex_tpu.observability.fleetobs.TraceContext`): the
    router mints it, the engines stamp flow events against it, and it
    rides the request through retry/hedge/migration so the merged
    timeline shows one connected flow per request.
    """
    request_id: int
    prompt: Sequence[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)
    deadline: Optional[float] = None
    timeout: Optional[float] = None
    seed: int = 0
    trace: Optional[TraceContext] = None


@dataclasses.dataclass
class Response:
    """Completed (or evicted) request: ``tokens`` holds the generated
    ids (including the eos token when one was emitted);
    ``finish_reason`` is ``"eos"``, ``"length"`` (max_new_tokens or
    cache row exhausted), ``"evicted"`` (deadline), ``"timeout"``
    (per-request budget), ``"error"`` (poison request quarantined —
    ``error`` carries the exception message) or ``"preempted"`` (the
    engine was preempted and this request could not be requeued —
    :meth:`InferenceEngine.preempt` requeues whenever resume is
    possible, so this is the exception, not the rule).  The fleet
    router (:class:`apex_tpu.serving.FleetRouter`) additionally emits
    router-level responses with ``"shed"`` (retry budget exhausted;
    ``tokens`` carries any progress already streamed) and reuses
    ``"preempted"`` for a migrated request whose context no longer
    fits the target replica."""
    request_id: int
    prompt: List[int]
    tokens: List[int]
    finish_reason: str
    error: Optional[str] = None


@dataclasses.dataclass
class _Active:
    request: Request
    prompt_len: int
    next_token: int        # fed to the next decode step
    position: int          # absolute position next_token is written at
    generated: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass(frozen=True)
class _DeviceRow:
    """One row of logits left on the device (``logits[index]``) and the
    arg-max the device took of it.  ``_sample`` returns ``picked`` for a
    greedy request and never looks at the row; a sampler that needs the
    numbers asks for them (``np.asarray``), and only then does the row,
    not its batch, cross to the host."""
    logits: object      # a tick's (slots, vocab) or a prefill's (1, s, vocab)
    index: tuple
    picked: int

    def __len__(self) -> int:
        return self.logits.shape[-1]

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.logits[self.index], dtype)


def _picking(program):
    """``program`` (a model's ``decode_step*``: ``(logits, *cache)``) as
    the engine's tick: ``(logits, ids, *cache)``, ``ids`` the arg-max of
    every row as ``int32[slots]``, taken where the logits are.  It keeps
    the program's ``__name__``: ``jax.jit`` names the compiled module after
    it, and the benchmark finds the tick as ``jit_decode_step_paged``."""
    @functools.wraps(program)
    def tick(*args):
        logits, *cache = program(*args)
        with jax.named_scope("pick"):
            ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (logits, ids, *cache)
    return tick


@jax.jit
def first_token_id(logits, position):
    """The arg-max of row ``position`` of a prefill's ``(1, bucket, vocab)``
    logits, as an int32 scalar.  ``position`` is data, so a bucket compiles
    this once whatever the prompt's length."""
    return jnp.argmax(logits[0, position]).astype(jnp.int32)


class InferenceEngine:
    """Continuous batching over a :class:`KVCache` slot ring."""

    def __init__(self, model, params, *, max_slots: int = 8,
                 max_seq: Optional[int] = None, cache_dtype=None,
                 clock: Callable[[], float] = time.monotonic,
                 metrics: Optional[ServingMetrics] = None,
                 registry=None, tracer=None,
                 min_prompt_bucket: int = 8,
                 max_queue: Optional[int] = None,
                 plan=None):
        model._check_decode_supported()
        cfg = model.cfg
        if plan is not None:
            # decode runs one stage deep and token-at-a-time: of the
            # plan, only the tp degree applies, and it must match the
            # model the engine was handed
            if plan.pp > 1:
                raise ValueError(
                    f"serving does not pipeline: plan.pp={plan.pp}")
            if plan.sequence_parallel:
                raise ValueError(
                    "sequence_parallel shards the seq axis the decode "
                    "path appends to; serve with sequence_parallel=False")
            if plan.tp != cfg.tensor_parallel_size:
                raise ValueError(
                    f"plan.tp={plan.tp} does not match the model's "
                    f"tensor_parallel_size={cfg.tensor_parallel_size}; "
                    "build the model from the same plan "
                    "(GPTConfig(plan=plan))")
        self.plan = plan
        self.model = model
        if getattr(cfg, "weight_quant", None) == "int8":
            # quantize ONCE at init (never per step): every jitted
            # program below closes over the int8 tree, and the layer /
            # head dispatch keys on the weight_scale leaves.  Works
            # per-TP-shard unchanged — per-output-channel scales
            # commute with the row slices and only tighten on the
            # column slices
            from apex_tpu.models.gpt import quantize_decode_params
            params = quantize_decode_params(params)
        self.params = params
        # weight HBM per replica (the bench/CI legs' bytes accounting);
        # .nbytes on a jax array is metadata — no host transfer
        self.weight_bytes = int(sum(
            getattr(l, "nbytes", 0)
            for l in jax.tree_util.tree_leaves(params)))
        self.clock = clock
        # `registry` merges this engine's serving series into a shared
        # apex_tpu.observability.MetricsRegistry (one Prometheus/JSONL
        # sink for training + serving); ignored when `metrics` is given
        self.metrics = metrics or ServingMetrics(clock, registry=registry)
        # `tracer` (an observability.Tracer) turns on per-request Chrome
        # trace emission; the lifecycle bookkeeping itself is always on
        # and feeds the queue-wait / decode-ticks serving series
        self.trace = RequestTracer(clock=clock, tracer=tracer,
                                   metrics=self.metrics)
        # the loop's host phases (`serving.*`): profiler annotations
        # always, Chrome events beside the per-request rows when a
        # tracer was given
        self._span = functools.partial(span, tracer=tracer)
        self._min_bucket = min_prompt_bucket
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None: unbounded)")
        self.max_queue = max_queue
        self._queue: collections.deque = collections.deque()
        # backend fault hooks: the serving fleet's fault injector sets
        # this per tick ("reject_admission" fails submit with QueueFull,
        # "kv_pool_exhaustion" stalls admission); empty in normal runs
        self.injected_faults: frozenset = frozenset()
        self._active: dict = {}          # slot -> _Active
        self._submit_time: dict = {}     # request_id -> submit clock value
        self._progress: dict = {}        # request_id -> tokens generated
                                         # before a preemption requeue
        self._done: List[Response] = []
        # where tokens were picked: a greedy row's on the device, any other
        # from its row of logits, fetched for it alone
        self._c_picked = self.metrics.registry.counter(
            "serving_tokens_picked_on_device_total",
            "tokens that crossed to the host as the device's arg-max")
        self._c_fetched = self.metrics.registry.counter(
            "serving_logit_rows_fetched_total",
            "rows of logits brought to the host for a sampler")
        self._init_backend(max_slots, max_seq or cfg.max_seq_len,
                           cache_dtype or cfg.dtype)
        # cache-accounting gauges (registry-deduplicated): the router
        # and admission policies read capacity in bytes, not slots
        self._g_kv_free = self.metrics.registry.gauge(
            "serving_kv_free_bytes", "free KV-cache bytes")
        self._g_kv_occ = self.metrics.registry.gauge(
            "serving_kv_occupancy",
            "fraction of KV-cache capacity in use (token-granular)")
        self._export_cache_gauges()

    def _init_backend(self, max_slots: int, max_seq: int,
                      cache_dtype) -> None:
        """Backend hook: build the KV store and the jitted device
        programs.  The base engine is the contiguous slot ring;
        :class:`apex_tpu.serving.PagedInferenceEngine` overrides this
        with the block pool."""
        cfg = self.model.cfg
        # a layer pattern is served from the paged pool alone
        self.model._check_decode_supported("decode_step")
        self.cache = KVCache(max_slots, cfg.num_layers, max_seq,
                             cfg.local_heads, cfg.head_dim, cache_dtype)
        self.max_seq = self.cache.max_seq
        # the cache buffer threads through every step: donate it so XLA
        # updates it in place — without donation every decode step holds
        # TWO full caches (the lint rule donation/missing).  Donation
        # works on every backend when the output aliases the input
        # shape/dtype, which the cache ring guarantees; step() rebinds
        # self.cache.data from the output, so nothing re-reads the
        # donated buffer
        self._decode = jax.jit(_picking(self.model.decode_step),
                               donate_argnums=(2,))
        self._prefill = jax.jit(self.model.prefill)

    def _export_cache_gauges(self) -> None:
        self._g_kv_free.set(self.cache.free_bytes())
        self._g_kv_occ.set(self.cache.occupancy())

    # -- request lifecycle ---------------------------------------------------

    def _validate(self, request: Request) -> None:
        """Reject statically-checkable poison at the door (what this
        can't see — e.g. a sampling config that only fails at decode
        time — the step-loop quarantine catches)."""
        if not 0 < len(request.prompt) < self.max_seq:
            raise ValueError(
                f"prompt length {len(request.prompt)} must be in "
                f"(0, {self.max_seq}) to leave room for decode")
        vocab = self.model.cfg.vocab_size
        for t in request.prompt:
            if not isinstance(t, (int, np.integer)) or not 0 <= t < vocab:
                raise ValueError(
                    f"prompt token {t!r} is not an int in [0, {vocab})")
        if not isinstance(request.max_new_tokens, (int, np.integer)) \
                or request.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens {request.max_new_tokens!r} must be a "
                "positive int")
        if not isinstance(request.sampling, SamplingParams):
            raise ValueError(
                f"sampling must be a SamplingParams, got "
                f"{type(request.sampling).__name__}")
        if request.eos_id is not None and not isinstance(
                request.eos_id, (int, np.integer)):
            raise ValueError(f"eos_id {request.eos_id!r} must be an int")
        if request.timeout is not None and not request.timeout > 0:
            raise ValueError(
                f"timeout {request.timeout!r} must be positive")

    def submit(self, request: Request) -> None:
        """Validate and enqueue; raises :class:`QueueFull` when the
        bounded queue is at capacity (explicit backpressure — nothing is
        silently dropped)."""
        self._validate(request)
        if "reject_admission" in self.injected_faults:
            raise QueueFull("injected fault: admission rejected at this "
                            "replica")
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            raise QueueFull(
                f"submit queue is full ({len(self._queue)}/"
                f"{self.max_queue}); retry after step() drains it")
        self._submit_time[request.request_id] = self.clock()
        self.metrics.request_submitted(request.request_id)
        self.trace.enqueue(request.request_id, ctx=request.trace)
        self._queue.append(request)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def active_requests(self) -> int:
        return len(self._active)

    def _bucket(self, n: int) -> int:
        b = self._min_bucket
        while b < n:
            b *= 2
        return min(b, self.max_seq)

    def _sample(self, req: Request, logits_row, token_index: int) -> int:
        """The one place where a row of logits becomes a token.  The row
        is a host array or a :class:`_DeviceRow`; of the latter a greedy
        request takes the device's pick and any other fetches the row."""
        on_device = isinstance(logits_row, _DeviceRow)
        if req.sampling.greedy:
            if on_device:
                self._c_picked.inc()
                return logits_row.picked
            return int(np.argmax(logits_row))
        if on_device:
            self._c_fetched.inc()
            logits_row = np.asarray(logits_row)
        key = jax.random.fold_in(jax.random.PRNGKey(req.seed),
                                 token_index)
        return int(sample(jnp.asarray(logits_row), req.sampling, key))

    def _release(self, slot: int, st: _Active) -> None:
        """Backend hook: return ``slot``'s KV storage (a cache row here;
        pool blocks + the draft row in the paged engine)."""
        self.cache.free(slot)

    def _finish(self, slot: int, st: _Active, reason: str,
                error: Optional[str] = None) -> None:
        self._release(slot, st)
        del self._active[slot]
        self._finish_response(st.request, st.generated, reason, error)

    def _finish_response(self, req: Request, generated: List[int],
                         reason: str, error: Optional[str] = None) -> None:
        """Common completion tail for active AND still-queued requests:
        metrics dispatch + the Response record."""
        self._submit_time.pop(req.request_id, None)
        self._progress.pop(req.request_id, None)
        if reason == "evicted":
            self.metrics.request_evicted(req.request_id)
        elif reason == "timeout":
            self.metrics.request_timeout(req.request_id)
        elif reason == "error":
            self.metrics.request_error(req.request_id)
        else:
            # eos/length: the metrics layer drops the request's
            # transient state (TTFT bookkeeping) — every terminal path
            # must reach ServingMetrics or the engine leaks an entry
            # per request
            self.metrics.request_finished(req.request_id, reason)
        self.trace.finish(req.request_id, reason, error=error)
        self._done.append(Response(req.request_id, list(req.prompt),
                                   generated, reason, error=error))

    def _maybe_finish(self, slot: int, st: _Active) -> bool:
        req = st.request
        if req.eos_id is not None and st.generated[-1] == req.eos_id:
            self._finish(slot, st, "eos")
        elif len(st.generated) >= req.max_new_tokens:
            self._finish(slot, st, "length")
        elif st.position >= self.max_seq:
            self._finish(slot, st, "length")      # cache row exhausted
        else:
            return False
        return True

    def _evict_expired(self) -> None:
        now = self.clock()

        def expired(req):
            # deadline wins when both trip the same tick: "no longer
            # wanted" is the stronger statement than "over budget"
            if req.deadline is not None and now >= req.deadline:
                return "evicted"
            if req.timeout is not None:
                t0 = self._submit_time.get(req.request_id)
                if t0 is not None and now - t0 >= req.timeout:
                    return "timeout"
            return None

        for slot in [s for s in sorted(self._active)
                     if expired(self._active[s].request)]:
            st = self._active[slot]
            self._finish(slot, st, expired(st.request))
        keep: collections.deque = collections.deque()
        while self._queue:
            req = self._queue.popleft()
            reason = expired(req)
            if reason:
                # a requeued request keeps its partial progress in the
                # terminal Response
                done = self._progress.get(req.request_id, [])
                self._finish_response(req, list(done), reason)
            else:
                keep.append(req)
        self._queue = keep

    def preempt(self) -> int:
        """Drain on preemption: requeue every in-flight request instead
        of dropping it.  Each active request's slot is freed, its
        generated-so-far tokens are stashed, and the request goes back
        to the FRONT of the queue (lowest slot first — nearest to done,
        first re-admitted); the next :meth:`_admit` re-prefills prompt +
        generated and resumes the per-request sampling stream at the
        token index it stopped at, so greedy (and seeded stochastic)
        outputs are unchanged by the interruption.  Timeout budgets keep
        running across the requeue (the interruption is the server's
        fault, but the deadline semantics are the client's).  Returns
        the number of requests requeued.  A request whose context no
        longer fits a cache row finishes with ``reason="preempted"``
        instead.
        """
        requeued = 0
        for slot in sorted(self._active, reverse=True):
            requeued += self._preempt_slot(slot)
        return requeued

    def _preempt_slot(self, slot: int) -> int:
        """Requeue one in-flight request (the per-slot body of
        :meth:`preempt`; the paged engine also invokes it to reclaim
        blocks under pool pressure).  Returns 1 when requeued, 0 when
        the request had to finish instead."""
        st = self._active[slot]
        req = st.request
        if len(req.prompt) + len(st.generated) >= self.max_seq:
            self._finish(slot, st, "preempted")
            return 0
        self._release(slot, st)
        del self._active[slot]
        self._progress[req.request_id] = list(st.generated)
        self.metrics.request_requeued(req.request_id)
        self.trace.requeue(req.request_id)
        self._queue.appendleft(req)
        return 1

    def adopt(self, request: Request, progress: Sequence[int] = ()) -> None:
        """Admit a request migrated from another replica: ``progress``
        is the tokens it already streamed there.  Validation and
        backpressure are :meth:`submit`'s; the progress stash makes the
        next :meth:`_admit` re-prefill ``prompt + progress`` and resume
        the ``(seed, token-index)`` sampling stream at
        ``len(progress)`` — the cross-replica form of the preemption
        requeue, token-bitwise for the same reason."""
        if len(request.prompt) + len(progress) >= self.max_seq:
            raise ValueError(
                f"context {len(request.prompt)} + {len(progress)} does "
                f"not fit max_seq={self.max_seq}; finish with "
                "reason='preempted' instead of adopting")
        self.submit(request)
        if progress:
            self._progress[request.request_id] = list(progress)

    def export_inflight(self) -> List:
        """Strip every in-flight and queued request off this engine for
        cross-replica migration; returns ``[(request, generated)]`` in
        the preemption-requeue order (ascending slot — nearest to done
        first — then the waiting queue).  ``generated`` is exactly what
        was already streamed to the client, which is why a replica that
        dies without warning still leaves its requests recoverable: a
        healthy replica :meth:`adopt`\\ s each one and the resumed
        stream is token-bitwise the uninterrupted one.  On THIS engine
        each request terminates with reason ``"migrated"`` (metrics +
        trace, no Response — the adopting replica owns the eventual
        Response)."""
        out = []
        for slot in sorted(self._active):
            st = self._active[slot]
            out.append((st.request, list(st.generated)))
        for slot in sorted(self._active, reverse=True):
            st = self._active.pop(slot)
            self._release(slot, st)
        while self._queue:
            req = self._queue.popleft()
            out.append((req, list(self._progress.get(req.request_id, []))))
        for req, _ in out:
            rid = req.request_id
            self._submit_time.pop(rid, None)
            self._progress.pop(rid, None)
            self.metrics.request_migrated(rid)
            self.trace.finish(rid, "migrated")
        return out

    def cancel(self, request_id) -> bool:
        """Withdraw one request with NO Response (the fleet uses this
        for the losing copy of a hedged dispatch): frees its slot or
        queue entry, terminal metrics reason ``"cancelled"``.  Returns
        False when the id is not on this engine."""
        for slot, st in list(self._active.items()):
            if st.request.request_id == request_id:
                self._release(slot, st)
                del self._active[slot]
                break
        else:
            hit = None
            for req in self._queue:
                if req.request_id == request_id:
                    hit = req
                    break
            if hit is None:
                return False
            self._queue.remove(hit)
        self._submit_time.pop(request_id, None)
        self._progress.pop(request_id, None)
        self.metrics.request_cancelled(request_id)
        self.trace.finish(request_id, "cancelled")
        return True

    def _stalled(self, held: int, request_id):
        """The span ``serving.decode.stalled``, opened beside an admission's
        ``serving.admit.request`` when it finds ``held`` sequences decoding:
        the replies in flight get no tick until it is done (the blocks'
        acquisition and the wait for the prefill included; an attempt the
        pool turns away holds them up as well), and the span says how many
        were held up, by whom, for how long.  Nobody decoding: no span."""
        if not held:
            return contextlib.nullcontext()
        return self._span("serving.decode.stalled", sequences=held,
                          request_id=request_id)

    def _admit(self) -> int:
        """Admit queued requests while slots are free; returns how many."""
        if "kv_pool_exhaustion" in self.injected_faults:
            return 0                    # injected: no capacity to admit
        admitted = 0
        while self._queue and self.cache.free_slots:
            req = self._queue.popleft()
            admitted += 1
            waited = self.clock() - self._submit_time[req.request_id]
            with self._span("serving.admit.request",
                            request_id=req.request_id,
                            prompt_len=len(req.prompt), shared_tokens=0,
                            queue_wait_ms=1e3 * waited), \
                    self._stalled(len(self._active), req.request_id):
                slot = self.cache.allocate()
                prev = self._progress.pop(req.request_id, None)
                if prev is None:
                    self.trace.admit(req.request_id)
                plen = len(req.prompt)
                ctx = list(req.prompt) + (prev or [])
                clen = len(ctx)
                with self._span("serving.admit.prefill"):
                    toks = np.zeros((1, self._bucket(clen)), np.int32)
                    toks[0, :clen] = ctx
                    # a compile or device failure of the jitted program is
                    # the engine's, not the request's: it raises out of run()
                    logits, kv = self._prefill(self.params, jnp.asarray(toks))
                with self._span("serving.admit.kv_write"):
                    self.cache.write_prompt(slot, kv[:, :, 0], clen)
                try:
                    # the wait for the prefill and its last row's arg-max
                    # (one id), then the sample
                    with self._span("serving.admit.first_token"):
                        row = _DeviceRow(
                            logits, (0, clen - 1),
                            int(first_token_id(logits, clen - 1)))
                        nxt = self._sample(req, row, len(prev or []))
                except Exception as e:      # quarantine: free the slot,
                    self.cache.free(slot)   # fail ONE request, keep going
                    self._finish_response(req, list(prev or []), "error",
                                          error=f"{type(e).__name__}: {e}")
                    continue
                if prev is None:
                    self.metrics.first_token(req.request_id)
                    self.trace.first_token(req.request_id)
                else:
                    # a resumed request's TTFT already happened; the token
                    # re-enters the throughput series only
                    self.metrics.token(req.request_id)
                    self.trace.decode_tick(req.request_id)
                    self.trace.resumed(req.request_id)
                st = _Active(req, plen, next_token=nxt, position=clen,
                             generated=(prev or []) + [nxt])
                self._active[slot] = st
                self._maybe_finish(slot, st)
        return admitted

    # -- the decode loop -----------------------------------------------------

    # The spans sit inline and `step` calls the jitted programs from the
    # depth it always did: a helper frame between `run()` and a program
    # that is being traced shifts where CPython's 16 KiB frame-stack chunks
    # end under jax's deep tracing stacks, and set-up then swings by seconds
    # (PERF.md, section 6, PR 25).

    def step(self) -> bool:
        """One engine iteration: evict, admit, one batched decode step.
        Returns True while there is (or may be) work left."""
        with self._span("serving.step"):
            with self._span("serving.evict"):
                self._evict_expired()
            with self._span("serving.admit") as sp:
                sp.set_metadata(admitted=self._admit())
            self._export_cache_gauges()
            if not self._active:
                return bool(self._queue)
            n = self.cache.slots
            # the ring has nothing to grow: the dispatch is its inputs and
            # the launch of the jitted call, which returns before the device
            # is done
            with self._span("serving.decode.dispatch",
                            batch=len(self._active)):
                with self._span("serving.decode.inputs"):
                    tokens = np.zeros((n,), np.int32)
                    positions = np.zeros((n,), np.int32)
                    for slot, st in self._active.items():
                        tokens[slot] = st.next_token
                        positions[slot] = st.position
                    tokens = jnp.asarray(tokens)
                    positions = jnp.asarray(positions)
                with self._span("serving.decode.launch"):
                    logits, ids, self.cache.data = self._decode(
                        self.params, tokens, self.cache.data, positions)
            self.metrics.step(len(self._active), n)
            # the ids alone cross: the logits stay on the device
            with self._span("serving.decode.wait"):
                ids = np.asarray(ids)
            with self._span("serving.sample") as sp:
                sp.set_metadata(host_rows=self._advance_slots(
                    sorted(self._active), ids, logits))
            return bool(self._active or self._queue)

    def _cache_advance(self, slot: int, st: _Active) -> None:
        """Backend hook: record that the fed token's K/V is cached."""
        self.cache.advance(slot)

    def _advance_slots(self, slots: Sequence[int], ids, logits) -> int:
        """Post-decode tail shared by every backend: sample each row at
        its stream index, append, and run the completion checks.  This
        being single-sourced is what keeps the paged engine's sampling
        stream bitwise-identical to the contiguous one.  ``ids`` is the
        tick's arg-max of every row, on the host; ``logits`` its rows,
        where the tick left them.  Returns how many rows a sampler
        brought to the host."""
        fetched = self._c_fetched.value()
        for slot in slots:
            st = self._active[slot]
            self._cache_advance(slot, st)      # the fed token is cached now
            try:
                tok = self._sample(
                    st.request, _DeviceRow(logits, (slot,), int(ids[slot])),
                    len(st.generated))
            except Exception as e:      # poison sampling config detonated
                self._finish(slot, st, "error",
                             error=f"{type(e).__name__}: {e}")
                continue
            self.metrics.token(st.request.request_id)
            self.trace.decode_tick(st.request.request_id)
            st.generated.append(tok)
            st.next_token = tok
            st.position += 1
            self._maybe_finish(slot, st)
        return int(self._c_fetched.value() - fetched)

    def run(self, max_steps: Optional[int] = None) -> List[Response]:
        """Drive :meth:`step` until every submitted request completes
        (or ``max_steps``); returns responses in completion order."""
        steps = 0
        while self._queue or self._active:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return list(self._done)

    @property
    def completed(self) -> List[Response]:
        return list(self._done)
