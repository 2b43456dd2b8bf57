"""Paged continuous-batching engine: the serving tier of apex_tpu.

:class:`PagedInferenceEngine` subclasses the contiguous
:class:`~apex_tpu.inference.InferenceEngine` and swaps ONLY the memory
backend and the per-tick device plan; the whole request lifecycle —
validation, bounded-queue backpressure, eviction/timeout, quarantine,
preemption-requeue, metrics/trace — is inherited, and so is the
sampling stream (``_sample`` keyed by ``(seed, token-index)``) and where a
token is picked: the paged tick, like the ring's, returns the arg-max of
every row beside its logits, the ids alone cross to the host, and only a
request that is not greedy has its own row fetched (the paragraph at the
top of :mod:`apex_tpu.inference.engine`).  That
shared lifecycle plus the gather-identical paged attention path is why
the engine's outputs are token-BITWISE-identical to the contiguous
engine for greedy and seeded sampling (asserted by
``tests/test_serving.py::TestPagedEngine``),
while memory goes from ``slots * max_seq`` rows to demand-allocated
blocks with prefix sharing.

Three independently-switchable serving features:

* **Paged KV + prefix sharing** (always on): admission acquires blocks
  from :class:`~apex_tpu.serving.PagedKVCache`; a prompt sharing a
  cached full-block prefix skips both the KV writes AND (under chunked
  prefill) the forward compute for the shared tokens.  When the pool
  runs dry mid-decode the engine preempts the most recently admitted
  request (release blocks → requeue-with-progress → recompute later),
  the vLLM recovery policy, reusing the resilience machinery of
  ``preempt()``.
* **Chunked prefill** (``chunked_prefill=True``): prompts are processed
  in scheduler-budgeted chunks mixed into decode ticks instead of one
  monolithic prefill at admission — no head-of-line blocking of decode
  behind a long prompt.  Chunked token parity vs the contiguous path is
  deterministic and asserted at token level (the chunk forward is a
  different — gather-based — compute schedule from the bucketed
  prefill, so per-logit bitwiseness is not guaranteed by construction
  the way pure paged decode is).
* **Speculative decoding** (``speculative=SpeculativeConfig(...)``):
  see :mod:`apex_tpu.serving.speculative` — the draft proposes γ
  tokens, one (γ+1)-wide target chunk verifies, exact-match acceptance
  preserves the sampling stream exactly.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.inference.engine import (InferenceEngine, QueueFull, Request,
                                       _Active, _DeviceRow, _picking,
                                       first_token_id)
from apex_tpu.inference.kv_cache import KVCache
from apex_tpu.serving.paged_kv import PagedKVCache, QuantizedPagedKVCache
from apex_tpu.serving.scheduler import TickScheduler
from apex_tpu.serving.speculative import SpeculativeConfig


@dataclasses.dataclass
class KvHandoff:
    """A request's KV state in flight between engines — what
    :meth:`PagedInferenceEngine.export_kv` produces and
    :meth:`PagedInferenceEngine.adopt_kv` installs (the disaggregated
    prefill→decode handoff; see :mod:`apex_tpu.serving.disagg`).

    ``payload`` is the exporting pool's raw block storage
    (:meth:`PagedKVCache.export_blocks` — ``data``, plus ``scales`` for
    the int8 pool), ``kv_tokens`` the ``kv_len`` tokens it backs
    (``prompt + generated[:-1]`` — ``generated[-1]`` is the next token
    to FEED, whose KV the first decode step writes), and ``kind`` /
    ``block_size`` the storage-compatibility tags the adopting pool
    must match for a bitwise install."""
    request: Request
    generated: List[int]
    kv_len: int
    kv_tokens: List[int]
    payload: dict
    block_size: int
    kind: str
    src_replica: int = -1

    def nbytes(self) -> int:
        """Bytes the handoff moves over the wire (block storage only;
        the request metadata is negligible and identical across cache
        kinds)."""
        return int(sum(a.nbytes for a in jax.tree.leaves(self.payload)))


@dataclasses.dataclass
class _ChunkPrefill:
    """Progress of one chunked prefill: ``ctx`` is the full context
    (prompt + requeued progress), ``done`` how many positions already
    hold KV (starts at the trie-shared prefix — shared tokens are never
    re-forwarded, the compute half of the prefix-cache win)."""
    ctx: List[int]
    done: int
    prev_len: int       # generated-so-far count (resume stream index)


class PagedInferenceEngine(InferenceEngine):
    """Continuous batching over a paged block pool."""

    def __init__(self, model, params, *, block_size: int = 8,
                 num_blocks: Optional[int] = None,
                 share_prefixes: bool = True,
                 chunked_prefill: bool = False,
                 scheduler: Optional[TickScheduler] = None,
                 speculative: Optional[SpeculativeConfig] = None,
                 kv_quant: Optional[str] = None,
                 prefill_only: bool = False,
                 **kw):
        if kv_quant not in (None, "int8"):
            raise ValueError(f"kv_quant must be None or 'int8', "
                             f"got {kv_quant!r}")
        if kv_quant is not None and speculative is not None:
            raise ValueError(
                "kv_quant is incompatible with speculative decoding: "
                "the verify chunk consumes INTERMEDIATE chunk logits, "
                "which later proposals' block requantization perturbs — "
                "only the final row of a quantized chunk is "
                "schedule-invariant")
        if kv_quant is not None and not chunked_prefill:
            raise ValueError(
                "kv_quant requires chunked_prefill=True: the chunked "
                "path appends+requantizes per token, which is what "
                "makes re-prefill (migration/preemption resume) bitwise "
                "on a quantized cache; monolithic prefill quantizes "
                "each block one-shot and cannot replay decode's "
                "per-token history")
        if prefill_only and not chunked_prefill:
            raise ValueError("prefill_only requires chunked_prefill=True "
                             "(prefill replicas run chunked prefill only)")
        self._block_size = block_size
        self._num_blocks = num_blocks
        self._share_prefixes = share_prefixes
        self.chunked_prefill = chunked_prefill
        self.kv_quant = kv_quant
        self.prefill_only = prefill_only
        self.scheduler = scheduler or TickScheduler()
        self.spec = speculative
        # runtime switch over the configured spec path: the fleet's
        # degradation ladder (level 1) turns speculation off under SLO
        # burn and back on when burn clears.  Exact-match acceptance
        # makes the toggle token-invisible; only throughput changes.
        self.spec_enabled = True
        self.spec_proposed = 0
        self.spec_accepted = 0
        super().__init__(model, params, **kw)

    @property
    def _spec_active(self) -> bool:
        return self.spec is not None and self.spec_enabled

    # -- backend -------------------------------------------------------------

    def _init_backend(self, max_slots: int, max_seq: int,
                      cache_dtype) -> None:
        cfg = self.model.cfg
        bs = self._block_size
        if max_seq % bs:
            raise ValueError(
                f"max_seq ({max_seq}) must be a multiple of block_size "
                f"({bs}) — equal logical depth is what keeps paged "
                "attention bitwise-identical to the contiguous cache")
        if cfg.layer_pattern is not None:
            # a pattern's layers have the default configuration's cache
            # paths and no others: say which mechanism is missing
            for on, path in ((self.chunked_prefill, "decode_chunk"),
                             (self.spec is not None, "decode_chunk"),
                             (self.kv_quant is not None,
                              "decode_step_paged_quant")):
                if on:
                    self.model._check_decode_supported(path)
        self.max_seq = max_seq
        self.max_slots = max_slots
        self.max_blocks = max_seq // bs
        if self._num_blocks is None:
            # as roomy as the contiguous ring it replaces (+ garbage
            # block); real deployments size this to HBM, not to slots
            self._num_blocks = 1 + max_slots * self.max_blocks
        pool_cls = (QuantizedPagedKVCache if self.kv_quant == "int8"
                    else PagedKVCache)
        # the record is the model's: which layers cache, and what
        self.pool = pool_cls(
            self._num_blocks, bs, cfg.num_layers, cfg.local_heads,
            cfg.head_dim, cache_dtype, share_prefixes=self._share_prefixes,
            registry=self.metrics.registry,
            record=self.model.cache_record())
        # sparse attention: a tick scores every cached position of a row
        # and reads min(context, index_topk) latent records of it
        self._index_topk = cfg.index_topk
        if self._index_topk:
            r = self.metrics.registry
            self._c_scored = r.counter(
                "serving_indexer_scored_tokens_total",
                "cached positions the indexers' ticks scored, one layer")
            self._c_selected = r.counter(
                "serving_sparse_selected_tokens_total",
                "latent records the ticks' sparse attention read, one "
                "layer")
        self._free_slots = list(range(max_slots - 1, -1, -1))
        self._seqs: dict = {}            # slot -> PagedSequence
        self._tables = np.zeros((max_slots, self.max_blocks), np.int32)
        self._prefilling: dict = {}      # slot -> _ChunkPrefill
        self._prefill_order: List[int] = []
        self._handoff_ready: List[int] = []   # parked prefill_only slots
        self._admit_stamp: dict = {}     # slot -> admission counter
        self._admitted = 0
        if self.kv_quant == "int8":
            self._decode_paged_q = jax.jit(
                _picking(self.model.decode_step_paged_quant),
                donate_argnums=(2, 3))
            self._chunk_q = jax.jit(self.model.decode_chunk_quant,
                                    donate_argnums=(2, 3))
        else:
            self._decode_paged = jax.jit(
                _picking(self.model.decode_step_paged), donate_argnums=(2,))
            self._chunk = jax.jit(self.model.decode_chunk,
                                  donate_argnums=(2,))
        self._prefill = jax.jit(self.model.prefill)
        if self.spec is not None:
            self.spec.validate_against(self.model)
            dcfg = self.spec.model.cfg
            # the draft keeps a plain contiguous ring aligned on the
            # same slot ids (it is small — paging it buys nothing)
            self._draft_cache = KVCache(max_slots, dcfg.num_layers,
                                        max_seq, dcfg.local_heads,
                                        dcfg.head_dim, cache_dtype)
            self._draft_decode = jax.jit(self.spec.model.decode_step,
                                         donate_argnums=(2,))
            self._draft_prefill = jax.jit(self.spec.model.prefill)
            r = self.metrics.registry
            self._c_spec_prop = r.counter(
                "serving_spec_proposed_total", "draft tokens proposed")
            self._c_spec_acc = r.counter(
                "serving_spec_accepted_total",
                "draft tokens matching the canonical stream")

    def _export_cache_gauges(self) -> None:
        self._g_kv_free.set(self.pool.free_bytes())
        self._g_kv_occ.set(self.pool.occupancy())

    def _release(self, slot: int, st) -> None:
        seq = self._seqs.pop(slot, None)
        if seq is not None:
            self.pool.release(seq)
        self._tables[slot] = 0
        self._prefilling.pop(slot, None)
        if slot in self._prefill_order:
            self._prefill_order.remove(slot)
        if slot in self._handoff_ready:
            self._handoff_ready.remove(slot)
        self._admit_stamp.pop(slot, None)
        self._free_slots.append(slot)

    def _cache_advance(self, slot: int, st: _Active) -> None:
        # _advance_slots calls this before it appends the sampled token and
        # advances st.position (as the contiguous engine does): the token
        # fed this step sits at st.position, so position + 1 are valid
        self._seqs[slot].num_tokens = st.position + 1

    # -- admission -----------------------------------------------------------

    def _admit(self) -> int:
        if "kv_pool_exhaustion" in self.injected_faults:
            return 0                    # injected: no blocks to admit with
        admitted = 0
        while self._queue and self._free_slots:
            req = self._queue[0]
            waited = self.clock() - self._submit_time[req.request_id]
            with self._span("serving.admit.request",
                            request_id=req.request_id,
                            prompt_len=len(req.prompt),
                            queue_wait_ms=1e3 * waited) as sp, \
                    self._stalled(len(self._decoding()), req.request_id):
                prev = self._progress.get(req.request_id)
                ctx = list(req.prompt) + (prev or [])
                seq = self.pool.acquire(ctx)
                if seq is None:
                    # pool exhausted even after trie eviction: requests
                    # wait queued until decode completions free blocks
                    break
                sp.set_metadata(shared_tokens=seq.shared_tokens)
                admitted += 1
                self._queue.popleft()
                self._progress.pop(req.request_id, None)
                slot = self._free_slots.pop()
                self._admitted += 1
                self._admit_stamp[slot] = self._admitted
                if prev is None:
                    self.trace.admit(req.request_id)
                clen = len(ctx)
                self._seqs[slot] = seq
                self._tables[slot] = self.pool.table_row(seq, self.max_blocks)
                if self.chunked_prefill:
                    # defer ALL device work to budgeted chunks; the slot is
                    # active (evictable, preemptable) but not yet decoding
                    st = _Active(req, len(req.prompt), next_token=-1,
                                 position=clen, generated=list(prev or []))
                    self._active[slot] = st
                    self._prefilling[slot] = _ChunkPrefill(
                        ctx, seq.shared_tokens, len(prev or []))
                    self._prefill_order.append(slot)
                    continue
                # monolithic prefill — same bucketing, same program, same
                # logits as the contiguous engine (the bitwise mode); device
                # programs raise, only sampling is quarantined (as the base)
                with self._span("serving.admit.prefill", prompt_len=clen,
                                bucket=self._bucket(clen)):
                    toks = np.zeros((1, self._bucket(clen)), np.int32)
                    toks[0, :clen] = ctx
                    logits, kv = self._prefill(self.params, jnp.asarray(toks))
                with self._span("serving.admit.kv_write",
                                bucket=toks.shape[1],
                                record_bytes=self.pool.token_bytes) as sp:
                    # blocks really written: not the shared prefix's, not
                    # the garbage entries that pad the write to its bucket
                    sp.set_metadata(
                        blocks=self.pool.write_context_kv(seq, kv, clen))
                    self.pool.register_prefix(seq, ctx)
                self._draft_admit(slot, ctx)
                try:
                    # the wait for the prefill and its last row's arg-max
                    # (one id), then the sample
                    with self._span("serving.admit.first_token"):
                        row = _DeviceRow(
                            logits, (0, clen - 1),
                            int(first_token_id(logits, clen - 1)))
                        nxt = self._sample(req, row, len(prev or []))
                except Exception as e:      # quarantine, as in the base
                    self._release(slot, None)
                    self._finish_response(req, list(prev or []), "error",
                                          error=f"{type(e).__name__}: {e}")
                    continue
                if prev is None:
                    self.metrics.first_token(req.request_id)
                    self.trace.first_token(req.request_id)
                else:
                    self.metrics.token(req.request_id)
                    self.trace.decode_tick(req.request_id)
                    self.trace.resumed(req.request_id)
                st = _Active(req, len(req.prompt), next_token=nxt,
                             position=clen, generated=(prev or []) + [nxt])
                self._active[slot] = st
                self._maybe_finish(slot, st)
        return admitted

    def _draft_admit(self, slot: int, ctx: List[int]) -> None:
        if self.spec is None:
            return
        toks = np.zeros((1, self._bucket(len(ctx))), np.int32)
        toks[0, :len(ctx)] = ctx
        _, kv = self._draft_prefill(self.spec.params, jnp.asarray(toks))
        self._draft_cache.write_prompt(slot, kv[:, :, 0], len(ctx))

    # -- pool pressure -------------------------------------------------------

    def _grow(self, slot: int, n_tokens: int) -> bool:
        """Extend ``slot``'s block table to ``n_tokens`` positions,
        preempting the most recently admitted OTHER request when the
        pool (and the prefix trie's evictable tail) cannot supply
        blocks — recompute-on-readmission, the vLLM policy, riding the
        engine's existing requeue machinery."""
        seq = self._seqs[slot]
        while not self.pool.ensure_capacity(seq, n_tokens):
            victims = [s for s in self._admit_stamp if s != slot
                       and s in self._active]
            if not victims:
                return False
            self._preempt_slot(max(victims, key=self._admit_stamp.get))
        self._tables[slot] = self.pool.table_row(seq, self.max_blocks)
        return True

    # -- the tick loop -------------------------------------------------------

    def _decoding(self) -> List[int]:
        """The slots a tick would decode: active, done with their prompt,
        not parked for hand-off."""
        return [s for s in self._active if s not in self._prefilling
                and s not in self._handoff_ready]

    def step(self) -> bool:
        with self._span("serving.step"):
            with self._span("serving.evict"):
                self._evict_expired()
            with self._span("serving.admit") as sp:
                sp.set_metadata(admitted=self._admit())
            self._export_cache_gauges()
            if not self._active:
                return bool(self._queue)
            decoding = self._decoding()
            if self._prefilling:
                plan = self.scheduler.plan(
                    len(decoding),
                    [(s, len(self._prefilling[s].ctx)
                      - self._prefilling[s].done)
                     for s in self._prefill_order],
                    self.spec.num_tokens if (self._spec_active and decoding)
                    else 0)
                for slot, n in plan.chunks.items():
                    if slot in self._prefilling:     # may have been evicted
                        with self._span(
                                "serving.prefill_chunk", tokens=n,
                                request_id=self._active[slot]
                                .request.request_id):
                            self._run_prefill_chunk(slot, n)
            decoding = sorted(self._decoding())
            if decoding:
                if self._spec_active:
                    self._spec_round(decoding)
                else:
                    self._decode_round(decoding)
            return bool(self._active or self._queue)

    def _decode_round(self, decoding: List[int]) -> None:
        # the dispatch in three phases that tile it: grow, inputs, launch
        with self._span("serving.decode.dispatch") as sp:
            with self._span("serving.decode.grow") as grow:
                before = len(self._active)
                for slot in list(decoding):
                    if slot in self._active and not self._grow(
                            slot, self._active[slot].position + 1):
                        self._preempt_slot(slot)  # cannot even hold one more
                grow.set_metadata(preempted=before - len(self._active))
            with self._span("serving.decode.inputs"):
                decoding = [s for s in decoding if s in self._active]
                n = self.max_slots
                bs = self.pool.block_size
                tokens = np.zeros((n,), np.int32)
                positions = np.zeros((n,), np.int32)
                live_blocks = 0
                for slot in decoding:
                    st = self._active[slot]
                    tokens[slot] = st.next_token
                    positions[slot] = st.position
                    live_blocks += st.position // bs + 1
                # of the table's slots x max_blocks entries, what the tick
                # holds: the decode kernel walks these and no others
                sp.set_metadata(batch=len(decoding), live_blocks=live_blocks)
                if not decoding:
                    return
                if self._index_topk:
                    context = [int(positions[s]) + 1 for s in decoding]
                    selected = sum(min(c, self._index_topk) for c in context)
                    sp.set_metadata(context_tokens=sum(context),
                                    selected_tokens=selected)
                    self._c_scored.inc(sum(context))
                    self._c_selected.inc(selected)
                tokens = jnp.asarray(tokens)
                tables = jnp.asarray(self._tables)
                positions = jnp.asarray(positions)
            # the jitted call alone: it returns before the device is done
            with self._span("serving.decode.launch"):
                if self.kv_quant == "int8":
                    logits, ids, self.pool.data, self.pool.scales = \
                        self._decode_paged_q(
                            self.params, tokens, self.pool.data,
                            self.pool.scales, tables, positions)
                else:
                    logits, ids, self.pool.data = self._decode_paged(
                        self.params, tokens, self.pool.data, tables,
                        positions)
        self.metrics.step(len(decoding), n)
        # the ids alone cross: the logits stay on the device
        with self._span("serving.decode.wait"):
            ids = np.asarray(ids)
        with self._span("serving.sample") as sp:
            sp.set_metadata(host_rows=self._advance_slots(
                decoding, ids, logits))

    # -- chunked prefill -----------------------------------------------------

    def _run_prefill_chunk(self, slot: int, n: int) -> None:
        cs = self._prefilling[slot]
        st = self._active[slot]
        seq = self._seqs[slot]
        bs = self.pool.block_size
        start = cs.done
        end = min(start + n, len(cs.ctx))
        c = end - start
        pad = self._bucket(c)
        toks = np.zeros((1, pad), np.int32)
        pos = np.zeros((1, pad), np.int32)
        wb = np.zeros((1, pad), np.int32)    # pad rows -> garbage block 0
        wo = np.zeros((1, pad), np.int32)
        toks[0, :c] = cs.ctx[start:end]
        for j in range(c):
            p = start + j
            pos[0, j] = p
            wb[0, j] = seq.block_ids[p // bs]
            wo[0, j] = p % bs
        if self.kv_quant == "int8":
            logits, self.pool.data, self.pool.scales = self._chunk_q(
                self.params, jnp.asarray(toks), self.pool.data,
                self.pool.scales,
                jnp.asarray(self._tables[slot:slot + 1]),
                jnp.asarray(pos), jnp.asarray(wb), jnp.asarray(wo))
        else:
            logits, self.pool.data = self._chunk(
                self.params, jnp.asarray(toks), self.pool.data,
                jnp.asarray(self._tables[slot:slot + 1]),
                jnp.asarray(pos), jnp.asarray(wb), jnp.asarray(wo))
        cs.done = end
        if end < len(cs.ctx):
            return
        # prefill complete: publish, admit the draft, first token
        self.pool.register_prefix(seq, cs.ctx)
        self._draft_admit(slot, cs.ctx)
        try:
            nxt = self._sample(st.request, np.asarray(logits)[0, c - 1],
                               cs.prev_len)
        except Exception as e:              # quarantine (sampling only)
            self._finish(slot, st, "error",
                         error=f"{type(e).__name__}: {e}")
            return
        if cs.prev_len == 0:
            self.metrics.first_token(st.request.request_id)
            self.trace.first_token(st.request.request_id)
        else:
            self.metrics.token(st.request.request_id)
            self.trace.decode_tick(st.request.request_id)
            self.trace.resumed(st.request.request_id)
        st.next_token = nxt
        st.generated.append(nxt)
        del self._prefilling[slot]
        self._prefill_order.remove(slot)
        if not self._maybe_finish(slot, st) and self.prefill_only:
            # disaggregated prefill replica: the request is done with
            # its prefill phase — park it (no decode steps here) until
            # the fleet ships its KV to a decode replica via export_kv
            self._handoff_ready.append(slot)

    # -- disaggregated KV handoff ----------------------------------------------

    def handoffs_ready(self) -> List[tuple]:
        """``(slot, request_id)`` pairs parked after a completed prefill
        on a ``prefill_only`` engine, ascending slot — the export queue
        the disaggregated fleet drains each tick."""
        return [(s, self._active[s].request.request_id)
                for s in sorted(self._handoff_ready)]

    def export_kv(self, request_id) -> KvHandoff:
        """Strip ``request_id`` off this engine WITH its KV blocks — the
        block-shipping generalization of :meth:`export_inflight`.  The
        returned :class:`KvHandoff` carries the raw storage of every
        block backing ``kv_len = position`` valid positions (i.e. the KV
        of ``prompt + generated[:-1]``; ``generated[-1]`` is the next
        token to feed, whose KV the adopting engine's first decode step
        writes), so :meth:`adopt_kv` resumes WITHOUT re-running prefill
        — bitwise, because paged attention only ever gathers the block
        storage this payload is a literal copy of.  Terminal on this
        engine like a migration: reason ``"migrated"``, no Response.
        Raises KeyError when the id is not active here and ValueError
        while its prefill is still chunking (no complete KV to ship —
        let it finish or fall back to :meth:`export_inflight`)."""
        slot = next((s for s, st in self._active.items()
                     if st.request.request_id == request_id), None)
        if slot is None:
            raise KeyError(f"request {request_id!r} is not active on "
                           "this engine")
        if slot in self._prefilling:
            raise ValueError(
                f"request {request_id!r} is mid-prefill; its KV is "
                "incomplete — export_inflight() re-prefills instead")
        st = self._active[slot]
        seq = self._seqs[slot]
        kv_len = st.position
        ids = seq.block_ids[:self.pool.blocks_for(kv_len)]
        handoff = KvHandoff(
            request=st.request,
            generated=list(st.generated),
            kv_len=kv_len,
            kv_tokens=list(st.request.prompt) + list(st.generated[:-1]),
            payload=self.pool.export_blocks(ids),
            block_size=self.pool.block_size,
            kind=self.pool.kind)
        st = self._active.pop(slot)
        self._release(slot, st)
        rid = st.request.request_id
        self._submit_time.pop(rid, None)
        self._progress.pop(rid, None)
        self.metrics.request_migrated(rid)
        self.trace.finish(rid, "migrated")
        return handoff

    def adopt_kv(self, handoff: KvHandoff) -> int:
        """Install a shipped-KV request: acquire blocks for its
        ``kv_tokens``, copy the payload into them, and resume decode at
        ``position = kv_len`` feeding ``generated[-1]`` — no re-prefill.
        Storage tags must match (``kind``, ``block_size``): a bitwise
        install is a literal block copy, so bf16→int8 (or mismatched
        block geometry) must go through the re-prefill fallback
        (:meth:`~apex_tpu.inference.InferenceEngine.adopt`) instead.
        Admission is immediate (no queue pass): raises
        :class:`QueueFull` when no slot or no blocks are available so
        the fleet can retry or fall back, ValueError on tag/context
        misfit.  Returns the slot."""
        req = handoff.request
        if handoff.kind != self.pool.kind:
            raise ValueError(
                f"handoff cache kind {handoff.kind!r} does not match "
                f"this pool ({self.pool.kind!r}); re-prefill via adopt()")
        if handoff.block_size != self.pool.block_size:
            raise ValueError(
                f"handoff block_size {handoff.block_size} does not "
                f"match this pool ({self.pool.block_size})")
        if len(req.prompt) + len(handoff.generated) >= self.max_seq:
            raise ValueError(
                f"context {len(req.prompt)} + {len(handoff.generated)} "
                f"does not fit max_seq={self.max_seq}; finish with "
                "reason='preempted' instead of adopting")
        self._validate(req)
        if "reject_admission" in self.injected_faults:
            raise QueueFull("injected fault: admission rejected at this "
                            "replica")
        if not self._free_slots:
            raise QueueFull("no free decode slot for the KV handoff; "
                            "retry after step() completes one")
        seq = self.pool.acquire(handoff.kv_tokens)
        if seq is None:
            raise QueueFull("no free blocks for the KV handoff; retry "
                            "after decode completions release some")
        # trie-shared prefix blocks already hold bitwise-identical KV
        # (published by an earlier adopt of the same prefix), so the
        # payload rows are only copied for the fresh tail
        start = seq.shared_tokens // self.pool.block_size
        self.pool.import_blocks(
            seq.block_ids[start:],
            {k: jax.tree.map(lambda a: a[start:], v)
             for k, v in handoff.payload.items()})
        self.pool.register_prefix(seq, handoff.kv_tokens)
        slot = self._free_slots.pop()
        self._admitted += 1
        self._admit_stamp[slot] = self._admitted
        self._seqs[slot] = seq
        self._tables[slot] = self.pool.table_row(seq, self.max_blocks)
        rid = req.request_id
        self._submit_time[rid] = self.clock()
        self.metrics.request_submitted(rid)
        self.trace.enqueue(rid, ctx=req.trace)
        self.trace.admit(rid)
        self.trace.resumed(rid)
        self._draft_admit(slot, handoff.kv_tokens)
        st = _Active(req, len(req.prompt),
                     next_token=handoff.generated[-1],
                     position=handoff.kv_len,
                     generated=list(handoff.generated))
        self._active[slot] = st
        self._maybe_finish(slot, st)
        return slot

    # -- speculative decoding ------------------------------------------------

    def _spec_round(self, decoding: List[int]) -> None:
        k = self.spec.num_tokens
        # grow, inputs and launch as in _decode_round; the draft's k
        # proposals between them are the dispatch's own time
        with self._span("serving.decode.dispatch") as sp:
            with self._span("serving.decode.grow") as grow:
                before = len(self._active)
                for slot in list(decoding):
                    if slot in self._active and not self._grow(
                            slot, min(self._active[slot].position + k + 1,
                                      self.max_seq)):
                        self._preempt_slot(slot)
                grow.set_metadata(preempted=before - len(self._active))
            decoding = [s for s in decoding if s in self._active]
            sp.set_metadata(batch=len(decoding))
            if not decoding:
                return
            n = self.max_slots
            # 1) draft proposes k tokens (k cheap batched steps), sampling
            #    with the SAME (seed, index) stream the target will replay
            dtok = np.zeros((n,), np.int32)
            dpos = np.zeros((n,), np.int32)
            for s in decoding:
                st = self._active[s]
                dtok[s] = st.next_token
                dpos[s] = st.position
            proposals = np.zeros((n, k), np.int32)
            data = self._draft_cache.data
            cur = dtok
            for j in range(k):
                dlogits, data = self._draft_decode(
                    self.spec.params, jnp.asarray(cur), data,
                    jnp.asarray(dpos + j))
                dl = np.asarray(dlogits)
                for s in decoding:
                    st = self._active[s]
                    try:
                        proposals[s, j] = self._sample(
                            st.request, dl[s], len(st.generated) + j)
                    except Exception:
                        # a poison sampling config detonates identically in
                        # the verify loop, where quarantine handles it
                        proposals[s, j] = 0
                cur = proposals[:, j]
            # one write-only step: on a full accept (all k proposals + the
            # bonus token) the next round starts at p+k+1, so the draft
            # needs d_k's KV at p+k — without this its later attention reads
            # a stale row there (correctness is unaffected either way; the
            # target verifies everything, this only protects accept rate)
            _, data = self._draft_decode(
                self.spec.params, jnp.asarray(cur), data,
                jnp.asarray(dpos + k))
            self._draft_cache.data = data
            # 2) one (k+1)-wide target chunk verifies [t, d1..dk]
            with self._span("serving.decode.inputs"):
                c = k + 1
                toks = np.zeros((n, c), np.int32)
                pos = np.zeros((n, c), np.int32)
                wb = np.zeros((n, c), np.int32)
                wo = np.zeros((n, c), np.int32)
                bs = self.pool.block_size
                lim = {}
                for s in decoding:
                    st = self._active[s]
                    seq = self._seqs[s]
                    toks[s] = [st.next_token] + list(proposals[s])
                    lim[s] = min(c, self.max_seq - st.position)
                    for j in range(lim[s]):
                        p = st.position + j
                        pos[s, j] = p
                        wb[s, j] = seq.block_ids[p // bs]
                        wo[s, j] = p % bs
                toks, tables, pos, wb, wo = (
                    jnp.asarray(a) for a in (toks, self._tables, pos, wb, wo))
            with self._span("serving.decode.launch"):
                vlogits, self.pool.data = self._chunk(
                    self.params, toks, self.pool.data, tables, pos, wb, wo)
        self.metrics.step(len(decoding), n)
        with self._span("serving.decode.wait"):
            vl = np.asarray(vlogits)
        with self._span("serving.sample"):
            self._spec_accept(decoding, proposals, vl, lim)

    def _spec_accept(self, decoding, proposals, vl, lim) -> None:
        # 3) exact-match acceptance: consume canonical tokens while the
        #    draft predicted them; first mismatch (or the bonus final
        #    sample) ends the round
        for s in decoding:
            st = self._active[s]
            seq = self._seqs[s]
            for j in range(lim[s]):
                try:
                    tok = self._sample(st.request, vl[s, j],
                                       len(st.generated))
                except Exception as e:
                    self._finish(s, st, "error",
                                 error=f"{type(e).__name__}: {e}")
                    break
                self.metrics.token(st.request.request_id)
                self.trace.decode_tick(st.request.request_id)
                st.generated.append(tok)
                st.next_token = tok
                st.position += 1
                seq.num_tokens = st.position
                if self._maybe_finish(s, st):
                    break
                if j == lim[s] - 1:
                    break
                self.spec_proposed += 1
                self._c_spec_prop.inc()
                if tok != proposals[s, j]:
                    break               # rejected KV stays masked garbage
                self.spec_accepted += 1
                self._c_spec_acc.inc()

    @property
    def spec_accept_rate(self) -> float:
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed else 0.0)
