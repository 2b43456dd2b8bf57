"""The serving jobs.  One thread drives the program's own engine: submit
every request that is due, call ``engine.step()``, repeat; sleep only when
nothing is queued or active.

``serve_open``: arrivals on a schedule fixed by the mix, whatever the
server does (lead-in, window, drain); the tails of the requests due inside
the window are what it reports.  ``serve_backlog``: the queue never falls
under ``queue_floor``; it reports the tokens of the requests completed
inside the window.
"""

from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np

from . import stats, traffic, trace as tracing
from .job import CompileCounter, Run, load_module

SPANS = ("submit", "engine.step")
LOGIT_TOL = 4e-2    # bf16 activations through 24 layers against float32:
                    # a few percent of the logits' range; anything coarser
                    # than bf16, or a dropped term, lands well outside
LEFT_OUT_CAP = 0.5  # of the checked positions; the harness's, not a
                    # configuration's: a check that leaves out more than it
                    # compares has compared nothing


class Compared(NamedTuple):
    err: float | None   # first-step logits, of the range; None: left out
    gap: float          # the decoded tokens' worst gap, of the range
    compared: int       # decoded positions held to their gap
    left_out: int       # decoded positions the mask named
    ok: bool


def compare(ref, first, prompt_len, tokens, left_out, tol):
    """Hold one request to its reference, on ``numpy`` arrays alone.

    ``ref``: the reference's ``(s, vocab)`` logits over the prompt and the
    decoded ``tokens`` (rows past them are padding and are not read);
    ``first``: the engine's ``(vocab,)`` logits at the prompt's last
    position; ``left_out``: a boolean ``(s,)``, the positions the reference
    names as near ties.  Token ``j`` was decoded from row ``prompt_len - 1 +
    j``; a row the mask names is held to nothing, and the first-step
    comparison is such a row's too.  Tokens flip on rounding with random
    weights, so each must sit within ``2 x tol`` of the reference's best
    logit; the first-step logits within ``tol``, both as shares of the
    reference's range.  Rows after a near tie read its keys and values and
    are compared all the same: PR 27's and PR 31's chip runs found that
    dilution harmless (p99 0.0326 and 0.0176 of the range over the kept
    positions), so the rule is not widened to them."""
    last = prompt_len - 1
    scale = float(np.abs(ref[:prompt_len + len(tokens)]).max())
    err = None
    if not left_out[last]:
        err = float(np.abs(first - ref[last]).max()) / scale
    gaps = [float(ref[last + j].max() - ref[last + j][t]) / scale
            for j, t in enumerate(tokens) if not left_out[last + j]]
    gap = max(gaps, default=0.0)
    return Compared(err, gap, len(gaps), len(tokens) - len(gaps),
                    (err is None or err <= tol) and gap <= 2 * tol)


def counts_notes(compared, left_out, checked, min_compared):
    """What the counts over all check prompts fail, as notes: the harness's
    own cap on what a configuration may leave out, and the configuration's
    least number of positions compared."""
    notes = []
    if left_out > LEFT_OUT_CAP * checked:
        notes.append(f"{left_out} of {checked} checked positions left out "
                     "as near ties: more than half")
    if compared < min_compared:
        notes.append(f"{compared} of {checked} checked positions compared, "
                     f"fewer than {min_compared}")
    return notes


def check_limits(config):
    """``(near_tie_margin, min_compared)`` of a configuration's ``check``
    group.  Without the group a configuration is held as ``gpt2-medium``
    always was: no position ever left out, every checked position
    compared.  The limit itself, ``LOGIT_TOL``, is no configuration's."""
    check = config.get("check", {})
    unknown = set(check) - {"near_tie_margin", "min_compared"}
    if unknown:
        raise SystemExit("unknown keys in the configuration's check group: "
                         f"{sorted(unknown)}")
    return check.get("near_tie_margin"), check.get("min_compared")


def reference_of(ref_mod, cfg, margin):
    """The configuration's two functions of ``(params, tokens)``, one row,
    each under one ``jax.jit``: its float32 logits and, where the
    configuration gives a margin, its near ties.  A margin without a
    ``near_ties`` stops the run: never silently unguarded."""
    import jax

    if margin is not None and not hasattr(ref_mod, "near_ties"):
        raise SystemExit(
            f"{ref_mod.__file__} defines no near_ties(), and the "
            "configuration's check group gives a near_tie_margin")
    logits = jax.jit(lambda params, toks: ref_mod.gpt_reference_logits(
        params, toks, cfg)[0])
    if margin is None:
        return logits, None
    return logits, jax.jit(lambda params, toks: ref_mod.near_ties(
        params, toks, cfg, margin)[0])


def _stamps(clock):
    """A ``ServingMetrics`` that also keeps the benchmark's own time
    stamps, unbounded, on the benchmark's clock."""
    from apex_tpu.utils.profiling import ServingMetrics

    class Stamps(ServingMetrics):
        def __init__(self):
            super().__init__(clock)
            self.token_times, self.admitted, self.finished = {}, {}, {}
            self.ticks = []                 # (t, sequences decoded)

        def first_token(self, rid):
            self.token_times[rid] = [clock()]
            super().first_token(rid)

        def token(self, rid):
            self.token_times.setdefault(rid, []).append(clock())
            super().token(rid)

        def request_admitted(self, rid, wait):
            self.admitted[rid] = clock()
            super().request_admitted(rid, wait)

        def step(self, active, total):
            self.ticks.append((clock(), active))
            super().step(active, total)

        def request_finished(self, rid, reason="done"):
            self.finished[rid] = (clock(), reason)
            super().request_finished(rid, reason)

        def request_evicted(self, rid):
            self.finished[rid] = (clock(), "evicted")
            super().request_evicted(rid)

        def request_error(self, rid):
            self.finished[rid] = (clock(), "error")
            super().request_error(rid)

        def request_timeout(self, rid):
            self.finished[rid] = (clock(), "timeout")
            super().request_timeout(rid)

    return Stamps()


class Server:
    """The model, its weights from the seed and the engine, built once."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from apex_tpu.models.gpt import GPTConfig, GPTModel
        from apex_tpu.serving import PagedInferenceEngine

        self.ctx = ctx
        cfg = ctx.config
        dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
        kw = {k: dtypes.get(v, v) if isinstance(v, str) else v
              for k, v in cfg["model"].items()}
        self.cfg = GPTConfig(**kw)
        self.model = GPTModel(self.cfg)
        self.params = jax.jit(self.model.init_params)(
            jax.random.PRNGKey(ctx.seed))
        jax.block_until_ready(self.params)
        ctx.lap("weights")
        self.zero = time.perf_counter()
        self.clock = lambda: time.perf_counter() - self.zero
        self.stamps = _stamps(self.clock)
        ekw = dict(cfg["engine"])
        ekw["cache_dtype"] = dtypes[ekw["cache_dtype"]]
        self.engine = PagedInferenceEngine(
            self.model, self.params, clock=self.clock, metrics=self.stamps,
            **ekw)
        self.compiles = CompileCounter()
        self.submitted = {}
        self.asked = {}                     # rid -> Arrival
        self.traced_span = None             # [start, end] on self.clock
        from apex_tpu.inference import Request
        from jax.profiler import TraceAnnotation
        self._request, self._span = Request, TraceAnnotation

    def submit(self, a, prompt=None):
        if prompt is None:
            prompt = traffic.tokens(self.ctx.seed, a.rid, a.prompt_len,
                                    self.cfg.vocab_size)
        self.asked[a.rid] = a
        self.submitted[a.rid] = self.clock()
        with self._span("submit"):
            self.engine.submit(self._request(
                request_id=a.rid, prompt=prompt,
                max_new_tokens=a.new_tokens, eos_id=None))

    def step(self):
        with self._span("engine.step"):
            self.engine.step()

    @property
    def busy(self):
        return self.engine.queue_depth + self.engine.active_requests

    # -- set-up ---------------------------------------------------------------

    def check_and_warm_up(self):
        """Hold a seeded sample of prompts against the float32 reference
        (first-step logits, then every decoded token), and run every prompt
        length the mix offers through the engine once, so that the window
        compiles nothing.  Returns the failed checks and what the run's
        notes say of the comparison."""
        import jax.numpy as jnp

        ctx, mix, vocab = self.ctx, self.ctx.traffic, self.cfg.vocab_size
        ref_mod = load_module(ctx.root, ctx.config["reference"], "bench_ref")
        margin, min_compared = check_limits(ctx.config)
        reference, near_ties = reference_of(ref_mod, self.cfg, margin)
        new = mix["check_new_tokens"]
        pad = self.engine._bucket(max(mix["check_prompts"]) + new)
        notes, worst, rows, compared, left_out = [], 0.0, 0, 0, 0
        checks = [traffic.Arrival(f"check-{n}", 0.0, n, new)
                  for n in mix["check_prompts"]]
        # a stream of its own: no prompt of the window shares a prefix
        prompts = {a.rid: traffic.tokens(ctx.seed + 1, i, a.prompt_len, vocab)
                   for i, a in enumerate(checks)}
        for a in checks:
            self.submit(a, prompts[a.rid])
        for n in traffic.prompt_lengths(mix):
            self.submit(traffic.Arrival(f"warm-{n}", 0.0, n, 2),
                        traffic.tokens(ctx.seed + 1, 1000 + n, n, vocab))
        done = {r.request_id: r for r in self.engine.run()}
        for a in checks:
            r, prompt = done[a.rid], prompts[a.rid]
            if r.finish_reason != "length" or len(r.tokens) != new:
                notes.append(f"{a.rid}: {r.finish_reason} {len(r.tokens)}")
                continue
            toks = np.zeros((1, pad), np.int32)     # causal: padding inert
            full = prompt + list(r.tokens)
            toks[0, :len(full)] = full
            ref = np.asarray(reference(self.params, jnp.asarray(toks)))
            mask = np.zeros(pad, bool) if near_ties is None else np.asarray(
                near_ties(self.params, jnp.asarray(toks)))
            # the engine's own prefill program on the same prompt
            ptoks = np.zeros((1, self.engine._bucket(len(prompt))), np.int32)
            ptoks[0, :len(prompt)] = prompt
            logits, _ = self.engine._prefill(self.params, jnp.asarray(ptoks))
            got = np.asarray(logits[0, len(prompt) - 1], np.float32)
            c = compare(ref, got, len(prompt), r.tokens, mask, LOGIT_TOL)
            worst = max(worst, c.err or 0.0, c.gap / 2)
            rows += c.err is not None
            compared += c.compared
            left_out += c.left_out
            if not c.ok:
                notes.append(f"{a.rid}: first-step logits off the float32 "
                             f"reference by {c.err or 0.0:.4f}, decoded "
                             f"tokens by {c.gap:.4f} of the range")
        checked = new * len(checks)
        if min_compared is None:
            min_compared = checked          # every one
        notes += counts_notes(compared, left_out, checked, min_compared)
        self.engine.pool.flush_prefixes()   # the window starts with an
        self.stamps.ticks.clear()           # empty prefix cache
        ctx.lap("check_compile_or_cache_and_warmup")
        return notes, (f"worst_logit_err={worst:.4f} "
                       f"compared={rows}+{compared} left_out={left_out}")

    # -- the two loops ----------------------------------------------------------

    def open_loop(self, mix, seconds, trace_slice=0.0, seed=None):
        """Offer ``mix`` for ``seconds``; returns (arrivals, window zero,
        traced).  With a ``trace_slice`` the last part of the window is
        traced, requests due in it are not sampled, and nothing drains."""
        seed = self.ctx.seed if seed is None else seed
        arrivals = traffic.open_loop(mix, seconds)
        vocab = self.cfg.vocab_size
        prompts = {a.rid: traffic.tokens(seed, a.rid, a.prompt_len, vocab)
                   for a in arrivals}
        sample_end = seconds - trace_slice
        for a in arrivals:
            a.sampled = a.sampled and a.due < sample_end
        waiting = {a.rid for a in arrivals if a.sampled}
        t0 = self.clock() + mix["lead_in_s"]
        i, armed, tracing_on = 0, False, False
        hard_end = seconds if trace_slice else seconds + mix["drain_s"]
        while True:
            now = self.clock() - t0
            if not armed and now >= 0:
                armed = True
                self.ctx.lap("lead_in")
                self.compiles.arm()
            while i < len(arrivals) and arrivals[i].due <= now:
                self.submit(arrivals[i], prompts[arrivals[i].rid])
                i += 1
            if trace_slice and not tracing_on and now >= sample_end:
                tracing.start(self.ctx.trace_dir)
                tracing_on = True
                self.traced_span = [self.clock(), None]
            if now >= seconds:
                waiting -= self.stamps.finished.keys()
                if not waiting or now >= hard_end:
                    break
            if self.busy:
                self.step()
            elif i < len(arrivals):
                time.sleep(max(min(arrivals[i].due - now, 0.05), 0.0))
            else:
                break
        traced = None
        if tracing_on:
            self.traced_span[1] = self.clock()
            tracing.stop()
            traced = tracing.load(self.ctx.trace_dir, SPANS)
        return arrivals, t0, traced

    def backlog(self, mix, seconds, trace_slice=0.0):
        """Keep ``queue_floor`` requests waiting for ``lead_in_s`` and then
        for the window; returns (window start, window end, traced).  With
        a ``trace_slice`` the window ends when the traced slice does."""
        gen = traffic.backlog(mix, self.ctx.seed)
        t0 = self.clock() + mix["lead_in_s"]
        t_open = trace_at = None
        if trace_slice:
            seconds = seconds / 4 + trace_slice
        while True:
            now = self.clock() - t0
            if t_open is None and now >= 0:
                t_open = self.clock()
                self.ctx.lap("lead_in")
                self.compiles.arm()
            if trace_slice and trace_at is None \
                    and now >= seconds - trace_slice:
                tracing.start(self.ctx.trace_dir)
                trace_at = now
                self.traced_span = [self.clock(), None]
            if now >= seconds:
                break
            while self.engine.queue_depth < mix["queue_floor"]:
                self.submit(next(gen))
            self.step()
        t_close = self.clock()
        traced = None
        if trace_at is not None:
            self.traced_span[1] = t_close
            tracing.stop()
            traced = tracing.load(self.ctx.trace_dir, SPANS)
        return t_open, t_close, traced

    def request_samples(self, arrivals, t0):
        """``stats.request_stats`` of ``arrivals`` on the clock whose zero
        is the start of the window."""
        st = self.stamps
        rel = lambda d: {k: v - t0 for k, v in d.items()}    # noqa: E731
        return stats.request_stats(
            arrivals, rel(self.submitted), rel(st.admitted),
            {k: [t - t0 for t in v] for k, v in st.token_times.items()},
            {k: (t - t0, why) for k, (t, why) in st.finished.items()})

    def batch_sizes(self, t_from, t_to):
        return [n for t, n in self.stamps.ticks if t_from <= t < t_to]

    def drain(self):
        self.engine.run()
        self.engine.pool.flush_prefixes()
        self.stamps.ticks.clear()

    def stalls(self, t_from, t_to):
        """The longest wait between two decode ticks, and when: a stall
        that a percentile hides is still said."""
        ts = [t for t, _ in self.stamps.ticks if t_from <= t < t_to]
        gap, at = max(((b - a, a - t_from) for a, b in zip(ts, ts[1:])),
                      default=(0.0, 0.0))
        return f"longest_tick_gap={gap:.3f}s at {at:.1f}s"


def _facts(server, ctx):
    """Sizes the readers need.  ``decode_context_tokens``: the cached
    positions the decode ticks of the traced span had to read, one layer:
    the j-th token of a request is decoded over its prompt and j tokens."""
    facts = {"chips": ctx.chips, "layers": server.cfg.num_layers,
             "shapes": ctx.config.get("kernel_shapes", {})}
    if server.traced_span:
        t0, t1 = server.traced_span
        facts["decode_context_tokens"] = sum(
            server.asked[rid].prompt_len + j
            for rid, times in server.stamps.token_times.items()
            for j, t in enumerate(times) if j and t0 <= t <= t1)
    return facts


def run_open(ctx):
    server = Server(ctx)
    mix = ctx.traffic
    notes, said = server.check_and_warm_up()
    slice_s = mix["trace_slice_s"] if ctx.trace else 0.0
    arrivals, t0, traced = server.open_loop(mix, ctx.seconds, slice_s)
    samples, failed = server.request_samples(arrivals, t0)
    attempted = sum(a.sampled for a in arrivals)
    if ctx.trace:       # no drain: what the slice cut short is not a failure
        failed = [f for f in failed if f[1] != "unfinished"]
        attempted = len(samples["ttft_s"]) + len(failed)
    samples["decode_batch"] = server.batch_sizes(
        t0, t0 + ctx.seconds - slice_s)
    if server.compiles.count:
        notes.append(f"{server.compiles.count} programs compiled inside "
                     "the window")
    notes += [f"failed request {f}" for f in failed[:5]]
    e2e = {}
    if samples["ttft_s"]:
        e2e = {"ttft_p90_s": stats.percentile(samples["ttft_s"], 90),
               "tpot_p90_s": stats.percentile(samples["tpot_s"], 90)}
    return Run(correct=not notes, attempted=attempted, failed=len(failed),
               end_to_end=e2e, samples=samples, trace=traced,
               facts=_facts(server, ctx),
               notes=notes + [f"sampled={attempted} {said} ticks="
                              f"{len(samples['decode_batch'])} "
                              f"submit_late_max="
                              f"{max(samples['submit_late_s'], default=0):.3f}"
                              f"s " + server.stalls(t0, t0 + ctx.seconds)])


def run_backlog(ctx):
    server = Server(ctx)
    mix = ctx.traffic
    notes, said = server.check_and_warm_up()
    slice_s = mix["trace_slice_s"] if ctx.trace else 0.0
    t_open, t_close, traced = server.backlog(mix, ctx.seconds, slice_s)
    st = server.stamps
    done = [rid for rid, (t, _) in st.finished.items()
            if t_open <= t <= t_close and rid in server.asked
            and not str(rid).startswith(("check", "warm"))]
    bad = [rid for rid in done if st.finished[rid][1] != "length"
           or len(st.token_times[rid]) != server.asked[rid].new_tokens]
    tokens = sum(server.asked[r].prompt_len + server.asked[r].new_tokens
                 for r in done if r not in bad)
    if server.compiles.count:
        notes.append(f"{server.compiles.count} programs compiled inside "
                     "the window")
    notes += [f"failed request {r}: {st.finished[r]}" for r in bad[:5]]
    return Run(correct=not notes and bool(done), attempted=len(done),
               failed=len(bad),
               end_to_end={"served_tokens_per_s":
                           tokens / (t_close - t_open)},
               samples={"decode_batch": server.batch_sizes(t_open, t_close)},
               trace=traced, facts=_facts(server, ctx),
               notes=notes + [f"completed={len(done)} window_s="
                              f"{t_close - t_open:.3f} {said} "
                              + server.stalls(t_open, t_close)])


def sweep(ctx, rates):
    """Find the knee once: the same server under one open-loop rate after
    another; prints a table line per rate."""
    server = Server(ctx)
    notes, _ = server.check_and_warm_up()
    print("sweep notes:", notes, flush=True)
    for k, rate in enumerate(rates):
        mix = {**ctx.traffic, "rate_rps": rate}
        t_begin = server.clock()
        # prompts of its own for every rate: none finds a prefix cached
        arrivals, t0, _ = server.open_loop(mix, ctx.seconds,
                                           seed=ctx.seed + 7 * k)
        st = server.stamps
        samples, failed = server.request_samples(arrivals, t0)
        due = lambda t: sum(a.due < t for a in arrivals)    # noqa: E731
        fin = lambda t: sum(                                # noqa: E731
            st.finished.get(a.rid, (1e18,))[0] - t0 < t for a in arrivals)
        half, end = ctx.seconds / 2, ctx.seconds
        batch = server.batch_sizes(t0, t0 + ctx.seconds)
        p = stats.percentile
        print(f"SWEEP rate={rate} sampled={sum(a.sampled for a in arrivals)}"
              f" failed={len(failed)}"
              f" backlog@0={due(0) - fin(0)} backlog@half="
              f"{due(half) - fin(half)} backlog@end={due(end) - fin(end)}"
              f" ttft_p50={p(samples['ttft_s'], 50):.4f}"
              f" ttft_p90={p(samples['ttft_s'], 90):.4f}"
              f" tpot_p50={p(samples['tpot_s'], 50):.4f}"
              f" tpot_p90={p(samples['tpot_s'], 90):.4f}"
              f" qwait_p90={p(samples['queue_wait_s'], 90):.4f}"
              f" batch_mean={sum(batch) / max(len(batch), 1):.2f}"
              f" ticks={len(batch)}"
              f" tick_s={ctx.seconds / max(len(batch), 1):.4f}"
              f" compiles={server.compiles.count}"
              f" {server.stalls(t0, t0 + ctx.seconds)}"
              f" took={server.clock() - t_begin:.1f}", flush=True)
        server.drain()
        for d in (server.submitted, st.admitted, st.token_times,
                  st.finished, server.asked):
            d.clear()
        server.compiles.armed, server.compiles.count = False, 0
