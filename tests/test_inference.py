"""apex_tpu.inference: KV-cache decode + continuous-batching engine.

Correctness contract under test (beyond-reference serving leg):

* the single-query decode kernel matches its masked reference AND the
  full-sequence flash kernel's last position;
* ``prefill`` + N ``decode_step`` calls reproduce the full forward's
  logits token-for-token (serial f32 exactly; bf16 cache within bf16
  tolerance; TP=2 shard_map identically to serial);
* the engine's batched greedy decode is token-identical to decoding
  every request in isolation, across admission/slot-reuse/eviction.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.inference import (InferenceEngine, KVCache, Request,
                                SamplingParams, sample)
from apex_tpu.models.gpt import GPTConfig, GPTModel, pack_for_shard_map
from apex_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_decode,
    flash_attention_decode_reference,
)
from apex_tpu.utils import set_force_pallas


def tiny_cfg(**kw):
    base = dict(vocab_size=32, hidden_size=16, num_layers=2,
                num_attention_heads=2, max_seq_len=16)
    base.update(kw)
    return GPTConfig(**base)


def _model_and_params(key=0, **kw):
    model = GPTModel(tiny_cfg(**kw))
    return model, model.init_params(jax.random.PRNGKey(key))


def _clone(req: Request) -> Request:
    return dataclasses.replace(req)


# -- decode attention kernel -------------------------------------------------

class TestDecodeKernel:
    @pytest.fixture(autouse=True)
    def _force_pallas(self):
        set_force_pallas(True)
        yield
        set_force_pallas(None)

    @pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_reference_ragged_lens(self, rng, cache_dtype):
        b, S, h, d = 4, 160, 3, 64
        q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, S, h, d), cache_dtype)
        v = jnp.asarray(rng.randn(b, S, h, d), cache_dtype)
        # lengths hitting the edges: 1 token, mid-block, block boundary,
        # full cache
        lens = jnp.asarray([1, 97, 128, S], jnp.int32)
        out = flash_attention_decode(q, k, v, lens)
        ref = flash_attention_decode_reference(q, k, v, lens)
        tol = 2e-5 if cache_dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

    def test_matches_full_sequence_kernel(self, rng):
        """Decode of the last token over a full cache == the causal
        full-sequence kernel's last position."""
        b, s, h, d = 2, 128, 2, 32
        q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
        full = flash_attention(q, k, v, causal=True)       # (b, h, s, d)
        dec = flash_attention_decode(
            q[:, :, -1], k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            jnp.full((b,), s, jnp.int32))
        np.testing.assert_allclose(np.asarray(dec),
                                   np.asarray(full[:, :, -1]),
                                   rtol=2e-5, atol=2e-5)

    def test_masked_rows_do_not_leak(self, rng):
        """Garbage beyond each row's length must not affect the output."""
        b, S, h, d = 2, 256, 2, 32
        q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, S, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, S, h, d), jnp.float32)
        lens = jnp.asarray([40, 200], jnp.int32)
        out = flash_attention_decode(q, k, v, lens)
        poisoned_k = k.at[0, 40:].set(1e4).at[1, 200:].set(1e4)
        poisoned_v = v.at[0, 40:].set(1e4).at[1, 200:].set(1e4)
        out_p = flash_attention_decode(q, poisoned_k, poisoned_v, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_p),
                                   rtol=1e-6, atol=1e-6)


# -- prefill + decode vs full forward ----------------------------------------

def _decode_tail(model, params, tokens, prefill_len, cache_dtype):
    """Prefill ``prefill_len`` tokens, decode the rest; returns the
    decode-step logits stacked ``(b, s - prefill_len, vocab)``."""
    cfg = model.cfg
    b, s = tokens.shape
    logits_p, kv = model.prefill(params, tokens[:, :prefill_len])
    cache = jnp.zeros((b, cfg.num_layers, 2, cfg.max_seq_len,
                       cfg.local_heads, cfg.head_dim), cache_dtype)
    cache = cache.at[:, :, :, :prefill_len].set(
        kv.transpose(2, 0, 1, 3, 4, 5).astype(cache_dtype))
    step = jax.jit(model.decode_step)
    out = []
    for i in range(prefill_len, s):
        lg, cache = step(params, tokens[:, i], cache,
                         jnp.full((b,), i, jnp.int32))
        out.append(lg)
    return logits_p, jnp.stack(out, axis=1)


class TestPrefillDecodeParity:
    @pytest.mark.parametrize(
        "rotary", [pytest.param(True, marks=pytest.mark.slow), False])
    def test_serial_f32_exact(self, rng, rotary):
        model, params = _model_and_params(rotary=rotary)
        tokens = jnp.asarray(rng.randint(0, 32, (2, 12)))
        full = model(params, tokens)
        logits_p, dec = _decode_tail(model, params, tokens, 7, jnp.float32)
        np.testing.assert_allclose(np.asarray(logits_p),
                                   np.asarray(full[:, :7]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dec),
                                   np.asarray(full[:, 7:]),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_cache(self, rng):
        model, params = _model_and_params()
        tokens = jnp.asarray(rng.randint(0, 32, (2, 12)))
        full = model(params, tokens)
        _, dec = _decode_tail(model, params, tokens, 7, jnp.bfloat16)
        np.testing.assert_allclose(np.asarray(dec),
                                   np.asarray(full[:, 7:]),
                                   rtol=5e-2, atol=5e-2)

    def test_tp2_shard_map_matches_serial(self, rng):
        """Prefill + decode under TP=2 shard_map: vocab-parallel logits
        gathered over the model axis must match the serial decode
        token-for-token (the TP layers are reused unchanged)."""
        model, params = _model_and_params(key=1)
        tokens = jnp.asarray(rng.randint(0, 32, (2, 10)))
        p = 6
        full = model(params, tokens)

        cfg_p = tiny_cfg(tensor_parallel_size=2, axis_name="model")
        par = GPTModel(cfg_p)
        mesh = jax.make_mesh((2,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        packed, in_specs, local_fn, _ = pack_for_shard_map(par, params)

        def prefill(sp, toks):
            return par.prefill(local_fn(sp), toks)

        # logits are vocab-parallel (gather last axis); kv is
        # head-parallel (gather axis 4)
        logits_p, kv = jax.jit(shard_map(
            prefill, mesh=mesh, in_specs=(in_specs, P()),
            out_specs=(P(None, None, "model"),
                       P(None, None, None, None, "model"))))(
            packed, tokens[:, :p])
        np.testing.assert_allclose(np.asarray(logits_p),
                                   np.asarray(full[:, :p]),
                                   rtol=1e-4, atol=1e-4)

        b = tokens.shape[0]
        cache = jnp.zeros((b, cfg_p.num_layers, 2, cfg_p.max_seq_len,
                           cfg_p.num_attention_heads, cfg_p.head_dim),
                          jnp.float32)
        cache = cache.at[:, :, :, :p].set(kv.transpose(2, 0, 1, 3, 4, 5))

        def decode(sp, toks, cache, pos):
            return par.decode_step(local_fn(sp), toks, cache, pos)

        cache_spec = P(None, None, None, None, "model")
        step = jax.jit(shard_map(
            decode, mesh=mesh,
            in_specs=(in_specs, P(), cache_spec, P()),
            out_specs=(P(None, "model"), cache_spec)))
        for i in range(p, tokens.shape[1]):
            lg, cache = step(packed, tokens[:, i], cache,
                             jnp.full((b,), i, jnp.int32))
            np.testing.assert_allclose(np.asarray(lg),
                                       np.asarray(full[:, i]),
                                       rtol=1e-4, atol=1e-4)


# -- KV cache manager --------------------------------------------------------

class TestKVCache:
    def _cache(self, slots=3):
        return KVCache(slots, layers=2, max_seq=8, kv_heads=2, head_dim=4,
                       dtype=jnp.bfloat16)

    def test_allocate_free_reuse(self):
        c = self._cache(2)
        a, b = c.allocate(), c.allocate()
        assert {a, b} == {0, 1}
        assert c.allocate() is None          # exhausted
        c.free(a)
        assert c.allocate() == a             # freed slot comes back
        with pytest.raises(ValueError):
            c.free(b)
            c.free(b)                        # double free

    def test_write_prompt_casts_and_masks(self, rng):
        c = self._cache()
        kv = jnp.asarray(rng.randn(2, 2, 8, 2, 4), jnp.float32)
        c.write_prompt(1, kv, length=5)
        assert c.data.dtype == jnp.bfloat16
        assert c.lengths[1] == 5
        np.testing.assert_allclose(np.asarray(c.data[1], np.float32),
                                   np.asarray(kv.astype(jnp.bfloat16),
                                              np.float32))
        c.advance(1)
        assert c.lengths[1] == 6

    def test_write_prompt_validation(self, rng):
        c = self._cache()
        with pytest.raises(ValueError):
            c.write_prompt(0, jnp.zeros((2, 2, 9, 2, 4)), 9)  # > max_seq
        with pytest.raises(ValueError):
            c.write_prompt(0, jnp.zeros((2, 2, 8, 2, 4)), 0)  # empty

    def test_byte_accounting(self, rng):
        """free_bytes is slot-granular (allocatable capacity);
        used_bytes/occupancy are token-granular (valid entries) — the
        gap between them is the internal fragmentation the paged cache
        exists to remove."""
        c = self._cache(2)                       # 2 slots x 8 positions
        assert c.free_bytes() == 2 * c.slot_bytes
        assert c.used_bytes() == 0 and c.occupancy() == 0.0
        slot = c.allocate()
        c.write_prompt(slot, jnp.asarray(rng.randn(2, 2, 8, 2, 4),
                                         jnp.float32), length=4)
        assert c.free_bytes() == 1 * c.slot_bytes
        assert c.used_bytes() == c.slot_bytes // 2   # 4 of 8 positions
        assert c.occupancy() == pytest.approx(4 / 16)
        c.advance(slot)
        assert c.occupancy() == pytest.approx(5 / 16)
        c.free(slot)
        assert c.free_bytes() == 2 * c.slot_bytes and c.occupancy() == 0.0


# -- sampling ----------------------------------------------------------------

class TestSampling:
    def test_greedy_is_argmax(self):
        logits = jnp.asarray([[0.1, 2.0, -1.0], [3.0, 0.0, 1.0]])
        np.testing.assert_array_equal(np.asarray(sample(logits)), [1, 0])

    def test_stochastic_requires_key(self):
        with pytest.raises(ValueError):
            sample(jnp.zeros((4,)), SamplingParams(temperature=1.0))

    def test_top_k_restricts_support(self):
        logits = jnp.asarray([5.0, 4.0, -10.0, -10.0])
        p = SamplingParams(temperature=1.0, top_k=2)
        draws = {int(sample(logits, p, jax.random.PRNGKey(i)))
                 for i in range(32)}
        assert draws <= {0, 1} and len(draws) == 2

    def test_top_p_restricts_to_nucleus(self):
        # probs ~ [0.64, 0.24, 0.09, 0.03]: a 0.7 nucleus keeps the top
        # two (the crossing token is included), never tokens 2 or 3
        logits = jnp.asarray([4.0, 3.0, 2.0, 1.0])
        p = SamplingParams(temperature=1.0, top_p=0.7)
        draws = {int(sample(logits, p, jax.random.PRNGKey(i)))
                 for i in range(64)}
        assert draws <= {0, 1} and len(draws) == 2

    def test_top_p_always_keeps_one_token(self):
        # a tiny nucleus still samples: the argmax survives even when
        # its probability alone exceeds top_p
        logits = jnp.asarray([10.0, 0.0, 0.0, 0.0])
        p = SamplingParams(temperature=1.0, top_p=0.01)
        assert all(int(sample(logits, p, jax.random.PRNGKey(i))) == 0
                   for i in range(8))

    def test_top_p_composes_with_top_k(self):
        # k=3 keeps {0,1,2}; the 0.75 nucleus over the survivors' mass
        # then drops token 2 as well
        logits = jnp.asarray([4.0, 3.0, 2.0, 1.9])
        p = SamplingParams(temperature=1.0, top_k=3, top_p=0.75)
        draws = {int(sample(logits, p, jax.random.PRNGKey(i)))
                 for i in range(64)}
        assert draws <= {0, 1}

    def test_top_p_one_is_full_vocab(self):
        logits = jnp.asarray([0.0, 0.1, 0.2, 0.3])
        p = SamplingParams(temperature=5.0, top_p=1.0)
        draws = {int(sample(logits, p, jax.random.PRNGKey(i)))
                 for i in range(128)}
        assert draws == {0, 1, 2, 3}

    def test_invalid_params(self):
        with pytest.raises(ValueError):
            SamplingParams(temperature=-1.0)
        with pytest.raises(ValueError):
            SamplingParams(top_k=0)
        with pytest.raises(ValueError):
            SamplingParams(top_p=0.0)
        with pytest.raises(ValueError):
            SamplingParams(top_p=1.5)


# -- continuous-batching engine ----------------------------------------------

class TestEngine:
    def _requests(self, rng, n=8, vocab=32):
        return [Request(request_id=i,
                        prompt=[int(t) for t in
                                rng.randint(1, vocab,
                                            int(rng.randint(2, 9)))],
                        max_new_tokens=int(rng.randint(1, 7)))
                for i in range(n)]

    def test_mixed_batch_matches_isolated_greedy(self, rng):
        """The headline invariant: every response from a mixed 8-request
        workload on 3 slots is identical to running that request alone."""
        model, params = _model_and_params()
        reqs = self._requests(rng)
        eng = InferenceEngine(model, params, max_slots=3,
                              cache_dtype=jnp.float32)
        for r in reqs:
            eng.submit(_clone(r))
        batched = {r.request_id: r.tokens for r in eng.run()}
        assert len(batched) == len(reqs)
        # no deadlines in this workload: the eviction counter must stay 0
        assert eng.metrics.summary()["evicted"] == 0
        for r in reqs:
            solo = InferenceEngine(model, params, max_slots=1,
                                   cache_dtype=jnp.float32)
            solo.submit(_clone(r))
            assert solo.run()[0].tokens == batched[r.request_id], \
                f"request {r.request_id} diverged under batching"

    def test_slot_reuse_and_admission_under_full_occupancy(self, rng):
        """More requests than slots: the engine must queue, admit as
        slots free, and reuse every slot without leaking."""
        model, params = _model_and_params()
        reqs = self._requests(rng, n=6)
        eng = InferenceEngine(model, params, max_slots=2,
                              cache_dtype=jnp.float32)
        for r in reqs:
            eng.submit(r)
        # after one step both slots are busy and the rest are queued
        eng.step()
        assert eng.cache.free_slots == 0 or len(eng.completed) > 0
        assert len(eng._queue) <= 4
        out = eng.run()
        assert sorted(r.request_id for r in out) == list(range(6))
        assert eng.cache.free_slots == 2         # all slots returned
        occ = [a for a, _ in eng.metrics.occupancy]
        assert max(occ) == 2                     # full occupancy reached

    def test_deadline_eviction(self, rng):
        """A fake clock advances one unit per reading: requests whose
        deadline passes mid-decode are evicted with partial output."""
        model, params = _model_and_params()
        t = [0.0]

        def clock():
            t[0] += 1.0
            return t[0]

        eng = InferenceEngine(model, params, max_slots=2, clock=clock,
                              cache_dtype=jnp.float32)
        eng.submit(Request(request_id=0, prompt=[1, 2, 3],
                           max_new_tokens=100, deadline=30.0))
        eng.submit(Request(request_id=1, prompt=[4, 5],
                           max_new_tokens=3))
        out = {r.request_id: r for r in eng.run(max_steps=200)}
        assert out[1].finish_reason == "length"
        assert out[0].finish_reason == "evicted"
        assert 0 < len(out[0].tokens) < 100
        # the eviction reached the serving stats (not just the Response)
        assert eng.metrics.summary()["evicted"] == 1
        # queued-but-never-run requests past deadline evict empty
        eng2 = InferenceEngine(model, params, max_slots=1, clock=clock,
                               cache_dtype=jnp.float32)
        eng2.submit(Request(request_id=7, prompt=[1], deadline=t[0] - 1))
        (r,) = eng2.run()
        assert r.finish_reason == "evicted" and r.tokens == []
        assert eng2.metrics.summary()["evicted"] == 1

    def test_eos_and_cache_exhaustion(self, rng):
        model, params = _model_and_params()
        eng = InferenceEngine(model, params, max_slots=1,
                              cache_dtype=jnp.float32)
        # find the greedy continuation, then rerun with its first token
        # as eos — the request must stop immediately after emitting it
        eng.submit(Request(request_id=0, prompt=[3, 4, 5],
                           max_new_tokens=4))
        first = eng.run()[0].tokens[0]
        eng2 = InferenceEngine(model, params, max_slots=1,
                               cache_dtype=jnp.float32)
        eng2.submit(Request(request_id=1, prompt=[3, 4, 5],
                            max_new_tokens=4, eos_id=first))
        (r,) = eng2.run()
        assert r.finish_reason == "eos" and r.tokens == [first]
        # a request that would overrun max_seq stops with "length"
        eng3 = InferenceEngine(model, params, max_slots=1,
                               cache_dtype=jnp.float32)
        eng3.submit(Request(request_id=2, prompt=[1] * 14,
                            max_new_tokens=100))
        (r,) = eng3.run()
        assert r.finish_reason == "length"
        # cache rows allow decode feeds at positions 14 and 15; with the
        # prefill-sampled token that is max_seq - prompt_len + 1 outputs
        # (the final sample needs no cache write of its own)
        assert len(r.tokens) == 16 - 14 + 1

    def test_prompt_validation(self, rng):
        model, params = _model_and_params()
        eng = InferenceEngine(model, params, max_slots=1)
        with pytest.raises(ValueError):
            eng.submit(Request(request_id=0, prompt=[]))
        with pytest.raises(ValueError):
            eng.submit(Request(request_id=1, prompt=[1] * 16))

    def test_serving_metrics(self, rng):
        model, params = _model_and_params()
        t = [0.0]

        def clock():
            t[0] += 0.5
            return t[0]

        eng = InferenceEngine(model, params, max_slots=2, clock=clock,
                              cache_dtype=jnp.float32)
        for i in range(3):
            eng.submit(Request(request_id=i, prompt=[1 + i, 2],
                               max_new_tokens=3))
        eng.run()
        s = eng.metrics.summary()
        assert s["requests"] == 3
        assert s["tokens"] == 9
        assert s["tokens_per_s"] > 0
        assert s["ttft_p50_s"] > 0
        assert s["token_latency_p50_s"] > 0
        assert 0 < s["slot_occupancy_mean"] <= 1
