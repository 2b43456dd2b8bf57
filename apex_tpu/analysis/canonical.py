"""The canonical train/serve programs the lint gate covers.

Six programs spanning every execution shape the repo ships: the GPT
train step at dp=N, at tp=2 + sequence parallelism, and at pp=2 (ring
1F1B under a ``while``); the anomaly-guarded train step; and the two
serving programs (batch prefill, cache-ring decode).  Each is the SAME
idiom the tests, ``__graft_entry__.dryrun_multichip`` and the examples use —
linting a toy stand-in would gate nothing.

Models are tiny (vocab 32, hidden 16, 2 layers): the lint rules key on
STRUCTURE (dataflow, donation, collective chains), not size, and tiny
programs keep the CI leg seconds-cheap.  Builders construct fn + args
only; compilation happens lazily inside ``lint()``.

``tools/lint_graph.py`` runs these against the committed baseline
(``tools/lint_baseline.json``); ``tests/test_analysis.py::TestCanonical``
carries the same check on the 8-device CPU mesh.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

from apex_tpu.analysis.program import LintProgram

TINY = dict(vocab_size=32, hidden_size=16, num_layers=2,
            num_attention_heads=4, max_seq_len=8)


def _tiny_batch(n_rows: int, seq: int, seed: int = 0):
    import jax.numpy as jnp
    import numpy as np
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randint(0, TINY["vocab_size"], (n_rows, seq))),
            jnp.asarray(r.randint(0, TINY["vocab_size"], (n_rows, seq))))


def make_gpt_train_dp(n_devices: int) -> LintProgram:
    """Data-parallel GPT train step: shard_map grads + pmean + FusedAdam,
    params and opt state donated."""
    import jax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam

    dp = max(2, n_devices)
    mesh = jax.make_mesh((dp,), ("data",), devices=jax.devices()[:dp])
    model = GPTModel(GPTConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0))
    adam = FusedAdam(lr=1e-3)
    opt_state = adam.init(params)

    def dp_body(p, tk, tg):
        loss, g = jax.value_and_grad(model.loss)(p, tk, tg)
        return (jax.lax.pmean(loss, "data"),
                jax.tree_util.tree_map(
                    lambda a: jax.lax.pmean(a, "data"), g))

    grad = jax.shard_map(dp_body, mesh=mesh,
                         in_specs=(P(), P("data"), P("data")),
                         out_specs=(P(), P()), check_vma=False)

    def train_step(p, opt, tk, tg):
        loss, g = grad(p, tk, tg)
        new_p, new_opt = adam.step(g, p, opt)
        return loss, new_p, new_opt

    tokens, targets = _tiny_batch(dp * 2, TINY["max_seq_len"], seed=1)
    return LintProgram("gpt_train_dp", fn=train_step,
                       args=(params, opt_state, tokens, targets),
                       donate_argnums=(0, 1))


def make_gpt_train_tp_sp(n_devices: int) -> LintProgram:
    """tp=2 + sequence-parallel GPT train step (Megatron-SP collective
    algebra: gather(tiled)/psum_scatter edges)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import (GPTConfig, GPTModel,
                                     pack_for_shard_map)
    from apex_tpu.optimizers import FusedAdam

    tp = 2
    if n_devices < tp:
        raise ValueError(f"gpt_train_tp_sp needs >= {tp} devices")
    mesh = jax.make_mesh((tp,), ("model",), devices=jax.devices()[:tp])
    model = GPTModel(GPTConfig(tensor_parallel_size=tp, axis_name="model",
                               sequence_parallel=True, **TINY))
    init = GPTModel(GPTConfig(**TINY)).init_params(jax.random.PRNGKey(2))
    packed, in_specs, local_fn, repack_fn = pack_for_shard_map(model, init)
    adam = FusedAdam(lr=1e-3)
    opt_state = adam.init(packed)

    def body(sp, tk, tg):
        loss, g = jax.value_and_grad(model.loss)(local_fn(sp), tk, tg)
        return loss, repack_fn(g)

    grad = jax.shard_map(body, mesh=mesh,
                         in_specs=(in_specs, P(), P()),
                         out_specs=(P(), in_specs), check_vma=False)

    def train_step(p, opt, tk, tg):
        loss, g = grad(p, tk, tg)
        new_p, new_opt = adam.step(g, p, opt)
        return loss, new_p, new_opt

    tokens, targets = _tiny_batch(2, TINY["max_seq_len"], seed=2)
    return LintProgram("gpt_train_tp_sp", fn=train_step,
                       args=(packed, opt_state, tokens, targets),
                       donate_argnums=(0, 1))


def make_gpt_train_pp(n_devices: int) -> LintProgram:
    """pp=2 GPT train step: ring 1F1B ``pipeline_step`` under shard_map
    on the (data, pipe) mesh from ``parallel_state``."""
    import jax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import (GPTConfig, GPTModel,
                                     pack_for_shard_map, pipeline_step)
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state

    pp = 2
    if n_devices < pp:
        raise ValueError(f"gpt_train_pp needs >= {pp} devices")
    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        1, pp, devices=jax.devices()[:n_devices])
    dp = parallel_state.get_data_parallel_world_size()

    kw = dict(TINY, num_layers=2 * pp)
    model = GPTModel(GPTConfig(**kw))
    params = model.init_params(jax.random.PRNGKey(3))
    M, mb, seq = 2, 2, kw["max_seq_len"]
    packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
        model, params, n_stages=pp, tensor_axis=None)
    adam = FusedAdam(lr=1e-3)
    opt_state = adam.init(packed)

    def grad_step(sp, tokens, targets):
        tk = tokens.reshape(M, mb, seq)
        tg = targets.reshape(M, mb, seq)
        loss, g = pipeline_step(model, local_fn(sp), tk, tg,
                                pipe_axis="pipe", data_axis="data")
        return loss, repack_fn(g)

    def train_step(p, opt, tokens, targets):
        loss, grads = jax.shard_map(
            grad_step, mesh=mesh,
            in_specs=(in_specs, P("data"), P("data")),
            out_specs=(P(), in_specs), check_vma=False)(p, tokens, targets)
        new_p, new_opt = adam.step(grads, p, opt)
        return loss, new_p, new_opt

    tokens, targets = _tiny_batch(dp * M * mb, seq, seed=3)
    return LintProgram("gpt_train_pp", fn=train_step,
                       args=(packed, opt_state, tokens, targets),
                       donate_argnums=(0, 1))


def make_guarded_step(n_devices: int) -> LintProgram:
    """The anomaly-guarded train step's jitted core (`_raw_step`):
    detect/skip/telemetry fused with the optimizer update, full train
    state donated (the ``donate=True`` guard configuration)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import GuardedTrainStep
    from apex_tpu.resilience.guard import _null_scaler_state

    model = GPTModel(GPTConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(4))
    adam = FusedAdam(lr=1e-3)
    guard = GuardedTrainStep(model.loss, adam, donate=True)
    opt_state = adam.init(params)
    gstate = guard.init_state()
    sstate = _null_scaler_state()
    inj = jnp.asarray([0.0, 0.0, 1.0], jnp.float32)
    tokens, targets = _tiny_batch(2, TINY["max_seq_len"], seed=4)
    return LintProgram(
        "guarded_step", fn=guard._raw_step,
        args=(params, opt_state, gstate, sstate, inj, tokens, targets),
        donate_argnums=(0, 1, 2, 3))


def make_prefill(n_devices: int) -> LintProgram:
    """Serving prefill: full-prompt forward returning (logits, kv).
    Nothing donated — params serve every subsequent request."""
    import jax

    from apex_tpu.models.gpt import GPTConfig, GPTModel

    model = GPTModel(GPTConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(5))
    tokens, _ = _tiny_batch(1, TINY["max_seq_len"], seed=5)
    return LintProgram("prefill", fn=model.prefill, args=(params, tokens))


def make_decode(n_devices: int) -> LintProgram:
    """Serving decode: one batched step over the KV-cache slot ring,
    cache donated (the in-place update the inference engine relies on —
    without it every step holds two full caches)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTConfig, GPTModel

    model = GPTModel(GPTConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(6))
    slots = 4
    head_dim = TINY["hidden_size"] // TINY["num_attention_heads"]
    cache = jnp.zeros((slots, TINY["num_layers"], 2, TINY["max_seq_len"],
                       TINY["num_attention_heads"], head_dim),
                      jnp.float32)
    tokens = jnp.zeros((slots,), jnp.int32)
    positions = jnp.ones((slots,), jnp.int32)
    return LintProgram("decode", fn=model.decode_step,
                       args=(params, tokens, cache, positions),
                       donate_argnums=(2,))


BUILDERS: Dict[str, Callable[[int], LintProgram]] = {
    "gpt_train_dp": make_gpt_train_dp,
    "gpt_train_tp_sp": make_gpt_train_tp_sp,
    "gpt_train_pp": make_gpt_train_pp,
    "guarded_step": make_guarded_step,
    "prefill": make_prefill,
    "decode": make_decode,
}


def canonical_programs(names: Optional[Sequence[str]] = None,
                       n_devices: Optional[int] = None
                       ) -> List[LintProgram]:
    """Build the requested canonical programs (all six by default)."""
    import jax
    if n_devices is None:
        n_devices = jax.device_count()
    names = list(names) if names else list(BUILDERS)
    out = []
    for name in names:
        if name not in BUILDERS:
            raise KeyError(
                f"unknown canonical program {name!r}; have "
                f"{sorted(BUILDERS)}")
        out.append(BUILDERS[name](n_devices))
    return out
