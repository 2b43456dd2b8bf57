"""The body the one-rank recipes share (``nemotron_h/pretrain_nemotron_h.py``,
``lfm2/pretrain_lfm2.py``): one expert-parallel rank's share of a model that
``apex_tpu.models.gpt.GPTModel`` builds under a ``layer_pattern``, trained
with amp O2, ``FusedAdam(master_weights=True)`` per leaf and every layer
recomputed in the backward.  A recipe gives its configurations (``GPTConfig``
keywords by name) and the leaves that stay float32 under O2 beside the norms
amp keeps, and binds the functions here to them.

The same wiring as ``examples/bert/pretrain_bert.py``; random tokens,
next-token loss over the sliced vocabulary.  The share is one rank's: more
than one device is refused (expert parallelism over chips is not here yet).
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def parse_args(configs, description, argv=None):
    p = argparse.ArgumentParser(description=description)
    p.add_argument("--config", default="share", choices=sorted(configs))
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--seq-len", type=int, default=8192)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--opt-level", default="O2", choices=["O0", "O2"])
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--weight-decay", type=float, default=0.1)
    p.add_argument("--no-remat", action="store_true",
                   help="keep every layer's activations for the backward")
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def model_config(configs, args):
    import jax.numpy as jnp
    from apex_tpu.models.gpt import GPTConfig
    return GPTConfig(
        **configs[args.config], max_seq_len=args.seq_len,
        remat=not args.no_remat,
        dtype=jnp.bfloat16 if args.opt_level == "O2" else jnp.float32)


def init_params(keep_f32, args, model, amp_state, device):
    """Seeded weights on ``device`` under the opt level's cast (O2: bf16,
    with the norms and ``keep_f32`` float32) and their true count."""
    import jax
    with jax.default_device(device):
        full = model.init_params(jax.random.PRNGKey(args.seed))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(full))
    params = jax.tree_util.tree_map_with_path(
        lambda path, cast, kept: kept if any(
            k in jax.tree_util.keystr(path) for k in keep_f32) else cast,
        amp_state.cast_params(full), full)
    return params, n_params


def zero_counters():
    import jax.numpy as jnp
    return {k: jnp.zeros((), jnp.int32)
            for k in ("routed_pairs", "held_pairs", "expert_tokens_max")}


def expert_load(counters, steps, n_expert_layers, held):
    """What the step's counters say since they were last zeroed: tokens per
    held expert and layer (mean, max) and the share of routed pairs that
    landed on a held expert."""
    c = {k: int(v) for k, v in counters.items()}
    return {"expert_tokens_mean": c["held_pairs"]
            / max(steps * n_expert_layers * held, 1),
            "expert_tokens_max": c["expert_tokens_max"],
            "held_pair_share": c["held_pairs"] / max(c["routed_pairs"], 1)}


def build(configs, keep_f32, args, devices=None):
    """The recipe as data, as ``examples/bert/pretrain_bert.py::build``
    returns it: ``(train_step, state, make_batch, n_params)`` with
    ``train_step(*state, *batch) -> (*state, loss)``.  ``state`` is
    ``(params, opt_state, scaler_state, counters)``; the counters are the
    expert load the step counted (``expert_load`` reads them)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.models.gpt import GPTModel
    from apex_tpu.optimizers import FusedAdam

    devices = list(devices if devices is not None else jax.devices())
    if len(devices) != 1:
        raise SystemExit(
            f"this recipe is one expert-parallel rank's share of the model "
            f"and runs on one device, not {len(devices)}: the experts of "
            "the other ranks and the exchange with them are not here yet")
    cfg = model_config(configs, args)
    model = GPTModel(cfg)
    # the router is replicated over the expert-parallel ranks and its
    # gradient is the sum over them; this rank has one term of it (the held
    # experts' outputs), and stepping the router on that alone teaches it to
    # avoid the held experts: their load fell from 3 000 pairs a layer to
    # none within 31 steps.  So the share computes the gradient and leaves
    # the step to the exchange that is not here (rate 0 in its own group)
    adam = FusedAdam(lr=args.lr, weight_decay=args.weight_decay,
                     master_weights=args.opt_level == "O2",
                     param_group_fn=lambda path: "router"
                     if "'router'" in path else "default",
                     param_groups={"router": {"lr": 0.0}})
    state = amp.initialize(model.apply, adam, opt_level=args.opt_level)
    params, n_params = init_params(keep_f32, args, model, state, devices[0])
    scaler_state = state.scaler.init()
    opt_state = adam.init(params)
    params, opt_state, scaler_state, counters = jax.device_put(
        (params, opt_state, scaler_state, zero_counters()), devices[0])

    rng = np.random.RandomState(args.seed % 2 ** 32)

    def make_batch():
        ids = rng.randint(0, cfg.vocab_size,
                          (args.batch_size, args.seq_len + 1))
        return (jax.device_put(ids[:, :-1], devices[0]),
                jax.device_put(ids[:, 1:], devices[0]))

    def step(params, opt_state, scaler_state, counters, tokens, targets):
        def loss_fn(p):
            raw, load = model.loss(p, tokens, targets,
                                   return_expert_load=True)
            return amp.scale_loss(raw, scaler_state), load

        (loss, load), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        loss = loss / scaler_state.loss_scale
        params, opt_state, scaler_state, _ = amp.unscale_step(
            adam, grads, params, opt_state, state.scaler, scaler_state)
        counters = {
            "routed_pairs": counters["routed_pairs"]
            + load.shape[0] * tokens.size * cfg.moe_top_k,
            "held_pairs": counters["held_pairs"] + jnp.sum(load),
            "expert_tokens_max": jnp.maximum(
                counters["expert_tokens_max"], jnp.max(load))}
        return params, opt_state, scaler_state, counters, loss

    train_step = jax.jit(step, donate_argnums=(0, 1, 2))
    return train_step, (params, opt_state, scaler_state, counters), \
        make_batch, n_params


def main(configs, keep_f32, description):
    args = parse_args(configs, description)

    import jax

    train_step, state, make_batch, n_params = build(configs, keep_f32, args)
    cfg = model_config(configs, args)
    n_expert_layers = cfg.layer_pattern.count("E")

    *state, loss = train_step(*state, *make_batch())      # compile + warmup
    jax.block_until_ready(loss)
    state[3] = zero_counters()

    t0 = time.perf_counter()
    seen = since = 0
    for step in range(1, args.steps + 1):
        *state, loss = train_step(*state, *make_batch())
        seen += args.batch_size * args.seq_len
        since += 1
        if step % args.print_freq == 0 or step == args.steps:
            load = expert_load(state[3], since, n_expert_layers,
                               cfg.moe_held[1])
            state[3], since = zero_counters(), 0
            print(f"step {step:5d}  loss {float(loss):.4f}  "
                  f"{seen / (time.perf_counter() - t0):9.1f} tokens/s  "
                  f"tokens/expert mean {load['expert_tokens_mean']:.1f} "
                  f"max {load['expert_tokens_max']}  "
                  f"held share {load['held_pair_share']:.4f}", flush=True)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    print(f"DONE config={args.config} ({n_params/1e6:.1f}M parameters held) "
          f"opt_level={args.opt_level} throughput={seen / dt:.1f} tokens/s")
