#!/usr/bin/env python
"""Pretraining recipe for the ``nemotron_h`` hybrid (Mamba-2 layers,
sigmoid-routed experts with a shared expert, grouped attention), as ONE
expert-parallel rank sees it: ``--config share`` is one chip's share of a
16-chip layer of NVIDIA-Nemotron-3-Nano-30B-A3B at the published widths
(the first 9 layers ``MEMEM*EME``, experts 0-7 of 128, rows 0-16 383 of
the 131 072-row vocabulary; ``benchmarks/configs/README.md``).

The same wiring as ``examples/bert/pretrain_bert.py``:

* model  — ``apex_tpu.models.gpt.GPTModel`` under a ``layer_pattern``
* opt    — ``FusedAdam(master_weights=True)``, per leaf
* amp O2 — bf16 weights and activations; norms, the router, the Mamba
           decays (``A_log``, ``dt_bias``, ``D``) and the masters float32
* remat  — every layer recomputed in the backward

Random tokens, next-token loss over the sliced vocabulary.  The share is
one rank's: more than one device is refused (expert parallelism over
chips is not here yet), and serving this model raises.

Run:  python examples/nemotron_h/pretrain_nemotron_h.py --config tiny \\
          --batch-size 2 --seq-len 256 --steps 20
"""

from __future__ import annotations

import argparse
import time

import numpy as np

_BLOCK = dict(norm="rmsnorm", ffn_activation="relu2", bias=False,
              tie_head=False, rotary=False, moe_router="sigmoid",
              moe_top_k=6, moe_routed_scale=2.5, mamba_conv_kernel=4,
              layer_pattern="MEMEM*EME")
_CONFIGS = {
    # the published widths; 8 of 128 experts, 1/8 of the vocabulary
    "share": dict(
        _BLOCK, vocab_size=16384, hidden_size=2688, num_attention_heads=32,
        num_kv_heads=2, head_dim=128, ffn_hidden_size=1856, n_experts=128,
        moe_held=(0, 8), moe_shared_ffn=3712, mamba_num_heads=64,
        mamba_head_dim=64, mamba_state_size=128, mamba_groups=8,
        mamba_chunk_size=128),
    # the CPU's size: the same pattern, 4 of 16 experts
    "tiny": dict(
        _BLOCK, vocab_size=512, hidden_size=64, num_attention_heads=4,
        num_kv_heads=2, head_dim=32, ffn_hidden_size=48, n_experts=16,
        moe_held=(0, 4), moe_shared_ffn=96, mamba_num_heads=8,
        mamba_head_dim=16, mamba_state_size=16, mamba_groups=2,
        mamba_chunk_size=32),
}
# float32 under O2 beside the norms amp keeps: what decides an expert or a
# decay must not round to 8 bits
_KEEP_F32 = ("router", "A_log", "dt_bias", "'D'")


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu nemotron_h pretrain")
    p.add_argument("--config", default="share", choices=sorted(_CONFIGS))
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


def model_config(args):
    import jax.numpy as jnp
    from apex_tpu.models.gpt import GPTConfig
    return GPTConfig(
        **_CONFIGS[args.config], max_seq_len=args.seq_len,
        remat=not args.no_remat,
        dtype=jnp.bfloat16 if args.opt_level == "O2" else jnp.float32)


def init_params(args, model, amp_state, device):
    """Seeded weights on ``device`` under the opt level's cast (O2: bf16,
    with the norms and ``_KEEP_F32`` float32) and their true count."""
    import jax
    with jax.default_device(device):
        full = model.init_params(jax.random.PRNGKey(args.seed))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(full))
    params = jax.tree_util.tree_map_with_path(
        lambda path, cast, kept: kept if any(
            k in jax.tree_util.keystr(path) for k in _KEEP_F32) else cast,
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


def build(args, devices=None):
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
    cfg = model_config(args)
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
    params, n_params = init_params(args, model, state, devices[0])
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


def main():
    args = parse_args()

    import jax

    train_step, state, make_batch, n_params = build(args)
    cfg = model_config(args)
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


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
