#!/usr/bin/env python
"""BERT pretraining recipe — BASELINE workload 2 (reference lineage:
NVIDIA's MLPerf BERT submissions are the reason apex carries
``DistributedFusedLAMB``, ``fmha`` and FastLayerNorm; apex itself ships
no BERT script, so this example IS the missing recipe wired from
apex-surface parts).

The apex-entrypoint wiring, per BASELINE ("FusedLAMB + FusedLayerNorm +
amp O2 -> bf16"):

* model  — ``apex_tpu.models.bert`` (MixedFusedLayerNorm + flash
           attention inside)
* opt    — ``FusedLAMB`` (or ``FusedMixedPrecisionLamb`` under O2: fp32
           master weights over bf16 model params)
* amp O2 — params cast to bf16 (LN kept fp32), loss scaling
* DP     — with more than one device the whole step runs per device
           under ``jax.shard_map`` over "data" (Pallas kernels cannot be
           partitioned by GSPMD) and ``DistributedDataParallel.reduce``
           averages the gradients

Synthetic MLM batches (15% masked).  Reports sequences/s and achieved
model FLOP/s.

Run:  python examples/bert/pretrain_bert.py --config large \\
          --batch-size 32 --seq-len 512 --steps 50
"""

from __future__ import annotations

import argparse
import time

import numpy as np

_CONFIGS = {
    # hidden, layers, heads
    "tiny": (128, 2, 2),
    "base": (768, 12, 12),
    "large": (1024, 24, 16),
}


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu BERT pretrain")
    p.add_argument("--config", default="large", choices=sorted(_CONFIGS))
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--vocab-size", type=int, default=30528)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--lr", type=float, default=4e-4)
    p.add_argument("--weight-decay", type=float, default=0.01)
    p.add_argument("--mask-prob", type=float, default=0.15)
    p.add_argument("--remat", action="store_true",
                   help="per-layer activation recompute (the cell "
                        "bert-large.pretrain-1chip runs WITHOUT "
                        "remat at micro-batch 16 — see PERF.md)")
    p.add_argument("--optimizer-layout", default="per_leaf",
                   choices=["per_leaf", "packed"],
                   help="per_leaf: XLA-fused per-leaf state, the "
                        "single-chip speed path (~1.9x faster steps); "
                        "packed: the (rows, 128) multi-tensor engine "
                        "(the ZeRO/distributed layout)")
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def build(args, devices=None):
    """The recipe as data: ``(train_step, state, make_batch, n_params)``
    with ``state = (params, opt_state, scaler_state)`` and
    ``train_step(*state, *batch) -> (*state, loss)``.  ``main`` loops over
    it; ``chip_smoke.py`` takes a few steps of the same objects."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models.bert import BertConfig, BertModel
    from apex_tpu.optimizers import FusedLAMB, FusedMixedPrecisionLamb
    from apex_tpu.parallel import DistributedDataParallel

    hidden, layers, heads = _CONFIGS[args.config]
    devices = list(devices if devices is not None else jax.devices())
    n_dev = len(devices)
    mesh = jax.make_mesh((n_dev,), ("data",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    data_sharding = NamedSharding(mesh, P("data"))
    replicated = NamedSharding(mesh, P())
    if args.batch_size % n_dev:
        raise SystemExit(f"--batch-size must divide {n_dev} devices")

    # O2/O3 cast the model to bf16; O1 keeps f32 params and relies on the
    # per-op autocast interpreter (apex O1 semantics)
    half = jnp.bfloat16
    cfg = BertConfig(
        vocab_size=args.vocab_size, hidden_size=hidden, num_layers=layers,
        num_attention_heads=heads, max_seq_len=args.seq_len,
        remat=args.remat,
        dtype=half if args.opt_level in ("O2", "O3") else jnp.float32)
    model = BertModel(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))

    # O2: FusedMixedPrecisionLamb = LAMB + fp32 master weights
    lamb_cls = (FusedMixedPrecisionLamb if args.opt_level == "O2"
                else FusedLAMB)
    lamb = lamb_cls(lr=args.lr, weight_decay=args.weight_decay,
                    bucketed=args.optimizer_layout == "packed")
    state = amp.initialize(model.apply, lamb, opt_level=args.opt_level)
    params = state.cast_params(params)
    scaler_state = state.scaler.init()
    opt_state = lamb.init(params)
    params, opt_state, scaler_state = jax.device_put(
        (params, opt_state, scaler_state), replicated)

    rng = np.random.RandomState(args.seed)

    def make_batch():
        tokens = rng.randint(4, args.vocab_size,
                             (args.batch_size, args.seq_len))
        masked = rng.rand(args.batch_size, args.seq_len) < args.mask_prob
        labels = np.where(masked, tokens, -1)
        tokens = np.where(masked, 3, tokens)          # [MASK] id = 3
        types = np.zeros_like(tokens)
        return (jax.device_put(tokens, data_sharding),
                jax.device_put(labels, data_sharding),
                jax.device_put(types, data_sharding))

    # O1: the autocast interpreter wraps the WHOLE loss (per-op policy);
    # other levels run the loss at the model's own dtype
    raw_loss = (amp.autocast(model.loss)
                if state.properties.patch_torch_functions else model.loss)
    ddp = DistributedDataParallel(mesh=mesh, axis_name="data")

    def step(params, opt_state, scaler_state, tokens, labels, types):
        def loss_fn(p):
            raw = raw_loss(p, tokens, labels, token_type_ids=types)
            return amp.scale_loss(raw, scaler_state)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = loss / scaler_state.loss_scale
        if n_dev > 1:
            grads = ddp.reduce(grads)             # apex's allreduce hook
            loss = jax.lax.pmean(loss, "data")
        params, opt_state, scaler_state, _ = amp.unscale_step(
            lamb, grads, params, opt_state, state.scaler, scaler_state)
        return params, opt_state, scaler_state, loss

    if n_dev > 1:
        step = jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), P(), P(), P("data"), P("data"), P("data")),
            out_specs=(P(), P(), P(), P()), check_vma=False)
    train_step = jax.jit(step, donate_argnums=(0, 1, 2))
    return train_step, (params, opt_state, scaler_state), make_batch, \
        n_params


def main():
    args = parse_args()

    import jax

    train_step, (params, opt_state, scaler_state), make_batch, n_params = \
        build(args)
    n_dev = len(jax.devices())
    # the training state must live on every device, not all on the first
    for leaf in jax.tree_util.tree_leaves((params, opt_state)):
        assert len(leaf.sharding.device_set) == n_dev, leaf.sharding

    # compile + warmup
    batch = make_batch()
    params, opt_state, scaler_state, loss = train_step(
        params, opt_state, scaler_state, *batch)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    seen = 0
    for step in range(1, args.steps + 1):
        batch = make_batch()
        params, opt_state, scaler_state, loss = train_step(
            params, opt_state, scaler_state, *batch)
        seen += args.batch_size
        if step % args.print_freq == 0 or step == args.steps:
            print(f"step {step:5d}  mlm_loss {float(loss):.4f}  "
                  f"{seen / (time.perf_counter() - t0):8.2f} seq/s",
                  flush=True)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    seq_s = seen / dt
    flops = 6 * n_params * args.seq_len * seq_s   # fwd+bwd per token
    print(f"DONE config={args.config} ({n_params/1e6:.1f}M params) "
          f"opt_level={args.opt_level} devices={n_dev} "
          f"throughput={seq_s:.2f} seq/s "
          f"achieved={flops/1e12:.2f} TFLOP/s")


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
