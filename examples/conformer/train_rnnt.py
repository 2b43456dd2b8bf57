#!/usr/bin/env python
"""Conformer RNN-T training recipe — BASELINE workload 5 ("Conformer
RNN-T: apex.contrib.transducer + fused multihead attention").

Every compute block is a framework surface:

* encoder   — conv subsampling + conformer blocks built from
              ``contrib.multihead_attn.SelfMultiheadAttn``
              (``include_norm_add=True`` residual variant),
              ``FusedLayerNorm``-backed norms, and a conv module with
              NHWC depthwise conv + ``contrib.groupbn``-style BN math
* predictor — ``apex_tpu.RNN.LSTM`` (the deprecated-tier surface, used
              exactly where the reference workload uses an LSTM)
* joint     — ``contrib.transducer.TransducerJoint`` (fused broadcast
              add + ReLU)
* loss      — ``contrib.transducer.TransducerLoss`` (alpha-recursion
              RNN-T NLL)
* optimizer — ``FusedNovoGrad`` (the classic RNN-T recipe optimizer)

Synthetic log-mel features and token targets; reports utterances/s.

Run:  python examples/conformer/train_rnnt.py --steps 20
"""

from __future__ import annotations

import argparse
import time
import warnings

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description="apex_tpu conformer RNN-T")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--audio-len", type=int, default=200,
                   help="input frames (subsampled 4x by the stem)")
    p.add_argument("--target-len", type=int, default=20)
    p.add_argument("--n-mels", type=int, default=80)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--vocab", type=int, default=128)
    p.add_argument("--pred-hidden", type=int, default=256)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--print-freq", type=int, default=5)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = parse_args()

    import jax
    import jax.numpy as jnp

    from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn
    from apex_tpu.contrib.transducer import TransducerJoint, TransducerLoss
    from apex_tpu.normalization import FusedLayerNorm
    from apex_tpu.optimizers import FusedNovoGrad
    from apex_tpu.RNN import LSTM

    H, nh, L = args.hidden, args.heads, args.layers
    key = jax.random.PRNGKey(args.seed)

    attn = SelfMultiheadAttn(H, nh, include_norm_add=True)
    ln = FusedLayerNorm(H)      # stateless config holder, shared
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        predictor = LSTM(H, args.pred_hidden)
    joint = TransducerJoint(relu=True)
    loss_mod = TransducerLoss()

    def winit(key, *shape):
        return (shape[0] ** -0.5) * jax.random.normal(key, shape,
                                                      jnp.float32)

    def init_params(key):
        ks = iter(jax.random.split(key, 8 * L + 8))
        p = {
            # conv subsampling stem: (B, T, mels) -> (B, T/4, H)
            "stem1": winit(next(ks), 4 * args.n_mels, H),
            "stem_b1": jnp.zeros((H,)),
            "layers": [],
            "pred_embed": winit(next(ks), args.vocab, H),
            "predictor": predictor.init_params(next(ks)),
            "enc_proj": winit(next(ks), H, H),
            "pred_proj": winit(next(ks), args.pred_hidden, H),
            "out_proj": winit(next(ks), H, args.vocab + 1),
            "out_b": jnp.zeros((args.vocab + 1,)),
        }
        for i in range(L):
            p["layers"].append({
                "ff1": {"w1": winit(next(ks), H, 4 * H),
                        "w2": winit(next(ks), 4 * H, H),
                        "ln": ln.init_params()},
                "attn": attn.init_params(next(ks)),
                "conv": {"pw1": winit(next(ks), H, 2 * H),
                         "dw": 0.1 * jax.random.normal(next(ks), (5, H)),
                         "pw2": winit(next(ks), H, H),
                         "ln": ln.init_params()},
                "ff2": {"w1": winit(next(ks), H, 4 * H),
                        "w2": winit(next(ks), 4 * H, H),
                        "ln": ln.init_params()},
            })
        return p

    def feed_forward(p, x):
        h = ln(p["ln"], x)
        h = jax.nn.silu(h @ p["w1"]) @ p["w2"]
        return x + 0.5 * h

    def conv_module(p, x):
        h = ln(p["ln"], x)
        h = h @ p["pw1"]                          # (B, T, 2H)
        a, b = jnp.split(h, 2, axis=-1)
        h = a * jax.nn.sigmoid(b)                 # GLU
        # depthwise conv over time (kernel 5): ONE grouped conv, not a
        # per-channel python loop (feature_group_count=H)
        kern = p["dw"][:, None, :]                       # (K, 1, H) = WIO
        h = jax.lax.conv_general_dilated(
            h, kern, window_strides=(1,), padding="SAME",
            dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=H)
        h = jax.nn.silu(h)
        return x + h @ p["pw2"]

    def encoder(p, feats):
        b, t, m = feats.shape
        t4 = t // 4
        x = feats[:, :t4 * 4].reshape(b, t4, 4 * m)
        x = jax.nn.relu(x @ p["stem1"] + p["stem_b1"])
        for lp in p["layers"]:
            x = feed_forward(lp["ff1"], x)
            # SelfMultiheadAttn is (seq, batch, hidden) with fused
            # residual+LN (include_norm_add)
            x = attn(lp["attn"], x.transpose(1, 0, 2),
                     is_training=False).transpose(1, 0, 2)
            x = conv_module(lp["conv"], x)
            x = feed_forward(lp["ff2"], x)
        return x                                   # (B, T/4, H)

    def forward_loss(p, feats, labels, f_len, y_len):
        enc = encoder(p, feats)                    # (B, T', H)
        # predictor consumes blank-prepended targets, time-major
        tokens = jnp.pad(labels, ((0, 0), (1, 0)))  # (B, U+1)
        emb = jnp.take(p["pred_embed"], tokens, axis=0)
        pred, _ = predictor.apply(p["predictor"], emb.transpose(1, 0, 2))
        pred = pred.transpose(1, 0, 2)             # (B, U+1, Hp)
        f = enc @ p["enc_proj"]
        g = pred @ p["pred_proj"]
        h = joint(f, g)                            # (B, T', U+1, H) +relu
        logits = h @ p["out_proj"] + p["out_b"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = loss_mod(logp, labels, f_len, y_len, blank_idx=0)
        return jnp.mean(nll)

    params = init_params(key)
    opt = FusedNovoGrad(lr=args.lr)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, opt_state, feats, labels, f_len, y_len):
        loss, grads = jax.value_and_grad(forward_loss)(
            params, feats, labels, f_len, y_len)
        params, opt_state = opt.step(grads, params, opt_state)
        return params, opt_state, loss

    rng = np.random.RandomState(args.seed)
    t4 = args.audio_len // 4

    def make_batch():
        feats = jnp.asarray(rng.randn(args.batch_size, args.audio_len,
                                      args.n_mels), jnp.float32)
        labels = jnp.asarray(rng.randint(
            1, args.vocab, (args.batch_size, args.target_len)))
        f_len = jnp.asarray(rng.randint(t4 // 2, t4 + 1,
                                        (args.batch_size,)))
        y_len = jnp.asarray(rng.randint(args.target_len // 2,
                                        args.target_len + 1,
                                        (args.batch_size,)))
        return feats, labels, f_len, y_len

    batch = make_batch()
    params, opt_state, loss = train_step(params, opt_state, *batch)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for step in range(1, args.steps + 1):
        batch = make_batch()
        params, opt_state, loss = train_step(params, opt_state, *batch)
        if step % args.print_freq == 0 or step == args.steps:
            print(f"step {step:4d}  rnnt_loss {float(loss):9.4f}  "
                  f"{step * args.batch_size / (time.perf_counter() - t0):6.1f}"
                  " utt/s", flush=True)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    print(f"DONE layers={L} hidden={H} "
          f"throughput={args.steps * args.batch_size / dt:.1f} utt/s")


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
