#!/usr/bin/env python
"""DCGAN + amp example (reference: ``examples/dcgan/main_amp.py`` — the
apex example showing amp with MULTIPLE models/optimizers/losses: a
generator and a discriminator, each with its own loss scaler, via
``amp.initialize([netD, netG], [optD, optG], num_losses=3)``).

The functional translation keeps the interesting part — two models, two
fused optimizers, three scaled losses (errD_real, errD_fake, errG) with
INDEPENDENT loss scalers — inside two jitted steps.  Data is synthetic
64x64 images (the reference defaults to torchvision datasets but any
image folder; the GAN math is identical).

Run:  python examples/dcgan/main_amp.py --steps 50 --opt-level O1
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description="apex_tpu dcgan + amp")
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--image-size", type=int, default=64, choices=[64],
                   help="the DCGAN topology is fixed at 64x64 (4 stride-2 "
                        "stages), like the reference architecture")
    p.add_argument("--nz", type=int, default=100, help="latent dim")
    p.add_argument("--ngf", type=int, default=64)
    p.add_argument("--ndf", type=int, default=64)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--beta1", type=float, default=0.5)
    p.add_argument("--opt-level", default="O1",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = parse_args()

    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam

    _DN = ("NHWC", "HWIO", "NHWC")

    def conv(x, w, stride):
        return jax.lax.conv_general_dilated(
            x, w.astype(x.dtype), (stride, stride), "SAME",
            dimension_numbers=_DN)

    def deconv(x, w, stride):
        return jax.lax.conv_transpose(
            x, w.astype(x.dtype), (stride, stride), "SAME",
            dimension_numbers=_DN)

    def lrelu(x):
        return jnp.where(x > 0, x, 0.2 * x)

    key = jax.random.PRNGKey(args.seed)

    def winit(key, *shape):
        return 0.02 * jax.random.normal(key, shape, jnp.float32)

    nz, ngf, ndf = args.nz, args.ngf, args.ndf
    kg = jax.random.split(key, 5)
    # generator: z (1x1) -> 4x4 -> 8 -> 16 -> 32 -> 64
    gen_params = {
        "p0": winit(kg[0], 4, 4, nz, ngf * 8),        # project via deconv
        "d1": winit(kg[1], 4, 4, ngf * 8, ngf * 4),
        "d2": winit(kg[2], 4, 4, ngf * 4, ngf * 2),
        "d3": winit(kg[3], 4, 4, ngf * 2, ngf),
        "d4": winit(kg[4], 4, 4, ngf, 3),
    }
    kd = jax.random.split(jax.random.fold_in(key, 1), 5)
    disc_params = {
        "c1": winit(kd[0], 4, 4, 3, ndf),
        "c2": winit(kd[1], 4, 4, ndf, ndf * 2),
        "c3": winit(kd[2], 4, 4, ndf * 2, ndf * 4),
        "c4": winit(kd[3], 4, 4, ndf * 4, ndf * 8),
        "head": winit(kd[4], 4 * 4 * ndf * 8, 1),
    }

    # O2/O3 run the nets in bf16: cast the activations entering them
    # (weights are cast once by cast_params below)
    half_dtype = (jnp.bfloat16 if args.opt_level in ("O2", "O3")
                  else jnp.float32)

    def generator(p, z):
        z = z.astype(half_dtype)
        x = z.reshape(z.shape[0], 1, 1, nz)
        x = jax.nn.relu(deconv(x, p["p0"], 4))            # 4x4
        x = jax.nn.relu(deconv(x, p["d1"], 2))            # 8x8
        x = jax.nn.relu(deconv(x, p["d2"], 2))            # 16
        x = jax.nn.relu(deconv(x, p["d3"], 2))            # 32
        return jnp.tanh(deconv(x, p["d4"], 2))            # 64

    def discriminator(p, x):
        x = x.astype(half_dtype)
        x = lrelu(conv(x, p["c1"], 2))                    # 32
        x = lrelu(conv(x, p["c2"], 2))                    # 16
        x = lrelu(conv(x, p["c3"], 2))                    # 8
        x = lrelu(conv(x, p["c4"], 2))                    # 4
        x = x.reshape(x.shape[0], -1).astype(jnp.float32)
        return (x @ p["head"])[:, 0]

    optD = FusedAdam(lr=args.lr, betas=(args.beta1, 0.999))
    optG = FusedAdam(lr=args.lr, betas=(args.beta1, 0.999))

    # apex: amp.initialize([netD, netG], [optD, optG], num_losses=3) —
    # one scaler per loss; here each loss gets its own scaler state
    stateD = amp.initialize(discriminator, optD, opt_level=args.opt_level)
    stateG = amp.initialize(generator, optG, opt_level=args.opt_level)
    disc_params = stateD.cast_params(disc_params)
    gen_params = stateG.cast_params(gen_params)
    scalers = [stateD.scaler.init() for _ in range(2)] + \
        [stateG.scaler.init()]

    optD_state = optD.init(disc_params)
    optG_state = optG.init(gen_params)
    disc_apply, gen_apply = stateD.apply_fn, stateG.apply_fn

    def bce_logits(logits, target):
        # -(t*log s + (1-t)*log(1-s)) in the stable logits form
        return jnp.mean(jnp.maximum(logits, 0) - logits * target
                        + jnp.log1p(jnp.exp(-jnp.abs(logits))))

    @jax.jit
    def d_step(disc_params, optD_state, gen_params, s_real, s_fake,
               real, z):
        fake = gen_apply(gen_params, z)

        def loss_real(p):
            return amp.scale_loss(
                bce_logits(disc_apply(p, real), 1.0), s_real)

        def loss_fake(p):
            return amp.scale_loss(
                bce_logits(disc_apply(p, jax.lax.stop_gradient(fake)),
                           0.0), s_fake)

        # two backwards, two scalers — apex loss_id=0 and loss_id=1.
        # report errD with the scales used THIS step (update comes after),
        # and skip the whole update on overflow in either backward.
        lr_val, g_real = jax.value_and_grad(loss_real)(disc_params)
        lf_val, g_fake = jax.value_and_grad(loss_fake)(disc_params)
        errD = lr_val / s_real.loss_scale + lf_val / s_fake.loss_scale
        grads = jax.tree_util.tree_map(
            lambda a, b: a / s_real.loss_scale + b / s_fake.loss_scale,
            g_real, g_fake)
        finf_r = amp.LossScaler.found_inf(g_real)
        finf_f = amp.LossScaler.found_inf(g_fake)
        noop = jnp.maximum(finf_r, finf_f).astype(jnp.int32)
        disc_params, optD_state = optD.step(grads, disc_params, optD_state,
                                            noop_flag=noop)
        s_real = stateD.scaler.update(s_real, finf_r)
        s_fake = stateD.scaler.update(s_fake, finf_f)
        return disc_params, optD_state, s_real, s_fake, errD

    @jax.jit
    def g_step(gen_params, optG_state, disc_params, s_gen, z):
        def loss_gen(p):
            fake = gen_apply(p, z)
            return amp.scale_loss(
                bce_logits(disc_apply(disc_params, fake), 1.0), s_gen)

        lg_val, grads = jax.value_and_grad(loss_gen)(gen_params)
        errG = lg_val / s_gen.loss_scale       # this step's scale
        gen_params, optG_state, s_gen, _ = amp.unscale_step(
            optG, grads, gen_params, optG_state, stateG.scaler, s_gen)
        return gen_params, optG_state, s_gen, errG

    rng = np.random.RandomState(args.seed)
    t0 = time.perf_counter()
    for step in range(1, args.steps + 1):
        real = jnp.asarray(rng.randn(args.batch_size, args.image_size,
                                     args.image_size, 3), jnp.float32)
        z1 = jnp.asarray(rng.randn(args.batch_size, nz), jnp.float32)
        z2 = jnp.asarray(rng.randn(args.batch_size, nz), jnp.float32)
        disc_params, optD_state, scalers[0], scalers[1], errD = d_step(
            disc_params, optD_state, gen_params, scalers[0], scalers[1],
            real, z1)
        gen_params, optG_state, scalers[2], errG = g_step(
            gen_params, optG_state, disc_params, scalers[2], z2)
        if step % args.print_freq == 0 or step == args.steps:
            print(f"step {step:4d}  errD {float(errD):.4f}  "
                  f"errG {float(errG):.4f}", flush=True)
    dt = time.perf_counter() - t0
    print(f"DONE steps={args.steps} opt_level={args.opt_level} "
          f"{args.steps * args.batch_size / dt:.1f} img/s")


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
