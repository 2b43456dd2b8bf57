#!/usr/bin/env python
"""ImageNet training CLI — apex_tpu rebuild of the reference's flagship
example (``examples/imagenet/main_amp.py``: torchvision ResNet + amp
O0–O3 + apex DDP + optional FusedSGD + a CUDA-stream data prefetcher).

TPU translation of each piece:

* model      — ``apex_tpu.models.resnet`` (NHWC bottleneck ResNet,
               SyncBN-able batch norm)
* amp        — ``apex_tpu.amp.initialize(opt_level=O0|O1|O2|O3)`` +
               ``scale_loss`` / ``unscale_step`` inside one jitted step
* DDP        — GSPMD data parallelism: a 1-axis device mesh, batch
               sharded over "data", params replicated; XLA inserts the
               gradient psum (the bucketed-allreduce equivalent)
* FusedSGD   — packed-bucket Pallas optimizer (``--fused-sgd``, default)
               vs a plain hand-written SGD (``--no-fused-sgd``)
* prefetcher — a background thread stages the next host batch and
               ``jax.device_put``s it while the current step runs (the
               ``data_prefetcher`` stream-overlap equivalent)

Data is synthetic by default (``--synthetic``, the only mode wired here:
the benchmark protocol needs no JPEG pipeline), shaped and scaled like
ImageNet; pass ``--steps`` to bound the run.

Run:  python examples/imagenet/main_amp.py --arch resnet50 \\
          --batch-size 256 --opt-level O2 --steps 100
"""

from __future__ import annotations

import argparse
import queue
import threading
import time

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description="apex_tpu imagenet + amp")
    p.add_argument("--arch", default="resnet50",
                   choices=["resnet50", "resnet18"])
    p.add_argument("--batch-size", type=int, default=256,
                   help="GLOBAL batch size (split over the data axis)")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--opt-level", default="O1",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--loss-scale", default=None,
                   help='None, a float, or "dynamic"')
    p.add_argument("--sync-bn", action="store_true",
                   help="cross-device BN stats (apex convert_syncbn_model)")
    p.add_argument("--no-fused-sgd", dest="fused_sgd", action="store_false")
    p.add_argument("--synthetic", action="store_true", default=True)
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


class Prefetcher:
    """Host-side double buffering: generate + device_put the next batch
    while the device runs the current step."""

    def __init__(self, make_batch, put, depth=2):
        self.q = queue.Queue(maxsize=depth)
        self.make_batch, self.put = make_batch, put
        self.stop = threading.Event()
        self.error = None
        self.thread = threading.Thread(target=self._work, daemon=True)
        self.thread.start()

    def _work(self):
        try:
            while not self.stop.is_set():
                batch = self.put(*self.make_batch())
                while not self.stop.is_set():
                    try:
                        self.q.put(batch, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:          # surface in next(), don't hang
            self.error = e
            self.stop.set()

    def next(self):
        while True:
            try:
                return self.q.get(timeout=0.5)
            except queue.Empty:
                if self.error is not None:
                    raise RuntimeError("prefetcher worker died") \
                        from self.error

    def close(self):
        self.stop.set()
        while not self.q.empty():
            self.q.get_nowait()
        self.thread.join(timeout=2)


def main():
    args = parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models.resnet import resnet18, resnet50
    from apex_tpu.optimizers import FusedSGD

    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
    data_sharding = NamedSharding(mesh, P("data"))
    replicated = NamedSharding(mesh, P())
    if args.batch_size % n_dev:
        raise SystemExit(f"--batch-size must divide {n_dev} devices")

    half = jnp.bfloat16
    compute_dtype = half if args.opt_level in ("O2", "O3") else jnp.float32
    make = resnet50 if args.arch == "resnet50" else resnet18
    model = make(num_classes=args.num_classes,
                 axis_name=None,          # GSPMD: SyncBN comes from sharding
                 dtype=compute_dtype)
    if args.sync_bn:
        # under GSPMD the batch is globally sharded, so plain BN stats ARE
        # global-batch stats — matching apex sync BN semantics with no
        # explicit collective.  (shard_map recipes set axis_name instead.)
        pass

    sgd = FusedSGD(lr=args.lr, momentum=args.momentum,
                   weight_decay=args.weight_decay,
                   master_weights=args.opt_level == "O2") if args.fused_sgd \
        else None

    params = model.init_params(jax.random.PRNGKey(args.seed))
    bn_state = model.init_state()

    loss_scale = args.loss_scale
    if isinstance(loss_scale, str):
        if loss_scale in ("None", "none"):
            loss_scale = None
        elif loss_scale != "dynamic":
            loss_scale = float(loss_scale)
    state = amp.initialize(model.apply, sgd, opt_level=args.opt_level,
                           loss_scale=loss_scale)
    params = state.cast_params(params)
    scaler_state = state.scaler.init()

    if sgd is not None:
        opt_state = sgd.init(params)
    else:
        # f32 momentum regardless of param dtype (the update promotes to
        # f32; a bf16 init would flip dtype after step 1 -> recompile)
        opt_state = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

    params, bn_state, opt_state = jax.device_put(
        (params, bn_state, opt_state), replicated)

    rng = np.random.RandomState(args.seed)
    shape = (args.batch_size, args.image_size, args.image_size, 3)

    def make_batch():
        x = rng.randn(*shape).astype(np.float32)
        y = rng.randint(0, args.num_classes, (args.batch_size,))
        return x, y

    def put(x, y):
        return (jax.device_put(x, data_sharding),
                jax.device_put(y, data_sharding))

    def loss_fn(p, bn, x, y, scaler_state):
        # state.apply_fn is the (possibly O1-autocast) model apply
        logits, new_bn = state.apply_fn(p, bn, x, training=True)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return amp.scale_loss(jnp.mean(nll), scaler_state), new_bn

    @jax.jit
    def train_step(params, bn_state, opt_state, scaler_state, x, y):
        (loss, new_bn), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, bn_state, x, y, scaler_state)
        loss = loss / scaler_state.loss_scale
        if sgd is not None:
            params, opt_state, scaler_state, _ = amp.unscale_step(
                sgd, grads, params, opt_state, state.scaler, scaler_state)
        else:  # hand-written momentum SGD baseline
            inv = 1.0 / scaler_state.loss_scale
            finf = amp.LossScaler.found_inf(grads)
            keep = 1.0 - finf          # 0 on overflow: skip the update
            opt_state = jax.tree_util.tree_map(
                lambda m, g: jnp.where(
                    finf > 0, m,
                    args.momentum * m + g.astype(jnp.float32) * inv),
                opt_state, grads)
            params = jax.tree_util.tree_map(
                lambda p, m: (p - keep * args.lr
                              * (m + args.weight_decay
                                 * p.astype(jnp.float32))).astype(p.dtype),
                params, opt_state)
            scaler_state = state.scaler.update(scaler_state, finf)
        return params, new_bn, opt_state, scaler_state, loss

    pre = Prefetcher(make_batch, put)
    try:
        # warmup/compile
        x, y = pre.next()
        params, bn_state, opt_state, scaler_state, loss = train_step(
            params, bn_state, opt_state, scaler_state, x, y)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        seen = 0
        for step in range(1, args.steps + 1):
            x, y = pre.next()
            params, bn_state, opt_state, scaler_state, loss = train_step(
                params, bn_state, opt_state, scaler_state, x, y)
            seen += args.batch_size
            if step % args.print_freq == 0 or step == args.steps:
                loss_host = float(loss)
                dt = time.perf_counter() - t0
                print(f"step {step:5d}  loss {loss_host:.4f}  "
                      f"{seen / dt:9.1f} img/s  "
                      f"scale {float(scaler_state.loss_scale):.0f}",
                      flush=True)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        print(f"DONE arch={args.arch} opt_level={args.opt_level} "
              f"devices={n_dev} throughput={seen / dt:.1f} img/s")
    finally:
        pre.close()


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
