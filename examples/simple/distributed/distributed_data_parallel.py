#!/usr/bin/env python
"""Minimal DDP example (reference:
``examples/simple/distributed/distributed_data_parallel.py`` — ~60 lines:
init_process_group, wrap a toy model in apex DDP, train on random data).

The TPU translation is the explicit-collective form: a 1-axis mesh, the
model run per-device under ``shard_map``, and gradients reduced with
``apex_tpu.parallel.allreduce_gradients`` (the bucketed-allreduce
equivalent — XLA fuses the psums).  Works on any device count, including
the 8 virtual CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    JAX_PLATFORMS=cpu python distributed_data_parallel.py
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.parallel import DistributedDataParallel


def main():
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))

    def model(params, x):
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]

    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(16, 32), jnp.float32) * 0.1,
              "b1": jnp.zeros((32,)),
              "w2": jnp.asarray(rng.randn(32, 4), jnp.float32) * 0.1,
              "b2": jnp.zeros((4,))}

    ddp = DistributedDataParallel(model, mesh=mesh, axis_name="data")

    def local_step(params, x, y):
        # runs per-device on the local batch shard: local grads first,
        # then ONE explicit allreduce (apex's bucketed-hook staging)
        params = ddp.mark_local(params)

        def loss_fn(p):
            pred = model(p, x)
            return jnp.mean((pred - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = ddp.reduce(grads)                         # the DDP hook
        loss = jax.lax.pmean(loss, "data")
        return loss, grads

    @jax.jit
    def train_step(params, x, y):
        loss, grads = shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P()))(params, x, y)
        return loss, jax.tree_util.tree_map(
            lambda p, g: p - 0.05 * g, params, grads)

    batch = 8 * n_dev
    for step in range(20):
        x = jnp.asarray(rng.randn(batch, 16), jnp.float32)
        y = jnp.asarray(rng.randn(batch, 4), jnp.float32)
        loss, params = train_step(params, x, y)
        if step % 5 == 0 or step == 19:
            print(f"step {step:3d}  loss {float(loss):.5f}")
    print(f"DONE devices={n_dev}")


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
