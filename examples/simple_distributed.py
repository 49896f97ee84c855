"""Minimal data-parallel training (the reference's simple example).

Reference: examples/simple/distributed/distributed_data_parallel.py —
the ~40-line "hello world" of apex DDP: toy model, DDP wrap, loss,
step. The TPU version: toy model, a mesh, `sync_gradients` inside
`shard_map` — everything else is ordinary JAX.

Run:  python examples/simple_distributed.py
CPU:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
          python examples/simple_distributed.py
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from rocm_apex_tpu.parallel import sync_gradients
from rocm_apex_tpu.utils.compile_cache import enable_compile_cache


def main():
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))
    dp = len(devices)

    w = jnp.zeros((10, 1))
    opt = optax.sgd(0.1)
    ostate = opt.init(w)

    def local_step(w, ostate, x, y):
        def loss_fn(w):
            return jnp.mean((x @ w - y) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(w)
        g = sync_gradients(g, "data")  # the DDP allreduce
        u, ostate2 = opt.update(g, ostate)
        return optax.apply_updates(w, u), ostate2, jax.lax.pmean(loss, "data")

    step = jax.jit(
        shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(), P("data"), P("data")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )

    true_w = jnp.linspace(-1, 1, 10)[:, None]
    rng = jax.random.PRNGKey(0)
    for i in range(20):
        rng, k = jax.random.split(rng)
        x = jax.random.normal(k, (8 * dp, 10))
        y = x @ true_w
        w, ostate, loss = step(w, ostate, x, y)
        if (i + 1) % 5 == 0:
            print(f"step {i + 1}: loss {float(loss):.6f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
