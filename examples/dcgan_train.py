"""DCGAN training with SyncBatchNorm + amp.

TPU-native rebuild of the reference's DCGAN example
(reference: examples/dcgan/main_amp.py — two models, two optimizers,
`amp.initialize(num_losses=3)` with a scaler per loss). Generator and
discriminator train data-parallel over the mesh; BatchNorm stats
optionally merge across replicas (--sync-bn), the BASELINE.json config-3
scenario.

CPU smoke:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/dcgan_train.py --steps 2 --batch-size 16
"""

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from rocm_apex_tpu import amp
from rocm_apex_tpu.models import Discriminator, Generator
from rocm_apex_tpu.optimizers import FusedAdam
from rocm_apex_tpu.parallel import sync_gradients
from rocm_apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args():
    p = argparse.ArgumentParser(description="rocm_apex_tpu dcgan example")
    p.add_argument("--opt-level", default="O5",
                   choices=["O0", "O1", "O2", "O3", "O4", "O5"])
    p.add_argument("--sync-bn", action="store_true")
    p.add_argument("--batch-size", type=int, default=64, help="global batch")
    p.add_argument("--nz", type=int, default=100)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--beta1", type=float, default=0.5)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--print-freq", type=int, default=10)
    return p.parse_args()


def bce_logits(logits, target):
    return optax.sigmoid_binary_cross_entropy(
        logits.astype(jnp.float32), target
    ).mean()


def main():
    args = parse_args()
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))
    dp = len(devices)
    local_b = args.batch_size // dp
    bn_axis = "data" if args.sync_bn else None

    netG = Generator(nz=args.nz, sync_bn_axis=bn_axis)
    netD = Discriminator(sync_bn_axis=bn_axis)

    z0 = jnp.zeros((local_b, 1, 1, args.nz))
    gvars = netG.init(jax.random.PRNGKey(0), z0)
    img0 = netG.apply(gvars, z0, train=False)
    dvars = netD.init(jax.random.PRNGKey(1), img0)

    optG = FusedAdam(args.lr, betas=(args.beta1, 0.999))
    optD = FusedAdam(args.lr, betas=(args.beta1, 0.999))
    gp, _, amp_state = amp.initialize(
        gvars["params"], opt_level=args.opt_level, num_losses=3
    )
    dp_params, _, _ = amp.initialize(
        dvars["params"], opt_level=args.opt_level, verbosity=0
    )
    g_bs, d_bs = gvars["batch_stats"], dvars["batch_stats"]
    og, od = optG.init(gp), optD.init(dp_params)
    sstates = amp_state.scaler_states

    def local_step(gp, dp_params, g_bs, d_bs, og, od, sstates, z, z2, real):
        st = amp_state.replace(scaler_states=sstates)

        # --- D step: real + fake (losses 0 and 1, separate scalers,
        # reference main_amp.py scale_loss(..., loss_id))
        def d_loss(dparams):
            fake, g_mut = netG.apply(
                {"params": gp, "batch_stats": g_bs}, z, mutable=["batch_stats"]
            )
            out_real, d_mut = netD.apply(
                {"params": dparams, "batch_stats": d_bs}, real,
                mutable=["batch_stats"],
            )
            out_fake, d_mut2 = netD.apply(
                {"params": dparams, "batch_stats": d_mut["batch_stats"]},
                jax.lax.stop_gradient(fake), mutable=["batch_stats"],
            )
            errD = bce_logits(out_real, jnp.ones_like(out_real)) + bce_logits(
                out_fake, jnp.zeros_like(out_fake)
            )
            return amp.scale_loss(errD, st, 0), (
                g_mut["batch_stats"], d_mut2["batch_stats"], errD
            )

        (_, (g_bs, d_bs, errD)), dgrads = jax.value_and_grad(
            d_loss, has_aux=True
        )(dp_params)
        dgrads = sync_gradients(dgrads, "data")
        dgrads, inf_d = amp.unscale_grads(dgrads, st, 0)
        st, skip_d = amp.update_scale(st, inf_d, 0)
        du, od2 = optD.update(dgrads, od, dp_params)
        dp2 = optax.apply_updates(dp_params, du)
        dp_params = amp.skip_step(skip_d, dp2, dp_params)
        od = amp.skip_step(skip_d, od2, od)

        # --- G step (loss 2)
        def g_loss(gparams):
            fake, g_mut = netG.apply(
                {"params": gparams, "batch_stats": g_bs}, z2,
                mutable=["batch_stats"],
            )
            out, _ = netD.apply(
                {"params": dp_params, "batch_stats": d_bs}, fake,
                mutable=["batch_stats"],
            )
            errG = bce_logits(out, jnp.ones_like(out))
            return amp.scale_loss(errG, st, 2), (g_mut["batch_stats"], errG)

        (_, (g_bs, errG)), ggrads = jax.value_and_grad(g_loss, has_aux=True)(
            gp
        )
        ggrads = sync_gradients(ggrads, "data")
        ggrads, inf_g = amp.unscale_grads(ggrads, st, 2)
        st, skip_g = amp.update_scale(st, inf_g, 2)
        gu, og2 = optG.update(ggrads, og, gp)
        gp2 = optax.apply_updates(gp, gu)
        gp = amp.skip_step(skip_g, gp2, gp)
        og = amp.skip_step(skip_g, og2, og)

        return gp, dp_params, g_bs, d_bs, og, od, st.scaler_states, errD, errG

    step = jax.jit(
        shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(), P(), P(),
                      P("data"), P("data"), P("data")),
            out_specs=(P(), P(), P(), P(), P(), P(), P(), P(), P()),
            check_vma=False,
        )
    )

    rng = jax.random.PRNGKey(2)
    t0 = time.perf_counter()
    for i in range(args.steps):
        rng, k1, k2, k3 = jax.random.split(rng, 4)
        z = jax.random.normal(k1, (args.batch_size, 1, 1, args.nz))
        z2 = jax.random.normal(k2, (args.batch_size, 1, 1, args.nz))
        real = jax.random.uniform(
            k3, (args.batch_size, 64, 64, 3), minval=-1.0, maxval=1.0
        )
        gp, dp_params, g_bs, d_bs, og, od, sstates, errD, errG = step(
            gp, dp_params, g_bs, d_bs, og, od, sstates, z, z2, real
        )
        if (i + 1) % args.print_freq == 0:
            dt = (time.perf_counter() - t0) / args.print_freq
            print(
                f"step {i + 1}: errD {float(errD):.4f} errG {float(errG):.4f}"
                f"  {args.batch_size / dt:.1f} img/s"
            )
            t0 = time.perf_counter()


if __name__ == "__main__":
    enable_compile_cache()
    main()
