"""ImageNet-style ResNet training under amp + data parallelism.

TPU-native rebuild of the reference's flagship example
(reference: examples/imagenet/main_amp.py — argparse flags at :44,
amp.initialize + apex DDP wrap + speed meter). One process drives all
local devices through a `shard_map` over the ``data`` mesh axis; the
reference's `torch.distributed.launch` + NCCL DDP become the mesh +
gradient psum. Synthetic data by default (this repo carries no
ImageNet); ``--data-dir`` drives the REAL input pipeline
(rocm_apex_tpu.data: ImageFolder scan, worker-thread decode, native
fast_collate, prefetch + async device_put with on-device
normalization — the reference's DataLoader + data_prefetcher).

Run (single host, all devices):
    python examples/imagenet_train.py --arch resnet50 --opt-level O5 \
        --batch-size 128 --steps 100 [--data-dir /data/imagenet/train]
CPU smoke:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/imagenet_train.py --arch resnet18 --steps 2 \
        --batch-size 16 --image-size 32
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

sys.path.insert(0, __file__.rsplit("/", 2)[0])  # repo root

from rocm_apex_tpu import amp, models
from rocm_apex_tpu.optimizers import FusedSGD
from rocm_apex_tpu.parallel import sync_gradients
from rocm_apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args():
    p = argparse.ArgumentParser(description="rocm_apex_tpu imagenet example")
    p.add_argument("--arch", default="resnet50",
                   choices=["resnet_tiny", "resnet18", "resnet34",
                            "resnet50", "resnet101"])
    p.add_argument("--opt-level", default="O5",
                   choices=["O0", "O1", "O2", "O3", "O4", "O5"])
    p.add_argument("--loss-scale", default=None,
                   help="static scale or 'dynamic' (default: per opt level)")
    p.add_argument("--keep-batchnorm-fp32", default=None, type=str)
    p.add_argument("--sync-bn", action="store_true")
    p.add_argument("--batch-size", type=int, default=128, help="global batch")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument(
        "--data-dir", default=None,
        help="ImageFolder root (class dirs of jpg/png/npy). Default: "
        "synthetic data (this repo carries no ImageNet).",
    )
    p.add_argument(
        "--loader-workers", type=int, default=4,
        help="decode threads for --data-dir (the reference's "
        "DataLoader num_workers; JPEG decode scales with host cores)",
    )
    return p.parse_args()


def build_training(
    arch="resnet50",
    opt_level="O5",
    *,
    batch_size,
    image_size,
    num_classes=1000,
    loss_scale=None,
    keep_batchnorm_fp32=None,
    sync_bn=False,
    lr=0.1,
    momentum=0.9,
    weight_decay=1e-4,
    seed=0,
    verbosity=1,
):
    """The example's training setup, importable: returns
    ``(step, state)`` where ``step(*state, x, y) -> (*state, loss)`` is
    the jitted shard_map train step over the ``data`` mesh axis and
    ``state = (params, batch_stats, opt_state, scaler_state)``.

    tests/L1/test_determinism_imagenet.py drives the determinism
    cross-product through THIS function — the real example step, mesh
    included — mirroring how the reference's L1 harness executes
    main_amp.py itself (reference: tests/L1/common/run_test.sh:20-27).
    """
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))
    dp = len(devices)
    if batch_size % dp:
        raise ValueError(f"batch size {batch_size} not divisible by {dp}")

    model = getattr(models, arch)(
        num_classes=num_classes,
        sync_bn_axis="data" if sync_bn else None,
    )

    x0 = jnp.zeros((batch_size // dp, image_size, image_size, 3))
    variables = model.init(jax.random.PRNGKey(seed), x0)
    params, batch_stats = variables["params"], variables.get("batch_stats", {})

    overrides = {}
    if loss_scale is not None:
        overrides["loss_scale"] = loss_scale
    if keep_batchnorm_fp32 is not None:
        overrides["keep_batchnorm_fp32"] = keep_batchnorm_fp32
    optimizer = FusedSGD(lr, momentum=momentum, weight_decay=weight_decay)
    params, optimizer, amp_state = amp.initialize(
        params, optimizer, opt_level=opt_level, verbosity=verbosity,
        **overrides
    )
    opt_state = optimizer.init(params)
    scaler_state = amp_state.scaler_states

    def local_step(params, batch_stats, opt_state, scaler_states, x, y):
        st = amp_state.replace(scaler_states=scaler_states)

        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": batch_stats},
                x,
                mutable=["batch_stats"],
            )
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y
            ).mean()
            return amp.scale_loss(ce, st), (mut["batch_stats"], ce)

        (_, (new_bs, ce)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        grads = sync_gradients(grads, "data")
        grads, found_inf = amp.unscale_grads(grads, st)
        st2, skip = amp.update_scale(st, found_inf)
        updates, new_opt = optimizer.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_params = amp.skip_step(skip, new_params, params)
        new_opt = amp.skip_step(skip, new_opt, opt_state)
        return new_params, new_bs, new_opt, st2.scaler_states, ce

    step = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(step), (params, batch_stats, opt_state, scaler_state)


def main():
    args = parse_args()

    loss_scale = None
    if args.loss_scale is not None:
        loss_scale = (
            "dynamic" if args.loss_scale == "dynamic" else float(args.loss_scale)
        )
    keep_bn = None
    if args.keep_batchnorm_fp32 is not None:
        keep_bn = args.keep_batchnorm_fp32 == "True"
    step, (params, batch_stats, opt_state, scaler_state) = build_training(
        args.arch,
        args.opt_level,
        batch_size=args.batch_size,
        image_size=args.image_size,
        num_classes=args.num_classes,
        loss_scale=loss_scale,
        keep_batchnorm_fp32=keep_bn,
        sync_bn=args.sync_bn,
        lr=args.lr,
        momentum=args.momentum,
        weight_decay=args.weight_decay,
    )

    def batches(rng):
        """Synthetic stand-in for the DataLoader + fast_collate pipeline
        (reference: main_amp.py data_prefetcher)."""
        while True:
            rng, k1, k2 = jax.random.split(rng, 3)
            x = jax.random.normal(
                k1,
                (args.batch_size, args.image_size, args.image_size, 3),
                jnp.float32,
            )
            y = jax.random.randint(k2, (args.batch_size,), 0, args.num_classes)
            yield x, y

    if args.data_dir:
        # the real input pipeline: ImageFolder scan, worker-thread
        # decode, native fast_collate, prefetch + async device_put
        # (rocm_apex_tpu/data — the reference's DataLoader +
        # data_prefetcher machinery)
        from rocm_apex_tpu.data import ImageFolder, PrefetchLoader

        it = iter(
            PrefetchLoader(
                ImageFolder(args.data_dir),
                batch_size=args.batch_size,
                image_size=args.image_size,
                rng=np.random.RandomState(1),
                num_workers=args.loader_workers,
                # bound the producer to the loop: without it the
                # loader thread outlives the break at args.steps
                steps=args.steps,
            )
        )
    else:
        it = batches(jax.random.PRNGKey(1))
    t0 = time.perf_counter()
    for i, (x, y) in enumerate(it):
        if i >= args.steps:
            break
        params, batch_stats, opt_state, scaler_state, ce = step(
            params, batch_stats, opt_state, scaler_state, x, y
        )
        if (i + 1) % args.print_freq == 0:
            loss = float(ce)  # value fetch = device sync
            dt = (time.perf_counter() - t0) / args.print_freq
            print(
                f"step {i + 1}: loss {loss:.4f}  "
                f"{args.batch_size / dt:.1f} img/s  "
                f"scale {float(scaler_state[0].loss_scale):.0f}"
            )
            t0 = time.perf_counter()


if __name__ == "__main__":
    enable_compile_cache()
    main()
