"""BERT pretraining with FusedLAMB + fused LayerNorm.

The BASELINE.json config-4 scenario ("BERT-Large pretrain with FusedLAMB
+ apex.normalization.FusedLayerNorm"; reference:
apex/transformer/testing/standalone_bert.py driven by the L0 BERT
minimal test, run_bert_minimal_test.py). Masked-LM objective on
synthetic data, LAMB with the usual no-decay mask for biases/LN,
data-parallel over the mesh.

CPU smoke:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/bert_pretrain.py --num-layers 2 --hidden-size 64 \
        --num-attention-heads 4 --seq-length 32 --micro-batch-size 2 \
        --train-iters 4 --log-interval 2
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from rocm_apex_tpu.amp import all_finite
from rocm_apex_tpu.models import BertConfig, BertModel
from rocm_apex_tpu.optimizers import fused_lamb
from rocm_apex_tpu.transformer.testing import parse_args
from rocm_apex_tpu.utils.compile_cache import enable_compile_cache
from rocm_apex_tpu.utils.tree import path_str


def main():
    args = parse_args(
        defaults=dict(
            num_layers=4, hidden_size=256, num_attention_heads=8,
            seq_length=128, max_position_embeddings=128,
            micro_batch_size=8, train_iters=20, lr=1e-3, log_interval=5,
            weight_decay=0.01,
        ),
        ignore_unknown_args=True,
    )
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))
    dp = len(devices)

    cfg = BertConfig(
        vocab_size=8192,
        hidden_size=args.hidden_size,
        num_layers=args.num_layers,
        num_attention_heads=args.num_attention_heads,
        max_position_embeddings=args.max_position_embeddings,
        ffn_hidden_size=args.ffn_hidden_size,
        hidden_dropout=0.0,
        attention_dropout=0.0,
        tensor_parallel_size=1,
        add_binary_head=False,
    )
    model = BertModel(cfg)
    b_local, seq = args.micro_batch_size, args.seq_length
    MASK_ID = 1

    tokens0 = jnp.ones((b_local, seq), jnp.int32)
    params = model.init(jax.random.PRNGKey(args.seed), tokens0)

    # LAMB no-decay mask for biases and norm params (the standard BERT
    # recipe; reference FusedLAMB exclude_from_weight_decay usage)
    decay_mask = jax.tree_util.tree_map_with_path(
        lambda path, leaf: not (
            leaf.ndim <= 1
            or "layernorm" in path_str(path).lower()
            or "bias" in path_str(path).lower()
        ),
        params,
    )
    opt = fused_lamb(
        args.lr, weight_decay=args.weight_decay, weight_decay_mask=decay_mask
    )
    ostate = opt.init(params)

    def local_step(params, ostate, tokens, labels, mlm_mask):
        def loss_fn(p):
            losses, _ = model.apply(
                p, tokens, jnp.ones_like(tokens), lm_labels=labels
            )
            return jnp.sum(losses * mlm_mask) / jnp.maximum(
                jnp.sum(mlm_mask), 1.0
            )

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.lax.pmean(grads, "data")
        u, ostate2 = opt.update(grads, ostate, params)
        return (
            optax.apply_updates(params, u),
            ostate2,
            jax.lax.pmean(loss, "data"),
        )

    step = jax.jit(
        shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(), P("data"), P("data"), P("data")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )

    rng = jax.random.PRNGKey(args.seed + 1)
    t0 = time.perf_counter()
    for it in range(args.train_iters):
        rng, k1, k2 = jax.random.split(rng, 3)
        labels = jax.random.randint(
            k1, (b_local * dp, seq), 2, cfg.vocab_size
        )
        mlm = jax.random.bernoulli(k2, 0.15, (b_local * dp, seq))
        tokens = jnp.where(mlm, MASK_ID, labels)
        params, ostate, loss = step(
            params, ostate, tokens, labels, mlm.astype(jnp.float32)
        )
        if (it + 1) % args.log_interval == 0:
            lv = float(loss)
            dt = (time.perf_counter() - t0) / args.log_interval
            print(
                f"iter {it + 1}: mlm loss {lv:.4f}  "
                f"{b_local * dp * seq / dt:.0f} tokens/s"
            )
            t0 = time.perf_counter()


if __name__ == "__main__":
    enable_compile_cache()
    main()
