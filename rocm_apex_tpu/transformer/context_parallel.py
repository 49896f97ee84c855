"""Context/sequence parallelism: ring attention + Ulysses all-to-all.

The reference has NO context parallelism (SURVEY.md §5: long-context
support there stops at seqlen-2048 softmax kernels + activation
checkpointing); this module is the capability the mesh design makes
natural — long sequences sharded over a ``context`` axis with two
interchangeable strategies:

* **ring attention** (`ring_flash_attention`): K/V shards rotate around
  the axis via `ppermute`; each hop computes a flash partial (o, lse)
  against the resident K/V block and the partials merge with the
  log-sum-exp rule. Peak memory per chip is O(s_local); the ring hides
  transfer behind compute the same way the published ring-attention
  schedules do, with XLA overlapping the collective.
* **Ulysses / all-to-all** (`ulysses_attention`): `all_to_all` swaps the
  sharded dimension from sequence to heads, each chip runs ordinary
  flash attention on full sequences for its head subset, and a second
  `all_to_all` swaps back. Cheaper collectives when heads >= axis size.

Both run inside `shard_map` with the context axis bound (sequence
sharded contiguously in axis order), are causal-correct across shards,
and differentiate through (the ppermute/all_to_all transpose is the
reverse collective; flash partial grads use the lse cotangent path).
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.lax import axis_size

from rocm_apex_tpu.ops.flash_attention import flash_attention_with_lse
from rocm_apex_tpu.transformer import parallel_state

__all__ = ["ring_flash_attention", "ulysses_attention"]


def _merge(o1, lse1, o2, lse2):
    """Combine two disjoint-key partials: the online-softmax rule.
    Safe when both partials are empty (lse = -inf): weights become 0
    instead of exp(-inf - -inf) = nan."""
    lse = jnp.logaddexp(lse1, lse2)
    safe = jnp.where(jnp.isneginf(lse), 0.0, lse)
    w1 = jnp.exp(lse1 - safe)[..., None]
    w2 = jnp.exp(lse2 - safe)[..., None]
    return o1.astype(jnp.float32) * w1 + o2.astype(jnp.float32) * w2, lse


def ring_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = parallel_state.CONTEXT_AXIS,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Flash attention over a sequence sharded on `axis_name`.

    Operands are the LOCAL shards (bh, s_local, d), sequence split
    contiguously in axis order (rank r holds tokens
    [r*s_local, (r+1)*s_local)). Returns the local output shard.
    """
    n = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    bh, s_loc, dh = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]

    def full_fn(kv):
        kc, vc = kv
        return flash_attention_with_lse(q, kc, vc, None, False, scale)

    def tri_fn(kv):
        kc, vc = kv
        return flash_attention_with_lse(q, kc, vc, None, True, scale)

    def skip_fn(kv):
        return (
            jnp.zeros_like(q),
            jnp.full((bh, s_loc), -jnp.inf, jnp.float32),
        )

    def body(carry, i):
        kc, vc, o, lse = carry
        src = (my - i) % n  # which rank's block currently resides here
        if causal:
            # src <  my: keys strictly in the past -> full attention
            # src == my: the diagonal block -> causal triangle
            # src >  my: the future -> contributes nothing
            case = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
            o_i, lse_i = jax.lax.switch(
                case, [full_fn, tri_fn, skip_fn], (kc, vc)
            )
        else:
            o_i, lse_i = full_fn((kc, vc))
        o, lse = _merge(o, lse, o_i, lse_i)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (kc, vc, o, lse), None

    o0 = jnp.zeros((bh, s_loc, dh), jnp.float32)
    lse0 = jnp.full((bh, s_loc), -jnp.inf, jnp.float32)
    (_, _, o, _), _ = jax.lax.scan(
        body, (k, v, o0, lse0), jnp.arange(n)
    )
    return o.astype(q.dtype)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = parallel_state.CONTEXT_AXIS,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    Operands are local shards (b, s_local, h, d) with the FULL head
    count; `h` must be divisible by the axis size. Internally the
    sharding swaps seq->heads, local flash attention runs over the full
    sequence for h/n heads, and the output swaps back. Returns
    (b, s_local, h, d).
    """
    n = axis_size(axis_name)
    b, s_loc, h, dh = q.shape
    if h % n:
        raise ValueError(f"num heads {h} not divisible by axis size {n}")

    def seq_to_heads(x):
        # (b, s_loc, h, d) -> (b, n*s_loc, h/n, d)
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    s_full, h_loc = qg.shape[1], qg.shape[2]

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h_loc, s_full, dh)

    o, _ = flash_attention_with_lse(
        flat(qg), flat(kg), flat(vg), None, causal, scale
    )
    o = o.reshape(b, h_loc, s_full, dh).transpose(0, 2, 1, 3)
    return heads_to_seq(o)
