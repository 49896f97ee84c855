"""A served decoder whose layer is two latent-attention blocks, two
dense gated MLPs and one expert layer on a SHORTCUT.

The layer (``N_*`` four RMSNorms of its own; `LatentAttention` ``A_j``,
`GatedMLP` ``F_j``, `transformer/moe.py::HeldExperts` ``M``):

    h1 = x  + A_0(N_a0(x))
    u1 = N_m0(h1);  m = M(u1);  h2 = h1 + F_0(u1)
    h3 = h2 + A_1(N_a1(h2))
    y  = h3 + F_1(N_m1(h3)) + m

The expert layer reads the same normed input as the first dense MLP and
its output joins the residual stream a whole attention-and-MLP block
later, so nothing between depends on it. Then a final RMSNorm and an
untied head. Positions are rotary (`ops/mla.py::rotary`).

A latent-attention block (MLA) projects its input down to a query
latent and a K/V latent, normalises both (RMSNorm, then a fixed scale
``sqrt(hidden / rank)``), and per
position caches ONE row for all heads: the normalised K/V latent and
the rotated positional key. The decode grid attends in that latent
space (the ABSORBED form: `ops/mla.py::mla_decode_paged`); a packed
chunk expands keys and values for its own rows, attends them under
segment-causal masking, reads each row's pre-chunk prefix in the latent
space and merges the two by their log-sum-exps.

The model serves through `InferenceEngine` in the frame
`models/hybrid.py::ServedDecoder` (``apply(params, tokens, cache=,
chunk=)`` returns ``(logits, cache)``); what it keeps per request it
declares (`cache_spec`): two ``latent`` entries a layer. Serving only.
"""

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from rocm_apex_tpu.models.hybrid import (
    RMSNorm, ServedDecoder, join_rows, part_rows, rms_norm,
)
from rocm_apex_tpu.ops.mla import (
    bounded_lengths, latent_width, mla_decode_paged, rotary,
)
from rocm_apex_tpu.ops.paging import paged_scatter
from rocm_apex_tpu.transformer.moe import HeldExperts

__all__ = ["LatentConfig", "LatentModel"]

BLOCKS = 2  # attention blocks (and dense MLPs) a layer


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    # latent attention
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    # dense MLP, experts
    ffn_hidden_size: int
    num_experts: int  # routed; the router also scores the zero experts
    zero_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_tok: int
    expert_width: int
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    dtype: Any = jnp.bfloat16
    params_dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    # debugging: the mask of experts each position's router chose, in
    # one more paged pool (`PagedKVCache.routes`)
    log_routes: bool = False
    # the engine reads this of every served model
    tensor_parallel_size: int = 1

    def __post_init__(self):
        if self.tensor_parallel_size != 1:
            raise ValueError(
                "LatentModel is not tensor-parallel: every head reads the "
                "one latent row")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary positions rotate pairs")

    @property
    def latent_width(self) -> int:
        return latent_width(self.kv_lora_rank, self.qk_rope_head_dim)


def _param(mod, name, shape):
    return mod.param(
        name, nn.initializers.normal(mod.cfg.init_std), shape,
        mod.cfg.params_dtype)


class LatentAttention(nn.Module):
    """``u`` (T, hidden) -> (T, hidden), with the block's latent
    ``pool`` written at every row's ``(slots, positions)`` and read part
    by part of ``rows``: by a packed chunk (``segments`` = its slot ids)
    or by a decode grid. The projections, the write and the absorbed
    query run once over all rows."""

    cfg: LatentConfig

    @nn.compact
    def __call__(self, u, pool, rows):
        cfg = self.cfg
        positions, w_slots = rows["positions"], rows["slots"]
        nh, dn, dr, dv = (
            cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim)
        rq, rkv, h = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.hidden_size
        q_down = _param(self, "q_down", (h, rq))
        q_norm = _param(self, "q_norm", (rq,))
        q_up = _param(self, "q_up", (rq, nh * (dn + dr)))
        kv_down = _param(self, "kv_down", (h, rkv + dr))
        kv_norm = _param(self, "kv_norm", (rkv,))
        kv_up = _param(self, "kv_up", (rkv, nh, dn + dv))
        o_proj = _param(self, "o_proj", (nh * dv, h))
        t = u.shape[0]
        dt = cfg.dtype
        s_q, s_kv = math.sqrt(h / rq), math.sqrt(h / rkv)
        table = rows["parts"][0]["paged"]["page_table"]
        capacity = table.shape[1] * rows["parts"][0]["paged"]["page_size"]

        with jax.named_scope("mla_proj"):
            cq = s_q * rms_norm(
                jnp.dot(u, q_down.astype(dt)), q_norm, cfg.rms_norm_eps)
            q = jnp.dot(cq.astype(dt), q_up.astype(dt)).reshape(
                t, nh, dn + dr)
            ckr = jnp.dot(u, kv_down.astype(dt))
            c = (s_kv * rms_norm(
                ckr[:, :rkv], kv_norm, cfg.rms_norm_eps)).astype(dt)
            k_r = rotary(ckr[:, rkv:], positions, cfg.rope_theta)
            q_n, q_r = q[..., :dn], rotary(
                q[..., dn:], positions, cfg.rope_theta)
            row = jnp.concatenate([c, k_r], axis=-1)
            pad = cfg.latent_width - rkv - dr  # zero lanes of a stored row
            row = jnp.pad(row, ((0, 0), (0, pad)))
            pool = paged_scatter(
                pool, table, w_slots, positions, row[:, None, :])
            # the absorbed query: W_uk goes into it, and the lanes the
            # pool pads with zeros meet zeros
            q_lat = jnp.einsum(
                "thn,rhn->thr", q_n, kv_up[..., :dn].astype(dt))
            q_abs = jnp.concatenate(
                [q_lat, q_r, jnp.zeros((t, nh, pad), dt)], axis=-1)
        scale = 1.0 / math.sqrt(dn + dr)

        def latent_read(scope, q, rows_table, rows_lengths):
            # the kernel's instructions in a trace are named after the
            # innermost scope (a `cond` branch would rename them)
            with jax.named_scope(scope):
                o, lse = mla_decode_paged(
                    q, pool, rows_table, rows_lengths, scale, rkv)
            # W_uv takes the weighted latent to each head's values
            return jnp.einsum(
                "thr,rhv->thv", o, kv_up[..., dn:].astype(dt),
                preferred_element_type=jnp.float32), lse

        def read(part):
            lengths, chunk = part["paged"]["lengths"], part["segments"]
            q_abs_p = part_rows(q_abs, part)
            if chunk is None:
                return latent_read(
                    "mla_decode", q_abs_p, table,
                    jnp.minimum(lengths + 1, capacity))[0]
            from rocm_apex_tpu.ops.flash_attention_segments import (
                flash_attention_segments_with_lse,
            )

            slots_n = table.shape[0]
            c_p, k_r_p = part_rows(c, part), part_rows(k_r, part)
            q_p = jnp.concatenate(
                [part_rows(q_n, part), part_rows(q_r, part)], axis=-1)
            tp = c_p.shape[0]
            with jax.named_scope("mla_chunk"):
                # (A) the chunk's own rows, keys and values expanded
                kv = jnp.einsum("tr,rhe->the", c_p, kv_up.astype(dt))
                k = jnp.concatenate([
                    kv[..., :dn],
                    jnp.broadcast_to(k_r_p[:, None, :], (tp, nh, dr)),
                ], axis=-1)
                v = jnp.pad(kv[..., dn:], ((0, 0), (0, 0), (0, dn + dr - dv)))
                o_a, lse_a = flash_attention_segments_with_lse(
                    q_p.transpose(1, 0, 2),
                    k.transpose(1, 0, 2), v.transpose(1, 0, 2), chunk,
                    causal=True, scale=scale)
                o_a = o_a.transpose(1, 0, 2)[..., :dv].astype(jnp.float32)
                lse_a = lse_a.transpose(1, 0)
                # (B) each row's pre-chunk prefix, in the latent space:
                # a row is a "slot" of the kernel with its own slot's
                # page list. No row has a prefix in most chunks (every
                # prompt in it begins in it): the read is then skipped
                real = chunk < slots_n
                row_slot = jnp.clip(chunk, 0, slots_n - 1)
                prefix = jnp.where(real, lengths[row_slot], 0)
                o_b, lse_b = jax.lax.cond(
                    jnp.any(prefix > 0),
                    lambda: latent_read(
                        "mla_chunk_prefix", q_abs_p, table[row_slot],
                        prefix),
                    lambda: (
                        jnp.zeros((tp, nh, dv), jnp.float32),
                        jnp.full((tp, nh), -1e30, jnp.float32)),
                )
                m = jnp.maximum(lse_a, lse_b)
                w_a, w_b = jnp.exp(lse_a - m), jnp.exp(lse_b - m)
                return (
                    w_a[..., None] * o_a + w_b[..., None] * o_b
                ) / (w_a + w_b)[..., None]

        ctx = join_rows([read(part) for part in rows["parts"]])
        ctx = ctx.astype(dt).reshape(t, nh * dv)
        return jnp.dot(ctx, o_proj.astype(dt)), pool


class GatedMLP(nn.Module):
    """``(silu(u W_g) * (u W_u)) W_d``; gate and up halves in one
    matrix."""

    cfg: LatentConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        f = cfg.ffn_hidden_size
        w_in = _param(self, "w_in", (cfg.hidden_size, 2 * f))
        w_out = _param(self, "w_out", (f, cfg.hidden_size))
        with jax.named_scope("dense_mlp"):
            ab = jnp.dot(u, w_in.astype(cfg.dtype))
            act = (
                jax.nn.silu(ab[:, :f].astype(jnp.float32))
                * ab[:, f:].astype(jnp.float32)
            ).astype(cfg.dtype)
            return jnp.dot(act, w_out.astype(cfg.dtype))


class ShortcutLayer(nn.Module):
    cfg: LatentConfig

    @nn.compact
    def __call__(self, h, pools, rows):
        cfg = self.cfg
        norm = dict(
            size=cfg.hidden_size, eps=cfg.rms_norm_eps, dtype=cfg.dtype,
            params_dtype=cfg.params_dtype)
        pools = list(pools)
        moe = counts = None
        for j in range(BLOCKS):
            y, pools[j] = LatentAttention(cfg, name=f"attn_{j}")(
                RMSNorm(**norm, name=f"norm_a{j}")(h), pools[j], rows)
            h = h + y
            u = RMSNorm(**norm, name=f"norm_m{j}")(h)
            if j == 0:
                moe, counts = HeldExperts(
                    hidden_size=cfg.hidden_size, num_experts=cfg.num_experts,
                    held=cfg.experts_held, top_k=cfg.num_experts_per_tok,
                    expert_width=cfg.expert_width, shared_width=0,
                    dtype=cfg.dtype, params_dtype=cfg.params_dtype,
                    init_std=cfg.init_std, log_chosen=cfg.log_routes,
                    routing="scores_bias", zero_experts=cfg.zero_experts,
                    scaling=cfg.routed_scaling_factor, name="moe",
                )(u, rows["live"])
            h = h + GatedMLP(cfg, name=f"mlp_{j}")(u)
        return h + moe, tuple(pools), counts


class LatentModel(ServedDecoder):
    """`models/hybrid.py::ServedDecoder` over `ShortcutLayer`s, with an
    untied head."""

    cfg: LatentConfig
    untied_head = True
    no_deferred_commit = "a latent block defers no speculative row's commit"

    def cache_spec(self):
        """Per attention block one paged latent row a position (two
        entries a layer); every layer counts what its experts did; with
        ``log_routes`` every layer also keeps the words of its
        chosen-experts mask per position."""
        cfg = self.cfg
        words = (
            -(-(cfg.num_experts + cfg.zero_experts) // 32)
            if cfg.log_routes else 0)
        block = dict(
            kind="latent", rank=cfg.kv_lora_rank, rope=cfg.qk_rope_head_dim)
        out = []
        for _ in range(cfg.num_layers):
            out.append(dict(block, counters=True, route_words=words))
            out.extend(dict(block) for _ in range(BLOCKS - 1))
        return out

    def layer(self, i):
        return ShortcutLayer(self.cfg, name=f"layer_{i}")

    def layer_states(self, cache):
        return [
            cache.latent[i: i + BLOCKS]
            for i in range(0, len(cache.latent), BLOCKS)]

    def with_states(self, cache, states):
        return cache.replace(latent=tuple(p for pair in states for p in pair))

    def own_rows(self, part, cache):
        """A chunk's rows are live where they name a slot, and its
        segments are its slot ids."""
        if part["chunk"] is None:
            return dict(part, segments=None)
        return dict(
            part, segments=part["slots"],
            live=part["slots"] < cache.num_slots)

    def tick_counts(self, part, cache):
        if part["chunk"] is not None:
            return {}
        # cached positions the decode grid attended over, all blocks
        _, read = bounded_lengths(
            cache.page_table,
            jnp.minimum(cache.lengths + 1, cache.capacity),
            cache.num_pages, cache.page_size)
        return dict(latent_rows_read=len(cache.latent) * jnp.sum(read))
