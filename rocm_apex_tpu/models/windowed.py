"""A served decoder whose attention layers are DECLARED ``global`` or
``window``, each followed by routed experts whose router reads the
layer's input from BEFORE the attention.

A ``window`` layer turns its queries and keys by rotary positions
(pairs by halves: `ops/mla.py::rotary`) and attends the
``sliding_window`` keys that end at a row's own; a ``global`` layer has
no positional encoding and attends every earlier key. Both have fewer
K/V heads than query heads. One layer, on rows ``x``:

    n = RMSNorm_1(x)
    h = x + Attention(n)
    y = h + Experts(RMSNorm_2(h); routed by n)

with gated experts under a ReLU gate (`transformer/moe.py`
`HeldExperts`), the k largest router logits and the softmax over those.
Then a final RMSNorm and an untied head.

The model serves through `InferenceEngine` under `models/hybrid.py`
`ServedDecoder`'s contract. What it keeps per request it declares
(`cache_spec`): paged K/V for every layer, the window layers' with
``window=``, so that their pools live behind a table of their own whose
pages the engine frees once a slot's rows have left them
(`inference/paging.py`). Keys are cached rotated.

Serving only: no cache-less forward and no backward.
"""

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from rocm_apex_tpu.models.hybrid import (
    RMSNorm, ServedDecoder, _param, join_rows, part_rows,
)
from rocm_apex_tpu.ops.mla import rotary
from rocm_apex_tpu.ops.paging import paged_scatter
from rocm_apex_tpu.transformer.moe import HeldExperts

__all__ = ["WindowedConfig", "WindowedModel"]

# The paged chunk kernel scores the whole chunk against every slot it is
# handed, a grid row a slot, and a slot with nothing to read still costs
# its steps. A chunk mostly holds the rows of one to three slots, so the
# kernel is handed the table rows of at most this many, those that have
# rows in the chunk; a chunk of more slots takes the whole table.
CHUNK_SLOTS = 4


@dataclasses.dataclass(frozen=True)
class WindowedConfig:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]
    sliding_window: int
    rope_theta: float
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    # experts
    num_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_tok: int
    expert_width: int
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 16384
    dtype: Any = jnp.bfloat16
    params_dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    # debugging: keep, per position and layer, the mask of experts the
    # router chose, in one more paged pool (`PagedKVCache.routes`)
    log_routes: bool = False
    # the engine reads this of every served model
    tensor_parallel_size: int = 1

    def __post_init__(self):
        bad = set(self.layer_types) - {"global", "window"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of K/V heads")
        if self.head_dim % 2:
            raise ValueError("rotary positions rotate pairs")
        if self.sliding_window < 1:
            raise ValueError("a window holds at least the row's own key")
        if self.tensor_parallel_size != 1:
            raise ValueError(
                "WindowedModel is not tensor-parallel: the window "
                "group's pools and table have no sharded layout")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)


class WindowedAttention(nn.Module):
    """Causal attention with fewer K/V heads than query heads over this
    layer's paged pools ``kv``. ``kind`` ``"window"``: rotary positions
    and a window of ``cfg.sliding_window`` keys, the pools behind the
    window group's table; ``"global"``: neither. ``rows`` as
    `ServedDecoder` hands them, with this model's own (`own_rows`): the
    projections, the rotation and the write of every row's K/V run once
    over all rows, and each part reads the pools by its own kernel."""

    cfg: WindowedConfig
    kind: str

    @nn.compact
    def __call__(self, u, kv, rows):
        cfg = self.cfg
        nq, nkv, hd = (
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim)
        qkv_w = _param(self, "qkv", (cfg.hidden_size, (nq + 2 * nkv) * hd))
        o_w = _param(self, "o_proj", (nq * hd, cfg.hidden_size))
        t = u.shape[0]
        windowed = self.kind == "window"
        window = cfg.sliding_window if windowed else None
        first = rows["parts"][0]
        table = (
            first["window_table"] if windowed
            else first["paged"]["page_table"])
        w_slots, w_pos = rows["slots"], rows["positions"]
        scale = hd ** -0.5
        with jax.named_scope("attn_proj"):
            qkv = jnp.dot(u, qkv_w.astype(cfg.dtype))
            q = qkv[:, :nq * hd].reshape(t, nq, hd)
            k = qkv[:, nq * hd:(nq + nkv) * hd].reshape(t, nkv, hd)
            v = qkv[:, (nq + nkv) * hd:].reshape(t, nkv, hd)
        if windowed:
            with jax.named_scope("attn_rope"):
                q = rotary(q, w_pos, cfg.rope_theta, pairing="halves")
                k = rotary(k, w_pos, cfg.rope_theta, pairing="halves")
        k_buf, v_buf = kv
        k_buf = paged_scatter(k_buf, table, w_slots, w_pos, k)
        v_buf = paged_scatter(v_buf, table, w_slots, w_pos, v)

        def read_chunk(part):
            from rocm_apex_tpu.ops.flash_attention_segments import (
                flash_attention_chunk_paged,
            )

            q_p, k_p, v_p = (
                part_rows(x, part).transpose(1, 0, 2) for x in (q, k, v))

            def read(segments, table, kv_lengths):
                # the scope opens inside a `cond` branch, which would
                # otherwise name the kernels after the branch
                with jax.named_scope(f"attn_{self.kind}_chunk"):
                    return flash_attention_chunk_paged(
                        q_p, k_p, v_p, segments, k_buf, v_buf, table,
                        kv_lengths, scale, window=window,
                        positions=part["positions"] if windowed else None,
                    )

            few = part["few"]
            if few is None:
                return read(part["slots"], table, part["kv_lengths"])
            return jax.lax.cond(
                few["fits"],
                lambda: read(
                    few["segments"], table[few["slots"]],
                    part["kv_lengths"][few["slots"]]),
                lambda: read(part["slots"], table, part["kv_lengths"]))

        def read_grid(part):
            from rocm_apex_tpu.ops.flash_attention import (
                flash_attention_decode_paged,
            )

            with jax.named_scope(f"attn_{self.kind}_decode"):
                return flash_attention_decode_paged(
                    part_rows(q, part).reshape(-1, 1, hd), k_buf, v_buf,
                    table, part["kv_lengths"], scale, window=window,
                )

        with jax.named_scope("attn_proj"):
            ctx = join_rows([
                (read_grid if part["chunk"] is None else read_chunk)(part)
                .astype(cfg.dtype).reshape(-1, nq * hd)
                for part in rows["parts"]])
            return jnp.dot(ctx, o_w.astype(cfg.dtype)), (k_buf, v_buf)


class WindowedLayer(nn.Module):
    cfg: WindowedConfig
    kind: str

    @nn.compact
    def __call__(self, h, state, rows):
        cfg = self.cfg
        norm = dict(
            size=cfg.hidden_size, eps=cfg.rms_norm_eps, dtype=cfg.dtype,
            params_dtype=cfg.params_dtype)
        n = RMSNorm(**norm, name="norm1")(h)
        y, state = WindowedAttention(cfg, self.kind, name="self_attention")(
            n, state, rows)
        h = h + y
        u = RMSNorm(**norm, name="norm2")(h)
        y, counts = HeldExperts(
            hidden_size=cfg.hidden_size, num_experts=cfg.num_experts,
            held=cfg.experts_held, top_k=cfg.num_experts_per_tok,
            expert_width=cfg.expert_width, shared_width=0,
            dtype=cfg.dtype, params_dtype=cfg.params_dtype,
            init_std=cfg.init_std, log_chosen=cfg.log_routes, gate="relu",
            name="moe",
        )(u, rows["live"], router_input=n)
        return h + y, state, counts


class WindowedModel(ServedDecoder):
    """`ServedDecoder` over `WindowedLayer`s, with an untied head."""

    cfg: WindowedConfig
    untied_head = True
    no_deferred_commit = (
        "a window layer's freed pages cannot take a deferred row's commit")

    def cache_spec(self):
        """Every layer keeps K/V pages (heads, head size); a window
        layer's with ``window=``: the window group. Every layer counts
        what its experts did; with ``log_routes`` every layer also keeps
        the words of its chosen-experts mask per position."""
        cfg = self.cfg
        words = -(-cfg.num_experts // 32) if cfg.log_routes else 0
        return [
            dict(
                kind="kv", heads=cfg.num_key_value_heads,
                head_dim=cfg.head_dim,
                window=cfg.sliding_window if kind == "window" else None,
                counters=True, route_words=words)
            for kind in cfg.layer_types]

    def layer(self, i):
        return WindowedLayer(
            self.cfg, self.cfg.layer_types[i], name=f"layer_{i}")

    def layer_states(self, cache):
        everywhere = zip(cache.k, cache.v)
        behind = zip(cache.window_k, cache.window_v)
        return [
            next(behind) if kind == "window" else next(everywhere)
            for kind in self.cfg.layer_types]

    def with_states(self, cache, states):
        kinds = self.cfg.layer_types
        behind = [s for s, kind in zip(states, kinds) if kind == "window"]
        rest = [s for s, kind in zip(states, kinds) if kind != "window"]
        return cache.replace(
            k=tuple(s[0] for s in rest), v=tuple(s[1] for s in rest),
            window_k=tuple(s[0] for s in behind),
            window_v=tuple(s[1] for s in behind))

    def own_rows(self, part, cache):
        """The window group's table, the rows that are tokens, and the
        keys each slot's rows read from the cache: in the decode grid a
        live row reads its own too (the scatter wrote it), a dead one
        nothing; in a chunk a slot with no row in it reads nothing (the
        kernel scores the whole chunk against every slot that does),
        and ``few`` names the slots that have rows in it, where they are
        at most `CHUNK_SLOTS` of more."""
        lengths = part["paged"]["lengths"]
        part = dict(part, window_table=cache.window_table)
        if part["chunk"] is None:
            return dict(part, few=None, kv_lengths=jnp.where(
                part["live"], jnp.minimum(lengths + 1, cache.capacity), 0))
        slots_n = cache.num_slots
        live = part["slots"] < slots_n
        in_chunk = jnp.zeros((slots_n,), bool).at[part["slots"]].set(
            True, mode="drop")
        few = None
        if slots_n > CHUNK_SLOTS:
            # the slots with rows in the chunk, in slot order, first
            # (the chunk's segments stay non-decreasing under the new
            # ids); ``fits`` says whether that is all of them
            rank = jnp.cumsum(in_chunk.astype(jnp.int32)) - 1
            new_id = jnp.where(in_chunk, rank, CHUNK_SLOTS)
            few = dict(
                fits=rank[-1] < CHUNK_SLOTS,
                slots=jnp.argsort(~in_chunk, stable=True)[:CHUNK_SLOTS],
                segments=jnp.where(
                    live, new_id[jnp.clip(part["slots"], 0, slots_n - 1)],
                    CHUNK_SLOTS).astype(jnp.int32))
        return dict(
            part, live=live, few=few,
            kv_lengths=jnp.where(in_chunk, lengths, 0))

    def tick_counts(self, part, cache):
        """Cached positions the decode grid attended over, summed over
        live slots and layers: after the window's bound, and before."""
        if part["chunk"] is not None:
            return {}
        kinds = self.cfg.layer_types
        behind = sum(kind == "window" for kind in kinds)
        cached = jnp.sum(part["kv_lengths"])
        seen = jnp.sum(
            jnp.minimum(part["kv_lengths"], self.cfg.sliding_window))
        return dict(
            kv_rows_read=(len(kinds) - behind) * cached + behind * seen,
            kv_rows_cached=len(kinds) * cached)
