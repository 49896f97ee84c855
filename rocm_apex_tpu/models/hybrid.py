"""A served decoder whose layers are DECLARED: a pattern of mixers
(state-space or attention), each followed by routed experts.

`HybridConfig.layer_types` names each layer's mixer, ``"mamba"``
(Mamba-2: `ops/ssm.py`) or ``"attention"`` (grouped K/V heads, no
positional encoding); every layer then runs `transformer/moe.py`'s
`HeldExperts`. Normalisation is RMSNorm (the Mamba mixer's own is the
gated form over all of its channels, one group), and four scalars scale
the embedding, each residual branch, the attention scores and the
logits.

The model serves through `InferenceEngine` under the contract
`GPTModel` has: ``apply(params, tokens, cache=, chunk=)`` returns
``(logits, cache)``; a ``(1, budget)`` packed chunk with
``chunk=(slot_ids, positions)`` or a ``(slots, 1)`` decode grid. What it
keeps per request it declares (`cache_spec`), and the engine builds the
cache from that (`inference/paging.py` `PagedKVCache.from_spec`): paged
K/V for the attention layers only, a fixed-size recurrent state and convolution tail
per slot for the Mamba layers. The cache's ``lengths`` carry what the
engine knows: in a chunk a slot whose length is 0 is FRESH (its request
was just admitted, or re-admitted after preemption) and its segment
starts from a zero state; in the decode grid a row whose length is the
capacity sentinel is DEAD and leaves its state as it is.

Serving only: there is no cache-less forward and no backward here
(ROADMAP Queue 2 A7/A8 say what training needs).
"""

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from rocm_apex_tpu.ops import ssm
from rocm_apex_tpu.ops.paging import paged_scatter, paged_view
from rocm_apex_tpu.transformer.moe import HeldExperts

__all__ = ["HybridConfig", "HybridModel", "ServedDecoder"]


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]
    # attention mixer
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    # Mamba-2 mixer (one group of B and C)
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int
    # experts
    num_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_tok: int
    expert_width: int
    shared_width: int
    # scalars
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    dtype: Any = jnp.bfloat16
    params_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    attention_impl: str = "flash"  # or "jnp": gathers the paged view
    init_std: float = 0.02
    # debugging: keep, per position and layer, the mask of experts the
    # router chose, in one more paged pool (`PagedKVCache.routes`)
    log_routes: bool = False
    # the engine reads this of every served model
    tensor_parallel_size: int = 1

    def __post_init__(self):
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of K/V heads")
        if self.tensor_parallel_size != 1:
            raise ValueError(
                "HybridModel is not tensor-parallel: the gated RMSNorm "
                "of the Mamba mixer normalises over all heads")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        return self.mamba_d_inner + 2 * self.mamba_d_state


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


class RMSNorm(nn.Module):
    size: int
    eps: float
    dtype: Any
    params_dtype: Any

    @nn.compact
    def __call__(self, x):
        w = self.param(
            "weight", nn.initializers.ones, (self.size,), self.params_dtype)
        return rms_norm(x, w, self.eps).astype(self.dtype)


def _param(mod, name, shape):
    return mod.param(
        name, nn.initializers.normal(mod.cfg.init_std), shape,
        mod.cfg.params_dtype)


class MambaMixer(nn.Module):
    """``u`` (T, hidden) -> (T, hidden), with the slot states of
    ``state`` = (ssm (slots, n, heads * p), conv (slots, d_conv - 1,
    conv dim)) read and advanced: by the packed chunk (``chunk_geo`` and
    ``fresh``) or by the decode grid (``live``)."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, u, state, chunk_geo=None, fresh=None, live=None):
        cfg = self.cfg
        heads, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        di, cd, kw = cfg.mamba_d_inner, cfg.mamba_conv_dim, cfg.mamba_d_conv
        in_proj = _param(self, "in_proj", (cfg.hidden_size, di + cd + heads))
        conv_w = _param(self, "conv_w", (kw, cd))
        conv_b = _param(self, "conv_b", (cd,))
        dt_bias = _param(self, "dt_bias", (heads,))
        a_log = _param(self, "a_log", (heads,))
        d = _param(self, "d", (heads,))
        norm_w = _param(self, "norm_w", (di,))
        out_proj = _param(self, "out_proj", (di, cfg.hidden_size))
        ssm_state, conv_tail = state

        zxd = jnp.dot(u, in_proj.astype(cfg.dtype))
        z, xbc, dt = zxd[:, :di], zxd[:, di:di + cd], zxd[:, di + cd:]
        with jax.named_scope("ssm_conv"):
            if chunk_geo is not None:
                xbc, conv_tail = ssm.conv_chunk(
                    xbc, conv_w, conv_b, conv_tail, fresh, chunk_geo)
            else:
                xbc, conv_tail = ssm.conv_decode(
                    xbc, conv_w, conv_b, conv_tail, live)
            xbc = jax.nn.silu(xbc).astype(cfg.dtype)
        x = xbc[:, :di].reshape(-1, heads, p)
        b, c = xbc[:, di:di + n], xbc[:, di + n:]
        dt = jax.nn.softplus(
            dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        a = -jnp.exp(a_log.astype(jnp.float32))
        with jax.named_scope("ssm_scan"):
            if chunk_geo is not None:
                y, ssm_state = ssm.ssd_chunk(
                    x, dt, a, b, c, d, ssm_state, fresh, chunk_geo)
            else:
                y, ssm_state = ssm.ssd_decode(
                    x, dt, a, b, c, d, ssm_state, live)
        y = y.reshape(-1, di) * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm(y, norm_w, cfg.rms_norm_eps).astype(cfg.dtype)
        return jnp.dot(y, out_proj.astype(cfg.dtype)), (ssm_state, conv_tail)


class GroupedAttention(nn.Module):
    """Causal attention with fewer K/V heads than query heads over the
    paged pool, no positional encoding. ``kv`` = (k pool, v pool) of
    this layer; ``paged`` carries the table, the page size and the
    slots' lengths."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, u, kv, paged, chunk=None):
        cfg = self.cfg
        nq, nkv, hd = (
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim)
        qkv_w = _param(self, "qkv", (cfg.hidden_size, (nq + 2 * nkv) * hd))
        o_w = _param(self, "o_proj", (nq * hd, cfg.hidden_size))
        t = u.shape[0]
        qkv = jnp.dot(u, qkv_w.astype(cfg.dtype))
        q = qkv[:, :nq * hd].reshape(t, nq, hd)
        k = qkv[:, nq * hd:(nq + nkv) * hd].reshape(t, nkv, hd)
        v = qkv[:, (nq + nkv) * hd:].reshape(t, nkv, hd)
        k_buf, v_buf = kv
        table, lengths = paged["page_table"], paged["lengths"]
        slots_n = table.shape[0]
        capacity = table.shape[1] * paged["page_size"]
        scale = cfg.attention_multiplier
        group = nq // nkv
        if chunk is not None:
            w_slots, w_pos = chunk
        else:
            w_slots, w_pos = jnp.arange(t, dtype=jnp.int32), lengths
        k_buf = paged_scatter(k_buf, table, w_slots, w_pos, k)
        v_buf = paged_scatter(v_buf, table, w_slots, w_pos, v)
        if cfg.attention_impl == "jnp":
            # the plain read: each row attends its slot's gathered rows
            # [0, position + 1), which the scatter above has completed
            kc = paged_view(k_buf, table).astype(jnp.float32)
            vc = paged_view(v_buf, table).astype(jnp.float32)
            row_slot = jnp.clip(w_slots, 0, slots_n - 1)
            kr = jnp.repeat(kc[row_slot], group, axis=2)  # (t, cap, nq, hd)
            vr = jnp.repeat(vc[row_slot], group, axis=2)
            scores = jnp.einsum(
                "tnd,tcnd->tnc", q.astype(jnp.float32), kr) * scale
            bound = jnp.minimum(w_pos + 1, capacity)[:, None, None]
            col = jnp.arange(capacity)[None, None, :]
            scores = jnp.where(col < bound, scores, -1e30)
            ctx = jnp.einsum(
                "tnc,tcnd->tnd", jax.nn.softmax(scores, axis=-1), vr)
        elif chunk is not None:
            from rocm_apex_tpu.ops.flash_attention_segments import (
                flash_attention_chunk_paged,
            )

            ctx = flash_attention_chunk_paged(
                q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                v.transpose(1, 0, 2), chunk[0], k_buf, v_buf, table,
                lengths, scale,
            )
        else:
            from rocm_apex_tpu.ops.flash_attention import (
                flash_attention_decode_paged,
            )

            ctx = flash_attention_decode_paged(
                q.reshape(t * nq, 1, hd), k_buf, v_buf, table,
                jnp.minimum(lengths + 1, capacity), scale,
            )
        ctx = ctx.astype(cfg.dtype).reshape(t, nq * hd)
        return jnp.dot(ctx, o_w.astype(cfg.dtype)), (k_buf, v_buf)


class ServedDecoder(nn.Module):
    """The frame a served model of declared layers runs in: it checks
    what the engine hands over (a ``(1, budget)`` packed chunk with
    ``chunk=(slot_ids, positions)`` or a ``(slots, 1)`` decode grid),
    embeds, runs the layers over what each keeps in the cache, norms,
    projects onto the vocabulary, adds up the tick's counters, writes
    the routing log and advances the decode grid's lengths. A model
    declares its layers (`layer`, `layer_states`, `with_states`) and,
    where it has them, rows of its own (`own_rows`), counters of its own
    (`tick_counts`) and multipliers (`embed`, `project`).

    A layer is ``(h, state, rows) -> (h, state, counts)``. ``rows``
    says where the tick's rows live: ``paged`` (page table, page size,
    lengths), ``chunk`` (as given, None in the decode grid), each row's
    ``slots`` and ``positions``, and ``live`` (the decode grid's rows
    that are not DEAD; None in a chunk). ``counts`` are `HeldExperts`'.
    """

    cfg: Any
    untied_head = False  # True: an ``lm_head`` of its own
    # why a chunk's third element (rows whose commit waits) is refused
    no_deferred_commit = "this model's layers defer no row's commit"

    # -- what a model declares ------------------------------------------

    def layer(self, i):
        raise NotImplementedError

    def layer_states(self, cache):
        """Per layer, what it keeps in ``cache``."""
        raise NotImplementedError

    def with_states(self, cache, states):
        raise NotImplementedError

    def own_rows(self, rows, cache):
        return rows

    def tick_counts(self, rows, cache):
        return {}

    def embed(self, x):
        return x.astype(self.cfg.dtype)

    def project(self, h, head):
        return jnp.dot(h, head, preferred_element_type=jnp.float32)

    # -- the frame ------------------------------------------------------

    @nn.compact
    def __call__(self, tokens, cache=None, chunk=None, adapters=None):
        cfg, who = self.cfg, type(self).__name__
        if cache is None:
            raise ValueError(
                f"{who} serves through a cache (chunk= or the decode "
                f"grid); it has no cache-less forward")
        if adapters is not None:
            raise ValueError(f"{who} takes no adapters")
        table = self.param(
            "embedding", nn.initializers.normal(cfg.init_std),
            (cfg.vocab_size, cfg.hidden_size), cfg.params_dtype)
        lengths = cache.lengths
        if chunk is not None:
            if len(chunk) != 2:
                raise ValueError(
                    f"{self.no_deferred_commit}: chunk=(slot_ids, "
                    f"positions) only")
            if tokens.shape[0] != 1:
                raise ValueError("a packed chunk is one stream (batch 1)")
            ids = tokens[0]
            slots, positions = chunk
            live = None
        else:
            if tokens.shape[1] != 1:
                raise ValueError(
                    f"{who} takes a packed chunk or one token per slot, "
                    f"not a whole-prompt window")
            ids = tokens[:, 0]
            slots = jnp.arange(cache.num_slots, dtype=jnp.int32)
            positions = lengths
            live = lengths < cache.capacity
        rows = self.own_rows(dict(
            paged=dict(
                page_table=cache.page_table, page_size=cache.page_size,
                lengths=lengths),
            chunk=chunk, slots=slots, positions=positions, live=live,
        ), cache)
        h = self.embed(table[ids])
        states = self.layer_states(cache)
        chosen = []
        sums = dict(
            moe_assignments=jnp.int32(0), moe_experts_touched=jnp.int32(0),
            moe_load_max=jnp.int32(0), moe_zero_assignments=jnp.int32(0))
        for i in range(cfg.num_layers):
            h, states[i], counts = self.layer(i)(h, states[i], rows)
            if cfg.log_routes:
                chosen.append(counts["chosen"])
            sums["moe_assignments"] += counts["assignments"]
            sums["moe_experts_touched"] += counts["experts_touched"]
            sums["moe_zero_assignments"] += counts["zero_assignments"]
            sums["moe_load_max"] = jnp.maximum(
                sums["moe_load_max"], counts["load_max"])
        h = RMSNorm(
            cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype, cfg.params_dtype,
            name="final_norm")(h)
        if self.untied_head:
            head = self.param(
                "lm_head", nn.initializers.normal(cfg.init_std),
                (cfg.hidden_size, cfg.vocab_size), cfg.params_dtype,
            ).astype(cfg.dtype)
        else:
            head = table.astype(cfg.dtype).T
        logits = self.project(h, head)
        cache = self.with_states(cache, states).count(
            **sums, **self.tick_counts(rows, cache))
        if cfg.log_routes:
            # every layer's mask of chosen experts, one row a position,
            # in one paged write beside the layers' own
            masks = jnp.concatenate(chosen, axis=0).T  # (rows, layers * words)
            lanes = cache.routes.shape[-1]
            masks = jnp.pad(masks, ((0, 0), (0, lanes - masks.shape[1])))
            cache = cache.replace(routes=paged_scatter(
                cache.routes, cache.page_table, slots, positions,
                masks[:, None, :]))
        if chunk is not None:
            return logits[None], cache
        return logits[:, None, :], cache.replace(
            lengths=jnp.minimum(lengths + 1, cache.capacity))


class HybridLayer(nn.Module):
    cfg: HybridConfig
    kind: str

    @nn.compact
    def __call__(self, h, state, rows):
        cfg = self.cfg
        norm = dict(
            size=cfg.hidden_size, eps=cfg.rms_norm_eps, dtype=cfg.dtype,
            params_dtype=cfg.params_dtype)
        geo = rows["geo"]
        u = RMSNorm(**norm, name="norm1")(h)
        if self.kind == "mamba":
            y, state = MambaMixer(cfg, name="mamba")(
                u, state, geo, rows["fresh"], rows["live"])
        else:
            y, state = GroupedAttention(cfg, name="self_attention")(
                u, state, rows["paged"], rows["chunk"])
        h = h + (cfg.residual_multiplier * y).astype(cfg.dtype)
        u = RMSNorm(**norm, name="norm2")(h)
        tokens = geo["valid"] if geo is not None else rows["live"]
        y, counts = HeldExperts(
            hidden_size=cfg.hidden_size, num_experts=cfg.num_experts,
            held=cfg.experts_held, top_k=cfg.num_experts_per_tok,
            expert_width=cfg.expert_width, shared_width=cfg.shared_width,
            dtype=cfg.dtype, params_dtype=cfg.params_dtype,
            init_std=cfg.init_std, log_chosen=cfg.log_routes, name="moe",
        )(u, tokens)
        h = h + (cfg.residual_multiplier * y).astype(cfg.dtype)
        return h, state, counts


class HybridModel(ServedDecoder):
    cfg: HybridConfig
    no_deferred_commit = (
        "a recurrent state cannot defer a speculative row's commit")

    def cache_spec(self):
        """What each layer keeps per request, for the engine to build
        its cache from: K/V pages (heads, head size) or a recurrent
        state and a convolution tail per slot; every layer counts what
        its experts did; with ``log_routes`` every layer also keeps the
        words of its chosen-experts mask per position (in pages too)."""
        cfg = self.cfg
        words = -(-cfg.num_experts // 32) if cfg.log_routes else 0
        out = []
        for kind in cfg.layer_types:
            if kind == "attention":
                layer = dict(
                    kind="kv", heads=cfg.num_key_value_heads,
                    head_dim=cfg.head_dim)
            else:
                layer = dict(
                    kind="ssm",
                    state=(cfg.mamba_d_state, cfg.mamba_d_inner),
                    conv=(cfg.mamba_d_conv - 1, cfg.mamba_conv_dim),
                    state_dtype=cfg.state_dtype)
            out.append(dict(layer, counters=True, route_words=words))
        return out

    def layer(self, i):
        return HybridLayer(
            self.cfg, self.cfg.layer_types[i], name=f"layer_{i}")

    def layer_states(self, cache):
        kv = zip(cache.k, cache.v)
        state = zip(cache.ssm, cache.conv)
        return [
            next(kv) if kind == "attention" else next(state)
            for kind in self.cfg.layer_types]

    def with_states(self, cache, states):
        kinds = self.cfg.layer_types
        kv = [s for s, kind in zip(states, kinds) if kind == "attention"]
        state = [s for s, kind in zip(states, kinds) if kind != "attention"]
        return cache.replace(
            k=tuple(s[0] for s in kv), v=tuple(s[1] for s in kv),
            ssm=tuple(s[0] for s in state), conv=tuple(s[1] for s in state))

    def own_rows(self, rows, cache):
        """A chunk's segments for the scans, and which of its slots are
        FRESH; ``touched`` is how many slots' states the tick advances."""
        if rows["chunk"] is None:
            return dict(
                rows, geo=None, fresh=None,
                touched=jnp.sum(rows["live"].astype(jnp.int32)))
        geo = ssm.chunk_geometry(rows["slots"], cache.num_slots)
        return dict(
            rows, geo=geo, fresh=cache.lengths == 0,
            touched=jnp.sum((geo["counts"] > 0).astype(jnp.int32)))

    def tick_counts(self, rows, cache):
        return dict(state_slots_live=rows["touched"])

    def embed(self, x):
        return (
            self.cfg.embedding_multiplier * x.astype(jnp.float32)
        ).astype(self.cfg.dtype)

    def project(self, h, head):
        return super().project(h, head) / self.cfg.logits_scaling
