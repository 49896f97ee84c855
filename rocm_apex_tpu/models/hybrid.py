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
``chunk=(slot_ids, positions)``, a ``(slots, 1)`` decode grid, or both
at once (``grid=``: `ServedDecoder`). What it
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


def part_rows(x, part):
    """The rows of ``x`` (one a row of the tick) that are ``part``'s:
    all of them where the tick has one part."""
    lo, hi = part["span"]
    return x if (lo, hi) == (0, x.shape[0]) else x[lo:hi]


def join_rows(xs):
    """The parts' rows, in the tick's order."""
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0)


class MambaMixer(nn.Module):
    """``u`` (T, hidden) -> (T, hidden), with the slot states of
    ``state`` = (ssm (slots, n, heads * p), conv (slots, d_conv - 1,
    conv dim)) read and advanced part by part of ``rows``: by a packed
    chunk (its ``geo`` and ``fresh``) or by a decode grid (``live``).
    The two projections run once over all rows."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, u, state, rows):
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
        dt = jax.nn.softplus(
            dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        a = -jnp.exp(a_log.astype(jnp.float32))
        ys = []
        for part in rows["parts"]:
            geo, fresh, live = part["geo"], part["fresh"], part["live"]
            xbc_p, dt_p = part_rows(xbc, part), part_rows(dt, part)
            with jax.named_scope("ssm_conv"):
                if geo is not None:
                    xbc_p, conv_tail = ssm.conv_chunk(
                        xbc_p, conv_w, conv_b, conv_tail, fresh, geo)
                else:
                    xbc_p, conv_tail = ssm.conv_decode(
                        xbc_p, conv_w, conv_b, conv_tail, live)
                xbc_p = jax.nn.silu(xbc_p).astype(cfg.dtype)
            x = xbc_p[:, :di].reshape(-1, heads, p)
            b, c = xbc_p[:, di:di + n], xbc_p[:, di + n:]
            with jax.named_scope("ssm_scan"):
                if geo is not None:
                    y, ssm_state = ssm.ssd_chunk(
                        x, dt_p, a, b, c, d, ssm_state, fresh, geo)
                else:
                    y, ssm_state = ssm.ssd_decode(
                        x, dt_p, a, b, c, d, ssm_state, live)
            ys.append(y.reshape(-1, di))
        y = join_rows(ys) * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm(y, norm_w, cfg.rms_norm_eps).astype(cfg.dtype)
        return jnp.dot(y, out_proj.astype(cfg.dtype)), (ssm_state, conv_tail)


class GroupedAttention(nn.Module):
    """Causal attention with fewer K/V heads than query heads over the
    paged pool, no positional encoding. ``kv`` = (k pool, v pool) of
    this layer. The two projections run once over all rows; each part of
    ``rows`` then writes its rows' K/V and reads the pool by its own
    kernel, as an apply of that part alone does: a packed chunk under
    the lengths its slots had before it, a decode grid under its
    cursors."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, u, kv, rows):
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
        scale = cfg.attention_multiplier
        group = nq // nkv
        ctx = []
        for part in rows["parts"]:
            q_p, k_p, v_p = (part_rows(x, part) for x in (q, k, v))
            chunk, paged = part["chunk"], part["paged"]
            table, lengths = paged["page_table"], paged["lengths"]
            slots_n, tp = table.shape[0], q_p.shape[0]
            capacity = table.shape[1] * paged["page_size"]
            if chunk is not None:
                w_slots, w_pos = chunk
            else:
                w_slots, w_pos = jnp.arange(tp, dtype=jnp.int32), lengths
            k_buf = paged_scatter(k_buf, table, w_slots, w_pos, k_p)
            v_buf = paged_scatter(v_buf, table, w_slots, w_pos, v_p)
            if cfg.attention_impl == "jnp":
                # the plain read: each row attends its slot's gathered
                # rows [0, position + 1), which the scatter above has
                # completed
                kc = paged_view(k_buf, table).astype(jnp.float32)
                vc = paged_view(v_buf, table).astype(jnp.float32)
                row_slot = jnp.clip(w_slots, 0, slots_n - 1)
                kr = jnp.repeat(kc[row_slot], group, axis=2)  # (t, cap, nq, hd)
                vr = jnp.repeat(vc[row_slot], group, axis=2)
                scores = jnp.einsum(
                    "tnd,tcnd->tnc", q_p.astype(jnp.float32), kr) * scale
                bound = jnp.minimum(w_pos + 1, capacity)[:, None, None]
                col = jnp.arange(capacity)[None, None, :]
                scores = jnp.where(col < bound, scores, -1e30)
                out = jnp.einsum(
                    "tnc,tcnd->tnd", jax.nn.softmax(scores, axis=-1), vr)
            elif chunk is not None:
                from rocm_apex_tpu.ops.flash_attention_segments import (
                    flash_attention_chunk_paged,
                )

                out = flash_attention_chunk_paged(
                    q_p.transpose(1, 0, 2), k_p.transpose(1, 0, 2),
                    v_p.transpose(1, 0, 2), chunk[0], k_buf, v_buf, table,
                    lengths, scale,
                )
            else:
                from rocm_apex_tpu.ops.flash_attention import (
                    flash_attention_decode_paged,
                )

                out = flash_attention_decode_paged(
                    q_p.reshape(tp * nq, 1, hd), k_buf, v_buf, table,
                    jnp.minimum(lengths + 1, capacity), scale,
                )
            ctx.append(out.astype(cfg.dtype).reshape(tp, nq * hd))
        return jnp.dot(join_rows(ctx), o_w.astype(cfg.dtype)), (k_buf, v_buf)


class ServedDecoder(nn.Module):
    """The frame a served model of declared layers runs in: it checks
    what the engine hands over, embeds, runs the layers over what each
    keeps in the cache, norms, projects onto the vocabulary, adds up the
    tick's counters, writes the routing log and advances the decode
    grid's lengths. A model declares its layers (`layer`,
    `layer_states`, `with_states`) and, where it has them, rows of its
    own (`own_rows`), counters of its own (`tick_counts`) and
    multipliers (`embed`, `project`).

    A tick's rows come in one of three forms:

    * a ``(1, budget)`` packed chunk with ``chunk=(slot_ids,
      positions)``; ``cache.lengths`` are the slots' lengths before it;
    * a ``(slots, 1)`` decode grid; ``cache.lengths`` are the cursors,
      the capacity sentinel on DEAD rows;
    * BOTH AT ONCE (`mixed_in_one_pass`): the chunk as above and
      ``grid=(tokens, cursors, emit)``, each ``(slots,)``: the grid's
      tokens, its cursors (the sentinel on dead rows) and per slot the
      chunk row whose logits are wanted (the last row of a prompt the
      chunk completes; -1: none). The rows are the chunk's ``budget``
      followed by the grid's ``slots``; a slot has rows in one of the
      two, never in both. Every product with weights runs once over all
      of them; the head runs over ``2 x slots`` rows only, per slot the
      chunk row ``emit`` names (any row where it names none), then the
      grid's. Returns those logits ``(2 x slots, vocab)`` and the cache
      with the lengths it came with.

    A layer is ``(h, state, rows) -> (h, state, counts)``. ``rows``
    holds what is one value a row over ALL the tick's rows (each row's
    ``slots`` and ``positions``, and ``live``: the rows that are tokens,
    neither a chunk's padding nor a grid's dead rows) and ``parts``:
    per form in the tick what a mixer's core needs to read the cache its
    own way. A part says where its rows are (``span``) and carries
    ``paged`` (page table, page size, its lengths), ``chunk`` (as given;
    None for a grid), its own ``slots``, ``positions`` and ``live``, and
    what the model adds (`own_rows`). ``counts`` are `HeldExperts`'.
    """

    cfg: Any
    untied_head = False  # True: an ``lm_head`` of its own
    # why a chunk's third element (rows whose commit waits) is refused
    no_deferred_commit = "this model's layers defer no row's commit"
    # A mixed tick is ONE apply (``grid=`` beside ``chunk=``), and the
    # engine's step programs are built for that: every weight is read
    # once a tick, and a prompt the chunk completes emits its first
    # token in this tick and decodes from the next.
    mixed_in_one_pass = True

    # -- what a model declares ------------------------------------------

    def layer(self, i):
        raise NotImplementedError

    def layer_states(self, cache):
        """Per layer, what it keeps in ``cache``."""
        raise NotImplementedError

    def with_states(self, cache, states):
        raise NotImplementedError

    def own_rows(self, part, cache):
        """``part`` with what the model's layers need of it beside;
        ``cache.lengths`` are the part's."""
        return part

    def tick_counts(self, part, cache):
        return {}

    def embed(self, x):
        return x.astype(self.cfg.dtype)

    def project(self, h, head):
        return jnp.dot(h, head, preferred_element_type=jnp.float32)

    # -- the frame ------------------------------------------------------

    @nn.compact
    def __call__(self, tokens, cache=None, chunk=None, adapters=None,
                 grid=None):
        cfg, who = self.cfg, type(self).__name__
        if cache is None:
            raise ValueError(
                f"{who} serves through a cache (chunk= or the decode "
                f"grid); it has no cache-less forward")
        if adapters is not None:
            raise ValueError(f"{who} takes no adapters")
        if grid is not None and chunk is None:
            raise ValueError("grid= rides beside a chunk; alone it is tokens")
        table = self.param(
            "embedding", nn.initializers.normal(cfg.init_std),
            (cfg.vocab_size, cfg.hidden_size), cfg.params_dtype)
        ids, parts = [], []

        def add_part(part_ids, lengths, **part):
            lo = sum(i.shape[0] for i in ids)
            ids.append(part_ids)
            parts.append(self.own_rows(dict(
                part, span=(lo, lo + part_ids.shape[0]), paged=dict(
                    page_table=cache.page_table, page_size=cache.page_size,
                    lengths=lengths),
            ), cache.replace(lengths=lengths)))

        if chunk is not None:
            if len(chunk) != 2:
                raise ValueError(
                    f"{self.no_deferred_commit}: chunk=(slot_ids, "
                    f"positions) only")
            if tokens.shape[0] != 1:
                raise ValueError("a packed chunk is one stream (batch 1)")
            add_part(
                tokens[0], cache.lengths, chunk=chunk, slots=chunk[0],
                positions=chunk[1], live=None)
        else:
            if tokens.shape[1] != 1:
                raise ValueError(
                    f"{who} takes a packed chunk or one token per slot, "
                    f"not a whole-prompt window")
            grid = tokens[:, 0], cache.lengths, None
        emit = None  # set: both forms at once
        if grid is not None:
            grid_ids, cursors, emit = grid
            add_part(
                grid_ids, cursors, chunk=None,
                slots=jnp.arange(cache.num_slots, dtype=jnp.int32),
                positions=cursors, live=cursors < cache.capacity)
        rows = dict(parts=parts, **{
            name: join_rows([part[name] for part in parts])
            for name in ("slots", "positions", "live")})
        h = self.embed(table[join_rows(ids)])
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
        if emit is not None:
            # the head's rows: per slot the chunk row it names, then
            # the grid's
            budget = ids[0].shape[0]
            h = jnp.concatenate(
                [h[jnp.clip(emit, 0, budget - 1)], h[budget:]], axis=0)
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
        for part in parts:
            own = cache.replace(lengths=part["paged"]["lengths"])
            for name, value in self.tick_counts(part, own).items():
                sums[name] = sums[name] + value if name in sums else value
        cache = self.with_states(cache, states).count(**sums)
        if cfg.log_routes:
            # every layer's mask of chosen experts, one row a position,
            # in one paged write beside the layers' own
            masks = jnp.concatenate(chosen, axis=0).T  # (rows, layers * words)
            lanes = cache.routes.shape[-1]
            masks = jnp.pad(masks, ((0, 0), (0, lanes - masks.shape[1])))
            cache = cache.replace(routes=paged_scatter(
                cache.routes, cache.page_table, rows["slots"],
                rows["positions"], masks[:, None, :]))
        if emit is not None:
            return logits, cache
        if chunk is not None:
            return logits[None], cache
        return logits[:, None, :], cache.replace(
            lengths=jnp.minimum(cache.lengths + 1, cache.capacity))


class HybridLayer(nn.Module):
    cfg: HybridConfig
    kind: str

    @nn.compact
    def __call__(self, h, state, rows):
        cfg = self.cfg
        norm = dict(
            size=cfg.hidden_size, eps=cfg.rms_norm_eps, dtype=cfg.dtype,
            params_dtype=cfg.params_dtype)
        u = RMSNorm(**norm, name="norm1")(h)
        if self.kind == "mamba":
            y, state = MambaMixer(cfg, name="mamba")(u, state, rows)
        else:
            y, state = GroupedAttention(cfg, name="self_attention")(
                u, state, rows)
        h = h + (cfg.residual_multiplier * y).astype(cfg.dtype)
        u = RMSNorm(**norm, name="norm2")(h)
        y, counts = HeldExperts(
            hidden_size=cfg.hidden_size, num_experts=cfg.num_experts,
            held=cfg.experts_held, top_k=cfg.num_experts_per_tok,
            expert_width=cfg.expert_width, shared_width=cfg.shared_width,
            dtype=cfg.dtype, params_dtype=cfg.params_dtype,
            init_std=cfg.init_std, log_chosen=cfg.log_routes, name="moe",
        )(u, rows["live"])
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

    def own_rows(self, part, cache):
        """A chunk's segments for the scans, which of its rows are
        tokens and which of its slots are FRESH; ``touched`` is how many
        slots' states the part advances."""
        if part["chunk"] is None:
            return dict(
                part, geo=None, fresh=None,
                touched=jnp.sum(part["live"].astype(jnp.int32)))
        geo = ssm.chunk_geometry(part["slots"], cache.num_slots)
        return dict(
            part, geo=geo, live=geo["valid"], fresh=cache.lengths == 0,
            touched=jnp.sum((geo["counts"] > 0).astype(jnp.int32)))

    def tick_counts(self, part, cache):
        return dict(state_slots_live=part["touched"])

    def embed(self, x):
        return (
            self.cfg.embedding_multiplier * x.astype(jnp.float32)
        ).astype(self.cfg.dtype)

    def project(self, h, head):
        return super().project(h, head) / self.cfg.logits_scaling
