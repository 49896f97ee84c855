"""Family `gpt2`: GPT-2-shaped decoders (learned positions, pre-LayerNorm,
GELU MLP, tied head, full multi-head attention).

Two halves that share nothing but the seeded weight generator
(`harness/weights.py`):

* the system under test, built from the library's pieces (`GPTModel`,
  `InferenceEngine`);
* the plain reference: the same mathematics in `jax.numpy`, float32,
  matmul precision `highest`, no kernel, no cache, no batching tricks. It
  imports nothing of `rocm_apex_tpu`.

Departure from the published model, shared by both halves because the
program computes it so: GELU is the tanh approximation (`flax.linen.gelu`),
where GPT-2's `activation_function: gelu` of the Cerebras config is the
exact one. Listed under `assumed` in the configuration file.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import counts, weights

INIT_STD = 0.02
CAUSAL = True


# -- sizes and counts: this family's own -----------------------------------


def sizes(config):
    """The sizes of a GPT-2 configuration file under the names the
    benchmark uses."""
    return {
        "hidden": int(config["n_embd"]),
        "layers": int(config["n_layer"]),
        "heads": int(config["n_head"]),
        "ffn": int(config["n_inner"]),
        "vocab": int(config["vocab_size"]),
        "positions": int(config["n_positions"]),
    }


def total_params(config):
    """All parameters: the layers, the final LayerNorm, the tied table
    and the positions."""
    s = sizes(config)
    h = s["hidden"]
    return (
        s["layers"] * counts.layer_params(h, s["ffn"]) + 2 * h
        + s["vocab"] * h + s["positions"] * h
    )


def train_flops_per_token(config, seq):
    """The transformer body and the tied vocabulary projection."""
    s = sizes(config)
    return (
        counts.body_train_flops_per_token(
            s["hidden"], s["ffn"], s["layers"], seq)
        + 6 * s["vocab"] * s["hidden"]
    )


# (name inside a layer, shape as a function of (h, f), kind, scaled init)
_LAYER_LEAVES = (
    ("input_layernorm/weight", lambda h, f: (h,), "ln_weight", False),
    ("input_layernorm/bias", lambda h, f: (h,), "small", False),
    ("self_attention/query_key_value/kernel", lambda h, f: (h, 3 * h), "normal", False),
    ("self_attention/query_key_value/bias", lambda h, f: (3 * h,), "small", False),
    ("self_attention/dense/kernel", lambda h, f: (h, h), "normal", True),
    ("self_attention/dense/bias", lambda h, f: (h,), "small", False),
    ("post_attention_layernorm/weight", lambda h, f: (h,), "ln_weight", False),
    ("post_attention_layernorm/bias", lambda h, f: (h,), "small", False),
    ("mlp/dense_h_to_4h/kernel", lambda h, f: (h, f), "normal", False),
    ("mlp/dense_h_to_4h/bias", lambda h, f: (f,), "small", False),
    ("mlp/dense_4h_to_h/kernel", lambda h, f: (f, h), "normal", True),
    ("mlp/dense_4h_to_h/bias", lambda h, f: (h,), "small", False),
)


def layer_weights(key, s, layer, dtype):
    """The tensors of transformer layer ``layer`` (may be traced), by
    their names inside the layer. Residual-path projections start at
    std / sqrt(2 L), Megatron's rule."""
    h, f = s["hidden"], s["ffn"]
    out = {}
    for name, shape, kind, scaled in _LAYER_LEAVES:
        std = INIT_STD / math.sqrt(2.0 * s["layers"]) if scaled else INIT_STD
        out[name] = weights.leaf(
            key, "layer/" + name, shape(h, f), kind, std, dtype, layer=layer
        )
    return out


def outer_weights(key, s, dtype):
    """Embedding table, positions, final LayerNorm."""
    h = s["hidden"]
    return {
        "embedding/word_embeddings/weight": weights.leaf(
            key, "wte", (s["vocab"], h), "normal", INIT_STD, dtype),
        "embedding/position_embeddings": weights.leaf(
            key, "wpe", (s["positions"], h), "normal", INIT_STD, dtype),
        "transformer/final_layernorm/weight": weights.leaf(
            key, "lnf_w", (h,), "ln_weight", INIT_STD, dtype),
        "transformer/final_layernorm/bias": weights.leaf(
            key, "lnf_b", (h,), "small", INIT_STD, dtype),
    }


def body_params(key, s, dtype):
    """Every tensor of the decoder body under its path in the program's
    parameter tree (without the leading "params"), for sizes ``s``."""
    flat = dict(outer_weights(key, s, dtype))
    for i in range(s["layers"]):
        for name, value in layer_weights(key, s, i, dtype).items():
            flat[f"transformer/layer_{i}/{name}"] = value
    return flat


def flat_params(key, config, dtype):
    return body_params(key, sizes(config), dtype)


def params_tree(key, config, dtype):
    """The pytree `GPTModel.init` returns, with the seeded values."""
    return {"params": weights.nest(flat_params(key, config, dtype))}


def make_params(config, seed, dtype):
    """The program's parameters, made on the device in one jitted call."""
    return jax.jit(lambda key: params_tree(key, config, dtype))(
        weights.seed_key(seed))


# -- the system under test ----------------------------------------------


def model_config(config, **overrides):
    from rocm_apex_tpu.models.gpt import GPTConfig

    s = sizes(config)
    fields = dict(
        vocab_size=s["vocab"],
        hidden_size=s["hidden"],
        num_layers=s["layers"],
        num_attention_heads=s["heads"],
        ffn_hidden_size=s["ffn"],
        max_position_embeddings=s["positions"],
        layernorm_epsilon=float(config["layer_norm_epsilon"]),
        hidden_dropout=0.0,
        attention_dropout=0.0,
        tensor_parallel_size=1,
        init_method_std=INIT_STD,
    )
    fields.update(overrides)
    return GPTConfig(**fields)


def build_engine(config, mix, params, control=False):
    """`InferenceEngine` as a deployment builds it for this mix: paged
    cache, chunked prefill, greedy, tp 1, weights and K/V in bf16. The
    control stores K/V as int8, the engine's own lower-precision path."""
    from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
    from rocm_apex_tpu.models.gpt import GPTModel

    e = mix["engine"]
    cfg = model_config(
        config, params_dtype=jnp.bfloat16, dtype=jnp.bfloat16
    )
    return InferenceEngine(
        GPTModel(cfg), params,
        num_slots=int(e["num_slots"]),
        capacity=int(e["capacity"]),
        sampling=SamplingParams(temperature=0.0),
        seed=0,
        prefill_token_budget=int(e["prefill_token_budget"]),
        paged=True,
        page_size=int(e["page_size"]),
        num_pages=int(e["num_pages"]),
        kv_dtype=jnp.int8 if control else None,
        retrace_policy="raise",
        stats_retention=1 << 16,
    )


def serve_setup(config, mix, seed, control=False):
    params = make_params(config, seed, jnp.bfloat16)
    return build_engine(config, mix, params, control)


def reseed(engine, config, seed):
    """New weights for the same compiled programs (they take the
    parameters as an argument)."""
    engine.params = None
    engine.params = make_params(config, seed, jnp.bfloat16)


# -- the plain reference --------------------------------------------------


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def reference_layer(x, w, heads, eps, mask, with_kv=False):
    """One pre-LayerNorm transformer layer on (b, s, h) float32.
    ``mask`` is (b, 1, s, s) or (1, 1, s, s), True where attention is
    allowed. The fused QKV's columns are laid out per head as
    [q | k | v], Megatron's order. ``with_kv`` also returns the keys and
    values, (b, s, heads, head size) each: what a cache would hold."""
    b, s, h = x.shape
    hd = h // heads
    y = _layer_norm(x, w["input_layernorm/weight"], w["input_layernorm/bias"], eps)
    qkv = y @ w["self_attention/query_key_value/kernel"] + w["self_attention/query_key_value/bias"]
    qkv = qkv.reshape(b, s, heads, 3 * hd)
    q, k, v = qkv[..., :hd], qkv[..., hd:2 * hd], qkv[..., 2 * hd:]
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(hd)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, s, h)
    x = x + ctx @ w["self_attention/dense/kernel"] + w["self_attention/dense/bias"]
    y = _layer_norm(x, w["post_attention_layernorm/weight"], w["post_attention_layernorm/bias"], eps)
    y = _gelu_tanh(y @ w["mlp/dense_h_to_4h/kernel"] + w["mlp/dense_h_to_4h/bias"])
    x = x + y @ w["mlp/dense_4h_to_h/kernel"] + w["mlp/dense_4h_to_h/bias"]
    return (x, k, v) if with_kv else x


def _as_f32(tree):
    return {k: v.astype(jnp.float32) for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("dims", "eps", "stored"))
def _ref_embed(key, tokens, dims, eps, stored):
    s = dict(dims)
    o = _as_f32(outer_weights(key, s, stored))
    pos = jnp.arange(tokens.shape[1])
    return (
        o["embedding/word_embeddings/weight"][tokens]
        + o["embedding/position_embeddings"][pos][None]
    )


@functools.partial(
    jax.jit, static_argnames=("dims", "eps", "stored", "with_kv"))
def _ref_layer(key, layer, x, dims, eps, stored, with_kv=False):
    s = dict(dims)
    with jax.default_matmul_precision("highest"):
        w = _as_f32(layer_weights(key, s, layer, stored))
        n = x.shape[1]
        causal = jnp.tril(jnp.ones((n, n), bool))[None, None]
        return reference_layer(x, w, s["heads"], eps, causal, with_kv)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "stored"))
def _ref_logits(key, x, rows, cols, dims, eps, stored):
    """Float32 logits of the hidden states at (rows, cols)."""
    s = dict(dims)
    with jax.default_matmul_precision("highest"):
        o = _as_f32(outer_weights(key, s, stored))
        hid = _layer_norm(
            x[rows, cols], o["transformer/final_layernorm/weight"],
            o["transformer/final_layernorm/bias"], eps)
        return hid @ o["embedding/word_embeddings/weight"].T


@jax.jit
def _gaps(logits, picked):
    """Per row: how far the picked token's logit lies below the best,
    and the best's own margin over the second best."""
    top2, _ = jax.lax.top_k(logits, 2)
    got = jnp.take_along_axis(logits, picked[:, None], axis=-1)[:, 0]
    return top2[:, 0] - got, top2[:, 0] - top2[:, 1]


ROWS_PAD = 1024  # served positions are padded to a multiple: fixed shapes


def reference_gaps(config, seed, sequences, stored=jnp.bfloat16):
    """For each (prompt, served tokens): the float32 reference's full
    forward over prompt + tokens, and per served token the gap by which
    its reference logit lies below the reference's best at that position
    (and the reference's margin there).

    Layer by layer, each layer's weights made from the seed when it is
    needed, so only one layer's float32 weights are alive. Every block is
    padded to the whole context and a fixed number of rows, so the
    compiled programs are the same for every sample."""
    s = sizes(config)
    dims = tuple(sorted(s.items()))
    eps = float(config["layer_norm_epsilon"])
    key = weights.seed_key(seed)
    width = s["positions"]
    tokens = np.zeros((len(sequences), width), np.int32)
    rows, cols, served = [], [], []
    for i, (p, t) in enumerate(sequences):
        seq = list(p) + list(t)
        # the last served token is never fed back
        tokens[i, : len(seq) - 1] = seq[:-1]
        for j, tok in enumerate(t):
            rows.append(i)
            cols.append(len(p) - 1 + j)
            served.append(tok)
    n = len(served)
    pad = -n % ROWS_PAD
    rows_a = jnp.asarray(rows + [0] * pad)
    cols_a = jnp.asarray(cols + [0] * pad)
    served_a = jnp.asarray(served + [0] * pad, jnp.int32)
    x = _ref_embed(key, jnp.asarray(tokens), dims, eps, stored)
    for layer in range(s["layers"]):
        x = _ref_layer(key, layer, x, dims, eps, stored)
    logits = _ref_logits(key, x, rows_a, cols_a, dims, eps, stored)
    gaps, margin = (np.asarray(a)[:n] for a in _gaps(logits, served_a))
    if not np.all(np.isfinite(gaps)):
        raise FloatingPointError("non-finite reference logits")
    out, at = [], 0
    for _, t in sequences:
        out.append((gaps[at: at + len(t)], margin[at: at + len(t)]))
        at += len(t)
    return out


# -- the cache the timed ticks wrote, read back ---------------------------
#
# A greedy token cannot show a loss of precision under the resolution of
# the bf16 logits' argmax (PERF.md, PR 23: the engine's int8 K/V serves
# the same tokens). The keys and values the timed programs wrote can: at
# the window's close one live slot's rows are copied out of the pools
# through its page-table row, and after the run they are held against the
# keys and values of the float32 reference over the same tokens.


def _slot_kv(cache, slot):
    """One slot's cached rows, per layer: K and V as (layers, heads,
    pages_per_slot * page_size, head size). int8 pools are brought back
    by their per-(page, head) scales. Table entries beyond the slot's
    rows hold the sentinel; they are read from page 0 and never looked
    at."""
    pages = jnp.minimum(cache.page_table[slot], cache.num_pages - 1)

    def rows(pools, scales):
        out = []
        for i, pool in enumerate(pools):
            g = pool[pages]  # (pages, heads, page_size, head size)
            if scales is not None:
                g = g.astype(jnp.float32) * scales[i][pages][:, :, None, None]
            g = jnp.swapaxes(g, 0, 1)
            out.append(g.reshape(g.shape[0], -1, g.shape[-1]))
        return jnp.stack(out)

    return rows(cache.k, cache.k_scale), rows(cache.v, cache.v_scale)


def kv_snapshot_program(engine):
    """`_slot_kv` compiled ahead for this engine's cache (set-up), so
    that taking the snapshot compiles nothing and allocates only when it
    is taken."""
    return jax.jit(_slot_kv).lower(engine.cache, np.int32(0)).compile()


def kv_snapshot(engine, program):
    """The rows of the live decoding slot that holds the most, copied on
    the device (dispatched, not waited for). The engine has no public
    map from slot to request, so its `_slots` is read. None while no
    slot is decoding."""
    live = [
        (st.pos, slot) for slot, st in enumerate(engine._slots)
        if st is not None and st.generated and not st.prefilling
    ]
    if not live:
        return None
    rows, slot = max(live)
    k, v = program(engine.cache, np.int32(slot))
    return {
        "request_id": engine._slots[slot].req.request_id,
        "rows": int(rows), "k": k, "v": v,
    }


@jax.jit
def _kv_gap(k_ref, v_ref, k_got, v_got, rows):
    """Norm of (cached - reference) over the norm of the reference, for
    the first ``rows`` positions; K and V. Reference (1, s, heads, d),
    cached (heads, s, d)."""
    keep = (jnp.arange(k_got.shape[1]) < rows)[None, :, None]

    def gap(ref, got):
        ref = jnp.swapaxes(ref[0], 0, 1)
        d = jnp.where(keep, got.astype(jnp.float32) - ref, 0.0)
        r = jnp.where(keep, ref, 0.0)
        return jnp.sqrt(jnp.sum(d * d) / jnp.sum(r * r))

    return gap(k_ref, k_got), gap(v_ref, v_got)


def reference_kv_gaps(config, seed, tokens, snapshot, stored=jnp.bfloat16):
    """The float32 reference's forward over ``tokens`` (prompt then
    served tokens, at least ``snapshot['rows']`` of them), layer by
    layer, and for each layer how far the cached keys and values lie
    from the reference's: two lists of ``layers`` relative gaps."""
    s = sizes(config)
    dims = tuple(sorted(s.items()))
    eps = float(config["layer_norm_epsilon"])
    key = weights.seed_key(seed)
    rows, width = snapshot["rows"], snapshot["k"].shape[2]
    if len(tokens) < rows or width > s["positions"]:
        raise ValueError("the snapshot holds more rows than there are tokens")
    padded = np.zeros((1, width), np.int32)
    padded[0, :rows] = tokens[:rows]
    x = _ref_embed(key, jnp.asarray(padded), dims, eps, stored)
    out = []
    for layer in range(s["layers"]):
        x, k, v = _ref_layer(key, layer, x, dims, eps, stored, with_kv=True)
        out.append(_kv_gap(
            k, v, snapshot["k"][layer], snapshot["v"][layer], rows))
    k_gaps, v_gaps = np.asarray(jax.device_get(out)).T
    return [float(g) for g in k_gaps], [float(g) for g in v_gaps]
