"""Family `longcat_flash`: LongCat-Flash decoders (`LongcatFlash*`). Each
layer holds two latent-attention (MLA) blocks, two dense gated MLPs and
one expert layer on a shortcut; the router scores the routed experts and
the zero-compute experts alike; rotary positions; RMSNorm; an untied
head.

Two halves that share nothing but the seeded weight generator
(`harness/weights.py`) and the names and shapes of the tensors:

* the system under test, built from the library's pieces (`LatentModel`,
  `InferenceEngine`);
* the plain reference: the published equations in `jax.numpy`, float32,
  matmul precision `highest`: the UN-ABSORBED attention (full keys and
  values for every position, a materialised mask, a block of queries at
  a time), experts by a loop over those held. It imports nothing of
  `rocm_apex_tpu`.

One layer (``N_*`` its four RMSNorms; PERF.md section 4 has the
equations of ``A_j``, ``F_j`` and the router ``M`` in full):

    h1 = x  + A_0(N_a0(x))
    u1 = N_m0(h1);  m = M(u1);  h2 = h1 + F_0(u1)
    h3 = h2 + A_1(N_a1(h2))
    y  = h3 + F_1(N_m1(h3)) + m

The chip's share (`model-configs` guide, section 4): the configuration
file says which routed experts are held (`experts_held`, of
`router_experts`) and which rows of the vocabulary (`vocab_size` is the
slice); the router scores every routed and zero expert, a token goes to
its top k, zero experts are applied where the token lives, and what the
routed experts held elsewhere would add is left out, by both halves
alike.

What a request keeps, as the program stores it and `kv_snapshot` copies
it: per attention block and position one row, the normalised scaled
latent ``c'`` then the rotated positional key ``k_r`` (then zeros up to
whole 128-lane tiles); per layer and position the mask of experts the
router chose.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import weights

INIT_STD = 0.02
BIAS_STD = 2e-4  # the balancing bias: small against the scores' spread
BLOCKS = 2


# -- sizes: this family's own ------------------------------------------------


def sizes(config):
    """The sizes of a configuration file under the names the benchmark
    uses, and this family's own."""
    lo, hi = (int(x) for x in config["experts_held"])
    if hi - lo != int(config["n_routed_experts"]):
        raise ValueError("experts_held does not hold n_routed_experts")
    if config["zero_expert_type"] != "identity":
        raise ValueError("only identity zero experts are built")
    if not (config["mla_scale_q_lora"] and config["mla_scale_kv_lora"]):
        raise ValueError("only the scaled latents are built")
    return {
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_layers"]),
        "vocab": int(config["vocab_size"]),
        "positions": int(config["max_position_embeddings"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "ffn": int(config["ffn_hidden_size"]),
        "experts": int(config["router_experts"]),
        "zero": int(config["zero_expert_num"]),
        "held_lo": lo, "held_hi": hi,
        "top_k": int(config["moe_topk"]),
        "expert_width": int(config["expert_ffn_hidden_size"]),
    }


def _scalars(config):
    h = int(config["hidden_size"])
    return (
        math.sqrt(h / int(config["q_lora_rank"])),
        math.sqrt(h / int(config["kv_lora_rank"])),
        float(config["routed_scaling_factor"]),
        float(config["rope_theta"]), float(config["rms_norm_eps"]),
    )


def attention_params_count(s):
    h, nh = s["hidden"], s["heads"]
    return (
        h * s["q_rank"] + s["q_rank"]
        + s["q_rank"] * nh * (s["nope"] + s["rope"])
        + h * (s["kv_rank"] + s["rope"]) + s["kv_rank"]
        + s["kv_rank"] * nh * (s["nope"] + s["v_dim"])
        + nh * s["v_dim"] * h
    )


def layer_params_count(s):
    """Parameters of one layer as held here: two attention blocks, two
    dense MLPs, four norms, the router with its bias, the held experts."""
    h = s["hidden"]
    g = s["held_hi"] - s["held_lo"]
    outputs = s["experts"] + s["zero"]
    return (
        BLOCKS * (attention_params_count(s) + 3 * h * s["ffn"] + 2 * h)
        + h * outputs + outputs + g * 3 * h * s["expert_width"]
    )


def total_params(config):
    s = sizes(config)
    return (
        s["layers"] * layer_params_count(s)
        + 2 * s["vocab"] * s["hidden"] + s["hidden"]
    )


# -- weights from the seed ---------------------------------------------------


def layer_weights(key, s, layer, dtype):
    """The tensors of layer ``layer`` (may be traced) by their names in
    the program's parameter tree under ``layer_<i>/``. Every matrix at
    std 0.02; norm weights 1 + 0.02 N so that a dropped one shows; the
    router's balancing bias in float32 at std `BIAS_STD`."""
    h, nh = s["hidden"], s["heads"]
    g = s["held_hi"] - s["held_lo"]

    def leaf(name, shape, kind_="normal", std=INIT_STD, dt=dtype):
        return weights.leaf(
            key, "layer/" + name, shape, kind_, std, dt, layer=layer)

    w = {
        "moe/router": leaf("router", (h, s["experts"] + s["zero"])),
        "moe/router_bias": leaf(
            "router_bias", (s["experts"] + s["zero"],), std=BIAS_STD,
            dt=jnp.float32),
        "moe/w_in": leaf("w_in", (g, h, 2 * s["expert_width"])),
        "moe/w_out": leaf("w_out", (g, s["expert_width"], h)),
    }
    for j in range(BLOCKS):
        a = f"attn_{j}/"
        w.update({
            f"norm_a{j}/weight": leaf(f"norm_a{j}", (h,), "ln_weight"),
            f"norm_m{j}/weight": leaf(f"norm_m{j}", (h,), "ln_weight"),
            a + "q_down": leaf(a + "q_down", (h, s["q_rank"])),
            a + "q_norm": leaf(a + "q_norm", (s["q_rank"],), "ln_weight"),
            a + "q_up": leaf(
                a + "q_up", (s["q_rank"], nh * (s["nope"] + s["rope"]))),
            a + "kv_down": leaf(a + "kv_down", (h, s["kv_rank"] + s["rope"])),
            a + "kv_norm": leaf(a + "kv_norm", (s["kv_rank"],), "ln_weight"),
            a + "kv_up": leaf(
                a + "kv_up", (s["kv_rank"], nh, s["nope"] + s["v_dim"])),
            a + "o_proj": leaf(a + "o_proj", (nh * s["v_dim"], h)),
            f"mlp_{j}/w_in": leaf(f"mlp_{j}/w_in", (h, 2 * s["ffn"])),
            f"mlp_{j}/w_out": leaf(f"mlp_{j}/w_out", (s["ffn"], h)),
        })
    return w


def outer_weights(key, s, dtype):
    """The table and the untied head (the held slice of each), and the
    final norm."""
    return {
        "embedding": weights.leaf(
            key, "embedding", (s["vocab"], s["hidden"]), "normal", INIT_STD,
            dtype),
        "lm_head": weights.leaf(
            key, "lm_head", (s["hidden"], s["vocab"]), "normal", INIT_STD,
            dtype),
        "final_norm/weight": weights.leaf(
            key, "final_norm", (s["hidden"],), "ln_weight", INIT_STD, dtype),
    }


def make_params(config, seed, dtype):
    """The program's parameters, made on the device a layer at a time
    (one compiled maker): a layer's float32 draws are gone before the
    next layer's are made."""
    s = sizes(config)
    dims = tuple(sorted(s.items()))
    key = weights.seed_key(seed)

    @jax.jit
    def layer(key, i):
        return weights.nest(layer_weights(key, dict(dims), i, dtype))

    tree = jax.jit(lambda k: weights.nest(outer_weights(k, s, dtype)))(key)
    for i in range(s["layers"]):
        tree[f"layer_{i}"] = layer(key, jnp.int32(i))
    return {"params": tree}


# -- the system under test ----------------------------------------------------


def model_config(config, **overrides):
    from rocm_apex_tpu.models.latent import LatentConfig

    s = sizes(config)
    _, _, scaling, theta, eps = _scalars(config)
    fields = dict(
        vocab_size=s["vocab"], hidden_size=s["hidden"], num_layers=s["layers"],
        num_attention_heads=s["heads"], q_lora_rank=s["q_rank"],
        kv_lora_rank=s["kv_rank"], qk_nope_head_dim=s["nope"],
        qk_rope_head_dim=s["rope"], v_head_dim=s["v_dim"], rope_theta=theta,
        ffn_hidden_size=s["ffn"], num_experts=s["experts"],
        zero_experts=s["zero"], experts_held=(s["held_lo"], s["held_hi"]),
        num_experts_per_tok=s["top_k"], expert_width=s["expert_width"],
        routed_scaling_factor=scaling,
        rms_norm_eps=eps, max_position_embeddings=s["positions"],
        init_std=INIT_STD,
    )
    fields.update(overrides)
    return LatentConfig(**fields)


# the control's latent rows: the nearest stored precision below bfloat16
# (`reference_latent_gaps` with ``lowered``)
CONTROL_LATENT = jnp.float8_e4m3fn


def build_engine(config, mix, params):
    """`InferenceEngine` as a deployment builds it for this mix: a paged
    latent cache, chunked prefill, greedy, weights and latent rows in
    bfloat16."""
    from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
    from rocm_apex_tpu.models.latent import LatentModel

    e = mix["engine"]
    cfg = model_config(
        config, params_dtype=jnp.bfloat16, dtype=jnp.bfloat16,
        # the program's debugging log of each position's chosen experts,
        # which the comparison of the routing reads back
        log_routes=True,
    )
    return InferenceEngine(
        LatentModel(cfg), params,
        num_slots=int(e["num_slots"]),
        capacity=int(e["capacity"]),
        sampling=SamplingParams(temperature=0.0),
        seed=0,
        prefill_token_budget=int(e["prefill_token_budget"]),
        paged=True,
        page_size=int(e["page_size"]),
        num_pages=int(e["num_pages"]),
        retrace_policy="raise",
        stats_retention=1 << 16,
    )


def serve_setup(config, mix, seed, control=False):
    """The engine is the same under the control: what stands in another
    precision there is the reference (`kinds/serve_open_loop_latent.py`)."""
    return build_engine(config, mix, make_params(config, seed, jnp.bfloat16))


def reseed(engine, config, seed):
    engine.params = None
    engine.params = make_params(config, seed, jnp.bfloat16)


# -- the plain reference ------------------------------------------------------

Q_BLOCK = 512  # queries a block of the materialised scores


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rotate(x, theta):
    """(b, T, ..., d): interleaved pairs (x[2i], x[2i+1]) turned by
    pos * theta ** (-2i / d), as DeepSeek-V3 lays them."""
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((1, t) + (1,) * (x.ndim - 3) + (d // 2,))
    pair = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pair[..., 0], pair[..., 1]
    return jnp.stack(
        [a * jnp.cos(ang) - b * jnp.sin(ang),
         a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1).reshape(x.shape)


def reference_attention(u, w, a, s, scalars):
    """Block ``a`` ("attn_0/" or "attn_1/") on (b, T, hidden): its
    output, and what a position caches: ``c'`` and the rotated ``k_r``."""
    s_q, s_kv, _, theta, eps = scalars
    b, t, _ = u.shape
    nh, dn, dr, dv = s["heads"], s["nope"], s["rope"], s["v_dim"]
    rkv = s["kv_rank"]
    c_q = _rms(u @ w[a + "q_down"], w[a + "q_norm"], eps)
    q = ((s_q * c_q) @ w[a + "q_up"]).reshape(b, t, nh, dn + dr)
    ckr = u @ w[a + "kv_down"]
    c = s_kv * _rms(ckr[..., :rkv], w[a + "kv_norm"], eps)
    k_r = _rotate(ckr[..., rkv:], theta)
    kv = jnp.einsum("btr,rhe->bthe", c, w[a + "kv_up"])
    k_n, v = kv[..., :dn], kv[..., dn:]
    q_n, q_r = q[..., :dn], _rotate(q[..., dn:], theta)
    scale = 1.0 / math.sqrt(dn + dr)
    block = min(Q_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are no whole blocks of {block}")
    col = jnp.arange(t)

    def one(seq):  # a sequence at a time, a block of queries at a time
        q_n_i, q_r_i, k_n_i, k_r_i, v_i = seq

        def rows(start):
            qn = jax.lax.dynamic_slice_in_dim(q_n_i, start, block, 0)
            qr = jax.lax.dynamic_slice_in_dim(q_r_i, start, block, 0)
            scores = scale * (
                jnp.einsum("qhd,khd->hqk", qn, k_n_i)
                + jnp.einsum("qhd,kd->hqk", qr, k_r_i))
            mask = col[None, :] <= (start + jnp.arange(block))[:, None]
            probs = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v_i)

        ctx = jax.lax.map(rows, jnp.arange(0, t, block))
        return ctx.reshape(t, nh * dv)

    ctx = jax.lax.map(one, (q_n, q_r, k_n, k_r, v))
    return ctx @ w[a + "o_proj"], c, k_r


def reference_experts(u, w, s, scaling):
    """``M(u)``: the held routed experts' part plus the zero experts'
    part, the experts each token chose and the margin between its k-th
    and (k+1)-th biased score."""
    f, k = s["expert_width"], s["top_k"]
    scores = jax.nn.softmax(u @ w["moe/router"], axis=-1)
    top, ids = jax.lax.top_k(scores + w["moe/router_bias"], k + 1)
    margin = top[..., k - 1] - top[..., k]
    ids = ids[..., :k]
    gates = jnp.take_along_axis(scores, ids, axis=-1)

    def expert(acc, ew):
        w_in, w_out, e = ew
        gate = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)
        ab = u @ w_in
        y = (_silu(ab[..., :f]) * ab[..., f:]) @ w_out
        return acc + gate[..., None] * y, None

    held = jnp.arange(s["held_lo"], s["held_hi"])
    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(u), (w["moe/w_in"], w["moe/w_out"], held))
    zero = jnp.sum(jnp.where(ids >= s["experts"], gates, 0.0), axis=-1)
    return scaling * (routed + zero[..., None] * u), ids, margin


def reference_mlp(u, w, name, f):
    ab = u @ w[name + "/w_in"]
    return (_silu(ab[..., :f]) * ab[..., f:]) @ w[name + "/w_out"]


def reference_layer(x, w, s, scalars):
    """One layer on (b, T, hidden) float32. Returns the output and the
    layer's own record: per block the cached rows, and the routing."""
    eps = scalars[4]
    kept = {}
    m = None
    for j in range(BLOCKS):
        y, c, k_r = reference_attention(
            _rms(x, w[f"norm_a{j}/weight"], eps), w, f"attn_{j}/", s, scalars)
        kept[f"c{j}"], kept[f"k_r{j}"] = c, k_r
        x = x + y
        u = _rms(x, w[f"norm_m{j}/weight"], eps)
        if j == 0:
            m, kept["ids"], kept["margin"] = reference_experts(
                u, w, s, scalars[2])
        x = x + reference_mlp(u, w, f"mlp_{j}", s["ffn"])
    return x + m, kept


@functools.partial(jax.jit, static_argnames=("dims", "stored"))
def _ref_embed(key, tokens, dims, stored):
    o = outer_weights(key, dict(dims), stored)
    return o["embedding"].astype(jnp.float32)[tokens]


@functools.partial(
    jax.jit, static_argnames=("dims", "scalars", "stored", "full"))
def _ref_layer(key, layer, x, dims, scalars, stored, full=False):
    s = dict(dims)
    with jax.default_matmul_precision("highest"):
        w = {
            k: v.astype(jnp.float32)
            for k, v in layer_weights(key, s, layer, stored).items()}
        x, kept = reference_layer(x, w, s, scalars)
    return (x, kept) if full else x


@functools.partial(jax.jit, static_argnames=("dims", "scalars", "stored"))
def _ref_logits(key, x, rows, cols, dims, scalars, stored):
    with jax.default_matmul_precision("highest"):
        o = {
            k: v.astype(jnp.float32)
            for k, v in outer_weights(key, dict(dims), stored).items()}
        hid = _rms(x[rows, cols], o["final_norm/weight"], scalars[4])
        return hid @ o["lm_head"]


@jax.jit
def _gaps(logits, picked):
    top2, _ = jax.lax.top_k(logits, 2)
    got = jnp.take_along_axis(logits, picked[:, None], axis=-1)[:, 0]
    return top2[:, 0] - got, top2[:, 0] - top2[:, 1]


ROWS_PAD = 1024  # served positions are padded to a multiple: fixed shapes
WIDTH_PAD = 512  # and sequences to a multiple: few compiled widths


def _static(config):
    s = sizes(config)
    return s, tuple(sorted(s.items())), _scalars(config)


def _width(n):
    return -(-n // WIDTH_PAD) * WIDTH_PAD if n > Q_BLOCK else n


def reference_logits(config, seed, tokens, stored=jnp.bfloat16):
    """Float32 logits of the reference at every position of ``tokens``
    ((b, T) ids): the tests' full forward pass."""
    s, dims, scalars = _static(config)
    key = weights.seed_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    x = _ref_embed(key, tokens, dims, stored)
    for i in range(s["layers"]):
        x = _ref_layer(key, i, x, dims, scalars, stored)
    r, c = np.divmod(np.arange(b * t), t)
    return np.asarray(_ref_logits(
        key, x, jnp.asarray(r), jnp.asarray(c), dims, scalars, stored
    )).reshape(b, t, -1)


def reference_gaps(config, seed, sequences, stored=jnp.bfloat16):
    """For each (prompt, served tokens): the reference's full forward
    over prompt + tokens, and per served token the gap by which its
    reference logit lies below the reference's best at that position,
    and the reference's margin there. A layer at a time, each layer's
    weights made from the seed when it is needed."""
    s, dims, scalars = _static(config)
    key = weights.seed_key(seed)
    width = _width(max(len(p) + len(t) for p, t in sequences))
    tokens = np.zeros((len(sequences), width), np.int32)
    rows, cols, served = [], [], []
    for i, (p, t) in enumerate(sequences):
        seq = list(p) + list(t)
        tokens[i, : len(seq) - 1] = seq[:-1]  # the last is never fed back
        for j, tok in enumerate(t):
            rows.append(i)
            cols.append(len(p) - 1 + j)
            served.append(tok)
    n = len(served)
    pad = -n % ROWS_PAD
    x = _ref_embed(key, jnp.asarray(tokens), dims, stored)
    for i in range(s["layers"]):
        x = _ref_layer(key, i, x, dims, scalars, stored)
    logits = _ref_logits(
        key, x, jnp.asarray(rows + [0] * pad), jnp.asarray(cols + [0] * pad),
        dims, scalars, stored)
    gaps, margin = (
        np.asarray(a)[:n]
        for a in _gaps(logits, jnp.asarray(served + [0] * pad, jnp.int32)))
    if not np.all(np.isfinite(gaps)):
        raise FloatingPointError("non-finite reference logits")
    out, at = [], 0
    for _, t in sequences:
        out.append((gaps[at: at + len(t)], margin[at: at + len(t)]))
        at += len(t)
    return out


# -- what the timed ticks left in the cache, read back -------------------------
#
# A greedy token cannot show a loss of precision under the resolution of
# the logits' argmax. What the timed programs wrote can: at the window's
# close one live slot's latent rows in every attention block and the
# experts the router chose at each of its positions are copied on the
# device, and after the run they are held against the reference's over
# the same tokens.


def _slot_state(cache, slot):
    pages = jnp.minimum(cache.page_table[slot], cache.num_pages - 1)
    return {
        # (blocks, rows, width): a position's row in every block
        "latent": jnp.stack([
            pool[pages, 0].reshape(-1, pool.shape[-1])
            for pool in cache.latent]),
        # (rows, lanes): a position's row holds the layers' masks in turn
        "routes": cache.routes[pages][:, 0].reshape(
            -1, cache.routes.shape[-1]),
    }


def kv_snapshot_program(engine):
    return jax.jit(_slot_state).lower(engine.cache, np.int32(0)).compile()


def kv_snapshot(engine, program):
    """What one live decoding slot keeps, copied on the device
    (dispatched, not waited for): the slot that has decoded most tokens
    (most of its rows were written one at a time by the decode grid, the
    rest by chunks). None while none decodes."""
    live = [
        (len(st.generated), st.pos, slot)
        for slot, st in enumerate(engine._slots)
        if st is not None and st.generated and not st.prefilling
    ]
    if not live:
        return None
    _, rows, slot = max(live)
    return dict(
        program(engine.cache, np.int32(slot)),
        request_id=engine._slots[slot].req.request_id, rows=int(rows))


def _rel(got, ref):
    d = got.astype(jnp.float32) - ref
    return jnp.sqrt(jnp.sum(d * d) / jnp.sum(ref * ref))


def _masks(ids, words):
    bit = jnp.left_shift(jnp.uint32(1), (ids % 32).astype(jnp.uint32))
    return jnp.stack([
        jnp.sum(jnp.where(ids // 32 == w, bit, jnp.uint32(0)), axis=1)
        for w in range(words)], axis=1)  # (T, words)


@functools.partial(jax.jit, static_argnames=("rank", "rope", "lowered"))
def _kept_gaps(kept, latent, routes, rows, rank, rope, lowered=None):
    """How far what the slot kept lies from the reference's, for one
    layer: per block the relative norm of the difference of the latent
    rows and of the rotary keys, and the positions whose chosen experts
    differ. With ``lowered`` the reference's own rows rounded to that
    type stand in the slot's place."""
    ids = kept["ids"][0]  # (T, k)
    t = ids.shape[0]
    live = jnp.arange(t) < rows
    differ = jnp.any(_masks(ids, routes.shape[1]) != routes[:t], axis=1) & live
    out = {
        "routing_differs": jnp.sum(differ),
        "margin_where_differs": jnp.max(
            jnp.where(differ, kept["margin"][0], 0.0)),
    }
    keep = live[:, None]
    for j in range(BLOCKS):
        c = jnp.where(keep, kept[f"c{j}"][0], 0.0)
        k_r = jnp.where(keep, kept[f"k_r{j}"][0], 0.0)
        if lowered is None:
            got = jnp.where(keep, latent[j][:t].astype(jnp.float32), 0.0)
            got_c, got_r = got[:, :rank], got[:, rank:rank + rope]
        else:
            # `reduce_precision`: inside one fusion the chip keeps a
            # cast there and back at full precision (it read 0.0 so)
            fi = jnp.finfo(lowered)
            got_c = jax.lax.reduce_precision(c, fi.nexp, fi.nmant)
            got_r = jax.lax.reduce_precision(k_r, fi.nexp, fi.nmant)
        out[f"latent{j}"] = _rel(got_c, c)
        out[f"rope{j}"] = _rel(got_r, k_r)
    return out


def reference_latent_gaps(config, seed, tokens, snapshot, stored=jnp.bfloat16,
                          lowered=None):
    """The reference's forward over ``tokens`` (prompt then served
    tokens, at least ``snapshot['rows']``), a layer at a time, and how
    far what the slot kept lies from it: ``latent`` and ``rope`` by
    attention block (two a layer, in order), ``routing_differs`` and
    ``margin_where_differs`` by layer."""
    s, dims, scalars = _static(config)
    key = weights.seed_key(seed)
    rows = int(snapshot["rows"])
    width = min(_width(rows), snapshot["latent"].shape[1])
    if len(tokens) < rows or rows > width:
        raise ValueError("the snapshot holds more rows than there are tokens")
    padded = np.zeros((1, width), np.int32)
    padded[0, :rows] = tokens[:rows]
    x = _ref_embed(key, jnp.asarray(padded), dims, stored)
    words = -(-(s["experts"] + s["zero"]) // 32)
    out = {k: [] for k in (
        "latent", "rope", "routing_differs", "margin_where_differs")}
    for i in range(s["layers"]):
        x, kept = _ref_layer(key, i, x, dims, scalars, stored, full=True)
        gaps = jax.device_get(_kept_gaps(
            kept, snapshot["latent"][BLOCKS * i: BLOCKS * (i + 1)],
            snapshot["routes"][:, i * words:(i + 1) * words],
            jnp.int32(rows), s["kv_rank"], s["rope"], lowered=lowered))
        for j in range(BLOCKS):
            out["latent"].append(float(gaps[f"latent{j}"]))
            out["rope"].append(float(gaps[f"rope{j}"]))
        out["routing_differs"].append(int(gaps["routing_differs"]))
        out["margin_where_differs"].append(float(gaps["margin_where_differs"]))
    return out
