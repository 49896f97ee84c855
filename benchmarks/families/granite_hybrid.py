"""Family `granite_hybrid`: Granite-4.0-H decoders (`GraniteMoeHybrid*`):
a pattern of Mamba-2 and attention mixers, each layer followed by routed
experts plus one shared expert; RMSNorm; no positional encoding; four
scalar multipliers; a tied head.

Two halves that share nothing but the seeded weight generator
(`harness/weights.py`) and the names and shapes of the tensors:

* the system under test, built from the library's pieces (`HybridModel`,
  `InferenceEngine`);
* the plain reference: the same mathematics in `jax.numpy`, float32,
  matmul precision `highest`; the recurrence as a sequential scan over
  positions, attention with a materialised mask, experts by a loop over
  those held. It imports nothing of `rocm_apex_tpu`.

The chip's share (`model-configs` guide, section 4): the configuration
file says which routed experts are held (`experts_held`, of
`router_experts`); the router scores all of them, a token goes to its
top k, and what the experts held elsewhere would add is left out, by
both halves alike.

Layout of what a request keeps, as the program stores it and as
`kv_snapshot` copies it: K and V of the attention layers through the
page table; per Mamba layer the state (state dim, heads * head dim) and
the last `d_conv - 1` rows that entered the convolution; per layer and
position the mask of experts the router chose.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import weights

INIT_STD = 0.02


# -- sizes: this family's own ------------------------------------------------


def sizes(config):
    """The sizes of a configuration file under the names the benchmark
    uses, and this family's own."""
    heads = int(config["mamba_n_heads"])
    p = int(config["mamba_d_head"])
    n = int(config["mamba_d_state"])
    lo, hi = (int(x) for x in config["experts_held"])
    if hi - lo != int(config["num_local_experts"]):
        raise ValueError("experts_held does not hold num_local_experts")
    if heads * p != int(config["mamba_expand"]) * int(config["hidden_size"]):
        raise ValueError("mamba heads x head size != expand x hidden")
    return {
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "vocab": int(config["vocab_size"]),
        "positions": int(config["max_position_embeddings"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["hidden_size"]) // int(config["num_attention_heads"]),
        "m_heads": heads, "m_p": p, "m_n": n,
        "m_conv": int(config["mamba_d_conv"]),
        "m_inner": heads * p,
        "m_convdim": heads * p + 2 * n * int(config["mamba_n_groups"]),
        "experts": int(config["router_experts"]),
        "held_lo": lo, "held_hi": hi,
        "top_k": int(config["num_experts_per_tok"]),
        "expert_width": int(config["intermediate_size"]),
        "shared_width": int(config["shared_intermediate_size"]),
        "emb_mult": float(config["embedding_multiplier"]),
    }


def layer_types(config):
    kinds = tuple(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types does not match num_hidden_layers")
    return kinds


def _scalars(config):
    return (
        float(config["embedding_multiplier"]),
        float(config["residual_multiplier"]),
        float(config["attention_multiplier"]),
        float(config["logits_scaling"]),
        float(config["rms_norm_eps"]),
    )


def layer_params_count(s, kind):
    """Parameters of one layer as held here."""
    h = s["hidden"]
    g = s["held_hi"] - s["held_lo"]
    moe = (
        h * s["experts"] + g * 3 * h * s["expert_width"]
        + 3 * h * s["shared_width"]
    )
    if kind == "mamba":
        mixer = (
            h * (s["m_inner"] + s["m_convdim"] + s["m_heads"])
            + s["m_inner"] * h + (s["m_conv"] + 1) * s["m_convdim"]
            + 3 * s["m_heads"] + s["m_inner"]
        )
    else:
        mixer = h * (s["heads"] + 2 * s["kv_heads"]) * s["head_dim"] + (
            s["heads"] * s["head_dim"] * h)
    return mixer + moe + 2 * h


def total_params(config):
    s = sizes(config)
    return (
        sum(layer_params_count(s, k) for k in layer_types(config))
        + s["vocab"] * s["hidden"] + s["hidden"]
    )


# -- weights from the seed ---------------------------------------------------


def _inv_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


def layer_weights(key, s, layer, kind, dtype):
    """The tensors of layer ``layer`` (may be traced) by their names in
    the program's parameter tree under ``layer_<i>/``. Every matrix
    starts at std 0.02, the source's `_init_weights` (no scaling by
    depth: the layers' outputs, not the token's own embedding, then carry
    the residual stream, as in a trained model, and a served sequence is
    not one token repeated). ``a_log`` puts A in (1, 16) and ``dt_bias``
    the step in (0.001, 0.1), Mamba-2's own ranges, drawn from the seed
    head by head."""
    h = s["hidden"]
    g = s["held_hi"] - s["held_lo"]

    def leaf(name, shape, kind_="normal", std=INIT_STD, dt=dtype):
        return weights.leaf(
            key, "layer/" + name, shape, kind_, std, dt, layer=layer)

    w = {
        "norm1/weight": leaf("norm1", (h,), "ln_weight"),
        "norm2/weight": leaf("norm2", (h,), "ln_weight"),
        "moe/router": leaf("router", (h, s["experts"])),
        "moe/w_in": leaf("w_in", (g, h, 2 * s["expert_width"])),
        "moe/w_out": leaf("w_out", (g, s["expert_width"], h)),
        "moe/shared_in": leaf("shared_in", (h, 2 * s["shared_width"])),
        "moe/shared_out": leaf("shared_out", (s["shared_width"], h)),
    }
    if kind == "mamba":
        heads, di, cd = s["m_heads"], s["m_inner"], s["m_convdim"]
        u = jax.nn.sigmoid(leaf("a_u", (heads,), std=1.0, dt=jnp.float32))
        v = jax.nn.sigmoid(leaf("dt_u", (heads,), std=1.0, dt=jnp.float32))
        dt0 = jnp.exp(math.log(1e-3) + v * (math.log(1e-1) - math.log(1e-3)))
        w.update({
            "mamba/in_proj": leaf("in_proj", (h, di + cd + heads)),
            "mamba/conv_w": leaf("conv_w", (s["m_conv"], cd), std=0.3),
            "mamba/conv_b": leaf("conv_b", (cd,), "small"),
            "mamba/a_log": jnp.log(1.0 + 15.0 * u).astype(dtype),
            "mamba/dt_bias": _inv_softplus(dt0).astype(dtype),
            "mamba/d": leaf("d", (heads,), "ln_weight"),
            "mamba/norm_w": leaf("norm_w", (di,), "ln_weight"),
            "mamba/out_proj": leaf("out_proj", (di, h)),
        })
    else:
        nq, nkv, hd = s["heads"], s["kv_heads"], s["head_dim"]
        w.update({
            "self_attention/qkv": jnp.concatenate([
                leaf("q_proj", (h, nq * hd)), leaf("k_proj", (h, nkv * hd)),
                leaf("v_proj", (h, nkv * hd)),
            ], axis=1),
            "self_attention/o_proj": leaf("o_proj", (nq * hd, h)),
        })
    return w


def outer_weights(key, s, dtype):
    """The tied table at std 0.02 / embedding_multiplier, so that the
    MULTIPLIED embedding enters the residual stream at the std every
    other matrix has; and the final norm."""
    return {
        "embedding": weights.leaf(
            key, "embedding", (s["vocab"], s["hidden"]), "normal",
            INIT_STD / s["emb_mult"], dtype),
        "final_norm/weight": weights.leaf(
            key, "final_norm", (s["hidden"],), "ln_weight", INIT_STD, dtype),
    }


def make_params(config, seed, dtype):
    """The program's parameters, made on the device a layer at a time
    (one compiled maker per kind of layer): a layer's float32 draws are
    gone before the next layer's are made."""
    s = sizes(config)
    dims = tuple(sorted(s.items()))
    key = weights.seed_key(seed)

    @functools.partial(jax.jit, static_argnames=("kind",))
    def layer(key, i, kind):
        return weights.nest(layer_weights(key, dict(dims), i, kind, dtype))

    tree = jax.jit(lambda k: weights.nest(outer_weights(k, s, dtype)))(key)
    for i, kind in enumerate(layer_types(config)):
        tree[f"layer_{i}"] = layer(key, jnp.int32(i), kind)
    return {"params": tree}


# -- the system under test ----------------------------------------------------


def model_config(config, **overrides):
    from rocm_apex_tpu.models.hybrid import HybridConfig

    s = sizes(config)
    emb, res, att, logit, eps = _scalars(config)
    fields = dict(
        vocab_size=s["vocab"], hidden_size=s["hidden"],
        layer_types=layer_types(config),
        num_attention_heads=s["heads"], num_key_value_heads=s["kv_heads"],
        head_dim=s["head_dim"],
        mamba_n_heads=s["m_heads"], mamba_d_head=s["m_p"],
        mamba_d_state=s["m_n"], mamba_d_conv=s["m_conv"],
        num_experts=s["experts"], experts_held=(s["held_lo"], s["held_hi"]),
        num_experts_per_tok=s["top_k"], expert_width=s["expert_width"],
        shared_width=s["shared_width"],
        embedding_multiplier=emb, residual_multiplier=res,
        attention_multiplier=att, logits_scaling=logit, rms_norm_eps=eps,
        max_position_embeddings=s["positions"],
        init_std=INIT_STD,
    )
    fields.update(overrides)
    return HybridConfig(**fields)


def build_engine(config, mix, params, control=False):
    """`InferenceEngine` as a deployment builds it for this mix: paged
    K/V for the attention layers, the recurrent state beside it, chunked
    prefill, greedy, weights and K/V in bfloat16, the state in float32.
    The control stores the state in bfloat16: the nearest precision
    below the one the configuration states."""
    from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
    from rocm_apex_tpu.models.hybrid import HybridModel

    e = mix["engine"]
    cfg = model_config(
        config, params_dtype=jnp.bfloat16, dtype=jnp.bfloat16,
        state_dtype=jnp.bfloat16 if control else jnp.float32,
        # the program's debugging log of each position's chosen experts,
        # which the comparison of the routing reads back
        log_routes=True,
    )
    return InferenceEngine(
        HybridModel(cfg), params,
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
    return build_engine(
        config, mix, make_params(config, seed, jnp.bfloat16), control)


def reseed(engine, config, seed):
    engine.params = None
    engine.params = make_params(config, seed, jnp.bfloat16)


# -- the plain reference ------------------------------------------------------


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def reference_mamba(x, w, s, eps, rows):
    """(b, T, hidden) -> the mixer's output, and what a sequence cut
    after ``rows`` ((b,)) positions carries: the state (b, heads, p, n)
    and the last rows that entered the convolution."""
    b, t, _ = x.shape
    heads, p, n = s["m_heads"], s["m_p"], s["m_n"]
    di, cd, kw = s["m_inner"], s["m_convdim"], s["m_conv"]
    zxd = x @ w["mamba/in_proj"]
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + cd], zxd[..., di + cd:]
    padded = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
    conv = w["mamba/conv_b"] + sum(
        w["mamba/conv_w"][j] * padded[:, j:j + t] for j in range(kw))
    conv = _silu(conv)
    xs = conv[..., :di].reshape(b, t, heads, p)
    bm, cm = conv[..., di:di + n], conv[..., di + n:]
    dt = jax.nn.softplus(dt + w["mamba/dt_bias"])
    a = -jnp.exp(w["mamba/a_log"])

    def step(state, inp):
        x_t, dt_t, b_t, c_t, i = inp
        new = (
            jnp.exp(dt_t * a)[:, :, None, None] * state
            + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :]
        )
        y = jnp.einsum("bhpn,bn->bhp", new, c_t) + w["mamba/d"][None, :, None] * x_t
        keep = (i < rows)[:, None, None, None]
        return jnp.where(keep, new, state), y

    swap = lambda v: jnp.swapaxes(v, 0, 1)
    # position by position; unrolled eight to a loop step, which changes
    # the loop's overhead and not the order of the arithmetic
    state, ys = jax.lax.scan(
        step, jnp.zeros((b, heads, p, n), x.dtype),
        (swap(xs), swap(dt), swap(bm), swap(cm), jnp.arange(t)), unroll=8)
    y = swap(ys).reshape(b, t, di) * _silu(z)
    y = _rms(y, w["mamba/norm_w"], eps)
    at = rows[:, None] - (kw - 1) + jnp.arange(kw - 1)[None, :] + (kw - 1)
    tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    return y @ w["mamba/out_proj"], state, tail


def reference_attention(x, w, s, scale):
    b, t, _ = x.shape
    nq, nkv, hd = s["heads"], s["kv_heads"], s["head_dim"]
    qkv = x @ w["self_attention/qkv"]
    q = qkv[..., :nq * hd].reshape(b, t, nq, hd)
    k = qkv[..., nq * hd:(nq + nkv) * hd].reshape(b, t, nkv, hd)
    v = qkv[..., (nq + nkv) * hd:].reshape(b, t, nkv, hd)
    mask = jnp.tril(jnp.ones((t, t), bool))[None]

    def one(qkv_i):  # a sequence at a time: the scores are (heads, t, t)
        q_i, k_i, v_i = qkv_i
        k_r = jnp.repeat(k_i, nq // nkv, axis=1)
        v_r = jnp.repeat(v_i, nq // nkv, axis=1)
        scores = scale * jnp.einsum("qnd,knd->nqk", q_i, k_r)
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return jnp.einsum("nqk,knd->qnd", probs, v_r).reshape(t, nq * hd)

    ctx = jax.lax.map(one, (q, k, v))
    return ctx @ w["self_attention/o_proj"], k, v


def reference_experts(u, w, s):
    """Routed (the held share) plus shared, and the experts each token
    chose with the router's margin between its k-th and (k+1)-th."""
    f, fs, k = s["expert_width"], s["shared_width"], s["top_k"]
    logits = u @ w["moe/router"]
    top, ids = jax.lax.top_k(logits, k + 1)
    margin = top[..., k - 1] - top[..., k]
    gates = jax.nn.softmax(top[..., :k], axis=-1)
    ids = ids[..., :k]

    def expert(acc, ew):
        w_in, w_out, e = ew
        gate = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)
        ab = u @ w_in
        y = (_silu(ab[..., :f]) * ab[..., f:]) @ w_out
        return acc + gate[..., None] * y, None

    held = jnp.arange(s["held_lo"], s["held_hi"])
    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(u), (w["moe/w_in"], w["moe/w_out"], held))
    ab = u @ w["moe/shared_in"]
    shared = (_silu(ab[..., :fs]) * ab[..., fs:]) @ w["moe/shared_out"]
    return routed, shared, ids, margin


def reference_layer(x, w, s, kind, scalars, rows):
    """One layer on (b, T, hidden) float32. Returns the output and the
    layer's own record: K and V or state and tail, and the routing."""
    _, res, att, _, eps = scalars
    u = _rms(x, w["norm1/weight"], eps)
    if kind == "mamba":
        y, state, tail = reference_mamba(u, w, s, eps, rows)
        kept = {"state": state, "tail": tail}
    else:
        y, k, v = reference_attention(u, w, s, att)
        kept = {"k": k, "v": v}
    x = x + res * y
    routed, shared, ids, margin = reference_experts(
        _rms(x, w["norm2/weight"], eps), w, s)
    x = x + res * (routed + shared)
    return x, dict(kept, ids=ids, margin=margin)


def _as_f32(tree):
    return {k: v.astype(jnp.float32) for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("dims", "scalars", "stored"))
def _ref_embed(key, tokens, dims, scalars, stored):
    o = _as_f32(outer_weights(key, dict(dims), stored))
    return scalars[0] * o["embedding"][tokens]


@functools.partial(
    jax.jit,
    static_argnames=("dims", "kind", "scalars", "stored", "full", "compute"))
def _ref_layer(key, layer, x, rows, dims, kind, scalars, stored, full=False,
               compute=jnp.float32):
    """One layer of the reference. ``compute`` is float32; the control
    of the kept-state comparison runs the SAME code with every tensor,
    the carried state among them, in bfloat16."""
    s = dict(dims)
    with jax.default_matmul_precision("highest"):
        w = {
            k: v.astype(compute)
            for k, v in layer_weights(key, s, layer, kind, stored).items()}
        x, kept = reference_layer(x.astype(compute), w, s, kind, scalars, rows)
    return (x, kept) if full else x


@functools.partial(jax.jit, static_argnames=("dims", "scalars", "stored"))
def _ref_logits(key, x, rows, cols, dims, scalars, stored):
    with jax.default_matmul_precision("highest"):
        o = _as_f32(outer_weights(key, dict(dims), stored))
        hid = _rms(x[rows, cols], o["final_norm/weight"], scalars[4])
        return hid @ o["embedding"].T / scalars[3]


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


def reference_logits(config, seed, tokens, stored=jnp.bfloat16):
    """Float32 logits of the reference at every position of ``tokens``
    ((b, T) ids): the tests' full forward pass."""
    s, dims, scalars = _static(config)
    key = weights.seed_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    rows = jnp.full((b,), t, jnp.int32)
    x = _ref_embed(key, tokens, dims, scalars, stored)
    for i, kind in enumerate(layer_types(config)):
        x = _ref_layer(key, i, x, rows, dims, kind, scalars, stored)
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
    longest = max(len(p) + len(t) for p, t in sequences)
    width = -(-longest // WIDTH_PAD) * WIDTH_PAD
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
    x = _ref_embed(key, jnp.asarray(tokens), dims, scalars, stored)
    full = jnp.full((len(sequences),), width, jnp.int32)
    for i, kind in enumerate(layer_types(config)):
        x = _ref_layer(key, i, x, full, dims, kind, scalars, stored)
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
# close one live slot's K/V rows, its recurrent state and convolution
# tail in every Mamba layer, and the experts the router chose at each of
# its positions are copied on the device, and after the run they are held
# against the reference's over the same tokens.


def _slot_state(cache, slot):
    pages = jnp.minimum(cache.page_table[slot], cache.num_pages - 1)

    def kv(pools):
        out = []
        for pool in pools:
            g = jnp.swapaxes(pool[pages], 0, 1)  # (heads, pages, ps, hd)
            out.append(g.reshape(g.shape[0], -1, g.shape[-1]))
        return jnp.stack(out)

    return {
        "k": kv(cache.k), "v": kv(cache.v),
        "state": jnp.stack([a[slot] for a in cache.ssm]),
        "tail": jnp.stack([a[slot] for a in cache.conv]),
        # (rows, lanes): a position's row holds the layers' masks in turn
        "routes": cache.routes[pages][:, 0].reshape(
            -1, cache.routes.shape[-1]),
    }


def kv_snapshot_program(engine):
    return jax.jit(_slot_state).lower(engine.cache, np.int32(0)).compile()


def kv_snapshot(engine, program):
    """What one live decoding slot keeps, copied on the device
    (dispatched, not waited for): the slot that has decoded most tokens
    (a state stored or advanced in a lower precision loses at every
    rewrite of the decode grid what a long prompt's few chunk writes do
    not). None while none decodes."""
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


def _coarse_share(state):
    """The share of a kept state's non-zero elements whose float32 value
    has its low 8 mantissa bits zero, so that 15 bits of mantissa hold
    it: every bfloat16, float16 or tf32 value does. A state kept and
    advanced in float32 reads 2**-8; one that has passed through fewer
    bits anywhere since its last rewrite reads 1."""
    bits = jax.lax.bitcast_convert_type(
        state.astype(jnp.float32), jnp.uint32)
    some = state != 0
    coarse = ((bits & jnp.uint32(0xFF)) == 0) & some
    return jnp.sum(coarse) / jnp.maximum(jnp.sum(some), 1)


@functools.partial(jax.jit, static_argnames=("kind",))
def _kept_gaps(kept, got, rows, kind):
    """How far what the slot kept lies from the reference's, for one
    layer: relative norms, and the positions whose chosen experts
    differ."""
    ids = kept["ids"][0]  # (T, k)
    ref_mask = _masks(ids, got["routes"].shape[1])
    t = ids.shape[0]
    live = jnp.arange(t) < rows
    differ = jnp.any(ref_mask != got["routes"][:t], axis=1) & live
    out = {
        "routing_differs": jnp.sum(differ),
        "margin_where_differs": jnp.max(
            jnp.where(differ, kept["margin"][0], 0.0)),
    }
    if kind == "mamba":
        h, p, n = kept["state"].shape[1:]
        ref_state = kept["state"][0].transpose(2, 0, 1).reshape(n, h * p)
        out["state"] = _rel(got["state"], ref_state)
        out["tail"] = _rel(got["tail"], kept["tail"][0])
        out["coarse"] = _coarse_share(got["state"])
    else:
        keep = live[None, :, None]
        for name in ("k", "v"):
            ref = jnp.where(keep, jnp.swapaxes(kept[name][0], 0, 1), 0.0)
            out[name] = _rel(
                jnp.where(keep, got[name][:, :t], 0.0), ref)
    return out


def _masks(ids, words):
    bit = jnp.left_shift(jnp.uint32(1), (ids % 32).astype(jnp.uint32))
    return jnp.stack([
        jnp.sum(jnp.where(ids // 32 == w, bit, jnp.uint32(0)), axis=1)
        for w in range(words)], axis=1)  # (T, words)


def lowered_snapshot(config, seed, tokens, snapshot, stored=jnp.bfloat16):
    """The control of the kept-state comparison: what a slot would keep
    over ``snapshot['rows']`` of ``tokens`` if every operation and the
    carried state were bfloat16, a precision below the one the
    configuration states (float32 state, statistics, router and scan):
    the reference's own code in bfloat16, in the layout of ``snapshot``."""
    s, dims, scalars = _static(config)
    key = weights.seed_key(seed)
    rows = int(snapshot["rows"])
    cap = snapshot["k"].shape[2]
    width = min(-(-rows // WIDTH_PAD) * WIDTH_PAD, cap)
    padded = np.zeros((1, width), np.int32)
    padded[0, :rows] = tokens[:rows]
    low = jnp.bfloat16
    x = _ref_embed(key, jnp.asarray(padded), dims, scalars, stored).astype(low)
    at = jnp.full((1,), rows, jnp.int32)
    words = -(-s["experts"] // 32)
    out = {"k": [], "v": [], "state": [], "tail": []}
    routes = jnp.zeros(snapshot["routes"].shape, jnp.uint32)
    for i, kind in enumerate(layer_types(config)):
        x, kept = _ref_layer(
            key, i, x, at, dims, kind, scalars, stored, full=True, compute=low)
        routes = routes.at[:width, i * words:(i + 1) * words].set(
            _masks(kept["ids"][0], words))
        if kind == "mamba":
            h, p, n = kept["state"].shape[1:]
            out["state"].append(
                kept["state"][0].transpose(2, 0, 1).reshape(n, h * p))
            out["tail"].append(kept["tail"][0])
        else:
            for name in ("k", "v"):
                rows_kv = jnp.swapaxes(kept[name][0], 0, 1)
                out[name].append(
                    jnp.pad(rows_kv, ((0, 0), (0, cap - width), (0, 0))))
    return dict(
        {k: jnp.stack(v) for k, v in out.items()}, routes=routes,
        rows=rows, request_id=snapshot["request_id"])


def reference_state_gaps(config, seed, tokens, snapshot, stored=jnp.bfloat16):
    """The reference's forward over ``tokens`` (prompt then served
    tokens, at least ``snapshot['rows']``), a layer at a time, and per
    layer how far what the slot kept lies from it. Returns a dict of
    lists by layer (None where a layer keeps no such thing)."""
    s, dims, scalars = _static(config)
    key = weights.seed_key(seed)
    rows = int(snapshot["rows"])
    width = min(-(-rows // WIDTH_PAD) * WIDTH_PAD, snapshot["k"].shape[2])
    if len(tokens) < rows or rows > width:
        raise ValueError("the snapshot holds more rows than there are tokens")
    padded = np.zeros((1, width), np.int32)
    padded[0, :rows] = tokens[:rows]
    x = _ref_embed(key, jnp.asarray(padded), dims, scalars, stored)
    at = jnp.full((1,), rows, jnp.int32)
    out = {k: [] for k in (
        "k", "v", "state", "tail", "coarse", "routing_differs",
        "margin_where_differs")}
    ai = mi = 0
    for i, kind in enumerate(layer_types(config)):
        x, kept = _ref_layer(
            key, i, x, at, dims, kind, scalars, stored, full=True)
        words = -(-s["experts"] // 32)
        got = {"routes": snapshot["routes"][:, i * words:(i + 1) * words]}
        if kind == "mamba":
            got.update(state=snapshot["state"][mi], tail=snapshot["tail"][mi])
            mi += 1
        else:
            got.update(k=snapshot["k"][ai], v=snapshot["v"][ai])
            ai += 1
        gaps = jax.device_get(_kept_gaps(kept, got, at[0], kind))
        for name in out:
            out[name].append(
                float(gaps[name]) if name in gaps else None)
    return out
