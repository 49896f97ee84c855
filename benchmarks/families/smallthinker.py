"""Family `smallthinker`: SmallThinker decoders (`SmallThinker*`): layers
of grouped-head attention that are either GLOBAL (every earlier key, no
positional encoding) or WINDOW (rotary positions, the `sliding_window`
keys that end at a row's own), each followed by routed ReGLU experts
whose router reads the layer's input from BEFORE the attention; RMSNorm;
an untied head.

Two halves that share nothing but the seeded weight generator
(`harness/weights.py`) and the names and shapes of the tensors:

* the system under test, built from the library's pieces
  (`WindowedModel`, `InferenceEngine`);
* the plain reference: the published equations in `jax.numpy`, float32,
  matmul precision `highest`: full sequences, a materialised (causal AND
  window) mask, a block of queries at a time, experts by a loop over all
  of them. It imports nothing of `rocm_apex_tpu`.

One layer, on rows ``x`` (PERF.md section 4 has it in full):

    n = RMSNorm_1(x)
    q, k, v = n W_q, n W_k, n W_v            (no bias)
    window layer: q, k <- rotary(q, k)       (pairs by halves)
    h = x + softmax(mask(q k^T / sqrt(d))) v W_o
    u = RMSNorm_2(h)
    g = n W_router; the k largest; w = softmax over those
    y = h + sum_chosen w_e (relu(u W_gate_e) * (u W_up_e)) W_down_e

Departures from the published description, each also under `assumed` in
the configuration file: the config gives switches and sizes, not
formulas, so the router's input (RMSNorm_1's output), the rotary pairing
(halves), a window that counts the row's own key, no bias and no q/k
norm follow the model's public code as the issue's writer knew it; the
weights are random from the seed.

What a request keeps, as the program stores it and `kv_snapshot` copies
it: per layer and position K (rotated in a window layer) and V, a global
layer's through the page table, a window layer's through the window
group's table, where the pages behind the window are gone; per layer and
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


def layer_types(config):
    """``global`` / ``window`` per layer, from the two published layouts,
    which must agree: a layer with a window is a layer with rotary
    positions."""
    window = [int(x) for x in config["sliding_window_layout"]]
    rope = [int(x) for x in config["rope_layout"]]
    if window != rope:
        raise ValueError(
            "only layers whose window and rotary switches agree are built")
    if len(window) != int(config["num_hidden_layers"]):
        raise ValueError("the layouts do not match num_hidden_layers")
    return tuple("window" if x else "global" for x in window)


def sizes(config):
    """The sizes of a configuration file under the names the benchmark
    uses, and this family's own."""
    experts = int(config["moe_num_primary_experts"])
    lo, hi = (int(x) for x in config.get("experts_held", (0, experts)))
    if not config["moe_primary_router_apply_softmax"]:
        raise ValueError("only the softmax over the chosen is built")
    if config.get("rope_scaling") is not None:
        raise ValueError("no long-context rotary scaling is built")
    if config["tie_word_embeddings"]:
        raise ValueError("only the untied head is built")
    return {
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "vocab": int(config["vocab_size"]),
        "positions": int(config["max_position_embeddings"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "window": int(config["sliding_window_size"]),
        "experts": experts,
        "held_lo": lo, "held_hi": hi,
        "top_k": int(config["moe_num_active_primary_experts"]),
        "expert_width": int(config["moe_ffn_hidden_size"]),
    }


def _scalars(config):
    return float(config["rope_theta"]), float(config["rms_norm_eps"])


def layer_params_count(s):
    """Parameters of one layer: the four projections, two norms, the
    router, the held experts' three matrices."""
    h = s["hidden"]
    g = s["held_hi"] - s["held_lo"]
    attention = h * (s["heads"] + 2 * s["kv_heads"]) * s["head_dim"] + (
        s["heads"] * s["head_dim"] * h)
    return attention + 2 * h + h * s["experts"] + g * 3 * h * s["expert_width"]


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
    std 0.02; norm weights 1 + 0.02 N so that a dropped one shows."""
    h, hd = s["hidden"], s["head_dim"]
    g = s["held_hi"] - s["held_lo"]

    def leaf(name, shape, kind_="normal"):
        return weights.leaf(
            key, "layer/" + name, shape, kind_, INIT_STD, dtype, layer=layer)

    return {
        "norm1/weight": leaf("norm1", (h,), "ln_weight"),
        "norm2/weight": leaf("norm2", (h,), "ln_weight"),
        "self_attention/qkv": leaf(
            "qkv", (h, (s["heads"] + 2 * s["kv_heads"]) * hd)),
        "self_attention/o_proj": leaf("o_proj", (s["heads"] * hd, h)),
        "moe/router": leaf("router", (h, s["experts"])),
        "moe/w_in": leaf("w_in", (g, h, 2 * s["expert_width"])),
        "moe/w_out": leaf("w_out", (g, s["expert_width"], h)),
    }


def outer_weights(key, s, dtype):
    """The table, the untied head and the final norm."""
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
    from rocm_apex_tpu.models.windowed import WindowedConfig

    s = sizes(config)
    theta, eps = _scalars(config)
    fields = dict(
        vocab_size=s["vocab"], hidden_size=s["hidden"],
        layer_types=layer_types(config), sliding_window=s["window"],
        rope_theta=theta, num_attention_heads=s["heads"],
        num_key_value_heads=s["kv_heads"], head_dim=s["head_dim"],
        num_experts=s["experts"], experts_held=(s["held_lo"], s["held_hi"]),
        num_experts_per_tok=s["top_k"], expert_width=s["expert_width"],
        rms_norm_eps=eps, max_position_embeddings=s["positions"],
        init_std=INIT_STD,
    )
    fields.update(overrides)
    return WindowedConfig(**fields)


# the control's K and V: the nearest stored precision below bfloat16
# (`reference_kept_gaps` with ``lowered``)
CONTROL_KV = jnp.float8_e4m3fn


def build_engine(config, mix, params):
    """`InferenceEngine` as a deployment builds it for this mix: a paged
    K/V cache in two groups (global, window), chunked prefill, greedy,
    weights and K/V in bfloat16."""
    from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
    from rocm_apex_tpu.models.windowed import WindowedModel

    e = mix["engine"]
    cfg = model_config(
        config, params_dtype=jnp.bfloat16, dtype=jnp.bfloat16,
        # the program's debugging log of each position's chosen experts,
        # which the comparison of the routing reads back
        log_routes=True,
    )
    return InferenceEngine(
        WindowedModel(cfg), params,
        num_slots=int(e["num_slots"]),
        capacity=int(e["capacity"]),
        sampling=SamplingParams(temperature=0.0),
        seed=0,
        prefill_token_budget=int(e["prefill_token_budget"]),
        paged=True,
        page_size=int(e["page_size"]),
        num_pages=(int(e["num_pages"]), int(e["window_pages"])),
        retrace_policy="raise",
        stats_retention=1 << 16,
    )


def serve_setup(config, mix, seed, control=False):
    """The engine is the same under the control: what stands in another
    precision there is the reference (`kinds/serve_open_loop_windowed.py`)."""
    # a tree without the model fails here, at once, before any weight
    import rocm_apex_tpu.models.windowed  # noqa: F401

    return build_engine(config, mix, make_params(config, seed, jnp.bfloat16))


def reseed(engine, config, seed):
    engine.params = None
    engine.params = make_params(config, seed, jnp.bfloat16)


# -- the plain reference ------------------------------------------------------

Q_BLOCK = 512  # queries a block of the materialised scores


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, theta, offset=0):
    """(b, T, heads, d) at positions ``offset..offset + T``: pairs by
    halves (x[i], x[i + d/2]) turned by pos * theta ** (-2i / d)."""
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (offset + jnp.arange(t, dtype=jnp.float32))[:, None] * inv[None, :]
    ang = ang.reshape(1, t, 1, d // 2)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [a * jnp.cos(ang) - b * jnp.sin(ang),
         a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def reference_attention(n, w, s, kind, theta, window=None, rope=None):
    """The attention of a ``kind`` layer on (b, T, hidden): its output
    and what a position caches, K (rotated in a window layer) and V.
    ``window`` and ``rope`` stand in the layer's own (the tests' wrong
    structures)."""
    b, t, _ = n.shape
    nq, nkv, hd = s["heads"], s["kv_heads"], s["head_dim"]
    windowed = kind == "window"
    window = (s["window"] if windowed else None) if window is None else window
    rope = windowed if rope is None else rope
    qkv = n @ w["self_attention/qkv"]
    q = qkv[..., :nq * hd].reshape(b, t, nq, hd)
    k = qkv[..., nq * hd:(nq + nkv) * hd].reshape(b, t, nkv, hd)
    v = qkv[..., (nq + nkv) * hd:].reshape(b, t, nkv, hd)
    if rope:
        q, k = _rotate(q, theta), _rotate(k, theta)
    scale = 1.0 / math.sqrt(hd)
    block = min(Q_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are no whole blocks of {block}")
    col = jnp.arange(t)
    group = nq // nkv

    def one(seq):  # a sequence at a time, a block of queries at a time
        q_i, k_i, v_i = seq

        def rows(start):
            qb = jax.lax.dynamic_slice_in_dim(q_i, start, block, 0)
            qb = qb.reshape(block, nkv, group, hd)  # head h reads h // group
            scores = scale * jnp.einsum("qngd,knd->ngqk", qb, k_i)
            row = (start + jnp.arange(block))[:, None]
            mask = col[None, :] <= row
            if window:
                mask &= row - col[None, :] < window
            probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), -1)
            return jnp.einsum("ngqk,knd->qngd", probs, v_i)

        ctx = jax.lax.map(rows, jnp.arange(0, t, block))
        return ctx.reshape(t, nq * hd)

    ctx = jax.lax.map(one, (q, k, v))
    return ctx @ w["self_attention/o_proj"], k, v


def reference_experts(u, r, w, s):
    """The held experts' part of the layer on ``u``, routed by ``r``: the
    k largest router logits, the softmax over those, ReGLU experts; the
    experts each token chose and the margin between its k-th and
    (k+1)-th logit."""
    f, k = s["expert_width"], s["top_k"]
    top, ids = jax.lax.top_k(r @ w["moe/router"], k + 1)
    margin = top[..., k - 1] - top[..., k]
    ids = ids[..., :k]
    gates = jax.nn.softmax(top[..., :k], axis=-1)

    def expert(acc, ew):
        w_in, w_out, e = ew
        gate = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)
        ab = u @ w_in
        y = (jax.nn.relu(ab[..., :f]) * ab[..., f:]) @ w_out
        return acc + gate[..., None] * y, None

    held = jnp.arange(s["held_lo"], s["held_hi"])
    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(u), (w["moe/w_in"], w["moe/w_out"], held))
    return out, ids, margin


def reference_layer(x, w, s, kind, scalars, window=None, rope=None):
    """One layer on (b, T, hidden) float32. Returns the output and the
    layer's own record: the cached K and V, and the routing."""
    theta, eps = scalars
    n = _rms(x, w["norm1/weight"], eps)
    a, k, v = reference_attention(n, w, s, kind, theta, window, rope)
    h = x + a
    u = _rms(h, w["norm2/weight"], eps)
    y, ids, margin = reference_experts(u, n, w, s)
    return h + y, dict(k=k, v=v, ids=ids, margin=margin)


@functools.partial(jax.jit, static_argnames=("dims", "stored"))
def _ref_embed(key, tokens, dims, stored):
    o = outer_weights(key, dict(dims), stored)
    return o["embedding"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=(
    "dims", "kind", "scalars", "stored", "full", "window", "rope"))
def _ref_layer(key, layer, x, dims, kind, scalars, stored, full=False,
               window=None, rope=None):
    s = dict(dims)
    with jax.default_matmul_precision("highest"):
        w = {
            k: v.astype(jnp.float32)
            for k, v in layer_weights(key, s, layer, stored).items()}
        x, kept = reference_layer(x, w, s, kind, scalars, window, rope)
    return (x, kept) if full else x


@functools.partial(jax.jit, static_argnames=("dims", "scalars", "stored"))
def _ref_logits(key, x, rows, cols, dims, scalars, stored):
    with jax.default_matmul_precision("highest"):
        o = {
            k: v.astype(jnp.float32)
            for k, v in outer_weights(key, dict(dims), stored).items()}
        hid = _rms(x[rows, cols], o["final_norm/weight"], scalars[1])
        return hid @ o["lm_head"]


@jax.jit
def _gaps(logits, picked):
    top2, _ = jax.lax.top_k(logits, 2)
    got = jnp.take_along_axis(logits, picked[:, None], axis=-1)[:, 0]
    return top2[:, 0] - got, top2[:, 0] - top2[:, 1]


ROWS_PAD = 1024  # served positions are padded to a multiple: fixed shapes
# and sequences to a multiple: four compiled widths up to the context's
# 16,384, whatever a seed's sample holds (a new width compiles both kinds
# of layer again, a quarter of a minute each on the chip)
WIDTH_PAD = 4096


def _static(config):
    s = sizes(config)
    return s, tuple(sorted(s.items())), _scalars(config), layer_types(config)


def _width(n):
    return -(-n // WIDTH_PAD) * WIDTH_PAD if n > Q_BLOCK else n


def reference_logits(config, seed, tokens, stored=jnp.bfloat16, wrong=None):
    """Float32 logits of the reference at every position of ``tokens``
    ((b, T) ids): the tests' full forward pass. ``wrong`` (the tests'
    wrong structures): ``dict(window=<keys>)`` gives the window layers
    another window, ``dict(rope_global=True)`` turns the global layers'
    queries and keys too."""
    s, dims, scalars, kinds = _static(config)
    wrong = wrong or {}
    key = weights.seed_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    x = _ref_embed(key, tokens, dims, stored)
    for i, kind in enumerate(kinds):
        x = _ref_layer(
            key, i, x, dims, kind, scalars, stored,
            window=wrong.get("window") if kind == "window" else None,
            rope=True if wrong.get("rope_global") else None)
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
    s, dims, scalars, kinds = _static(config)
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
    for i, kind in enumerate(kinds):
        x = _ref_layer(key, i, x, dims, kind, scalars, stored)
    rows, cols, served = (
        np.asarray(a + [0] * pad, np.int32) for a in (rows, cols, served))
    # the head a block of rows at a time: 151,936 float32 logits a row
    gaps, margin = [], []
    for a in range(0, n + pad, ROWS_PAD):
        logits = _ref_logits(
            key, x, jnp.asarray(rows[a: a + ROWS_PAD]),
            jnp.asarray(cols[a: a + ROWS_PAD]), dims, scalars, stored)
        g, m = _gaps(logits, jnp.asarray(served[a: a + ROWS_PAD]))
        gaps.append(np.asarray(g))
        margin.append(np.asarray(m))
    gaps, margin = np.concatenate(gaps)[:n], np.concatenate(margin)[:n]
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
# close one live slot's K and V in every layer (a window layer's as far
# as its pages are still mapped) and the experts the router chose at each
# of its positions are copied on the device, and after the run they are
# held against the reference's over the same tokens.


def _slot_state(cache, slot):
    def through(table, pools_k, pools_v):
        pages = jnp.minimum(table[slot], pools_k[0].shape[0] - 1)

        def rows(pool):  # (pages, heads, ps, hd) -> (positions, heads, hd)
            g = pool[pages].transpose(0, 2, 1, 3)
            return g.reshape(-1, g.shape[2], g.shape[3])

        return (jnp.stack([rows(p) for p in pools_k]),
                jnp.stack([rows(p) for p in pools_v]))

    k, v = through(cache.page_table, cache.k, cache.v)
    wk, wv = through(cache.window_table, cache.window_k, cache.window_v)
    pages = jnp.minimum(cache.page_table[slot], cache.num_pages - 1)
    return {
        # (layers of the group, positions, heads, head_dim)
        "k": k, "v": v, "window_k": wk, "window_v": wv,
        # (positions, lanes): a position's row holds the layers' masks
        "routes": cache.routes[pages][:, 0].reshape(
            -1, cache.routes.shape[-1]),
    }


def kv_snapshot_program(engine):
    return jax.jit(_slot_state).lower(engine.cache, np.int32(0)).compile()


def kv_snapshot(engine, program):
    """What one live decoding slot keeps, copied on the device
    (dispatched, not waited for): the slot that holds most positions
    among those that decode (it has decoded furthest past its window).
    ``first_live`` is the first position whose window-group page is
    still mapped. None while none decodes."""
    live = [
        (st.pos, slot) for slot, st in enumerate(engine._slots)
        if st is not None and st.generated and not st.prefilling
    ]
    if not live:
        return None
    rows, slot = max(live)
    st = engine._slots[slot]
    return dict(
        program(engine.cache, np.int32(slot)),
        request_id=st.req.request_id, rows=int(rows),
        first_live=int(st.window_head * engine.cache.page_size))


def _rel(got, ref):
    d = got.astype(jnp.float32) - ref
    return jnp.sqrt(jnp.sum(d * d) / jnp.sum(ref * ref))


def _masks(ids, words):
    bit = jnp.left_shift(jnp.uint32(1), (ids % 32).astype(jnp.uint32))
    return jnp.stack([
        jnp.sum(jnp.where(ids // 32 == w, bit, jnp.uint32(0)), axis=1)
        for w in range(words)], axis=1)  # (T, words)


@functools.partial(jax.jit, static_argnames=("lowered",))
def _kept_gaps(kept, got_k, got_v, routes, first, rows, clean, lowered=None):
    """How far what the slot kept lies from the reference's, for one
    layer, over positions ``[first, rows)``: the relative norm of the
    difference of K and of V, over all of them and over the ``clean``
    ones alone (below), and the positions (all of ``[0, rows)``) whose
    chosen experts differ. With ``lowered`` the reference's own rows
    rounded to that type stand in the slot's place."""
    ids = kept["ids"][0]  # (T, k)
    t = ids.shape[0]
    at = jnp.arange(t)
    differ = jnp.any(_masks(ids, routes.shape[1]) != routes[:t], axis=1) & (
        at < rows)
    out = {
        "differ": differ,
        "routing_differs": jnp.sum(differ),
        "margin_where_differs": jnp.max(
            jnp.where(differ, kept["margin"][0], 0.0)),
    }
    live = (at >= first) & (at < rows)
    for name, got in (("k", got_k), ("v", got_v)):
        if lowered is None:
            got = got[:t].astype(jnp.float32)
        else:
            # `reduce_precision`: inside one fusion the chip keeps a
            # cast there and back at full precision (PR 30 read 0.0 so)
            fi = jnp.finfo(lowered)
            got = jax.lax.reduce_precision(kept[name][0], fi.nexp, fi.nmant)
        for tag, keep in ((name, live), (name + "_clean", live & clean)):
            keep = keep[:, None, None]
            out[tag] = _rel(
                jnp.where(keep, got, 0.0), jnp.where(keep, kept[name][0], 0.0))
    return out


def reference_kept_gaps(config, seed, tokens, snapshot, stored=jnp.bfloat16,
                        lowered=None):
    """The reference's forward over ``tokens`` (prompt then served
    tokens, at least ``snapshot['rows']``), a layer at a time, and how
    far what the slot kept lies from it, by layer: ``k`` and ``v`` (a
    window layer's over the positions whose pages are live, from
    ``snapshot['first_live']`` on), ``routing_differs`` and
    ``margin_where_differs``; and ``k_clean``, ``v_clean``: the same
    over the positions at which NO earlier layer's router chose another
    set of experts than the reference's. A layer's K and V at a position
    are made from that position's residual alone, and past layer 0 it
    holds a whole expert's output wherever an earlier choice differed;
    where none did, what is left is precision (and the attention's
    reading of other positions' drift)."""
    s, dims, scalars, kinds = _static(config)
    key = weights.seed_key(seed)
    rows = int(snapshot["rows"])
    width = min(_width(rows), snapshot["k"].shape[1])
    if len(tokens) < rows or rows > width:
        raise ValueError("the snapshot holds more rows than there are tokens")
    padded = np.zeros((1, width), np.int32)
    padded[0, :rows] = tokens[:rows]
    x = _ref_embed(key, jnp.asarray(padded), dims, stored)
    words = -(-s["experts"] // 32)
    out = {k: [] for k in (
        "k", "v", "k_clean", "v_clean", "routing_differs",
        "margin_where_differs")}
    behind = everywhere = 0
    clean = jnp.ones((width,), bool)
    for i, kind in enumerate(kinds):
        x, kept = _ref_layer(key, i, x, dims, kind, scalars, stored, full=True)
        if kind == "window":
            got = snapshot["window_k"][behind], snapshot["window_v"][behind]
            first, behind = int(snapshot["first_live"]), behind + 1
        else:
            got = snapshot["k"][everywhere], snapshot["v"][everywhere]
            first, everywhere = 0, everywhere + 1
        gaps = _kept_gaps(
            kept, *got, snapshot["routes"][:, i * words:(i + 1) * words],
            jnp.int32(first), jnp.int32(rows), clean, lowered=lowered)
        clean = clean & ~gaps.pop("differ")
        gaps = jax.device_get(gaps)
        for name in ("k", "v", "k_clean", "v_clean"):
            out[name].append(float(gaps[name]))
        out["routing_differs"].append(int(gaps["routing_differs"]))
        out["margin_where_differs"].append(float(gaps["margin_where_differs"]))
    return out
