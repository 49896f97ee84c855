"""Family `megatron_bert`: Megatron-LM's BERT (pre-LayerNorm blocks, learned
positions and token types, no embedding LayerNorm, tied masked-LM head
over dense + GELU + LayerNorm, pooler and binary head).

The system under test is `BertModel` over `ParallelTransformer` with the
O5 recipe: bf16 compute, fp32 masters in `MixedPrecisionLamb`. The plain
reference is the same mathematics and the same LAMB in `jax.numpy`,
float32, precision `highest`, in blocks of rows; it imports nothing of
`rocm_apex_tpu`. The transformer layer and its seeded weights are the
`gpt2` family's (the program shares `ParallelTransformer` too).

Departures from the published model, computed so by the program and
therefore by both halves: tanh-approximated GELU; no bias on the
vocabulary projection of the LM head. Listed under `assumed` in the
configuration file.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families import gpt2
from benchmarks.harness import counts, weights

INIT_STD = gpt2.INIT_STD
CAUSAL = False


# -- sizes and counts: this family's own -----------------------------------


def sizes(config):
    """The sizes of a BERT configuration file under the names the
    benchmark uses."""
    return {
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": int(config["num_attention_heads"]),
        "ffn": int(config["intermediate_size"]),
        "vocab": int(config["vocab_size"]),
        "positions": int(config["max_position_embeddings"]),
    }


def total_params(config):
    """All parameters as the program builds the model: the layers, the
    final LayerNorm, the tied table, positions and token types, the LM
    head (dense, LayerNorm; no bias on the projection), the pooler and
    the binary head."""
    s = sizes(config)
    h = s["hidden"]
    n = s["layers"] * counts.layer_params(h, s["ffn"]) + 2 * h
    n += s["vocab"] * h + s["positions"] * h
    n += int(config["type_vocab_size"]) * h
    n += (h * h + h) + 2 * h
    n += (h * h + h) + (2 * h + 2)
    return n


def train_flops_per_token(config, seq):
    """The transformer body, the tied vocabulary projection and the LM
    head's dense layer."""
    s = sizes(config)
    h = s["hidden"]
    return (
        counts.body_train_flops_per_token(h, s["ffn"], s["layers"], seq)
        + 6 * s["vocab"] * h + 6 * h * h
    )



def head_weights(key, s, dtype, types):
    h = s["hidden"]

    def leaf(name, shape, kind):
        return weights.leaf(key, "bert/" + name, shape, kind, INIT_STD, dtype)

    return {
        "tokentype_embeddings": leaf("tte", (types, h), "normal"),
        "lm_head/dense/kernel": leaf("lm_dense_k", (h, h), "normal"),
        "lm_head/dense/bias": leaf("lm_dense_b", (h,), "small"),
        "lm_head/layernorm/weight": leaf("lm_ln_w", (h,), "ln_weight"),
        "lm_head/layernorm/bias": leaf("lm_ln_b", (h,), "small"),
        "pooler/kernel": leaf("pool_k", (h, h), "normal"),
        "pooler/bias": leaf("pool_b", (h,), "small"),
        "binary_head/kernel": leaf("bin_k", (h, 2), "normal"),
        "binary_head/bias": leaf("bin_b", (2,), "small"),
    }


def flat_params(key, config, dtype):
    """Every tensor of the model under its path in the program's
    parameter tree (without the leading "params")."""
    s = sizes(config)
    flat = gpt2.body_params(key, s, dtype)
    flat.update(head_weights(key, s, dtype, int(config["type_vocab_size"])))
    return flat


def decays(path):
    """LAMB's weight decay and trust ratio apply to matrices and
    embeddings, not to biases and LayerNorm parameters."""
    return not (path.endswith("bias") or "layernorm" in path.lower())


# -- seeded batches (host) ------------------------------------------------


class BatchMaker:
    """The input path of the loop: a fresh batch each step from a seeded
    host generator. Megatron's `short_seq_prob`: a fixed share of the
    sequences is short (uniform in [2, seq]) and padded, so the padding
    mask is real; every seed sees the same lengths in another order.
    15% of the real positions are masked for the LM loss."""

    def __init__(self, config, mix, seed):
        t = mix["train"]
        self.batch, self.seq = int(t["batch"]), int(t["seq"])
        self.vocab = sizes(config)["vocab"]
        self.types = int(config["type_vocab_size"])
        self.mask_prob = float(t["mask_prob"])
        self.mask_id = int(t["mask_token_id"])
        self.rng = np.random.default_rng(int(seed))
        # one period of lengths: `short_every`-th sequence is short
        every = int(round(1.0 / float(t["short_seq_prob"])))
        period = self.batch * every
        n_short = period // every
        shorts = np.linspace(2, self.seq, n_short + 2)[1:-1].round().astype(int)
        self.lengths = np.concatenate(
            [np.full(period - n_short, self.seq), shorts])
        self.at = len(self.lengths)

    def _next_lengths(self):
        if self.at + self.batch > len(self.lengths):
            self.lengths = self.rng.permutation(self.lengths)
            self.at = 0
        out = self.lengths[self.at: self.at + self.batch]
        self.at += self.batch
        return out

    def make(self):
        b, s, rng = self.batch, self.seq, self.rng
        lengths = self._next_lengths()
        pos = np.arange(s)[None, :]
        keep = pos < lengths[:, None]
        tokens = rng.integers(1, self.vocab, size=(b, s), dtype=np.int32)
        picked = (rng.random((b, s)) < self.mask_prob) & keep
        # at least one masked position in every batch
        picked[0, 0] = True
        labels = np.where(picked, tokens, 0).astype(np.int32)
        inputs = np.where(picked, self.mask_id, tokens)
        inputs = np.where(keep, inputs, 0).astype(np.int32)
        split = (lengths // 2)[:, None]
        types = ((pos >= split) & keep).astype(np.int32) % self.types
        return {
            "tokens": inputs,
            "attention_mask": keep.astype(np.int32),
            "tokentype_ids": types,
            "lm_labels": labels,
            "loss_mask": picked.astype(np.float32),
            "binary_labels": rng.integers(0, 2, size=(b,), dtype=np.int32),
        }


# -- the system under test ------------------------------------------------


def model_config(config, **overrides):
    from rocm_apex_tpu.models.bert import BertConfig

    s = sizes(config)
    fields = dict(
        vocab_size=s["vocab"],
        hidden_size=s["hidden"],
        num_layers=s["layers"],
        num_attention_heads=s["heads"],
        ffn_hidden_size=s["ffn"],
        max_position_embeddings=s["positions"],
        layernorm_epsilon=float(config["layer_norm_eps"]),
        hidden_dropout=float(config["hidden_dropout_prob"]),
        attention_dropout=float(config["attention_probs_dropout_prob"]),
        tensor_parallel_size=1,
        init_method_std=INIT_STD,
        num_token_types=int(config["type_vocab_size"]),
        add_binary_head=True,
    )
    fields.update(overrides)
    return BertConfig(**fields)


class TrainProgram:
    """The compiled step with its state: one object, driven through its
    first steps at set-up and then handed to the window."""

    def __init__(self, config, mix, break_step=False):
        from rocm_apex_tpu.models.bert import BertModel
        from rocm_apex_tpu.optimizers.mixed import MixedPrecisionLamb

        t = mix["train"]
        self.config, self.mix = config, mix
        self.tokens_per_step = int(t["batch"]) * int(t["seq"])
        cfg = model_config(
            config, checkpoint_activations=bool(t["recompute"]))
        model = BertModel(cfg)
        self.paths = sorted(
            jax.eval_shape(
                lambda k: flat_params(k, config, jnp.float32),
                weights.seed_key(0)))
        mask = {"params": weights.nest({p: decays(p) for p in self.paths})}
        o = t["optimizer"]
        opt = MixedPrecisionLamb(
            float(o["lr"]), weight_decay=float(o["weight_decay"]),
            weight_decay_mask=mask, betas=tuple(o["betas"]),
            eps=float(o["eps"]), max_grad_norm=float(o["max_grad_norm"]),
            compute_dtype=jnp.bfloat16,
            moment_dtype=jnp.dtype(o["moment_dtype"]),
            store_model=False,
        )
        self.b3 = 1.0 - float(o["betas"][0])

        def init(key):
            flat = flat_params(key, config, jnp.float32)
            return opt.init({"params": weights.nest(flat)})

        def loss_fn(p, batch):
            losses, binary = model.apply(
                p, batch["tokens"], batch["attention_mask"],
                batch["tokentype_ids"], lm_labels=batch["lm_labels"])
            m = batch["loss_mask"]
            lm = jnp.sum(losses.astype(jnp.float32) * m) / jnp.sum(m)
            logp = jax.nn.log_softmax(binary.astype(jnp.float32))
            sop = -jnp.mean(jnp.take_along_axis(
                logp, batch["binary_labels"][:, None], axis=-1))
            return lm + sop

        def train_step(state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(
                opt.model_params(state), batch)
            state, _ = opt.step_and_probe(state, grads)
            return state, loss

        def norms(tree):
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(
                    _get(tree["params"], p).astype(jnp.float32))))
                for p in self.paths])

        def delta_norms(master, key):
            start = flat_params(key, config, jnp.float32)
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(
                    _get(master["params"], p) - start[p])))
                for p in self.paths])

        self.init_fn, self.step_fn = init, train_step
        self._init = jax.jit(init)
        if break_step:  # the test's broken path: the state never moves
            self.step = jax.jit(lambda st, b: (st, train_step(st, b)[1]))
        else:
            self.step = jax.jit(train_step, donate_argnums=(0,))
        self._norms = jax.jit(norms)
        self._delta_norms = jax.jit(delta_norms)
        self.state = None

    def start(self, seed):
        """The state from the seed, made on the device in one call."""
        self._key = weights.seed_key(seed)
        self.state = self._init(self._key)

    def feed(self, batch):
        """Host batch -> device: the upload the timed loop pays."""
        return jax.device_put(batch)

    def first_gradient_norms(self):
        """Per-leaf norm of the first gradient as the optimizer got it
        (after clipping), from the first moment after one step:
        m1 = (1 - beta1) g."""
        return dict(zip(
            self.paths, np.asarray(self._norms(self.state.m)) / self.b3))

    def change_norms(self):
        """Per-leaf norm of master - initial master (the initial values
        are made again from the seed)."""
        return dict(zip(self.paths, np.asarray(
            self._delta_norms(self.state.master, self._key))))

    def free(self):
        if self.state is not None:
            for leaf in jax.tree_util.tree_leaves(self.state):
                leaf.delete()
        self.state = None


def _get(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return tree


def train_setup(config, mix, break_step=False):
    return TrainProgram(config, mix, break_step=break_step)


# -- the plain reference ----------------------------------------------------


def _stacked_layers(key, s, dtype):
    return jax.vmap(
        lambda i: gpt2.layer_weights(key, s, i, dtype)
    )(jnp.arange(s["layers"]))


def _ref_params(key, config, dtype):
    s = sizes(config)
    p = dict(gpt2.outer_weights(key, s, jnp.float32))
    p.update(head_weights(key, s, jnp.float32, int(config["type_vocab_size"])))
    p = {k: v.astype(dtype) for k, v in p.items()}
    p["layers"] = {
        k: v.astype(dtype)
        for k, v in _stacked_layers(key, s, jnp.float32).items()}
    return p


def _ref_loss_sums(p, block, heads, eps):
    """(sum of masked-LM losses, sum of binary losses) of a block of
    rows, in the parameters' own type."""
    tokens = block["tokens"]
    x = (
        p["embedding/word_embeddings/weight"][tokens]
        + p["embedding/position_embeddings"][jnp.arange(tokens.shape[1])][None]
        + p["tokentype_embeddings"][block["tokentype_ids"]]
    )
    keymask = (block["attention_mask"] > 0)[:, None, None, :]

    @jax.checkpoint
    def layer(x, w):
        return gpt2.reference_layer(x, w, heads, eps, keymask), None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = gpt2._layer_norm(
        x, p["transformer/final_layernorm/weight"],
        p["transformer/final_layernorm/bias"], eps)
    pooled = jnp.tanh(x[:, 0] @ p["pooler/kernel"] + p["pooler/bias"])
    binary = (pooled @ p["binary_head/kernel"] + p["binary_head/bias"])
    h = gpt2._gelu_tanh(x @ p["lm_head/dense/kernel"] + p["lm_head/dense/bias"])
    h = gpt2._layer_norm(
        h, p["lm_head/layernorm/weight"], p["lm_head/layernorm/bias"], eps)
    logits = (h @ p["embedding/word_embeddings/weight"].T).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    got = jnp.take_along_axis(logits, block["lm_labels"][..., None], axis=-1)[..., 0]
    lm = jnp.sum((lse - got) * block["loss_mask"])
    logp = jax.nn.log_softmax(binary.astype(jnp.float32))
    sop = -jnp.sum(jnp.take_along_axis(
        logp, block["binary_labels"][:, None], axis=-1))
    return lm, sop


def _lamb(p, g, m, v, count, o):
    """One LAMB step over a dict of leaves (layer leaves carry a leading
    layer axis and are one tensor per layer), as
    `apex.optimizers.FusedLAMB` defines it: global-norm clip, moments,
    bias correction, decoupled decay, per-tensor trust ratio for decayed
    tensors."""
    b1, b2 = o["betas"]
    eps, lr, wd_all = float(o["eps"]), float(o["lr"]), float(o["weight_decay"])
    t = count + 1.0
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    flat_g = jax.tree_util.tree_leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in flat_g))
    mgn = float(o["max_grad_norm"])
    clip = jnp.where(gnorm > mgn, mgn / gnorm, 1.0)

    def upd(path, stacked, p, g, m, v):
        dt = p.dtype
        wd = wd_all if decays(path) else 0.0
        gf = (g * clip.astype(g.dtype)).astype(dt)
        m2 = (b1 * m + (1.0 - b1) * gf).astype(m.dtype)
        v2 = (b2 * v + (1.0 - b2) * gf * gf).astype(v.dtype)
        u = (m2 / bc1.astype(dt)) / (jnp.sqrt(v2 / bc2.astype(dt)) + eps) + wd * p
        axes = tuple(range(1, p.ndim)) if stacked else None
        keep = stacked
        pn = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32)), axis=axes, keepdims=keep))
        un = jnp.sqrt(jnp.sum(jnp.square(u.astype(jnp.float32)), axis=axes, keepdims=keep))
        r = jnp.where((pn > 0) & (un > 0), pn / un, 1.0) if wd else 1.0
        p2 = (p - (lr * r * u).astype(dt)).astype(dt)
        return p2, m2, v2

    p2, m2, v2 = {"layers": {}}, {"layers": {}}, {"layers": {}}
    for k in p:
        if k == "layers":
            for lk in p["layers"]:
                a, b, c = upd(lk, True, p["layers"][lk], g["layers"][lk],
                              m["layers"][lk], v["layers"][lk])
                p2["layers"][lk], m2["layers"][lk], v2["layers"][lk] = a, b, c
        else:
            p2[k], m2[k], v2[k] = upd(k, False, p[k], g[k], m[k], v[k])
    return p2, m2, v2


def _per_leaf_norms(tree):
    """{program path: norm} of a reference tree."""
    out = {}
    for k, x in tree.items():
        if k == "layers":
            for lk, stacked in x.items():
                n = np.asarray(jnp.sqrt(jnp.sum(
                    jnp.square(stacked.astype(jnp.float32)),
                    axis=tuple(range(1, stacked.ndim)))))
                for i, val in enumerate(n):
                    out[f"transformer/layer_{i}/{lk}"] = float(val)
        else:
            out[k] = float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
    return out


def reference_train(config, mix, seed, batches, dtype=jnp.float32):
    """The first len(batches) optimizer steps in plain `jax.numpy`:
    returns the loss of each step, the per-leaf norm of the first
    gradient as LAMB gets it (clipped), and the per-leaf norm of the
    parameters' change after the last step. ``dtype`` float32 is the
    reference; bfloat16 (parameters, arithmetic and moments all in
    bfloat16: no fp32 masters) is the control."""
    t = mix["train"]
    o = dict(t["optimizer"])
    s = sizes(config)
    eps = float(config["layer_norm_eps"])
    rows = int(t.get("reference_block_rows", 4))
    key = weights.seed_key(seed)
    precision = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def init(key):
        return _ref_params(key, config, dtype)

    @jax.jit
    def block_grads(p, block, n_masked, n_rows):
        with jax.default_matmul_precision(precision):
            def f(p):
                lm, sop = _ref_loss_sums(p, block, s["heads"], eps)
                return lm / n_masked + sop / n_rows
            return jax.value_and_grad(f)(p)

    @jax.jit
    def add(a, b):
        return jax.tree_util.tree_map(lambda x, y: x + y, a, b)

    @jax.jit
    def lamb(p, g, m, v, count):
        return _lamb(p, g, m, v, count, o)

    p0 = init(key)
    p = p0
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first_grad = [], None
    for step, batch in enumerate(batches):
        n_rows = batch["tokens"].shape[0]
        n_masked = float(batch["loss_mask"].sum())
        loss, grads = 0.0, None
        for a in range(0, n_rows, rows):
            block = {k: jnp.asarray(x[a: a + rows]) for k, x in batch.items()}
            l, g = block_grads(p, block, n_masked, float(n_rows))
            loss += float(l)
            grads = g if grads is None else add(grads, g)
        losses.append(loss)
        p, m, v = lamb(p, grads, m, v, jnp.float32(step))
        if step == 0:
            b3 = 1.0 - float(o["betas"][0])
            first_grad = {k: n / b3 for k, n in _per_leaf_norms(m).items()}
    delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))(p, p0)
    return {
        "losses": losses,
        "first_gradient_norms": first_grad,
        "change_norms": _per_leaf_norms(delta),
    }
