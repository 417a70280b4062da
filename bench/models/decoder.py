"""A dense decoder-only language model, written out plainly.

Llama-style blocks, as DeepSeek LLM (arXiv 2401.02954) publishes them:
x = x + Attn(RMSNorm(x)); x = x + MLP(RMSNorm(x)); a final RMSNorm and an
untied output projection.  Attention is causal multi-head attention with
rotary position embeddings (the rotate-half form, frequencies
theta^(-2i/hd)); the MLP is SwiGLU, silu(h W_g) * (h W_i) W_o.

Helios units are attention heads, ``heads: (L, H)``, and MLP hidden units,
``mlp: (L, d_ff)``.  A mask multiplies the head's attention output and the
hidden unit's activation, so a masked unit's weights (its slices of wq, wk,
wv, wo, or of wi, wg, wo) get no gradient.

The configuration names a registry architecture (``arch``) and gives its
widths; ``program_config`` puts those widths into that architecture.

Work counts, per sequence of S tokens, count one multiply-add as two
operations, and every matmul of a layer at its alive units: the q, k, v
and output projections of the alive heads, the MLP's three matmuls over
its alive hidden units, and the output projection over the vocabulary.
QK^T and AV count at their causal share, S^2/2 query-key pairs each:
2 S^2 hd H_alive operations per sequence in the forward pass.  The
backward pass costs twice the forward for every one of them (the input
gradient too: the embedding table trains).  Norms, rotary embeddings and
the softmax are not counted.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops


def examples(traffic: dict) -> tuple:
    return traffic["train_sequences"], traffic["eval_sequences"]


def markov_topic_tokens(num: int, seq_len: int, vocab: int, topics: int,
                        branching: int, seed: int, table_seed: int = 1234):
    """(N, S) int32 token sequences.  Each sequence belongs to one of
    ``topics`` latent topics, and each topic is a sparse random Markov
    chain over the vocabulary: every token has ``branching`` successors
    (tables fixed by ``table_seed``, so every seed shares one language).
    The next token is predictable from the context, so the loss can fall
    from ln(vocab) toward ln(branching).  A copy of the program's
    ``markov_topic_tokens`` arithmetic."""
    trng = np.random.default_rng(table_seed)
    rng = np.random.default_rng(seed)
    succ = trng.integers(0, vocab, size=(topics, vocab, branching))
    topic = rng.integers(0, topics, size=num)
    out = np.empty((num, seq_len), np.int32)
    state = rng.integers(0, vocab, size=num)
    for t in range(seq_len):
        out[:, t] = state
        state = succ[topic, state, rng.integers(0, branching, size=num)]
    return out


def make_data(cfg: dict, traffic: dict, seeds: dict) -> tuple:
    n_train, n_eval = examples(traffic)

    def draw(num, seed):
        return {"tokens": markov_topic_tokens(
            num, traffic["seq_len"], cfg["vocab_size"], traffic["topics"],
            traffic["branching"], seed)}

    return draw(n_train, seeds["train"]), draw(n_eval, seeds["eval"])


def program_config(cfg):
    from repro.configs import ARCHS
    base = ARCHS[cfg["arch"]]
    if (base.family, base.activation, base.norm, base.tie_embeddings,
            base.qkv_bias) != ("dense", "silu", "rmsnorm", False, False) \
            or cfg["num_kv_heads"] != cfg["num_heads"]:
        raise ValueError(f"{cfg['arch']} is not the dense SwiGLU multi-head "
                         f"decoder that bench/models/decoder.py follows")
    return dataclasses.replace(
        base, num_layers=cfg["num_layers"], d_model=cfg["d_model"],
        num_heads=cfg["num_heads"], num_kv_heads=cfg["num_kv_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["d_ff"],
        vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"])


def param_shapes(cfg) -> dict:
    L, d, H, hd = (cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
                   cfg["head_dim"])
    F, V = cfg["d_ff"], cfg["vocab_size"]
    return {
        "embed": {"embedding": (V, d), "unembed": (d, V)},
        "blocks": {
            "attn_norm": {"scale": (L, d)},
            "attn": {"wq": (L, d, H, hd), "wk": (L, d, H, hd),
                     "wv": (L, d, H, hd), "wo": (L, H, hd, d)},
            "mlp_norm": {"scale": (L, d)},
            "mlp": {"wi": (L, d, F), "wg": (L, d, F), "wo": (L, F, d)},
        },
        "final_norm": {"scale": (d,)},
    }


def init(cfg, key) -> dict:
    """The published initialization: norm scales one, every other weight
    normal with standard deviation ``initializer_range``."""
    paths, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(paths):
        if path[-1].key == "scale":
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
                       * np.float32(cfg["initializer_range"]))
    return jax.tree_util.tree_unflatten(tree, out)


def mask_units(cfg) -> dict:
    return {"heads": (cfg["num_layers"], cfg["num_heads"]),
            "mlp": (cfg["num_layers"], cfg["d_ff"])}


def _rms_norm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, hd), rotated by position along S."""
    hd = x.shape[-1]
    freqs = theta ** (-np.arange(0, hd, 2, dtype=np.float32) / hd)
    angles = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(angles)[None, :, None, :], x.dtype)
    sin = jnp.asarray(np.sin(angles)[None, :, None, :], x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits(params, tokens, cfg, masks, precision):
    """(B, S, V) logits; masks: {"heads": (L, H), "mlp": (L, d_ff)} 0/1, or
    None for the whole model."""
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    x = params["embed"]["embedding"][tokens]
    s = tokens.shape[1]
    causal = np.tril(np.ones((s, s), bool))
    for l in range(cfg["num_layers"]):
        p = jax.tree.map(lambda t: t[l], params["blocks"])
        a = p["attn"]
        h = _rms_norm(x, p["attn_norm"]["scale"], eps)
        q, k, v = (jnp.einsum("bsd,dhk->bshk", h, a[w], precision=precision)
                   for w in ("wq", "wk", "wv"))
        q, k = _rope(q, theta), _rope(k, theta)
        z = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=precision) \
            / np.sqrt(cfg["head_dim"]).astype(x.dtype)
        z = jnp.where(causal, z, -jnp.inf)
        o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(z, axis=-1), v,
                       precision=precision)
        if masks is not None:
            o = o * masks["heads"][l].astype(o.dtype)[:, None]
        x = x + jnp.einsum("bqhk,hkd->bqd", o, a["wo"], precision=precision)
        m = p["mlp"]
        h = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        u = jax.nn.silu(jnp.dot(h, m["wg"], precision=precision)) \
            * jnp.dot(h, m["wi"], precision=precision)
        if masks is not None:
            u = u * masks["mlp"][l].astype(u.dtype)
        x = x + jnp.dot(u, m["wo"], precision=precision)
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    return jnp.dot(x, params["embed"]["unembed"], precision=precision)


def loss(params, batch, cfg, masks, precision):
    """Next-token cross-entropy over positions 0 to S-2, the mean over the
    batch's sequences and positions."""
    tokens = batch["tokens"]
    z = logits(params, tokens, cfg, masks, precision)[:, :-1] \
        .astype(jnp.float32)
    gold = jnp.take_along_axis(z, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)


def unit_scores(new, old, cfg) -> dict:
    def d(group, name):
        return jnp.abs(new["blocks"][group][name].astype(jnp.float32)
                       - old["blocks"][group][name].astype(jnp.float32))

    heads = sum(d("attn", w).sum(axis=(1, 3)) for w in ("wq", "wk", "wv")) \
        + d("attn", "wo").sum(axis=(2, 3))
    mlp = d("mlp", "wi").sum(axis=1) + d("mlp", "wg").sum(axis=1) \
        + d("mlp", "wo").sum(axis=2)
    return {"heads": heads, "mlp": mlp}


def _alive(cfg, alive) -> tuple:
    """Per layer: alive heads and alive MLP units."""
    L = cfg["num_layers"]
    if alive is None:
        return [cfg["num_heads"]] * L, [cfg["d_ff"]] * L
    return ([int(np.sum(np.asarray(r) > 0)) for r in alive["heads"]],
            [int(np.sum(np.asarray(r) > 0)) for r in alive["mlp"]])


def forward_ops(cfg, traffic, alive=None) -> int:
    s, d, hd = traffic["seq_len"], cfg["d_model"], cfg["head_dim"]
    heads, mlp = _alive(cfg, alive)
    macs = s * d * cfg["vocab_size"]
    for h, f in zip(heads, mlp):
        macs += 4 * s * d * hd * h + s * s * hd * h + 3 * s * d * f
    return 2 * macs


def train_ops(cfg, traffic, alive=None) -> int:
    return 3 * forward_ops(cfg, traffic, alive)


def kernel_costs(cfg: dict, traffic: dict, masks: dict | None) -> dict:
    """The masked-matmul pair of the MLP (wi and wg over alive column
    blocks, wo over alive row blocks) and the causal flash-attention
    forward over the alive heads; the attention's backward recomputes in
    plain XLA and is no kernel's."""
    b, s, d = traffic["batch"], traffic["seq_len"], cfg["d_model"]
    heads, _ = _alive(cfg, masks)
    mm = fa = (0, 0)
    for l, h in enumerate(heads):
        n = cfg["d_ff"] if masks is None else flops.alive_block_units(
            masks["mlp"][l], traffic["mask_block"])
        o, nb = flops.masked_matmul_cost(b * s, d, n)
        mm = (mm[0] + 3 * o, mm[1] + 3 * nb)
        o, nb = flops.flash_attention_cost(b, s, h, cfg["head_dim"])
        fa = (fa[0] + o, fa[1] + nb)
    return {"masked_matmul": mm, "flash_attention": fa}
