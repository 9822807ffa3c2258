"""Granite-3.0-3B-A800M: the benchmark's weights and its plain float32
reference.

``init`` draws the weights from a key with the published initialisation
(``initializer_range`` 0.02 for every matrix, RMSNorm scales of one), in
one jitted call, laid out as the parameter tree the program trains.  ``loss``
is the language-model loss of the published model written plainly in
``jax.numpy``, from the Hugging Face ``GraniteMoe`` modelling code: the
embedding times ``embedding_multiplier``; per layer an RMSNorm, grouped-query
causal attention with rotate-half RoPE and softmax scale
``attention_multiplier``, its output times ``residual_multiplier`` added to
the residual, an RMSNorm, a top-8-of-40 softmax router renormalised over the
chosen experts and SwiGLU experts, again times ``residual_multiplier``; a
final RMSNorm and the tied head, its logits divided by ``logits_scaling``.
Every expert runs on every token, masked by the routing weights.  It imports
nothing of the program.

Every matrix product goes through ``mm``, so that the precision control can
round the operands to a lower precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# the cell's rows fill Granite's whole context (``max_position_embeddings``):
# the attention products of train_flops_per_token are counted at this length
CONTEXT = 4096


def dims(conf: dict, rehearse: bool = False) -> dict:
    """The published model as run, named as the program's ArchConfig names
    it; the vocabulary gets one padding row so that it divides over 4."""
    d = {
        "n_layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["hidden_size"] // conf["num_attention_heads"],
        "n_experts": conf["num_local_experts"],
        "top_k": conf["num_experts_per_tok"],
        "d_ff_expert": conf["intermediate_size"],
        "vocab": conf["vocab_size"],
        "vocab_padded": -(-conf["vocab_size"] // 4) * 4,
        "rope_theta": float(conf["rope_theta"]),
        "norm_eps": conf["rms_norm_eps"],
        "tie_embeddings": conf["tie_word_embeddings"],
        "embedding_multiplier": conf["embedding_multiplier"],
        "attention_multiplier": conf["attention_multiplier"],
        "residual_multiplier": conf["residual_multiplier"],
        "logits_scaling": conf["logits_scaling"],
    }
    if rehearse:
        d.update(conf["rehearse"]["overrides"])
    return d


def init(d: dict, key, std: float = 0.02):
    """The parameter tree, in bfloat16 except the float32 router."""
    D, L, E, F = d["d_model"], d["n_layers"], d["n_experts"], d["d_ff_expert"]
    Q, KV = d["n_heads"] * d["head_dim"], d["n_kv_heads"] * d["head_dim"]
    V, Vp = d["vocab"], d["vocab_padded"]
    bf = jnp.bfloat16
    ks = iter(jax.random.split(key, 9))
    normal = lambda shape, dtype=bf: (
        jax.random.normal(next(ks), shape, jnp.float32) * std).astype(dtype)
    embed = jnp.where(jnp.arange(Vp)[:, None] < V, normal((Vp, D), jnp.float32), 0.0)
    ones = lambda *s: jnp.ones(s, bf)
    block = {
        "ln1": {"s": ones(L, D)},
        "attn": {"wq": {"w": normal((L, D, Q))}, "wk": {"w": normal((L, D, KV))},
                 "wv": {"w": normal((L, D, KV))}, "wo": {"w": normal((L, Q, D))}},
        "ln2": {"s": ones(L, D)},
        "moe": {"router": {"w": normal((L, D, E), jnp.float32)},
                "wg": normal((L, E, D, F)), "wu": normal((L, E, D, F)),
                "wo": normal((L, E, F, D))},
    }
    return {"embed": {"w": embed.astype(bf)}, "ln_f": {"s": ones(D)},
            "blocks": (block,)}


def matmul(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def _rope(x, theta):
    """Rotate-half RoPE over the whole head dim.  x: (B, S, ..., hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, hd/2)
    ang = ang.reshape(S, *([1] * (x.ndim - 3)), hd // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, d, mm):
    """Grouped-query causal attention: query head g*KV.. shares kv head."""
    B, S, _ = h.shape
    KV, hd = d["n_kv_heads"], d["head_dim"]
    G = d["n_heads"] // KV
    q = _rope(mm(h, p["wq"]["w"]).reshape(B, S, KV, G, hd), d["rope_theta"])
    k = _rope(mm(h, p["wk"]["w"]).reshape(B, S, KV, hd), d["rope_theta"])
    v = mm(h, p["wv"]["w"]).reshape(B, S, KV, hd)
    q = q.transpose(0, 2, 3, 1, 4)  # (B, KV, G, S, hd)
    k = k.transpose(0, 2, 1, 3)[:, :, None]  # (B, KV, 1, S, hd)
    v = v.transpose(0, 2, 1, 3)[:, :, None]
    s = mm(q, k.swapaxes(-1, -2)) * d["attention_multiplier"]  # (B, KV, G, S, S)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = mm(jax.nn.softmax(s, -1), v)  # (B, KV, G, S, hd)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, S, KV * G * hd)
    return mm(o, p["wo"]["w"])


def _experts(p, h, d, mm):
    """Top-k softmax routing; every expert on every token, weighted by its
    routing weight (zero for the experts not chosen)."""
    B, S, D = h.shape
    E, F = d["n_experts"], d["d_ff_expert"]
    x = h.reshape(B * S, D)
    probs = jax.nn.softmax(mm(x, p["router"]["w"]), -1)
    top, idx = jax.lax.top_k(probs, d["top_k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    w = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], 1)  # (T, E)
    act = jax.nn.silu(mm(x[None], p["wg"])) * mm(x[None], p["wu"])  # (E, T, F)
    act = act * w.T[:, :, None]
    y = mm(act.transpose(1, 0, 2).reshape(B * S, E * F), p["wo"].reshape(E * F, D))
    return y.reshape(B, S, D)


def loss(params, batch, d: dict, mm=matmul):
    """Mean next-token cross entropy over the real vocabulary, in float32."""
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
    params = f32(params)
    emb = params["embed"]["w"][:d["vocab"]]
    x = emb[batch["tokens"]] * d["embedding_multiplier"]
    eps, res = d["norm_eps"], d["residual_multiplier"]

    def layer(x, lp):
        x = x + _attention(lp["attn"], _rms(x, lp["ln1"]["s"], eps), d, mm) * res
        x = x + _experts(lp["moe"], _rms(x, lp["ln2"]["s"], eps), d, mm) * res
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"][0])
    x = _rms(x, params["ln_f"]["s"], eps)
    logits = mm(x, emb.T) / d["logits_scaling"]
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - tgt)


def train_flops_per_token(d: dict, chunk: int, seq: int = CONTEXT) -> float:
    """Forward and backward matrix-product operations per token of the
    published model at ``seq`` tokens a row: three times the forward, with
    no recomputation.  Only active parameters count (attention, the router,
    top-k of the experts, the head), and the causal attention products at
    their mean length (seq + 1) / 2.  ``chunk`` (an SSM's) is unused."""
    del chunk
    D, L, hd = d["d_model"], d["n_layers"], d["head_dim"]
    Q, KV = d["n_heads"] * hd, d["n_kv_heads"] * hd
    layer = (D * (2 * Q + 2 * KV) + D * d["n_experts"]
             + d["top_k"] * 3 * D * d["d_ff_expert"])
    attn = 2 * 2 * Q * (seq + 1) / 2  # q·k and p·v, per token
    fwd = 2 * (L * layer + d["vocab"] * D) + L * attn
    return 3 * fwd


def ssd_scan_shape(d: dict, rows: int, seq: int, chunk: int):
    """No SSD scan in this model."""
    return None
