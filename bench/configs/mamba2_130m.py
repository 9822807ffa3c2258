"""Mamba-2 130M: the benchmark's weights and its plain float32 reference.

``init`` draws the weights from a key with the published Mamba-2
initialisation (state-spaces/mamba, ``Mamba2.__init__`` and
``_init_weights``), in one jitted call, laid out as the parameter tree the
program trains.  ``loss`` is the language-model loss written from the
Mamba-2 paper (arXiv:2405.21060, Listing 1 for the SSD scan) in plain
``jax.numpy``.  It imports nothing of the program.  Where the program departs
from the published block, the reference follows the program and the
departure is listed under ``program.departures`` in ``mamba2_130m.json``.

Every matrix product goes through ``mm``, so that the precision control can
round the operands to a lower precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(conf: dict, rehearse: bool = False) -> dict:
    """Sizes as run, named as the program's ArchConfig names them."""
    a, mult = conf["assumed"], conf["pad_vocab_size_multiple"]
    d = {
        "n_layers": conf["n_layer"],
        "d_model": conf["d_model"],
        "vocab": conf["vocab_size"],
        "vocab_padded": -(-conf["vocab_size"] // mult) * mult,
        "ssm_state": a["d_state"],
        "ssm_head_dim": a["headdim"],
        "ssm_expand": a["expand"],
        "ssm_conv": a["d_conv"],
        "norm_eps": 1e-5,
    }
    if rehearse:
        d.update(conf["rehearse"]["overrides"])
    return d


def init(d: dict, key):
    """The parameter tree, in bfloat16 except the per-head float32 scalars."""
    D, L, N, K = d["d_model"], d["n_layers"], d["ssm_state"], d["ssm_conv"]
    DI = d["ssm_expand"] * D
    H = DI // d["ssm_head_dim"]
    V, Vp = d["vocab"], d["vocab_padded"]
    bf = jnp.bfloat16
    ks = iter(jax.random.split(key, 16))

    def uniform(shape, bound, dtype=bf):
        return jax.random.uniform(next(ks), shape, jnp.float32, -bound, bound).astype(dtype)

    # nn.Linear / nn.Conv1d defaults: U(-1/sqrt(fan_in), 1/sqrt(fan_in))
    lin = lambda din, dout: {"w": uniform((L, din, dout), 1 / math.sqrt(din))}
    conv = lambda c: uniform((L, K, c), 1 / math.sqrt(K))
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(jax.random.uniform(next(ks), (L, H)) * (hi - lo) + lo)
    dt = jnp.maximum(dt, 1e-4)
    embed = jax.random.normal(next(ks), (Vp, D), jnp.float32) * 0.02
    embed = jnp.where(jnp.arange(Vp)[:, None] < V, embed, 0.0)
    wo = lin(DI, D)
    # _init_weights(rescale_prenorm_residual): out_proj / sqrt(n_layer)
    wo["w"] = (wo["w"].astype(jnp.float32) / math.sqrt(L)).astype(bf)
    ones = lambda *s: jnp.ones(s, bf)
    ssm = {
        "wx": lin(D, DI), "wz": lin(D, DI), "wB": lin(D, N), "wC": lin(D, N),
        "wdt": lin(D, H),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # inverse softplus
        "A_log": jnp.log(jax.random.uniform(next(ks), (L, H), jnp.float32, 1.0, 16.0)),
        "Dskip": jnp.ones((L, H), jnp.float32),
        "conv_x": conv(DI), "conv_B": conv(N), "conv_C": conv(N),
        "out_norm": {"s": ones(L, DI)},
        "wo": wo,
    }
    return {
        "embed": {"w": embed.astype(bf)},
        "ln_f": {"s": ones(D)},
        "blocks": ({"ln1": {"s": ones(L, D)}, "ssm": ssm},),
    }


def matmul(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def _conv(x, w):
    """Causal depthwise convolution: y_t = sum_k w_k x_{t-K+1+k}."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, k:k + S] * w[k] for k in range(K))


def _segsum(a):
    """(..., T) -> (..., T, T): sum of a over (j, i] below the diagonal."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, -1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), seg, -jnp.inf)


def ssd(x, dt, A, B, C, chunk, mm=matmul):
    """SSD scan, Listing 1 of arXiv:2405.21060 (one group).

    x (b,s,h,p), dt (b,s,h), A (h,), B and C (b,s,n) -> y (b,s,h,p)."""
    b, s, h, p = x.shape
    q = min(chunk, s)
    c = s // q
    X = (x * dt[..., None]).reshape(b, c, q, h, p)
    a = (dt * A).reshape(b, c, q, h).transpose(0, 3, 1, 2)  # (b,h,c,q)
    Bc, Cc = B.reshape(b, c, q, -1), C.reshape(b, c, q, -1)
    a_cum = jnp.cumsum(a, -1)
    Lm = jnp.exp(_segsum(a))  # (b,h,c,q,q)
    CB = mm(Cc, Bc.swapaxes(-1, -2))  # (b,c,q,q)
    att = CB[:, None] * Lm  # (b,h,c,q,k)
    y_diag = mm(att, X.transpose(0, 3, 1, 2, 4))  # (b,h,c,q,p)
    decay = jnp.exp(a_cum[..., -1:] - a_cum)  # (b,h,c,q)
    Xd = X.transpose(0, 3, 1, 2, 4) * decay[..., None]  # (b,h,c,q,p)
    states = mm(Bc.swapaxes(-1, -2)[:, None], Xd)  # (b,h,c,n,p)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = jnp.concatenate([jnp.zeros_like(states[:, :, :1]), states], 2)
    states = jnp.einsum("bhzc,bhcnp->bhznp", chunk_decay, states,
                        precision=jax.lax.Precision.HIGHEST)[:, :, :-1]
    y_off = mm(Cc[:, None], states) * jnp.exp(a_cum)[..., None]  # (b,h,c,q,p)
    return (y_diag + y_off).transpose(0, 2, 3, 1, 4).reshape(b, s, h, p)


def _block(p, x, d, mm):
    S = x.shape[1]
    hp = d["ssm_head_dim"]
    xs = mm(x, p["wx"]["w"])
    z = mm(x, p["wz"]["w"])
    B = jax.nn.silu(_conv(mm(x, p["wB"]["w"]), p["conv_B"]))
    C = jax.nn.silu(_conv(mm(x, p["wC"]["w"]), p["conv_C"]))
    xs = jax.nn.silu(_conv(xs, p["conv_x"]))
    dt = jax.nn.softplus(mm(x, p["wdt"]["w"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(x.shape[0], S, -1, hp)
    y = ssd(xh, dt, A, B, C, 256, mm) + p["Dskip"][:, None] * xh
    y = y.reshape(x.shape[0], S, -1) * jax.nn.silu(z)
    g = y.reshape(*y.shape[:-1], -1, hp)  # gated RMSNorm per head group
    y = (g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + d["norm_eps"])
         ).reshape(y.shape) * p["out_norm"]["s"]
    return mm(y, p["wo"]["w"])


def loss(params, batch, d: dict, mm=matmul):
    """Mean next-token cross entropy over the real vocabulary, in float32."""
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
    params = f32(params)
    emb = params["embed"]["w"][:d["vocab"]]
    x = emb[batch["tokens"]]
    blocks = params["blocks"][0]

    def layer(x, lp):
        h = _rms(x, lp["ln1"]["s"], d["norm_eps"])
        return x + _block(lp["ssm"], h, d, mm), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, blocks)
    x = _rms(x, params["ln_f"]["s"], d["norm_eps"])
    logits = mm(x, emb.T)
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - tgt)


def train_flops_per_token(d: dict, chunk: int) -> float:
    """Forward and backward matrix-product operations per token of the
    published model: three times the forward, with no recomputation."""
    from bench.flops import ssd_flops_per_token

    D, L, V, N = d["d_model"], d["n_layers"], d["vocab"], d["ssm_state"]
    DI = d["ssm_expand"] * D
    H = DI // d["ssm_head_dim"]
    proj = D * (2 * DI + 2 * N + H) + DI * D  # in_proj and out_proj
    fwd = 2 * (L * proj + V * D) + L * ssd_flops_per_token(
        H, d["ssm_head_dim"], N, chunk)
    return 3 * fwd


def ssd_scan_shape(d: dict, rows: int, seq: int, chunk: int) -> dict:
    """The SSD kernel's shape as ``bench.flops.ssd_scan_cost`` takes it: one
    forward call per layer and microbatch of ``rows`` rows (the backward
    runs the jnp reference)."""
    DI = d["ssm_expand"] * d["d_model"]
    return {"b": rows, "s": seq, "h": DI // d["ssm_head_dim"],
            "p": d["ssm_head_dim"], "n": d["ssm_state"], "chunk": chunk}
