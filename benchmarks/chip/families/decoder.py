"""Decoder-only language model with sliding-window and full attention layers
and a share of sparse experts (Mellum2-style).

The same parts as ``bert.py``, each read by the harness by name:

* ``init`` and ``make_batch`` make the weights and the inputs on the device
  from a key, in the parameter layout of ``repro.models.lm`` (``lm_init``):
  the router over every expert, the FFNs of the experts held here only.
* ``program`` hands back the program's registry configuration, cut as the
  configuration file says, and ``lm.lm_loss``.  It is the only function
  here that imports the program.
* ``reference_loss`` is the plain float32 model in ``jax.numpy``, with the
  same expert share and vocabulary slice as the program: RMS norm, GQA
  attention computed in blocks of queries (plain RoPE on the window
  layers, YaRN on the full ones), a softmax router over every expert with
  the top ``k`` renormalised, and each held expert's SwiGLU applied to
  every token and weighted by that token's gate (zero where the token did
  not choose it).  The experts this share does not hold add nothing, in
  the program and here alike.  It follows the program where the program
  departs from the published model (the configuration file lists each).

``linears`` and ``attention`` give the needed work for ``work.py``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
QUERY_BLOCK = 512
AUX_COEF = 0.01


def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * INIT_STD


def _dims(conf):
    return (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["moe_intermediate_size"])


def _layer(key, conf):
    d, h, kv, hd, f = _dims(conf)
    e, held = conf["router_experts"], conf["num_experts"]
    ks = jax.random.split(key, 8)
    return {"ln1": {"g": jnp.ones((d,))},
            "attn": {"wq": _normal(ks[0], (d, h * hd)),
                     "wk": _normal(ks[1], (d, kv * hd)),
                     "wv": _normal(ks[2], (d, kv * hd)),
                     "wo": _normal(ks[3], (h * hd, d))},
            "ln2": {"g": jnp.ones((d,))},
            "moe": {"router": _normal(ks[4], (d, e)),
                    "wg_e": _normal(ks[5], (held, d, f)),
                    "wu_e": _normal(ks[6], (held, d, f)),
                    "wd_e": _normal(ks[7], (held, f, d))}}


def init(key, conf):
    """All weights from one key: N(0, 0.02) matrices, unit norm gains."""
    d, v = conf["hidden_size"], conf["vocab_size"]
    ks = jax.random.split(key, 3)
    return {"embed": _normal(ks[0], (v, d)),
            "final_norm": {"g": jnp.ones((d,))},
            "lm_head": _normal(ks[1], (d, v)),
            "blocks": jax.vmap(lambda k: _layer(k, conf))(
                jax.random.split(ks[2], conf["num_hidden_layers"]))}


def make_batch(key, conf, traffic):
    """Token ids uniform over the vocabulary slice; each position's label
    is the next token, the last one masked (-1)."""
    b, s = traffic["batch"], traffic["seq_len"]
    tokens = jax.random.randint(key, (b, s), 0, conf["vocab_size"],
                                jnp.int32)
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full((b, 1), -1, jnp.int32)], axis=1)
    return {"tokens": tokens, "labels": labels}


def positions(conf, traffic):
    """Positions trained per step."""
    return traffic["batch"] * traffic["seq_len"]


def _kinds(conf):
    return conf["layer_types"][:conf["num_hidden_layers"]]


def linears(conf, traffic):
    """Every integer matrix product of a step's forward pass as (name, M,
    K, N, count); the backward has a dX and a dW product for each.  The
    held experts' products are at an expert's mean rows, ``tokens x topk /
    router_experts``, one product per held expert and layer.  The router's
    float32 product is not among them."""
    d, h, kv, hd, f = _dims(conf)
    n = conf["num_hidden_layers"]
    t = positions(conf, traffic)
    rows = t * conf["num_experts_per_tok"] // conf["router_experts"]
    experts = n * conf["num_experts"]
    return [("attn.q", t, d, h * hd, n), ("attn.kv", t, d, kv * hd, 2 * n),
            ("attn.o", t, h * hd, d, n),
            ("expert.wg", rows, d, f, experts),
            ("expert.wu", rows, d, f, experts),
            ("expert.wd", rows, f, d, experts),
            ("lm_head", t, d, conf["vocab_size"], 1)]


def _visible_keys(s: int, window) -> int:
    """(query, key) pairs of one causal sequence of ``s`` positions, each
    query seeing at most ``window`` keys."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def attention(conf, traffic):
    """(batch, query length, keys a query sees summed over the layers,
    width, 1): the causal and window masks' needed pairs, not S x S."""
    s = traffic["seq_len"]
    pairs = sum(_visible_keys(s, conf["sliding_window"]
                              if k == "sliding_attention" else None)
                for k in _kinds(conf))
    return (traffic["batch"], s, pairs / s,
            conf["num_attention_heads"] * conf["head_dim"], 1)


def program(conf, traffic):
    """The program's registry configuration at the configuration file's
    sizes: its depth, vocabulary slice and expert share."""
    import dataclasses
    from repro.configs import registry
    from repro.models import lm
    d, h, kv, hd, f = _dims(conf)
    arch = dataclasses.replace(
        registry.get_config(conf["registry"]), name=conf["name"],
        n_layers=conf["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=kv, head_dim=hd, d_ff=f, vocab=conf["vocab_size"],
        sliding_window=conf["sliding_window"],
        moe_experts=conf["router_experts"],
        moe_topk=conf["num_experts_per_tok"],
        moe_shard=(conf["experts_first"], conf["num_experts"]))
    kinds = tuple(k.split("_")[0] for k in _kinds(conf))
    if kinds != arch.layer_pattern:
        raise ValueError(f"registry {conf['registry']!r} has the layer "
                         f"pattern {arch.layer_pattern}; the configuration "
                         f"states {kinds}")
    return arch, lm.lm_loss


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------

def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def inv_freq(rope, hd):
    """Inverse frequencies and the cos/sin scale of one attention kind:
    plain RoPE, or YaRN as Hugging Face's ``_compute_yarn_parameters``."""
    base = rope["rope_theta"]
    pos = base ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if rope["rope_type"] == "default":
        return (1.0 / pos).astype(np.float32), 1.0
    factor = rope["factor"]
    orig = rope["original_max_position_embeddings"]

    def dim_of(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0, 1)
    extrapolation = 1 - ramp
    inv = (1 / (factor * pos)) * (1 - extrapolation) + (1 / pos) * extrapolation
    return inv.astype(np.float32), rope["attention_factor"]


def apply_rope(x, freqs, scale):
    """x: (B, S, heads, hd), rotating the two halves of the head dim."""
    s, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(freqs)
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention_blocked(q, k, v, window):
    """Causal GQA attention, one block of queries at a time (recomputed in
    the backward pass) so that no S x S score matrix is held whole.
    q: (B, S, H, hd); k, v: (B, S, KV, hd)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    n = min(QUERY_BLOCK, s)
    qb = q.reshape(b, s // n, n, kvh, h // kvh, hd)
    kpos = jnp.arange(s)

    def block(carry, inp):
        qc, i = inp
        qpos = i * n + jnp.arange(n)
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qc, k) / jnp.sqrt(
            jnp.float32(hd))
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok = ok & (kpos[None, :] > qpos[:, None] - window)
        p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        return carry, jnp.einsum("bkgqs,bskd->bqkgd", p, v)

    _, o = jax.lax.scan(jax.checkpoint(block), None,
                        (qb.transpose(1, 0, 2, 3, 4, 5),
                         jnp.arange(s // n)))
    return o.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h * hd)


def experts(x, p, conf):
    """The held experts' part of the sparse layer over tokens x (T, D);
    returns (y, load-balancing term over every expert)."""
    e, k = conf["router_experts"], conf["num_experts_per_tok"]
    first, held = conf["experts_first"], conf["num_experts"]
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    gate, sel = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for j in range(held):
        w = jnp.sum(jnp.where(sel == first + j, gate, 0.0), axis=-1)
        hid = jax.nn.silu(x @ p["wg_e"][j]) * (x @ p["wu_e"][j])
        y = y + w[:, None] * (hid @ p["wd_e"][j])
    density = jnp.mean(jax.nn.one_hot(sel[:, 0], e), axis=0)
    return y, e * jnp.sum(density * jnp.mean(probs, axis=0))


def decoder_layer(x, p, conf, kind):
    b, s, d = x.shape
    _, h, kvh, hd, _ = _dims(conf)
    eps = conf["rms_norm_eps"]
    freqs, scale = inv_freq(conf["rope_parameters"][kind], hd)
    a = rms_norm(x, p["ln1"]["g"], eps)
    q = apply_rope((a @ p["attn"]["wq"]).reshape(b, s, h, hd), freqs, scale)
    k = apply_rope((a @ p["attn"]["wk"]).reshape(b, s, kvh, hd), freqs,
                   scale)
    v = (a @ p["attn"]["wv"]).reshape(b, s, kvh, hd)
    window = conf["sliding_window"] if kind == "sliding_attention" else None
    x = x + attention_blocked(q, k, v, window) @ p["attn"]["wo"]
    y, aux = experts(rms_norm(x, p["ln2"]["g"], eps).reshape(b * s, d),
                     p["moe"], conf)
    return x + y.reshape(b, s, d), aux


def reference_loss(params, batch, conf):
    """Next-token cross entropy over the labelled positions plus 0.01 x
    the layers' mean load-balancing term."""
    x = params["embed"][batch["tokens"]]
    aux = 0.0
    for i, kind in enumerate(_kinds(conf)):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        x, a = jax.checkpoint(
            lambda x, p, kind=kind: decoder_layer(x, p, conf, kind))(x, p)
        aux = aux + a
    x = rms_norm(x, params["final_norm"]["g"], conf["rms_norm_eps"])
    logp = jax.nn.log_softmax(x @ params["lm_head"], axis=-1)
    labels = batch["labels"]
    valid = labels >= 0
    ll = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                             axis=-1)[..., 0]
    ce = -jnp.sum(ll * valid) / jnp.sum(valid)
    return ce + AUX_COEF * aux / conf["num_hidden_layers"]
