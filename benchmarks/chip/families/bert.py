"""BERT encoder with the SQuAD span head.

Three parts, each read by the harness by name:

* ``init`` and ``make_batch`` make the weights and the inputs on the device
  from a key, in the parameter layout that ``repro.models.paper_models``
  reads (``bert_init(..., span_head=True)``).
* ``program`` hands back the program's own architecture config and loss.
  It is the only function here that imports the program.
* ``reference_loss`` is the plain float32 model in ``jax.numpy``: what the
  program computes with every integer layer replaced by its exact float
  form.  It follows the program where the program departs from the
  published BERT (the configuration file lists each departure): pre-LN
  blocks with no final norm, tanh GeLU, no Q/K/V/O or span-head bias, layer
  norm epsilon 1e-5, no token-type input.

``linears`` and ``attention`` give the needed work for ``work.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
INIT_STD = 0.02


def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * INIT_STD


def _layer(key, conf):
    d, f = conf["hidden_size"], conf["intermediate_size"]
    kq, kk, kv, ko, k1, k2 = jax.random.split(key, 6)
    return {"ln1": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "attn": {"wq": _normal(kq, (d, d)), "wk": _normal(kk, (d, d)),
                     "wv": _normal(kv, (d, d)), "wo": _normal(ko, (d, d))},
            "ln2": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "mlp": {"w1": _normal(k1, (d, f)), "b1": jnp.zeros((f,)),
                    "w2": _normal(k2, (f, d)), "b2": jnp.zeros((d,))}}


def init(key, conf):
    """All weights from one key: N(0, 0.02) matrices, unit/zero norms."""
    d = conf["hidden_size"]
    ks = jax.random.split(key, 7)
    return {
        "embed": _normal(ks[0], (conf["vocab_size"], d)),
        "pos_embed": _normal(ks[1], (conf["max_position_embeddings"], d)),
        "type_embed": _normal(ks[2], (conf["type_vocab_size"], d)),
        "embed_ln": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
        "blocks": jax.vmap(lambda k: _layer(k, conf))(
            jax.random.split(ks[3], conf["num_hidden_layers"])),
        "pooler": _normal(ks[4], (d, d)),
        "pooler_b": jnp.zeros((d,)),
        "head": _normal(ks[5], (d, 2)),
        "head_b": jnp.zeros((2,)),
        "span": _normal(ks[6], (d, 2)),
    }


def make_batch(key, conf, traffic):
    """SQuAD-shaped span rows: random word ids, an answer span of 1-5
    tokens starting in [1, S-8), marker ids on its two boundaries (the
    generator of ``benchmarks/tasks.py::make_span_task``, on the device)."""
    b, s, v = traffic["batch"], traffic["seq_len"], conf["vocab_size"]
    kt, ks, kl = jax.random.split(key, 3)
    tokens = jax.random.randint(kt, (b, s), 0, v - 2, jnp.int32)
    start = jax.random.randint(ks, (b,), 1, s - 8, jnp.int32)
    end = start + jax.random.randint(kl, (b,), 1, 6, jnp.int32)
    rows = jnp.arange(b)
    tokens = tokens.at[rows, start].set(v - 2).at[rows, end].set(v - 1)
    return {"tokens": tokens, "span_start": start, "span_end": end}


def positions(conf, traffic):
    """Positions trained per step."""
    return traffic["batch"] * traffic["seq_len"]


def linears(conf, traffic):
    """Every matrix product of a step's forward pass as (name, M, K, N,
    count); the backward has a dX and a dW product for each."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    n = conf["num_hidden_layers"]
    m = positions(conf, traffic)
    return [("attn.qkvo", m, d, d, 4 * n), ("mlp.w1", m, d, f, n),
            ("mlp.w2", m, f, d, n), ("span_head", m, d, 2, 1)]


def attention(conf, traffic):
    """(batch, query length, key length, width, layers) of the attention."""
    s = traffic["seq_len"]
    return (traffic["batch"], s, s, conf["hidden_size"],
            conf["num_hidden_layers"])


def program(conf, traffic):
    """The program's architecture config and loss for this family."""
    from repro.models import paper_models as pm
    arch = pm.bert_config(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"], name=conf["name"])
    if arch.max_position_embeddings != conf["max_position_embeddings"]:
        raise ValueError("the program fixes max_position_embeddings at "
                         f"{arch.max_position_embeddings}")
    return arch, pm.bert_span_loss


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------

def layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def encoder_layer(x, p, heads):
    """One pre-LN block: x + MHA(LN(x)), then x + FFN(LN(x))."""
    b, s, d = x.shape
    hd = d // heads
    h = layer_norm(x, p["ln1"]["g"], p["ln1"]["b"])
    q = (h @ p["attn"]["wq"]).reshape(b, s, heads, hd)
    k = (h @ p["attn"]["wk"]).reshape(b, s, heads, hd)
    v = (h @ p["attn"]["wv"]).reshape(b, s, heads, hd)
    att = jax.nn.softmax(
        jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd)),
        axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, d)
    x = x + o @ p["attn"]["wo"]
    h = layer_norm(x, p["ln2"]["g"], p["ln2"]["b"])
    h = gelu_tanh(h @ p["mlp"]["w1"] + p["mlp"]["b1"])
    return x + h @ p["mlp"]["w2"] + p["mlp"]["b2"]


def encoder(x, blocks, heads):
    """The layer stack, recomputed layer by layer in the backward pass so
    that only each layer's input is kept."""
    body = jax.checkpoint(lambda x, p: (encoder_layer(x, p, heads), None))
    return jax.lax.scan(body, x, blocks)[0]


def reference_loss(params, batch, conf):
    tokens = batch["tokens"]
    s = tokens.shape[1]
    x = params["embed"][tokens] + params["pos_embed"][None, :s]
    x = layer_norm(x, params["embed_ln"]["g"], params["embed_ln"]["b"])
    x = encoder(x, params["blocks"], conf["num_attention_heads"])
    logits = x @ params["span"]
    start = jax.nn.log_softmax(logits[..., 0], axis=-1)
    end = jax.nn.log_softmax(logits[..., 1], axis=-1)
    rows = jnp.arange(tokens.shape[0])
    return -0.5 * jnp.mean(start[rows, batch["span_start"]]
                           + end[rows, batch["span_end"]])
