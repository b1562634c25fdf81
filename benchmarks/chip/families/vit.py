"""ViT image classifier (patch embedding, CLS token, encoder, head).

Same three parts as ``bert.py``: ``init``/``make_batch`` on the device in
the layout of ``repro.models.paper_models.vit_init``; ``program`` (the only
import of the program); ``reference_loss``, the plain float32 model.  The
reference follows the program where it departs from ViT-B/16 (the
configuration file lists each): tanh GeLU, no Q/K/V/O bias, layer norm
epsilon 1e-5.  The encoder block is ``bert.py``'s: both models run through
the same code in the program too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import bert


def _patches(conf):
    return (conf["image_size"] // conf["patch_size"]) ** 2


def init(key, conf):
    d = conf["hidden_size"]
    p = conf["patch_size"]
    ks = jax.random.split(key, 5)
    return {
        "patch_w": bert._normal(ks[0], (p * p * conf["num_channels"], d)),
        "patch_b": jnp.zeros((d,)),
        "cls": bert._normal(ks[1], (1, 1, d)),
        "pos_embed": bert._normal(ks[2], (_patches(conf) + 1, d)),
        "blocks": jax.vmap(lambda k: bert._layer(k, conf))(
            jax.random.split(ks[3], conf["num_hidden_layers"])),
        "final_ln": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
        "head": bert._normal(ks[4], (d, conf["num_labels"])),
        "head_b": jnp.zeros((conf["num_labels"],)),
    }


def make_batch(key, conf, traffic):
    """Standard-normal pixels (a normalised image's scale) and uniform
    labels."""
    b, px = traffic["batch"], conf["image_size"]
    ki, kl = jax.random.split(key)
    return {"images": jax.random.normal(
                ki, (b, px, px, conf["num_channels"]), jnp.float32),
            "labels": jax.random.randint(kl, (b,), 0, conf["num_labels"],
                                         jnp.int32)}


def positions(conf, traffic):
    """Positions trained per step: patches plus the CLS token, per image."""
    return traffic["batch"] * (_patches(conf) + 1)


def linears(conf, traffic):
    d, f = conf["hidden_size"], conf["intermediate_size"]
    n = conf["num_hidden_layers"]
    b = traffic["batch"]
    m = positions(conf, traffic)
    pin = conf["patch_size"] ** 2 * conf["num_channels"]
    return [("patch_embed", b * _patches(conf), pin, d, 1),
            ("attn.qkvo", m, d, d, 4 * n), ("mlp.w1", m, d, f, n),
            ("mlp.w2", m, f, d, n), ("head", b, d, conf["num_labels"], 1)]


def attention(conf, traffic):
    s = _patches(conf) + 1
    return (traffic["batch"], s, s, conf["hidden_size"],
            conf["num_hidden_layers"])


def program(conf, traffic):
    import functools
    from repro.models import paper_models as pm
    arch = pm.vit_config(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        d_ff=conf["intermediate_size"],
        img=conf["image_size"], patch=conf["patch_size"], name=conf["name"])
    return arch, functools.partial(pm.vit_cls_loss,
                                   patch=conf["patch_size"])


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------

def patchify(images, p):
    """(B, H, W, C) -> (B, H/p * W/p, p*p*C), patches in row-major order,
    each flattened (row, column, channel) like a p-by-p convolution."""
    b, h, w, c = images.shape
    x = images.reshape(b, h // p, p, w // p, p, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // p) * (w // p), -1)


def reference_loss(params, batch, conf):
    x = patchify(batch["images"], conf["patch_size"]) @ params["patch_w"]
    x = x + params["patch_b"]
    b, _, d = x.shape
    cls = jnp.broadcast_to(params["cls"], (b, 1, d))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"][None]
    x = bert.encoder(x, params["blocks"], conf["num_attention_heads"])
    x = bert.layer_norm(x, params["final_ln"]["g"], params["final_ln"]["b"])
    logits = x[:, 0] @ params["head"] + params["head_b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(logp[jnp.arange(b), batch["labels"]])
