"""Synthetic proxy tasks + fine-tuning harness for the paper's tables.

GLUE/SQuAD/CIFAR do not ship in this container (DESIGN.md §8); the paper's
*claims* are about score deltas across bit-widths, so each benchmark
fine-tunes a small transformer on a structured synthetic task and reports the
same metric sweep. Tasks are built so the FP32 model reaches high accuracy
quickly, making quantization-induced drops visible.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qconfig import QuantConfig
from repro.models import paper_models as pm
from repro.train import optimizer as opt_lib


# ---------------------------------------------------------------------------
# task generators
# ---------------------------------------------------------------------------

def make_cls_task(vocab=512, seq=32, n_classes=4, seed=0):
    """GLUE proxy: class determined by which motif family dominates."""
    rng = np.random.default_rng(seed)
    motifs = rng.integers(0, vocab, size=(n_classes, 4, 6))

    def sample(n, seed2):
        r = np.random.default_rng((seed, seed2))
        y = r.integers(0, n_classes, n)
        toks = r.integers(0, vocab, (n, seq))
        for i in range(n):
            for _ in range(3):
                m = motifs[y[i], r.integers(0, 4)]
                pos = r.integers(0, seq - 6)
                toks[i, pos:pos + 6] = m
        return {"tokens": toks.astype(np.int32),
                "labels": y.astype(np.int32)}

    return sample


def make_span_task(vocab=512, seq=48, seed=0):
    """SQuAD proxy: an 'answer' span whose boundary tokens carry marker ids;
    the model predicts start/end positions. (Markers sit ON the boundaries —
    the proxy probes the integer pipeline's localization fidelity, which is
    what the paper's bit-width claims are about, not QA reasoning.)"""
    START, END = vocab - 2, vocab - 1

    def sample(n, seed2):
        r = np.random.default_rng((seed, seed2))
        toks = r.integers(0, vocab - 2, (n, seq))
        s = r.integers(1, seq - 8, n)
        ln = r.integers(1, 6, n)
        e = s + ln
        for i in range(n):
            toks[i, s[i]] = START
            toks[i, e[i]] = END
        return {"tokens": toks.astype(np.int32),
                "span_start": s.astype(np.int32),
                "span_end": e.astype(np.int32)}

    return sample


def make_img_task(img=32, patch=8, n_classes=4, seed=0):
    """CIFAR proxy: class = quadrant of a bright blob on noise."""
    def sample(n, seed2):
        r = np.random.default_rng((seed, seed2))
        y = r.integers(0, n_classes, n)
        x = r.standard_normal((n, img, img, 3)).astype(np.float32) * 0.3
        half = img // 2
        for i in range(n):
            qy, qx = divmod(int(y[i]), 2)
            x[i, qy * half:(qy + 1) * half, qx * half:(qx + 1) * half] += 1.5
        return {"images": x, "labels": y.astype(np.int32)}

    return sample


# ---------------------------------------------------------------------------
# fine-tuning harness
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FtConfig:
    steps: int = 150
    batch: int = 16
    eval_n: int = 256
    lr: float = 1e-3
    seed: int = 0


def _task_setup(task: str, key, ft: FtConfig):
    """Model config/params/sampler/loss for one proxy task."""
    if task == "cls":
        cfg = pm.bert_config(n_layers=4, d_model=128, n_heads=4, d_ff=256,
                             vocab=512, name="bert-tiny")
        params = pm.bert_init(key, cfg, num_labels=4)
        sampler = make_cls_task(vocab=512)
        loss_fn = pm.bert_cls_loss
    elif task == "span":
        cfg = pm.bert_config(n_layers=4, d_model=128, n_heads=4, d_ff=256,
                             vocab=512, name="bert-tiny")
        params = pm.bert_init(key, cfg, span_head=True)
        sampler = make_span_task(vocab=512)
        loss_fn = pm.bert_span_loss
    elif task == "img":
        cfg = pm.vit_config(n_layers=4, d_model=128, n_heads=4, d_ff=256,
                            img=32, patch=8, name="vit-tiny")
        params = pm.vit_init(key, cfg, num_classes=4, img=32, patch=8)
        sampler = make_img_task()
        loss_fn = lambda p, b, c, q, k: pm.vit_cls_loss(p, b, c, q, k, patch=8)
    else:
        raise KeyError(task)
    lr = {"span": 2e-3}.get(task, ft.lr)
    return cfg, params, sampler, loss_fn, lr


def finetune(task: str, qcfg: QuantConfig, ft: FtConfig = FtConfig(),
             return_losses: bool = False):
    """Fine-tune the task's model under ``qcfg``; returns (metric, losses)."""
    key = jax.random.PRNGKey(ft.seed)
    cfg, params, sampler, loss_fn, lr = _task_setup(task, key, ft)
    opt_cfg = opt_lib.OptimizerConfig(lr=lr, weight_decay=0.0)
    opt_state = opt_lib.init(params)

    @jax.jit
    def step(params, opt_state, batch, k):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, cfg, qcfg, k)
        params, opt_state, _ = opt_lib.update(opt_cfg, g, opt_state, params)
        return params, opt_state, loss

    losses = []
    for i in range(ft.steps):
        batch = {k_: jnp.asarray(v) for k_, v in sampler(ft.batch, i).items()}
        params, opt_state, loss = step(params, opt_state, batch,
                                       jax.random.fold_in(key, i))
        losses.append(float(loss))

    # ---- evaluate ----
    ev = sampler(ft.eval_n, 10_000_001)
    if task == "cls":
        logits = pm.bert_apply(params, jnp.asarray(ev["tokens"]), cfg, qcfg, None)
        acc = float(jnp.mean(jnp.argmax(logits, -1) == jnp.asarray(ev["labels"])))
        metric = 100 * acc
    elif task == "span":
        out = pm.bert_apply(params, jnp.asarray(ev["tokens"]), cfg, qcfg, None,
                            pool=False)
        s_hat = jnp.argmax(out[..., 0], -1)
        e_hat = jnp.argmax(out[..., 1], -1)
        em = jnp.mean((s_hat == jnp.asarray(ev["span_start"]))
                      & (e_hat == jnp.asarray(ev["span_end"])))
        metric = 100 * float(em)
    else:
        logits = pm.vit_apply(params, jnp.asarray(ev["images"]), cfg, qcfg,
                              None, patch=8)
        metric = 100 * float(jnp.mean(jnp.argmax(logits, -1)
                                      == jnp.asarray(ev["labels"])))
    return (metric, losses) if return_losses else (metric, None)


def step_stats(task: str, qcfg: QuantConfig, ft: FtConfig = FtConfig()
               ) -> Dict[str, int]:
    """Per-step traced-dispatch count of one train step.

    ``pallas_calls`` is the number of ``pallas_call`` equations traced into
    the value-and-grad step (0 on the sim/fp32 paths) — the quantity the
    single-dispatch limb fusion makes bit-width-independent.
    """
    from repro.utils import count_pallas_calls

    key = jax.random.PRNGKey(ft.seed)
    cfg, params, sampler, loss_fn, lr = _task_setup(task, key, ft)
    opt_cfg = opt_lib.OptimizerConfig(lr=lr, weight_decay=0.0)
    opt_state = opt_lib.init(params)
    batch = {k_: jnp.asarray(v) for k_, v in sampler(ft.batch, 0).items()}

    def step(params, opt_state, batch, k):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, cfg, qcfg, k)
        params, opt_state, _ = opt_lib.update(opt_cfg, g, opt_state, params)
        return params, opt_state, loss

    k0 = jax.random.fold_in(key, 0)
    return {"pallas_calls": count_pallas_calls(
        jax.make_jaxpr(step)(params, opt_state, batch, k0))}


def sweep(task: str, presets: List[str], ft: FtConfig = FtConfig()
          ) -> Dict[str, float]:
    out = {}
    for p in presets:
        t0 = time.time()
        metric, _ = finetune(task, QuantConfig.preset(p), ft)
        out[p] = metric
        print(f"  {task:5s} {p:10s} metric={metric:6.2f} ({time.time()-t0:.0f}s)",
              flush=True)
    return out
