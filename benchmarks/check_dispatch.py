"""Traced-dispatch regression gate (CI) — quantlint QL004.

Counts the ``pallas_call`` equations traced for every integer-layer entry
point on the pallas backend — the quantity the single-dispatch limb fusion
minimized (ISSUE 4) — and compares them against the checked-in baseline
``benchmarks/dispatch_baseline.json``.  Counting and comparison are the
analyzer's (``repro.analysis``): the layer sections (linears, norms, fused
attention fwd/bwd/decode) pin plain traced counts, while the model-level
``policy`` and ``serve`` sections pin BOTH the ``traced`` count
(program-text size) and the scan-``effective`` count (per-step kernel
launches, scan bodies multiplied by their trip count) — so neither a
reintroduced per-limb dispatch loop, an accidental layer-stack split, nor
an O(prompt_len) prompt-admission loop can land silently.  Any count ABOVE baseline fails the gate; counts below are
reported as improvements (refresh with ``--update`` to lock them in).

    PYTHONPATH=src python -m benchmarks.check_dispatch            # gate
    PYTHONPATH=src python -m benchmarks.check_dispatch --update   # re-pin

``tests/test_dispatch_baseline.py`` runs the same comparison as a tier-1
test, so the gate also trips locally before CI.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp

from repro.analysis import rules
from repro.core import int_ops
from repro.core.qconfig import QuantConfig
from repro.core.qpolicy import QuantPolicy, preset_rules
from repro.utils import count_pallas_calls

BASELINE_PATH = os.path.join(os.path.dirname(__file__),
                             "dispatch_baseline.json")


def _cfg(preset: str) -> QuantConfig:
    # backend pinned: the counts must not depend on $REPRO_BACKEND
    return dataclasses.replace(QuantConfig.preset(preset), backend="pallas",
                               stochastic_grad=False)


def current_counts() -> dict:
    """Traced pallas_call counts per layer/preset, forward and fwd+bwd."""
    key = jax.random.PRNGKey(0)
    counts: dict = {}

    def count(fn, *args):
        return count_pallas_calls(jax.make_jaxpr(fn)(*args))

    for preset in ("int8", "int12", "int16"):
        cfg = _cfg(preset)
        x = jax.random.normal(key, (4, 8, 32))
        w = jax.random.normal(jax.random.fold_in(key, 1), (32, 16)) * 0.1
        lin = lambda x, w: int_ops.int_linear(x, w, None, None, cfg)
        lin_l = lambda x, w: jnp.sum(lin(x, w) ** 2)

        xb = jax.random.normal(key, (4, 8, 32))
        wb = jax.random.normal(jax.random.fold_in(key, 2), (4, 32, 16)) * 0.1
        bl = lambda x, w: int_ops.int_batched_linear(x, w, None, cfg)
        bl_l = lambda x, w: jnp.sum(bl(x, w) ** 2)

        d = jax.random.normal(key, (16, 64))
        gm = jnp.ones((64,))
        bt = jnp.zeros((64,))
        ln = lambda x: int_ops.int_layernorm(x, gm, bt, None, cfg)
        ln_l = lambda x: jnp.sum(ln(x) ** 2)
        rn = lambda x: int_ops.int_rmsnorm(x, gm, None, cfg)
        rn_l = lambda x: jnp.sum(rn(x) ** 2)

        # fused integer flash attention: fwd is 3 quantizes + 1 kernel,
        # fwd+bwd adds the grad quantize and the dq / dkv kernels, decode
        # (Sq=1 over a cache) must match the fwd count — one fused launch
        # per direction, never a per-chunk or per-token dispatch loop
        qa = jax.random.normal(key, (2, 16, 2, 2, 32))
        ka = jax.random.normal(jax.random.fold_in(key, 3), (2, 16, 2, 32))
        va = jax.random.normal(jax.random.fold_in(key, 4), (2, 16, 2, 32))
        q1 = jax.random.normal(jax.random.fold_in(key, 5), (2, 1, 2, 2, 32))
        att = lambda q, k, v: int_ops.int_attention(
            q, k, v, jnp.asarray(0), None, cfg, cfg, True, None)
        att_l = lambda q, k, v: jnp.sum(att(q, k, v) ** 2)
        dec = lambda q, k, v: int_ops.int_attention(
            q, k, v, jnp.asarray(7), None, cfg, cfg, True, None)

        # grouped (sorted-rows) linear of an expert share: 2 groups of one
        # 8-row tile each, then an unused tile
        xg = jax.random.normal(key, (24, 32))
        wg = jax.random.normal(jax.random.fold_in(key, 6), (2, 32, 16)) * 0.1
        off = jnp.asarray([0, 8, 16], jnp.int32)
        gl = lambda x, w: int_ops.int_grouped_linear(x, w, off, None, cfg, 8)
        gl_l = lambda x, w: jnp.sum(gl(x, w) ** 2)

        counts[preset] = {
            "grouped_linear_fwd": count(gl, xg, wg),
            "grouped_linear_fwd_bwd": count(
                jax.grad(gl_l, argnums=(0, 1)), xg, wg),
            "linear_fwd": count(lin, x, w),
            "linear_fwd_bwd": count(jax.grad(lin_l, argnums=(0, 1)), x, w),
            "batched_linear_fwd": count(bl, xb, wb),
            "batched_linear_fwd_bwd": count(
                jax.grad(bl_l, argnums=(0, 1)), xb, wb),
            "layernorm_fwd": count(ln, d),
            "layernorm_fwd_bwd": count(jax.grad(ln_l), d),
            "rmsnorm_fwd": count(rn, d),
            "rmsnorm_fwd_bwd": count(jax.grad(rn_l), d),
            "attention_fwd": count(att, qa, ka, va),
            "attention_fwd_bwd": count(
                jax.grad(att_l, argnums=(0, 1, 2)), qa, ka, va),
            "attention_decode": count(dec, q1, ka, va),
        }
    counts["policy"] = policy_counts()
    counts["serve"] = serve_counts()
    return counts


def policy_counts() -> dict:
    """Model-level traced dispatch counts under mixed-precision policies.

    Pins the single-dispatch guarantee under non-uniform bit-widths: a
    mixed policy whose rules only touch non-stacked scopes (embeddings /
    head — ``int8_embed16``) must trace EXACTLY the uniform int8 count,
    and a policy that splits the layer stack (``int8_firstlast16``) traces
    one extra scan body per run of identically-resolved layers — both are
    pinned so neither a reintroduced per-limb loop nor an accidental
    stack split can land silently.  Each entry pins ``{"traced",
    "effective"}`` (statically derived by ``repro.analysis``): the traced
    number is program-text size, the effective number is per-step kernel
    launches with scan bodies multiplied by their trip count — a stack
    split grows the former but must NOT grow the latter.  Explicit
    ``QuantPolicy`` objects are used throughout so the counts are
    independent of ``$REPRO_QPOLICY``.
    """
    from repro.models import paper_models as pm

    key = jax.random.PRNGKey(0)
    cfg = pm.bert_config(n_layers=4, d_model=64, n_heads=4, d_ff=128,
                         vocab=128, name="bert-gate")
    params = pm.bert_init(key, cfg, num_labels=4)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab),
             "labels": jnp.zeros((2,), jnp.int32)}
    base = _cfg("int8")

    def step_counts(policy):
        def loss(p):
            return pm.bert_cls_loss(p, batch, cfg, policy, None)[0]
        return rules.dispatch_counts(jax.make_jaxpr(jax.grad(loss))(params))

    return {
        "bert_step_int8": step_counts(QuantPolicy(base=base)),
        "bert_step_int8_embed16": step_counts(
            QuantPolicy(base=base, rules=preset_rules("int8_embed16"))),
        "bert_step_int8_firstlast16": step_counts(
            QuantPolicy(base=base, rules=preset_rules("int8_firstlast16"))),
        # integer kept ops swap IN-KERNEL (exp/rsqrt) or at the XLA level
        # (activations) — ZERO extra dispatches vs the same uniform int8
        # step, pinned as its own entry so the property can't drift
        "bert_step_int8_keptint": step_counts(QuantPolicy(
            base=dataclasses.replace(base, kept_ops="integer"))),
    }


def serve_counts() -> dict:
    """Per-prompt prefill dispatch on the serve path.

    Pins the chunked-prefill guarantee: admitting a whole prompt is ONE
    ``lm_prefill_cache`` trace whose kernel-launch counts are independent of
    the prompt length's token count — a reintroduced per-token admission
    loop (O(prompt_len) decode dispatches, the pre-ISSUE-7 engine) would
    multiply the traced count by the prompt length and trip this gate.
    """
    from repro.configs import registry
    from repro.models import lm

    key = jax.random.PRNGKey(0)
    cfg = registry.get_config("smollm-135m").reduced()
    params = lm.lm_init(key, cfg)
    cache = lm.init_cache(cfg, 2, 32, dtype=jnp.float32)
    tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab)
    qcfg = _cfg("int8")

    def prefill(p, t, c):
        return lm.lm_prefill_cache(p, t, c, cfg, qcfg)

    return {"lm_prefill_len8": rules.dispatch_counts(
        jax.make_jaxpr(prefill)(params, tokens, cache))}


def compare(current: dict, baseline: dict) -> tuple[list, list]:
    """Returns (QL004 findings, improvements).

    Delegates to ``repro.analysis.rules.check_dispatch_budget``: any count
    above baseline, a baseline entry with no derived counterpart
    ("MISSING"), or a derived entry the baseline does not pin ("UNPINNED")
    is a finding — a newly counted layer must be pinned with ``--update``
    or it would silently escape the gate, exactly the code most likely to
    regress.  Improvements are ``(key, base, cur)`` rows to re-pin.
    """
    return rules.check_dispatch_budget(current, baseline)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", default=BASELINE_PATH)
    ap.add_argument("--update", action="store_true",
                    help="rewrite the baseline with the current counts")
    args = ap.parse_args()

    current = current_counts()
    if args.update:
        with open(args.baseline, "w") as f:
            json.dump(current, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.baseline}")
        return

    with open(args.baseline) as f:
        baseline = json.load(f)
    findings, improvements = compare(current, baseline)
    for key, base, cur in improvements:
        print(f"IMPROVED  {key}: {base} -> {cur} (run --update to pin)")
    if findings:
        for f in findings:
            print(f"REGRESSED {f}", file=sys.stderr)
        sys.exit(1)
    print(f"dispatch counts OK ({sum(len(v) for v in baseline.values())} "
          "entries at or below baseline)")


if __name__ == "__main__":
    main()
