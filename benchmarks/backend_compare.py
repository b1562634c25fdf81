"""sim-vs-pallas backend comparison: accuracy divergence + wall-clock.

For every preset in ``qconfig.PRESETS`` this task runs ``int_linear``
forward and backward through both backends on a transformer-ish shape grid,
and reports

* ``max_abs_diff`` / ``rel_diff`` — backend divergence (bounded by f32
  accumulation rounding; the pallas path is the bit-exact reference),
* ``prop1_bound`` — the Proposition 1 mapping step of the output, the
  acceptance envelope the divergence must stay inside,
* per-backend wall-clock (µs/call, best of ``repeats``; note the pallas
  backend runs in interpret mode off-TPU — its CPU timings measure the
  interpreter, not the kernel),
* an MoE section (``moe_dispatch``) counting traced ``pallas_call``
  dispatches of the batched expert-axis kernels vs the per-expert unrolled
  loop they replaced — the dispatch-count reduction is ~E× per direction,
* a norm section (``norm_bwd``) timing the fused layer-norm / RMS-norm
  forward+backward kernels against the sim backend and pinning their
  dispatch counts (3 fwd / 5 fwd+bwd — no XLA statistics recompute),
* a matmul section (``matmul_dispatch``): traced ``pallas_call`` counts and
  timings per direction (NN/NT/TN and batched E=8) per bit-width — ONE
  dispatch per direction at every width since the single-dispatch limb
  fusion (was ``limbs²`` ≤ 9) — plus the HBM-bytes traffic model from
  ``benchmarks/roofline.py`` (off-TPU the timings measure the Pallas
  interpreter, so the byte model is what makes them interpretable),
* a policy section (``policy``): per-scope resolved bit-widths of the
  ``int8_embed16`` mixed-precision QuantPolicy plus per-step traced
  dispatch counts for uniform-int8 vs mixed on the proxy fine-tune step —
  the mixed policy's dispatch delta is pinned at 0,
* a state-plane section (``state_plane``): the collective wire-bytes model
  (f32 vs QTensor int8/int16) for the two param-sized collectives of a real
  reduced config — FSDP param all-gather and grad psum — plus resident
  optimizer-moment bytes (f32 Adam m/v vs QTensor moments), all from
  ``eval_shape`` so no device work is involved,
* a kept-ops section (``kept_ops``): measured max error of every
  ``core/iapprox.py`` integer approximation against its exact-f64 oracle in
  ``kernels/ref.py`` over a dense domain grid, next to the DESIGN.md §10
  documented bound, plus wall-clock of the swapped layers (norm / attention
  / activation) and a BERT-tiny forward under ``kept_ops="fp32"`` vs
  ``kept_ops="integer"``,
* an attention section (``attention``): the fused integer flash-attention
  op per preset — sim-vs-pallas fwd/bwd divergence (bit-exact by
  construction: both backends quantize P and dS at identical points),
  traced dispatch counts (4 fwd / 7 fwd+bwd / 4 decode) and per-backend
  wall-clock on a training shape and a decode shape.

Emits a single JSON document (stdout, or ``--out FILE``):

    PYTHONPATH=src python -m benchmarks.backend_compare
    PYTHONPATH=src python -m benchmarks.backend_compare --out cmp.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dfx, int_ops
from repro.core.qconfig import PRESETS, QuantConfig
from repro.kernels import ops as kops
from repro.utils import count_pallas_calls

#: (M, K, N) grid: a decode-ish row count, a train-ish tile, a ragged shape.
SHAPES = ((32, 256, 128), (128, 128, 128), (96, 200, 72))

#: (E, C, K, N): a Mixtral-ish expert FFN tile, scaled to CPU interpret mode.
MOE_SHAPE = (8, 64, 256, 128)

#: (R, D) norm shapes: a train-ish tile and a ragged row count (pad path).
NORM_SHAPES = ((256, 512), (96, 384))


def _time_us(fn, repeats: int) -> float:
    fn()                                   # compile / warm the caches
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def compare_preset(preset: str, repeats: int = 3) -> dict:
    key = jax.random.PRNGKey(0)
    sim = dataclasses.replace(QuantConfig.preset(preset),
                              stochastic_grad=False, backend="sim")
    pal = dataclasses.replace(sim, backend="pallas")
    rows = []
    for (M, K, N) in SHAPES:
        x = jax.random.normal(key, (M, K))
        w = jax.random.normal(jax.random.fold_in(key, 1), (K, N)) * 0.1
        r = jax.random.normal(jax.random.fold_in(key, 2), (M, N))

        def loss(x, w, cfg):
            return jnp.sum(int_ops.int_linear(x, w, None, None, cfg) * r)

        grad = jax.grad(loss, argnums=(0, 1))
        fwd = {c.backend: jax.jit(lambda x, w, c=c: int_ops.int_linear(
            x, w, None, None, c)) for c in (sim, pal)}
        bwd = {c.backend: jax.jit(lambda x, w, c=c: grad(x, w, c))
               for c in (sim, pal)}

        ys, yp = fwd["sim"](x, w), fwd["pallas"](x, w)
        gs, gp = bwd["sim"](x, w), bwd["pallas"](x, w)
        diff = float(jnp.abs(ys - yp).max())
        gdiff = max(float(jnp.abs(a - b).max()) for a, b in zip(gs, gp))
        scale = float(jnp.abs(ys).max()) + 1e-12
        bits = min(sim.act_bits, sim.weight_bits) if sim.enabled else 24
        rows.append({
            "shape": [M, K, N],
            "fwd_max_abs_diff": diff,
            "fwd_rel_diff": diff / scale,
            "bwd_max_abs_diff": gdiff,
            "prop1_bound": float(dfx.error_bound(ys, bits)),
            "sim_fwd_us": _time_us(lambda: fwd["sim"](x, w), repeats),
            "pallas_fwd_us": _time_us(lambda: fwd["pallas"](x, w), repeats),
            "sim_bwd_us": _time_us(lambda: bwd["sim"](x, w), repeats),
            "pallas_bwd_us": _time_us(lambda: bwd["pallas"](x, w), repeats),
        })
    return {
        "preset": preset,
        "enabled": sim.enabled,
        "bits": {"weight": sim.weight_bits, "act": sim.act_bits,
                 "grad": sim.grad_bits},
        "sim_accum_exact": (dfx.sim_accum_exact(
            sim.act_bits, sim.weight_bits, SHAPES[0][1])
            if sim.enabled else True),
        "shapes": rows,
    }


def moe_dispatch_report(preset: str = "int8") -> dict:
    """Traced pallas_call dispatch counts for the MoE expert matmuls.

    ``batched_*`` is the shipped path (expert axis on the kernel grid, ONE
    launch per direction covering every expert and limb pair);
    ``unrolled_fwd`` re-creates the per-expert Python loop PR 2 removed, so
    the reduction factor is measured, not assumed.
    """
    E, C, K, N = MOE_SHAPE
    key = jax.random.PRNGKey(0)
    cfg = dataclasses.replace(QuantConfig.preset(preset), backend="pallas",
                              stochastic_grad=False)
    x = jax.random.normal(key, (E, C, K))
    w = jax.random.normal(jax.random.fold_in(key, 1), (E, K, N)) * 0.1

    def fwd(x, w):
        return int_ops.int_batched_linear(x, w, None, cfg)

    def loss(x, w):
        return jnp.sum(fwd(x, w) ** 2)

    def unrolled_fwd(x, w):
        ys = []
        for e in range(E):
            qx = int_ops._pallas_quantize(x[e], cfg.act_bits)
            qw = int_ops._pallas_quantize(w[e], cfg.weight_bits)
            ys.append(kops.dfx_matmul_tiled(qx.m, qx.exp, cfg.act_bits,
                                            qw.m, qw.exp, cfg.weight_bits))
        return jnp.stack(ys)

    n_fwd = count_pallas_calls(jax.make_jaxpr(fwd)(x, w))
    n_fwd_bwd = count_pallas_calls(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w))
    n_unrolled = count_pallas_calls(jax.make_jaxpr(unrolled_fwd)(x, w))
    return {
        "shape": {"E": E, "C": C, "K": K, "N": N},
        "preset": preset,
        "pallas_dispatches": {
            "batched_fwd": n_fwd,
            "batched_fwd_bwd": n_fwd_bwd,
            "unrolled_fwd": n_unrolled,
        },
        "fwd_dispatch_reduction": n_unrolled / n_fwd,
    }


def matmul_dispatch_report(repeats: int = 3) -> dict:
    """Traced ``pallas_call`` counts + timings per matmul direction/bit-width.

    The acceptance property of the single-dispatch limb fusion: every
    direction (forward NN, backward NT/TN — unbatched and batched at E=8)
    traces exactly ONE kernel launch at every bit-width; ``old_dispatches``
    records the ``limbs²`` launches the removed per-pair loop issued.  The
    ``hbm_bytes`` entries come from the traffic model in
    ``benchmarks/roofline.py`` (fused vs unfused, same block shapes).
    """
    from benchmarks.roofline import matmul_hbm_bytes
    from repro.kernels.dfx_quant import n_limbs

    key = jax.random.PRNGKey(0)
    M, K, N = 256, 384, 128
    E = 8
    x = jax.random.normal(key, (M, K)) * 2.0
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N)) * 0.3
    g = jax.random.normal(jax.random.fold_in(key, 2), (M, N))
    xb = jax.random.normal(jax.random.fold_in(key, 3), (E, M, K))
    wb = jax.random.normal(jax.random.fold_in(key, 4), (E, K, N)) * 0.3
    gb = jax.random.normal(jax.random.fold_in(key, 5), (E, M, N))

    out = {"shape": {"M": M, "K": K, "N": N, "E": E}, "bitwidths": {}}
    for bits in (8, 12, 16):
        L = n_limbs(bits)
        qx, qw, qg = (dfx.quantize(x, bits), dfx.quantize(w, bits),
                      dfx.quantize(g, bits))
        qxb = dfx.quantize(xb, bits, reduce_axes=(1, 2))
        qwb = dfx.quantize(wb, bits, reduce_axes=(1, 2))
        qgb = dfx.quantize(gb, bits, reduce_axes=(1, 2))
        dirs = {
            "nn": lambda: kops.dfx_matmul_tiled(
                qx.m, qx.exp, bits, qw.m, qw.exp, bits),
            "nt": lambda: kops.dfx_matmul_tiled_nt(
                qg.m, qg.exp, bits, qw.m, qw.exp, bits),
            "tn": lambda: kops.dfx_matmul_tiled_tn(
                qx.m, qx.exp, bits, qg.m, qg.exp, bits),
            "batched_nn": lambda: kops.dfx_matmul_tiled_batched(
                qxb.m, qxb.exp, bits, qwb.m, qwb.exp, bits),
            "batched_nt": lambda: kops.dfx_matmul_tiled_batched_nt(
                qgb.m, qgb.exp, bits, qwb.m, qwb.exp, bits),
            "batched_tn": lambda: kops.dfx_matmul_tiled_batched_tn(
                qxb.m, qxb.exp, bits, qgb.m, qgb.exp, bits),
        }
        rows = {}
        for name, fn in dirs.items():
            rows[name] = {
                "pallas_calls": count_pallas_calls(jax.make_jaxpr(fn)()),
                "us": _time_us(jax.jit(fn), repeats),
            }
        out["bitwidths"][f"b{bits}"] = {
            "limbs": L,
            "old_dispatches_per_direction": L * L,
            "directions": rows,
            "hbm_bytes_fused": matmul_hbm_bytes(M, K, N, L, L)["total"],
            "hbm_bytes_unfused": matmul_hbm_bytes(M, K, N, L, L,
                                                  fused=False)["total"],
        }
    return out


def norm_bwd_report(preset: str = "int16", repeats: int = 3) -> dict:
    """Fused norm fwd+bwd: traced dispatch counts + per-backend timing.

    ``fwd_pallas_calls`` / ``fwd_bwd_pallas_calls`` pin the acceptance
    property of the fused norm kernels: forward is 3 dispatches (quantize x,
    quantize gamma, fused multi-output fwd) and forward+backward is 5
    (+ quantize g, fused bwd) — the statistics are never recomputed in XLA.
    Timings carry the same caveat as the rest of this file: off-TPU the
    pallas numbers measure the interpreter (``pallas_interpret`` in the
    top-level document), not the kernel.
    """
    key = jax.random.PRNGKey(0)
    sim = dataclasses.replace(QuantConfig.preset(preset),
                              stochastic_grad=False, backend="sim")
    pal = dataclasses.replace(sim, backend="pallas")
    layers = {}
    for name in ("layernorm", "rmsnorm"):
        rows = []
        for (R, D) in NORM_SHAPES:
            x = jax.random.normal(key, (R, D)) * 2.0
            gm = jnp.ones((D,)) * 1.1
            bt = jnp.zeros((D,))
            if name == "layernorm":
                apply = lambda x, c: int_ops.int_layernorm(x, gm, bt, None, c)
            else:
                apply = lambda x, c: int_ops.int_rmsnorm(x, gm, None, c)
            fwd = {c.backend: jax.jit(lambda x, c=c: apply(x, c))
                   for c in (sim, pal)}
            bwd = {c.backend: jax.jit(jax.grad(
                lambda x, c=c: jnp.sum(apply(x, c) ** 2))) for c in (sim, pal)}
            ys, yp = fwd["sim"](x), fwd["pallas"](x)
            gs, gp = bwd["sim"](x), bwd["pallas"](x)
            rows.append({
                "shape": [R, D],
                "fwd_max_abs_diff": float(jnp.abs(ys - yp).max()),
                "bwd_max_abs_diff": float(jnp.abs(gs - gp).max()),
                "fwd_pallas_calls": count_pallas_calls(
                    jax.make_jaxpr(lambda x: apply(x, pal))(x)),
                "fwd_bwd_pallas_calls": count_pallas_calls(jax.make_jaxpr(
                    jax.grad(lambda x: jnp.sum(apply(x, pal) ** 2)))(x)),
                "sim_fwd_us": _time_us(lambda: fwd["sim"](x), repeats),
                "pallas_fwd_us": _time_us(lambda: fwd["pallas"](x), repeats),
                "sim_bwd_us": _time_us(lambda: bwd["sim"](x), repeats),
                "pallas_bwd_us": _time_us(lambda: bwd["pallas"](x), repeats),
            })
        layers[name] = rows
    return {"preset": preset, "layers": layers}


def policy_report(preset: str = "int8_embed16") -> dict:
    """Mixed-precision policy vs uniform base: per-scope resolved bits +
    per-step traced dispatches on the proxy fine-tune task.

    The resolved table is what a ``QuantPolicy`` actually hands each call
    site (the per-tensor-class leaf configs); the step rows pin the
    acceptance property that a policy touching only non-stacked scopes
    (embeddings/head) traces the exact uniform dispatch count.  Explicit
    policies are constructed so the section is independent of
    ``$REPRO_QPOLICY``.
    """
    from benchmarks.tasks import FtConfig, step_stats
    from repro.core.qpolicy import QuantPolicy, preset_rules

    base = dataclasses.replace(QuantConfig.int8(), backend="pallas",
                               stochastic_grad=False)
    uniform = QuantPolicy(base=base)
    mixed = QuantPolicy(base=base, rules=preset_rules(preset))
    probe_paths = ("embed", "embed_ln", "blocks.0.attn.wq", "blocks.0.mlp.w1",
                   "blocks.2.ln1", "head")
    resolved = {}
    for path in probe_paths:
        leaf = mixed.resolve(path)
        resolved[path] = {"weight_bits": leaf.weight_bits,
                          "act_bits": leaf.act_bits,
                          "grad_bits": leaf.grad_bits}
    ft = FtConfig(steps=1)
    rows = {}
    for name, pol in (("uniform_int8", uniform), (preset, mixed)):
        s = step_stats("cls", pol, ft)
        rows[name] = {"pallas_calls_per_step": s["pallas_calls"]}
    return {"preset": preset, "resolved_bits": resolved, "steps": rows,
            "dispatch_delta_vs_uniform":
                rows[preset]["pallas_calls_per_step"]
                - rows["uniform_int8"]["pallas_calls_per_step"]}


def state_plane_report(arch: str = "smollm-135m", n_shards: int = 8) -> dict:
    """Wire + resident bytes of the quantized state plane, f32 vs QTensor.

    Param counts come from ``eval_shape`` on the reduced config (no arrays
    are materialised).  The per-collective rows reuse the traffic model in
    ``benchmarks/roofline.py``; the ``optimizer_moments`` rows count the
    resident m/v bytes per ``core/qtensor.wire_bytes`` (one int32 exponent
    per moment tensor — FP32 masters are kept separately and unchanged, so
    they are excluded from both sides of the comparison).
    """
    from benchmarks.roofline import collective_wire_bytes
    from repro.configs import registry
    from repro.core import qtensor
    from repro.models import lm

    cfg = registry.get_config(arch).reduced()
    shapes = jax.eval_shape(lambda: lm.lm_init(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree_util.tree_leaves(shapes)
    n_params = int(sum(np.prod(l.shape) for l in leaves))

    out = {"arch": arch, "reduced": True, "n_params": n_params,
           "n_tensors": len(leaves), "n_shards": n_shards, "bitwidths": {}}
    for bits in (8, 16):
        wire = collective_wire_bytes(n_params, bits, n_shards=n_shards)
        f32_moments = 2 * 4 * n_params                     # Adam m + v
        q_moments = 2 * sum(qtensor.wire_bytes(int(np.prod(l.shape)), bits)
                            for l in leaves)
        out["bitwidths"][f"b{bits}"] = {
            "param_all_gather": wire["param_all_gather"],
            "grad_psum": wire["grad_psum"],
            "combined_wire_reduction": wire["combined_reduction"],
            "optimizer_moments": {
                "f32_bytes": f32_moments,
                "qtensor_bytes": q_moments,
                "reduction": f32_moments / q_moments,
            },
        }
    return out


def attention_report(repeats: int = 3) -> dict:
    """Fused integer flash attention: sim-vs-pallas divergence, traced
    dispatch counts and timings per preset.

    Both backends share every quantization point (q/k/v in, P at the static
    ``-(p_bits-1)`` exponent against the running max, dS at the norm-derived
    exponent), so fwd AND bwd divergence is exactly 0 — pinned here, and in
    tests/test_int_attention.py per preset.  Dispatch counts pin the fused
    property: 4 launches fwd (3 quantizes + kernel), 7 fwd+bwd (+ grad
    quantize, dq kernel, dkv kernel), 4 decode — independent of sequence
    length and never a per-chunk loop.
    """
    key = jax.random.PRNGKey(0)
    B, Sq, KV, G, hd = 2, 64, 2, 2, 32
    q = jax.random.normal(key, (B, Sq, KV, G, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sq, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sq, KV, hd))
    q1 = jax.random.normal(jax.random.fold_in(key, 3), (B, 1, KV, G, hd))

    rows = {}
    for preset in PRESETS:
        sim = dataclasses.replace(QuantConfig.preset(preset),
                                  stochastic_grad=False, backend="sim")
        if not sim.enabled:
            continue
        pal = dataclasses.replace(sim, backend="pallas")

        def att(q, k, v, cfg):
            return int_ops.int_attention(q, k, v, jnp.asarray(0), None,
                                         cfg, cfg, True, None)

        def att_l(q, k, v, cfg):
            return jnp.sum(att(q, k, v, cfg) ** 2)

        fwd = {c.backend: jax.jit(lambda q, k, v, c=c: att(q, k, v, c))
               for c in (sim, pal)}
        bwd = {c.backend: jax.jit(jax.grad(
            lambda q, k, v, c=c: att_l(q, k, v, c), argnums=(0, 1, 2)))
            for c in (sim, pal)}
        dec = jax.jit(lambda q, k, v, c=pal: int_ops.int_attention(
            q, k, v, jnp.asarray(Sq - 1), None, c, c, True, None))

        ys, yp = fwd["sim"](q, k, v), fwd["pallas"](q, k, v)
        gs, gp = bwd["sim"](q, k, v), bwd["pallas"](q, k, v)
        rows[preset] = {
            "fwd_max_abs_diff": float(jnp.abs(ys - yp).max()),
            "bwd_max_abs_diff": max(float(jnp.abs(a - b).max())
                                    for a, b in zip(gs, gp)),
            "fwd_pallas_calls": count_pallas_calls(jax.make_jaxpr(
                lambda q, k, v: att(q, k, v, pal))(q, k, v)),
            "fwd_bwd_pallas_calls": count_pallas_calls(jax.make_jaxpr(
                jax.grad(lambda q, k, v: att_l(q, k, v, pal),
                         argnums=(0, 1, 2)))(q, k, v)),
            "decode_pallas_calls": count_pallas_calls(jax.make_jaxpr(
                lambda q, k, v: dec(q, k, v))(q1, k, v)),
            "sim_fwd_us": _time_us(lambda: fwd["sim"](q, k, v), repeats),
            "pallas_fwd_us": _time_us(lambda: fwd["pallas"](q, k, v), repeats),
            "sim_bwd_us": _time_us(lambda: bwd["sim"](q, k, v), repeats),
            "pallas_bwd_us": _time_us(lambda: bwd["pallas"](q, k, v), repeats),
            "pallas_decode_us": _time_us(lambda: dec(q1, k, v), repeats),
        }
    return {"shape": {"B": B, "Sq": Sq, "KV": KV, "G": G, "hd": hd},
            "presets": rows}


def kept_ops_report(repeats: int = 3) -> dict:
    """Integer kept ops (DESIGN.md §10): measured error vs documented bound,
    and the cost of the swap.

    ``per_op`` evaluates each ``iapprox`` approximation on a dense grid over
    its documented domain against the exact-f64 oracle in ``kernels/ref.py``
    and reports the measured max error beside the §10 bound (the same table
    tests/test_iapprox.py enforces).  ``layers`` and ``bert_fwd`` time the
    swapped call sites under ``kept_ops="fp32"`` vs ``"integer"`` — off-TPU
    this measures XLA on the iapprox arithmetic, not a fused kernel, so the
    interesting number is the ratio staying O(1), not the absolute µs.
    """
    from repro.core import iapprox
    from repro.kernels import ref
    from repro.models import paper_models as pm

    key = jax.random.PRNGKey(0)
    f64 = lambda a: np.asarray(a, np.float64)              # noqa: E731

    def _rel(approx, exact):
        return float(np.max(np.abs(f64(approx) - f64(exact))
                            / np.maximum(np.abs(f64(exact)), 1e-300)))

    def _abs(approx, exact):
        return float(np.max(np.abs(f64(approx) - f64(exact))))

    x30 = jnp.asarray(np.linspace(-30.0, 30.0, 100_001), jnp.float32)
    x10 = jnp.asarray(np.linspace(-10.0, 10.0, 100_001), jnp.float32)
    pos = jnp.asarray(np.concatenate([
        np.linspace(0.5, 4.0, 50_001),
        np.logspace(-30, 30, 50_001, base=2.0)]).astype(np.float32))
    rows_x = jax.random.normal(key, (64, 128)) * 5.0
    per_op = {
        "i_exp": {"metric": "rel", "bound": 3e-4,
                  "measured": _rel(iapprox.i_exp(x30), ref.i_exp_ref(x30))},
        "i_recip": {"metric": "rel", "bound": 4e-4,
                    "measured": _rel(iapprox.i_recip(pos),
                                     ref.i_recip_ref(pos))},
        "i_rsqrt": {"metric": "rel", "bound": 4e-4,
                    "measured": _rel(iapprox.i_rsqrt(pos),
                                     ref.i_rsqrt_ref(pos))},
        "i_sqrt": {"metric": "rel", "bound": 4e-4,
                   "measured": _rel(iapprox.i_sqrt(pos),
                                    ref.i_sqrt_ref(pos))},
        "i_sigmoid": {"metric": "abs", "bound": 1e-3,
                      "measured": _abs(iapprox.i_sigmoid(x30),
                                       ref.i_sigmoid_ref(x30))},
        "i_tanh": {"metric": "abs", "bound": 1e-3,
                   "measured": _abs(iapprox.i_tanh(x30),
                                    ref.i_tanh_ref(x30))},
        "i_gelu": {"metric": "abs", "bound": 2e-3,
                   "measured": _abs(iapprox.i_gelu(x10),
                                    ref.i_gelu_ref(x10))},
        "i_silu": {"metric": "abs", "bound": 4e-3,
                   "measured": _abs(iapprox.i_silu(x30),
                                    ref.i_silu_ref(x30))},
        "i_softmax": {"metric": "abs", "bound": 1e-3,
                      "measured": _abs(iapprox.i_softmax(rows_x),
                                       ref.i_softmax_ref(rows_x))},
    }
    for name, row in per_op.items():
        assert row["measured"] <= row["bound"], (name, row)

    # swapped-layer timings: fp32-kept vs integer-kept, sim backend
    cfgs = {kept: dataclasses.replace(QuantConfig.int8(),
                                      stochastic_grad=False, backend="sim",
                                      kept_ops=kept)
            for kept in ("fp32", "integer")}
    xln = jax.random.normal(key, (256, 512))
    gm, bt = jnp.ones((512,)), jnp.zeros((512,))
    q = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 2, 2, 32))
    k = jax.random.normal(jax.random.fold_in(key, 2), (2, 64, 2, 32))
    v = jax.random.normal(jax.random.fold_in(key, 3), (2, 64, 2, 32))
    xact = jax.random.normal(jax.random.fold_in(key, 4), (256, 512))
    layer_fns = {
        "layernorm": lambda c: int_ops.int_layernorm(xln, gm, bt, None, c),
        "attention": lambda c: int_ops.int_attention(
            q, k, v, jnp.asarray(0), None, c, c, True, None),
        "gelu": lambda c: int_ops.int_activation(xact, c, "gelu"),
        "silu": lambda c: int_ops.int_activation(xact, c, "silu"),
    }
    layers = {}
    for name, fn in layer_fns.items():
        row = {kept: _time_us(jax.jit(lambda c=c: fn(c)), repeats)
               for kept, c in cfgs.items()}
        row["integer_over_fp32"] = row["integer"] / row["fp32"]
        layers[name] = row

    # the acceptance subject: BERT-tiny forward, both kept modes
    bcfg = pm.bert_config(n_layers=2, d_model=64, n_heads=2, d_ff=128,
                          vocab=128, name="bert-tiny")
    params = pm.bert_init(jax.random.PRNGKey(1), bcfg)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, bcfg.vocab, (2, 16)))
    bert = {}
    for kept, c in cfgs.items():
        step = jax.jit(lambda p, t, c=c: pm.bert_apply(p, t, bcfg, c, None))
        bert[kept] = _time_us(lambda: step(params, toks), repeats)
    bert["integer_over_fp32"] = bert["integer"] / bert["fp32"]
    return {"per_op": per_op, "layers": layers, "bert_fwd_us": bert}


def robustness_report(steps: int = 20) -> dict:
    """Fault-injection recovery + sentinel skip, measured end to end.

    Two experiments on a deterministic toy objective through ``int_linear``
    (pure function of (state, step), so restore-and-replay must reproduce the
    clean trajectory *exactly*):

    * ``chaos_vs_clean`` — a 20-step loop with an injected preemption, a
      state bit-flip and a dropped psum participant, recovered by
      ``run_with_recovery`` + crc-verified checkpoints; reports the
      structured event feed and the final-state delta vs the uninjected run
      (acceptance: exactly 0.0).
    * ``sentinel_skip`` — the sentinel step with an injected NaN gradient;
      reports the skipped flag and whether params/opt-state pass through the
      skipped step bit-identical (acceptance: yes).
    """
    import tempfile

    from repro.train import (chaos as chaos_lib, checkpoint, fault,
                             optimizer as opt_lib, sentinel as sentinel_lib)

    key = jax.random.PRNGKey(0)
    cfg_q = dataclasses.replace(QuantConfig.int8(), stochastic_grad=False)
    w0 = jax.random.normal(key, (16, 16)) * 0.1
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, 16))

    def loss(w):
        return jnp.mean(int_ops.int_linear(x, w, None, None, cfg_q) ** 2)

    sgd = jax.jit(lambda w: w - 0.1 * jax.grad(loss)(w))

    def run_loop(ccfg, ckpt_dir):
        events = []
        monkey = chaos_lib.ChaosMonkey(ccfg)

        def step_fn(state, step):
            return {"w": sgd(state["w"])}

        def restore_fn():
            got = checkpoint.restore_latest(ckpt_dir, {"w": w0},
                                            on_event=events.append)
            if got is None:
                return {"w": w0}, 0
            return got

        final = fault.run_with_recovery(
            monkey.wrap(step_fn), {"w": w0}, start_step=0, num_steps=steps,
            save_fn=lambda st, k: checkpoint.save(ckpt_dir, k, st),
            restore_fn=restore_fn, save_every=5, on_event=events.append)
        return final, events

    with tempfile.TemporaryDirectory() as d:
        clean, _ = run_loop(chaos_lib.ChaosConfig(), d)
    with tempfile.TemporaryDirectory() as d:
        chaotic, events = run_loop(chaos_lib.ChaosConfig(
            seed=7, preempt_at=(7,), bitflip_at=(12,), drop_psum_at=(16,),
            ckpt_dir=d), d)
    delta = float(jnp.abs(clean["w"] - chaotic["w"]).max())

    # sentinel: one injected-NaN step must skip with bit-identical state
    def toy_loss(params, batch, cfg, qcfg, key):
        y = int_ops.int_linear(batch["x"], params["w"], None, None, cfg_q)
        return jnp.mean(y ** 2), {"ce": jnp.mean(jnp.abs(y))}

    params = {"w": w0}
    opt_state = opt_lib.init(params)
    batch = {"x": x}
    step = jax.jit(sentinel_lib.make_sentinel_step(
        toy_loss, None, cfg_q, opt_lib.OptimizerConfig(lr=1e-2)))
    _, _, m_clean = step(params, opt_state, batch, key, jnp.float32(0.0))
    p2, o2, m_inj = step(params, opt_state, batch, key, jnp.float32(1.0))
    ident = lambda a, b: all(                             # noqa: E731
        bool(jnp.all(u == v))
        for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    return {
        "chaos_vs_clean": {
            "steps": steps,
            "injected": ["preempt@7", "bitflip@12", "drop_psum@16"],
            "events": events,
            "final_state_max_abs_delta": delta,
            "recovered_exactly": delta == 0.0,
        },
        "sentinel_skip": {
            "clean_skipped": float(m_clean["skipped"]),
            "injected_skipped": float(m_inj["skipped"]),
            "params_bit_identical_through_skip": ident(p2, params),
            "opt_state_bit_identical_through_skip": ident(o2, opt_state),
            "grad_nonfinite_count": float(m_inj["health"]["grads"]["nonfinite"]),
        },
    }


def run(repeats: int = 3, only: str = None) -> dict:
    sections = {
        "presets": lambda: [compare_preset(p, repeats) for p in PRESETS],
        "moe_dispatch": moe_dispatch_report,
        "matmul_dispatch": lambda: matmul_dispatch_report(repeats=repeats),
        "norm_bwd": lambda: norm_bwd_report(repeats=repeats),
        "policy": policy_report,
        "state_plane": state_plane_report,
        "attention": lambda: attention_report(repeats=repeats),
        "kept_ops": lambda: kept_ops_report(repeats=repeats),
        "robustness": robustness_report,
    }
    if only is not None and only not in sections:
        raise SystemExit(f"unknown section {only!r}; "
                         f"choose from {sorted(sections)}")
    doc = {
        "task": "backend_compare",
        "backend_device": jax.default_backend(),
        "pallas_interpret": jax.default_backend() != "tpu",
    }
    for name, fn in sections.items():
        if only is None or name == only:
            doc[name] = fn()
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--only", default=None,
                    help="emit a single section (e.g. robustness)")
    ap.add_argument("--out", default=None, help="write JSON here (else stdout)")
    args = ap.parse_args()
    doc = run(args.repeats, only=args.only)
    text = json.dumps(doc, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}", file=sys.stderr)
    else:
        print(text)


if __name__ == "__main__":
    main()
