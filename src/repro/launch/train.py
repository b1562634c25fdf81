"""Training launcher: end-to-end driver wiring every substrate layer.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen1.5-0.5b --reduced --steps 200 --quant int8 \
        --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

On real hardware the same driver runs per-host (jax.distributed initializes
from the cluster env); in this container it runs on CPU with ``--reduced``
configs. Demonstrates: mesh setup, sharded init, jit'd train step, data
pipeline with resumable state, atomic checkpointing, fault-tolerant step
loop, optional int8 cross-pod gradient compression.
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding, utils
from repro.configs import registry
from repro.core import grad_compress
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import encdec, lm
from repro.train import (chaos as chaos_lib, checkpoint, fault,
                         optimizer as opt_lib, sentinel as sentinel_lib,
                         trainer)

log = logging.getLogger("repro.train")


def _steps_list(s: str) -> tuple:
    """CLI step lists: "3,7,11" -> (3, 7, 11)."""
    return tuple(int(x) for x in s.split(",") if x)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b",
                    choices=list(registry.ARCH_IDS))
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU)")
    ap.add_argument("--quant", default="int8",
                    help="uniform QuantConfig preset or mixed-precision "
                         "QuantPolicy preset (e.g. int8_embed16)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1,
                    help="pod axis size (multi-host sim; >1 enables the "
                         "compressed cross-pod step)")
    ap.add_argument("--gather-bits", type=int, default=0,
                    help="0 = f32 FSDP param gather; 8 = int8 QTensor "
                         "all-gather (DESIGN.md §7)")
    ap.add_argument("--state-bits", type=int, default=0,
                    help="0 = FP32 Adam moments; 8 = QTensor moments with "
                         "stochastic-rounding EMA")
    ap.add_argument("--grad-compress-bits", type=int, default=0,
                    help="0 = off; 8 = int8 DFX cross-pod gradient "
                         "all-reduce with error feedback (needs --pods > 1)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--sentinel", action="store_true",
                    help="numerics-sentinel step: in-graph health counters, "
                         "lax.cond skip on non-finite grads, hysteresis-"
                         "gated per-scope bit escalation (DESIGN.md §9)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--chaos-preempt-at", type=_steps_list, default=(),
                    help="comma-separated steps at which to inject a "
                         "preemption (recover via restore + replay)")
    ap.add_argument("--chaos-drop-psum-at", type=_steps_list, default=(),
                    help="steps at which a psum participant drops")
    ap.add_argument("--chaos-bitflip-at", type=_steps_list, default=(),
                    help="steps at which a state QTensor mantissa bit flips")
    ap.add_argument("--chaos-corrupt-exp-at", type=_steps_list, default=(),
                    help="steps at which a shard scale-exponent goes stale")
    ap.add_argument("--chaos-nan-at", type=_steps_list, default=(),
                    help="steps at which gradients get a NaN injected "
                         "(needs --sentinel; proves one skipped step)")
    ap.add_argument("--chaos-straggle-at", type=_steps_list, default=(),
                    help="steps preceded by an injected straggler delay")
    ap.add_argument("--chaos-corrupt-ckpt-at", type=_steps_list, default=(),
                    help="steps at which the newest checkpoint leaf gets "
                         "flipped bytes (restore must fall back)")
    args = ap.parse_args()

    utils.use_compile_cache()
    logging.basicConfig(level=logging.INFO)
    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    qcfg = registry.get_quant(args.quant)
    compressed = args.grad_compress_bits > 0
    if compressed and args.pods < 2:
        ap.error("--grad-compress-bits needs --pods > 1 (a pod mesh axis)")
    if args.sentinel and compressed:
        ap.error("--sentinel and --grad-compress-bits are mutually "
                 "exclusive (the sentinel step owns the optimizer update)")
    if args.chaos_nan_at and not args.sentinel:
        ap.error("--chaos-nan-at needs --sentinel (the NaN rides the "
                 "sentinel step's inject operand)")
    mesh = make_host_mesh(args.model_parallel, pods=args.pods)
    sharding.set_mesh(mesh)

    if cfg.enc_dec:
        init_fn = lambda k: encdec.encdec_init(k, cfg)  # noqa: E731
        loss_fn = encdec.encdec_loss
    else:
        init_fn = lambda k: lm.lm_init(k, cfg)          # noqa: E731
        loss_fn = lm.lm_loss

    key = jax.random.PRNGKey(0)
    opt_cfg = opt_lib.OptimizerConfig(lr=args.lr, total_steps=args.steps,
                                      state_bits=args.state_bits)
    params, opt_state, pspecs = trainer.init_train_state(
        init_fn, key, mesh, fsdp=registry.use_fsdp(args.arch),
        opt_cfg=opt_cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    log.info("arch=%s params=%.2fM quant=%s mesh=%s gather_bits=%d "
             "state_bits=%d", cfg.name, n_params / 1e6, args.quant,
             dict(mesh.shape), args.gather_bits, args.state_bits)

    events = []

    def on_event(ev):
        events.append(ev)
        log.info("event: %s", ev)

    tcfg = trainer.TrainConfig(microbatches=args.microbatches,
                               grad_compress_bits=args.grad_compress_bits,
                               gather_bits=args.gather_bits)
    watch = None
    holder = {}
    if args.sentinel:
        watch = sentinel_lib.Sentinel(sentinel_lib.SentinelConfig(), qcfg,
                                      on_event=on_event)
        # mutable holder: an escalation rebuilds the policy and re-jits;
        # one_step always calls through holder["fn"]
        holder["fn"] = jax.jit(sentinel_lib.make_sentinel_step(
            loss_fn, cfg, qcfg, opt_cfg, tcfg, mesh=mesh,
            param_specs=pspecs))
        step_fn = None
        residuals = None
    elif compressed:
        step_fn = trainer.make_compressed_train_step(
            loss_fn, cfg, qcfg, opt_cfg, mesh, tcfg)
        residuals = grad_compress.init_residuals(params)
    else:
        step_fn = trainer.jit_train_step(
            trainer.make_train_step(loss_fn, cfg, qcfg, opt_cfg, tcfg,
                                    mesh=mesh, param_specs=pspecs),
            mesh, pspecs, opt_state_like=opt_state)
        residuals = None

    data = SyntheticLM(DataConfig(batch_size=args.batch, seq_len=args.seq,
                                  vocab=cfg.vocab))

    def state_like():
        like = {"params": params, "opt": opt_state, "data": data.state()}
        if compressed:
            # error-feedback residuals ride in the checkpoint: dropping
            # them on restart would bias the first post-restore steps
            like["residuals"] = residuals
        return like

    start = 0
    if args.ckpt_dir:
        # newest checkpoint that passes its crc manifest; corrupt steps are
        # skipped (ckpt-corrupt events) and the previous retained one loads
        got = checkpoint.restore_latest(args.ckpt_dir, state_like(),
                                        on_event=on_event)
        if got is not None:
            restored, latest = got
            params, opt_state = restored["params"], restored["opt"]
            if compressed:
                residuals = restored["residuals"]
            data.restore(restored["data"])
            start = latest
            log.info("restored step %d", latest)

    def make_batch(raw):
        if cfg.enc_dec:
            B = raw["tokens"].shape[0]
            frames = np.random.default_rng(0).standard_normal(
                (B, args.seq, cfg.d_model)).astype(np.float32)
            return {"frames": frames, **raw}
        if cfg.vlm_prefix:
            B = raw["tokens"].shape[0]
            pe = np.zeros((B, cfg.vlm_prefix, cfg.d_model), np.float32)
            return {"patch_embeds": pe, **raw}
        return raw

    state = (params, opt_state, residuals)

    monkey = chaos_lib.ChaosMonkey(chaos_lib.ChaosConfig(
        seed=args.chaos_seed,
        preempt_at=args.chaos_preempt_at,
        bitflip_at=args.chaos_bitflip_at,
        corrupt_exp_at=args.chaos_corrupt_exp_at,
        drop_psum_at=args.chaos_drop_psum_at,
        nan_grad_at=args.chaos_nan_at,
        straggle_at=args.chaos_straggle_at,
        corrupt_ckpt_at=args.chaos_corrupt_ckpt_at,
        ckpt_dir=args.ckpt_dir))

    def one_step(state, step):
        params, opt_state, residuals = state
        batch = make_batch(next(data))
        k = jax.random.fold_in(key, step)
        if args.sentinel:
            params, opt_state, metrics = holder["fn"](
                params, opt_state, batch, k, monkey.nan_flag(step))
            new_policy = watch.observe(step, jax.device_get(metrics))
            if new_policy is not None:
                holder["fn"] = jax.jit(sentinel_lib.make_sentinel_step(
                    loss_fn, cfg, new_policy, opt_cfg, tcfg, mesh=mesh,
                    param_specs=pspecs))
                log.info("sentinel: recompiled with escalated policy "
                         "(%d rules)", len(new_policy.rules))
        elif compressed:
            params, opt_state, residuals, metrics = step_fn(
                params, opt_state, residuals, batch, k)
        else:
            params, opt_state, metrics = step_fn(params, opt_state, batch, k)
        if step % args.log_every == 0:
            m = {k_: float(v) for k_, v in metrics.items()
                 if not isinstance(v, dict)}
            log.info("step %d loss=%.4f gnorm=%.3f", step, m.get("loss", -1),
                     m.get("grad_norm", -1))
        return params, opt_state, residuals

    def save_state(state, step):
        if args.ckpt_dir:
            blob = {"params": state[0], "opt": state[1], "data": data.state()}
            if compressed:
                blob["residuals"] = state[2]
            checkpoint.save(args.ckpt_dir, step, blob)
            log.info("checkpointed step %d", step)

    restore_fn = None
    if args.ckpt_dir:
        def restore_fn():
            got = checkpoint.restore_latest(args.ckpt_dir, state_like(),
                                            on_event=on_event)
            if got is None:
                raise RuntimeError("no usable checkpoint to restore from")
            blob, step = got
            data.restore(blob["data"])
            return ((blob["params"], blob["opt"], blob.get("residuals")),
                    step)

    t0 = time.time()
    state = fault.run_with_recovery(
        monkey.wrap(one_step), state, start_step=start, num_steps=args.steps,
        save_fn=save_state, restore_fn=restore_fn,
        save_every=args.ckpt_every, on_event=on_event)
    log.info("done: %d steps in %.1fs (%d events)", args.steps,
             time.time() - t0, len(events))
    if args.ckpt_dir:
        save_state(state, start + args.steps)


if __name__ == "__main__":
    main()
