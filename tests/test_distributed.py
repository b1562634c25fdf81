"""Distribution tests that need >1 device: run in a subprocess with
--xla_force_host_platform_device_count so the main pytest process keeps its
single-device view."""
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_sharded_train_step_matches_single_device():
    """The SPMD train step on a (2, 2) mesh computes the same loss and params
    as the unsharded step."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import sharding
        from repro.configs import registry
        from repro.core.qconfig import QuantConfig
        from repro.models import lm
        from repro.train import optimizer as opt_lib, trainer

        cfg = registry.get_config('qwen1.5-0.5b').reduced()
        qcfg = QuantConfig.fp32()
        key = jax.random.PRNGKey(0)
        mesh = sharding.make_mesh((2, 2), ("data", "model"))
        batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(key, (4, 32), 0, cfg.vocab)}
        opt_cfg = opt_lib.OptimizerConfig(lr=1e-3)
        step = trainer.make_train_step(lm.lm_loss, cfg, qcfg, opt_cfg)

        # single device reference
        params = lm.lm_init(key, cfg)
        opt = opt_lib.init(params)
        p1, o1, m1 = jax.jit(step)(params, opt, batch, key)

        # sharded
        sharding.set_mesh(mesh)
        params2, opt2, pspecs = trainer.init_train_state(
            lambda k: lm.lm_init(k, cfg), key, mesh, fsdp=True)
        stepj = trainer.jit_train_step(step, mesh, pspecs, donate=False)
        p2, o2, m2 = stepj(params2, opt2, batch, key)
        assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-4, (m1, m2)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
        print('SHARDED_MATCH_OK')
    """)
    assert "SHARDED_MATCH_OK" in out


def test_param_pspecs_rules():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro import sharding
        from repro.configs import registry
        from repro.models import lm

        mesh = sharding.make_mesh((2, 4), ("data", "model"))
        cfg = registry.get_config('qwen1.5-0.5b')
        shapes = jax.eval_shape(lambda k: lm.lm_init(k, cfg),
                                jax.eval_shape(lambda: jax.random.PRNGKey(0)))
        specs = sharding.param_pspecs(shapes, mesh, fsdp=True)
        # embedding: vocab on model, d_model on data (fsdp)
        assert specs['embed'].spec == P('model', 'data'), specs['embed']
        # stacked block weights: leading layer axis unsharded, TP on output
        wq = specs['blocks']['attn']['wq'].spec
        assert wq == P(None, 'data', 'model'), wq
        wo = specs['blocks']['attn']['wo'].spec
        assert wo == P(None, 'model', 'data'), wo
        # norm scales replicated
        assert specs['final_norm']['g'].spec == P(None,)
        print('PSPEC_RULES_OK')
    """)
    assert "PSPEC_RULES_OK" in out


def test_constrain_divisibility_fallback():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro import sharding
        mesh = sharding.make_mesh((2, 4), ("data", "model"))
        sharding.set_mesh(mesh)
        x = jnp.zeros((3, 5))          # neither dim divisible
        y = jax.jit(lambda x: sharding.constrain(x, "data", "model"))(x)
        assert y.shape == x.shape
        z = jnp.zeros((4, 8))
        z2 = jax.jit(lambda x: sharding.constrain(x, "data", "model"))(z)
        print('CONSTRAIN_OK')
    """)
    assert "CONSTRAIN_OK" in out


def test_compressed_psum_matches_plain_mean():
    """int8 DFX all-reduce + error feedback ~= FP32 mean all-reduce, and the
    residual carries the quantization error."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import sharding
        from repro.core import grad_compress

        mesh = sharding.make_mesh((4,), ("pod",))
        key = jax.random.PRNGKey(0)
        g_local = jax.random.normal(key, (4, 256, 512))   # per-pod grads

        def body(g, r):
            out, nr = grad_compress.compressed_psum_mean(
                {"w": g[0]}, {"w": r[0]}, bits=8, axis="pod", min_size=1)
            return out["w"][None], nr["w"][None]

        f = jax.shard_map(
            body, mesh=mesh, in_specs=(P("pod"), P("pod")),
            out_specs=(P("pod"), P("pod")), check_vma=False)
        r0 = jnp.zeros_like(g_local)
        out, res = f(g_local, r0)
        true_mean = jnp.mean(g_local, axis=0)
        # every pod sees the same compressed mean
        for i in range(4):
            np.testing.assert_allclose(np.asarray(out[i]),
                                       np.asarray(out[0]), rtol=0)
        err = float(jnp.abs(out[0] - true_mean).max())
        amax = float(jnp.abs(g_local).max())
        assert err <= amax * 2.0 ** -6, (err, amax)   # int8 step bound
        # error feedback: residual equals the per-pod quantization error
        assert float(jnp.abs(res).max()) > 0
        # EF telescopes: the CUMULATIVE estimate over two rounds stays
        # within ONE quantization step of the true cumulative mean, while
        # without EF the bias doubles (Karimireddy et al. 2019).
        out2, _ = f(g_local, res)
        cum_ef = float(jnp.abs(out[0] + out2[0] - 2 * true_mean).max())
        o2, _ = f(g_local, jnp.zeros_like(res))
        cum_no = float(jnp.abs(out[0] + o2[0] - 2 * true_mean).max())
        assert cum_ef <= amax * 2.0 ** -6 + 1e-7, (cum_ef, amax)
        assert cum_ef < 0.75 * cum_no, (cum_ef, cum_no)
        print('COMPRESS_OK')
    """)
    assert "COMPRESS_OK" in out


def test_quantized_all_gather_matches_per_shard_fake_quant():
    """The int8 QTensor param all-gather (sharding.quantized_all_gather) is
    bit-identical to quantizing each FSDP shard at its own scalar exponent
    and concatenating the dequantized images — the wire moved limb planes +
    per-shard exponents, never f32.  Bits come from $REPRO_GATHER_BITS (the
    state-plane CI leg pins 8)."""
    out = _run("""
        import os
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import sharding
        from repro.core import qtensor

        bits = int(os.environ.get("REPRO_GATHER_BITS") or 8)
        mesh = sharding.make_mesh((4, 2), ("data", "model"))
        key = jax.random.PRNGKey(0)
        params = {
            "w": jax.random.normal(key, (8, 16)),          # data x model
            "v": jax.random.normal(jax.random.fold_in(key, 1), (6, 4)),
            "g": jax.random.normal(jax.random.fold_in(key, 2), (12,)),
        }
        pspecs = {
            "w": NamedSharding(mesh, P("data", "model")),
            "v": NamedSharding(mesh, P(None, "data")),
            "g": NamedSharding(mesh, P()),                 # replicated
        }
        params = {k: jax.device_put(v, pspecs[k]) for k, v in params.items()}
        got = jax.jit(lambda p: sharding.quantized_all_gather(
            p, mesh, bits=bits, pspecs=pspecs))(params)

        def fq(x):
            return qtensor.dequantize(qtensor.quantize(x, bits))

        def ref_leaf(x, axis, n_shards):
            shards = jnp.split(x, n_shards, axis=axis)
            return jnp.concatenate([fq(s) for s in shards], axis=axis)

        # w is sharded on BOTH axes: each device's (data x model) block
        # quantizes at its own scalar exponent before the data gather
        ref_w = jnp.concatenate(
            [jnp.concatenate([fq(c) for c in jnp.split(r, 2, axis=1)],
                             axis=1)
             for r in jnp.split(jax.device_get(params["w"]), 4, axis=0)],
            axis=0)
        ref = {"w": ref_w,
               "v": ref_leaf(jax.device_get(params["v"]), 1, 4),
               "g": jax.device_get(params["g"])}           # untouched
        for k in params:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref[k]), err_msg=k)

        # gradients flow straight through the gather (custom_vjp identity)
        gr = jax.grad(lambda p: sum(
            jnp.sum(x) for x in jax.tree.leaves(
                sharding.quantized_all_gather(p, mesh, bits=bits,
                                              pspecs=pspecs))))(params)
        for k, g in gr.items():
            assert g.shape == params[k].shape
            np.testing.assert_array_equal(np.asarray(g),
                                          np.ones_like(np.asarray(g)))
        print('QGATHER_PARITY_OK')
    """)
    assert "QGATHER_PARITY_OK" in out


def test_quantized_state_plane_tracks_fp32_baseline():
    """The ISSUE 8 acceptance run: 200 multi-host-sim steps with the int8
    param all-gather (gather_bits=8, genuinely FSDP-sharded params) AND int8
    SR-EMA Adam moments track the FP32-state baseline's loss within 1%."""
    out = _run("""
        import os
        import jax, jax.numpy as jnp, numpy as np
        from repro import sharding
        from repro.configs import registry
        from repro.core.qconfig import QuantConfig
        from repro.data.pipeline import DataConfig, SyntheticLM
        from repro.models import lm
        from repro.train import optimizer as opt_lib, trainer

        cfg = registry.get_config('smollm-135m').reduced()
        qcfg = QuantConfig.fp32()
        key = jax.random.PRNGKey(0)
        mesh = sharding.make_mesh((4, 2), ("data", "model"))
        sharding.set_mesh(mesh)
        gb = int(os.environ.get("REPRO_GATHER_BITS") or 8)

        def run(gather_bits, state_bits, steps=200):
            opt_cfg = opt_lib.OptimizerConfig(lr=2e-3, weight_decay=0.0,
                                              state_bits=state_bits)
            params, opt_state, pspecs = trainer.init_train_state(
                lambda k: lm.lm_init(k, cfg), key, mesh, fsdp=True,
                opt_cfg=opt_cfg)
            tcfg = trainer.TrainConfig(gather_bits=gather_bits)
            step = trainer.jit_train_step(
                trainer.make_train_step(lm.lm_loss, cfg, qcfg, opt_cfg,
                                        tcfg, mesh=mesh, param_specs=pspecs),
                mesh, pspecs, opt_state_like=opt_state)
            data = SyntheticLM(DataConfig(batch_size=8, seq_len=32,
                                          vocab=cfg.vocab, seed=3))
            losses = []
            for i in range(steps):
                batch = {k: jnp.asarray(v) for k, v in next(data).items()}
                params, opt_state, m = step(params, opt_state, batch,
                                            jax.random.fold_in(key, i))
                losses.append(float(m["loss"]))
            return losses

        base = run(0, 0)
        quant = run(gb, 8)
        tail_b = float(np.mean(base[-20:]))
        tail_q = float(np.mean(quant[-20:]))
        assert quant[-1] < quant[0] - 0.5, (quant[0], quant[-1])
        assert abs(tail_q - tail_b) / tail_b < 0.01, (tail_b, tail_q)
        print('TRACKING_OK', tail_b, tail_q)
    """)
    assert "TRACKING_OK" in out


def test_multipod_mesh_shapes():
    out = _run("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh(multi_pod=False)
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m1.shape) == {"data": 16, "model": 16}, m1.shape
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}, m2.shape
        print('MESH_OK')
    """, devices=512)
    assert "MESH_OK" in out


def test_multihost_chaos_recovery_matches_clean():
    """Injected preemption + state bit-flip + dropped psum participant on a
    (2, 2) data/model mesh: run_with_recovery restores from crc-verified
    checkpoints and the recovered run reproduces the clean run's final loss
    (the step is a pure function of (state, step), so replay is exact)."""
    out = _run("""
        import dataclasses, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro import sharding
        from repro.configs import registry
        from repro.core.qconfig import QuantConfig
        from repro.data.pipeline import DataConfig, SyntheticLM
        from repro.models import lm
        from repro.train import (chaos, checkpoint, fault,
                                 optimizer as opt_lib, trainer)

        cfg = registry.get_config('smollm-135m').reduced()
        # sim pinned: the Pallas kernels do not shard over a model axis
        qcfg = dataclasses.replace(QuantConfig.int8(), backend="sim")
        key = jax.random.PRNGKey(0)
        mesh = sharding.make_mesh((2, 2), ("data", "model"))
        sharding.set_mesh(mesh)
        opt_cfg = opt_lib.OptimizerConfig(lr=1e-3)
        params, opt_state, pspecs = trainer.init_train_state(
            lambda k: lm.lm_init(k, cfg), key, mesh, fsdp=True)
        step = trainer.jit_train_step(
            trainer.make_train_step(lm.lm_loss, cfg, qcfg, opt_cfg,
                                    mesh=mesh, param_specs=pspecs),
            mesh, pspecs, donate=False)

        def run(ccfg, ckpt_dir, steps=14):
            data = SyntheticLM(DataConfig(batch_size=4, seq_len=32,
                                          vocab=cfg.vocab, seed=3))
            last = {}

            def one(state, k):
                p, o = state
                b = {n: jnp.asarray(v) for n, v in next(data).items()}
                p, o, m = step(p, o, b, jax.random.fold_in(key, k))
                last['loss'] = float(m['loss'])
                return (p, o)

            def save_fn(state, k):
                checkpoint.save(ckpt_dir, k,
                                {"params": state[0], "opt": state[1],
                                 "data": data.state()})

            def restore_fn():
                got = checkpoint.restore_latest(
                    ckpt_dir, {"params": params, "opt": opt_state,
                               "data": data.state()})
                assert got is not None, 'no usable checkpoint'
                blob, k = got
                data.restore(blob["data"])
                return (blob["params"], blob["opt"]), k

            monkey = chaos.ChaosMonkey(ccfg)
            final = fault.run_with_recovery(
                monkey.wrap(one), (params, opt_state), start_step=0,
                num_steps=steps, save_fn=save_fn, restore_fn=restore_fn,
                save_every=4)
            return final, last['loss']

        with tempfile.TemporaryDirectory() as d:
            _, clean_loss = run(chaos.ChaosConfig(), d)
        with tempfile.TemporaryDirectory() as d:
            _, chaos_loss = run(chaos.ChaosConfig(
                seed=11, preempt_at=(6,), bitflip_at=(9,),
                drop_psum_at=(12,), ckpt_dir=d), d)
        assert abs(clean_loss - chaos_loss) < 1e-5, (clean_loss, chaos_loss)
        print('CHAOS_MULTIHOST_OK')
    """, devices=4)
    assert "CHAOS_MULTIHOST_OK" in out


def test_pallas_step_shards_over_four_devices():
    """CPU rehearsal of the four-chip path: the int8 span fine-tuning step
    on the pallas backend (interpret mode) runs every ``pallas_call`` inside
    a ``shard_map`` on a data=4 mesh, and matches the same step on a
    one-device mesh.  Rows are split across devices, so only the products
    summed across them (dW, the norm dgamma/dbeta) add f32 rounding."""
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro import sharding
        from repro.analysis import walker
        from repro.core.qconfig import QuantConfig
        from repro.models import paper_models as pm
        from repro.train import optimizer as opt_lib, trainer

        cfg = pm.bert_config(n_layers=1, d_model=128, n_heads=2, d_ff=256,
                             vocab=512, name="bert-tiny")
        qcfg = dataclasses.replace(QuantConfig.int8(), backend="pallas")
        opt_cfg = opt_lib.OptimizerConfig(lr=1e-3)
        key = jax.random.PRNGKey(0)
        r = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(r.integers(0, 512, (8, 32)), jnp.int32),
                 "span_start": jnp.asarray(r.integers(1, 16, 8), jnp.int32),
                 "span_end": jnp.asarray(r.integers(16, 31, 8), jnp.int32)}

        def run(mesh):
            sharding.set_mesh(mesh)
            params, opt, pspecs = trainer.init_train_state(
                lambda k: pm.bert_init(k, cfg, span_head=True), key, mesh,
                fsdp=False)
            step = trainer.jit_train_step(
                trainer.make_train_step(pm.bert_span_loss, cfg, qcfg,
                                        opt_cfg),
                mesh, pspecs, donate=False)
            jaxpr = jax.make_jaxpr(step)(params, opt, batch, key)
            calls = [s for s in walker.iter_eqns(jaxpr)
                     if s.prim == "pallas_call" and not s.inside_pallas]
            p, _, m = step(params, opt, batch, key)
            sharding.set_mesh(None)
            return calls, float(m["loss"]), jax.device_get(p)

        calls4, loss4, p4 = run(sharding.make_mesh((4, 1), ("data", "model")))
        calls1, loss1, p1 = run(sharding.make_mesh(
            (1, 1), ("data", "model"), devices=jax.devices()[:1]))
        assert calls4 and all("shard_map" in s.path for s in calls4)
        assert len(calls4) == len(calls1), (len(calls4), len(calls1))
        dloss = abs(loss4 - loss1)
        dp = max(float(np.max(np.abs(a - b)))
                 for a, b in zip(jax.tree.leaves(p4), jax.tree.leaves(p1)))
        print("DIFFS", dloss, dp)
        assert dloss <= 1e-5 * abs(loss1), (loss4, loss1)
        assert dp <= 1e-5, dp
        print("PALLAS_SHARDED_OK", len(calls4))
    """, devices=4)
    assert "PALLAS_SHARDED_OK" in out
