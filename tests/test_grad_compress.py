"""First unit tests for the compressed cross-pod all-reduce
(core/grad_compress): error-feedback residual carry, int32-psum exactness,
the min_size FP32 passthrough, and the residual-treedef validation.

Multi-pod exactness runs in a subprocess with
--xla_force_host_platform_device_count (same pattern as test_distributed);
everything else uses a single-device mesh in-process.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import dfx, grad_compress

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _one_pod_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("pod",))


def _psum_mean(grads, residuals, **kw):
    mesh = _one_pod_mesh()
    f = jax.shard_map(
        lambda g, r: grad_compress.compressed_psum_mean(g, r, **kw),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False)
    return f(grads, residuals)


def test_error_feedback_carries_residual():
    """With a constant gradient, the EF residual makes the *running mean*
    of the compressed estimates converge to the true gradient — the
    single-shot quantization bias averages out (Karimireddy et al. 2019)."""
    key = jax.random.PRNGKey(0)
    g = {"w": jax.random.normal(key, (64, 64)) * 1e-3}
    res = grad_compress.init_residuals(g)

    outs = []
    for _ in range(16):
        out, res = _psum_mean(g, res, bits=8, min_size=1)
        outs.append(out["w"])
    single_err = float(jnp.max(jnp.abs(outs[0] - g["w"])))
    running_mean = sum(outs) / len(outs)
    ef_err = float(jnp.max(jnp.abs(running_mean - g["w"])))
    assert ef_err < single_err / 4, (ef_err, single_err)
    # and the residual is genuinely carried (non-zero between steps)
    assert float(jnp.max(jnp.abs(res["w"]))) > 0


def test_min_size_leaves_pass_through_fp32():
    """Leaves below min_size skip compression: the 1-pod mean is exact and
    their residual stays zero."""
    g = {"small": jnp.array([1.2345678, -2.5e-7, 3.0], jnp.float32),
         "big": jnp.ones((64, 64), jnp.float32) * 0.1}
    res = grad_compress.init_residuals(g)
    out, new_res = _psum_mean(g, res, bits=8, min_size=64)
    np.testing.assert_array_equal(np.asarray(out["small"]),
                                  np.asarray(g["small"]))
    np.testing.assert_array_equal(np.asarray(new_res["small"]),
                                  np.zeros_like(g["small"]))
    # the big leaf went through the quantized path: residual is non-trivial
    assert float(jnp.max(jnp.abs(new_res["big"]))) >= 0
    assert out["big"].dtype == jnp.float32


def test_residual_treedef_mismatch_raises():
    g = {"w": jnp.ones((4,)), "b": jnp.ones((4,))}
    bad = {"w": jnp.zeros((4,))}                    # missing a leaf
    with pytest.raises(ValueError, match="residual tree"):
        grad_compress.compressed_psum_mean(g, bad, min_size=1)


def test_single_pod_compression_is_quantize_dequantize():
    """With one pod the compressed estimate must equal the local DFX
    quantize/dequantize bit-for-bit (int32 psum of one mantissa is the
    identity)."""
    key = jax.random.PRNGKey(1)
    g = {"w": jax.random.normal(key, (32, 32))}
    out, _ = _psum_mean(g, None, bits=8, min_size=1)
    ref = dfx.quantize_dequantize(g["w"].astype(jnp.float32), 8)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(ref))


def test_multi_pod_int32_psum_exact():
    """8 pods: the int32 mantissa psum is exact, so the result equals the
    mean of the per-pod dequantized tensors computed in float64."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import grad_compress

        npods = 8
        mesh = Mesh(np.array(jax.devices()[:npods]), ("pod",))
        key = jax.random.PRNGKey(0)
        # per-pod distinct gradients, stacked on the pod axis
        gs = jax.random.normal(key, (npods, 16, 16), jnp.float32)

        f = jax.shard_map(
            lambda g, r: grad_compress.compressed_psum_mean(
                {"w": g[0]}, None, bits=8, min_size=1),
            mesh=mesh, in_specs=(P("pod"), None), out_specs=(P(), P()),
            check_vma=False)
        out, _ = f(gs, None)

        # reference: quantize each pod's tensor with the SHARED scale
        # (max exponent across pods), sum mantissas in python ints (exact),
        # dequantize, divide
        absmax = float(np.max(np.abs(np.asarray(gs))))
        e = np.frexp(absmax)[1] if absmax > 0 else 0
        exp = e - 7
        lim = 127.0
        ms = np.clip(np.round(np.asarray(gs, np.float64) / 2.0**exp),
                     -lim, lim).astype(np.int64)
        ref = (ms.sum(axis=0).astype(np.float64) * 2.0**exp) / npods
        np.testing.assert_array_equal(
            np.asarray(out["w"], np.float64), ref.astype(np.float32))
        print("PSUM_EXACT_OK")
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PSUM_EXACT_OK" in r.stdout
