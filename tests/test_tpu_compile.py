"""Compile the main-path Pallas kernels for a TPU v5e at bert-base widths.

Interpret mode lowers every kernel to plain XLA, so the CPU tests cannot see
what the chip's compiler (Mosaic) refuses: scalar loads from the wrong memory
space, int32 MXU operands, blocks that break the (8, 128) tiling rule, more
scoped VMEM than a kernel may use, or a kernel XLA would have to partition.
These tests compile, without a chip, for a described ``v5e:2x2`` topology
and check that a Mosaic kernel (``tpu_custom_call``) is in the program.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import sharding, utils
from repro.configs.bert_base import CONFIG as BERT_BASE
from repro.core.qconfig import QuantConfig
from repro.kernels import ops
from repro.kernels.dfx_quant import n_limbs
from repro.models import paper_models as pm
from repro.train import optimizer as opt_lib, trainer

#: bert-base at the SQuAD v1.1 fine-tuning shape: B=32, S=384 tokens.
B, S, D, F, H, HD = 32, 384, 768, 3072, 12, 64
M = B * S


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without
    # one, so keep these compiles out of any persistent cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """Compile ``fn`` for the shapes (ShapeDtypeStructs with shardings) and
    return the compiled HLO text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _s(shape, dtype, where):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=where)


@pytest.mark.parametrize("stochastic", [False, True])
def test_quantize_compiles(one_chip, stochastic):
    """The FFN activation (12288x3072) into b=12 limb planes, with and
    without the stochastic-rounding noise input."""
    def fn(x, exp, *u):
        return ops.quantize_pallas(x, exp, 12, u=u[0] if u else None,
                                   interpret=False, limb_planes=True)

    args = [_s((M, F), jnp.float32, one_chip), _s((), jnp.int32, one_chip)]
    if stochastic:
        args.append(_s((M, F), jnp.float32, one_chip))
    assert "tpu_custom_call" in _compile(fn, *args)


#: vit-base rows: 64 images x 197 positions (padded to 12672 = 128 x 99)
M_VIT = 64 * 197

#: the linears of one encoder layer as (K, N): q/k/v/o, FFN up and down
LINEARS = {"qkvo": (D, D), "w1": (D, F), "w2": (F, D)}


@pytest.mark.parametrize("bits", [(12, 8), (16, 16)], ids=["2x1", "3x3"])
@pytest.mark.parametrize("direction", ["nn", "nt", "tn"])
def test_matmul_compiles(one_chip, direction, bits):
    """Each product of the linears at the blocks the chooser gives them:
    w16 (3x3 limbs) at every bert (M=12288) and vit (M=12608) linear, the
    2x1 preset at the FFN up-projection — so Mosaic checks the tiling and
    the scoped VMEM each call asks for."""
    ba, bb = bits
    la, lb = n_limbs(ba), n_limbs(bb)
    i8 = functools.partial(_s, dtype=jnp.int8, where=one_chip)
    e = _s((), jnp.int32, one_chip)
    shapes = ([(rows, k, n) for rows in (M, M_VIT)
               for k, n in LINEARS.values()] if la == lb == 3
              else [(M, D, F)])
    for rows, k, n in shapes:
        if direction == "nn":
            fn = lambda a, ea, b, eb: ops.dfx_matmul_tiled(      # noqa: E731
                a, ea, ba, b, eb, bb, interpret=False)
            a, b = i8((la, rows, k)), i8((lb, k, n))
        elif direction == "nt":
            fn = lambda a, ea, b, eb: ops.dfx_matmul_tiled_nt(   # noqa: E731
                a, ea, ba, b, eb, bb, interpret=False)
            a, b = i8((la, rows, n)), i8((lb, k, n))
        else:
            fn = lambda a, ea, b, eb: ops.dfx_matmul_tiled_tn(   # noqa: E731
                a, ea, ba, b, eb, bb, interpret=False)
            a, b = i8((la, rows, k)), i8((lb, rows, n))
        assert "tpu_custom_call" in _compile(fn, a, e, b, e), (rows, k, n)


def test_layernorm_fwd_bwd_compile(one_chip):
    """Layer norm forward and backward over 12288x768 int16 mantissas."""
    f32 = functools.partial(_s, dtype=jnp.float32, where=one_chip)
    xm = _s((M, D), jnp.int16, one_chip)
    e = _s((), jnp.int32, one_chip)
    fwd = lambda xm, e, g, b: ops.layernorm_pallas(          # noqa: E731
        xm, e, g, b, interpret=False)
    assert "tpu_custom_call" in _compile(fwd, xm, e, f32((D,)), f32((D,)))
    bwd = lambda xm, e, gm, ge, g, mu, rstd: ops.layernorm_bwd_pallas(  # noqa: E731
        xm, e, gm, ge, g, mu, rstd, interpret=False)
    assert "tpu_custom_call" in _compile(
        bwd, xm, e, xm, e, f32((D,)), f32((M, 1)), f32((M, 1)))


def test_attention_fwd_bwd_compile(one_chip):
    """Integer flash attention, forward and backward, at B=32, H=12, S=384,
    hd=64 under the paper's int8 preset (12-bit q/k/v, 8-bit grads)."""
    L, Lg = n_limbs(12), n_limbs(8)
    q = _s((L, B, S, H, 1, HD), jnp.int8, one_chip)
    kv = _s((L, B, S, H, HD), jnp.int8, one_chip)
    g = _s((Lg, B, S, H, 1, HD), jnp.int8, one_chip)
    e = _s((), jnp.int32, one_chip)
    off = _s((B,), jnp.int32, one_chip)

    def fwd(q, k, v, e, off):
        return ops.attention_fwd(q, e, k, e, v, e, off, 12, causal=False,
                                 interpret=False)

    assert "tpu_custom_call" in _compile(fwd, q, kv, kv, e, off)

    def bwd(q, k, v, g, lse, delta, e, off):
        return ops.attention_bwd(q, e, k, e, v, e, g, e, lse, delta, e, off,
                                 12, 8, causal=False, interpret=False)

    lse = _s((B, H, 1, S), jnp.float32, one_chip)
    delta = _s((B, S, H, 1), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compile(bwd, q, kv, kv, g, lse, delta, e,
                                         off)


def _bert_step_text(devices, qcfg, n_layers):
    """Compiled text of one bert-base span fine-tuning step (depth cut to
    ``n_layers``) at B=32, S=384, data-parallel over ``devices``, through
    the trainer's own entry points."""
    cfg = dataclasses.replace(BERT_BASE, n_layers=n_layers)
    mesh = sharding.make_mesh((len(devices), 1), ("data", "model"),
                              devices=devices)
    init = functools.partial(pm.bert_init, cfg=cfg, span_head=True)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    pspecs = sharding.param_pspecs(shapes, mesh, fsdp=False)
    opt_cfg = opt_lib.OptimizerConfig()
    opt_shapes = jax.eval_shape(opt_lib.init, shapes)

    def placed(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, specs)

    rep = NamedSharding(mesh, P())
    batch_s = NamedSharding(mesh, P("data"))
    opt_specs = opt_lib.OptState(step=rep, m=pspecs, v=pspecs)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32,
                                            sharding=batch_s),
             "span_start": jax.ShapeDtypeStruct((B,), jnp.int32,
                                                sharding=batch_s),
             "span_end": jax.ShapeDtypeStruct((B,), jnp.int32,
                                              sharding=batch_s)}
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)
    step = trainer.jit_train_step(
        trainer.make_train_step(pm.bert_span_loss, cfg, qcfg, opt_cfg),
        mesh, pspecs)
    sharding.set_mesh(mesh)
    try:
        return step.lower(placed(shapes, pspecs),
                          placed(opt_shapes, opt_specs),
                          batch, key).compile().as_text()
    finally:
        sharding.set_mesh(None)


def test_data_parallel_step_compiles(topo, monkeypatch):
    """One int8 bert-base span fine-tuning step (depth cut to 2 layers),
    data-parallel over the four chips of the described 2x2 mesh, through
    the trainer's own entry points.  XLA refuses to partition a Mosaic
    kernel, so this fails unless every kernel call is shard_mapped."""
    # the described chips are not this process's backend: steer the kernel
    # wrappers off interpret mode for this trace only
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    qcfg = dataclasses.replace(QuantConfig.int8(), backend="pallas")
    assert "tpu_custom_call" in _bert_step_text(topo.devices, qcfg, 2)


def test_int16_step_compiles_on_one_chip(topo, monkeypatch):
    """The int16 (3x3-limb) bert-base step on one chip, 2 layers: the limb
    matmuls run at their full-size blocks inside the layer scan, whose
    backward writes each dW straight into the stacked gradient.  XLA
    compiles a kernel fused with that write under the default 16 MiB
    scoped VMEM, not the kernel's own limit, so this fails unless the dW
    kernel is kept out of the fusion."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    qcfg = dataclasses.replace(QuantConfig.int16(), backend="pallas")
    assert "bfp_matmul_tn" in _bert_step_text(topo.devices[:1], qcfg, 2)


def test_int16_step_compiles_keeping_residuals(topo, monkeypatch):
    """The same step with the layers' residuals kept (the remat policy a
    chip with room takes, forced here by the memory limit it reads): the
    backward reads the saved kernel outputs instead of re-running them."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(utils, "device_bytes_limit", lambda: 16 << 30)
    qcfg = dataclasses.replace(QuantConfig.int16(), backend="pallas")
    assert "bfp_matmul_tn" in _bert_step_text(topo.devices[:1], qcfg, 2)
    assert utils.remat_decisions()["blocks"].mode == "policy"


#: one chip's share of Mellum2-12B-A2.5B at B=2, S=8192: 8 held experts
#: of width 896 at d=2304, the rows sized for every token's 8 choices
#: landing here plus a 256-row tile per expert
D_M, F_M, E_M, TM_M = 2304, 896, 8, 256
ROWS_M = 2 * 8192 * 8 + E_M * TM_M


@pytest.mark.parametrize("direction", ["nn", "nt", "tn"])
def test_grouped_matmul_compiles(one_chip, direction):
    """The grouped limb matmul at the expert widths, w16 (3x3 limbs): the
    gate/up product (K=2304, N=896) and the down product (K=896, N=2304)
    in each direction, group offsets as a scalar-prefetch operand."""
    i8 = functools.partial(_s, dtype=jnp.int8, where=one_chip)
    e = _s((E_M,), jnp.int32, one_chip)
    off = _s((E_M + 1,), jnp.int32, one_chip)
    for k, n in ((D_M, F_M), (F_M, D_M)):
        if direction == "nn":
            fn = lambda a, b, e, o: ops.dfx_matmul_grouped(          # noqa: E731
                a, e, 16, b, e, 16, o, TM_M, interpret=False)
            a, b = i8((3, ROWS_M, k)), i8((3, E_M, k, n))
        elif direction == "nt":
            fn = lambda a, b, e, o: ops.dfx_matmul_grouped_nt(       # noqa: E731
                a, e, 16, b, e, 16, o, TM_M, interpret=False)
            a, b = i8((3, ROWS_M, n)), i8((3, E_M, k, n))
        else:
            fn = lambda a, b, e, o: ops.dfx_matmul_grouped_tn(       # noqa: E731
                a, e, 16, b, e, 16, o, TM_M, interpret=False)
            a, b = i8((3, ROWS_M, k)), i8((3, ROWS_M, n))
        assert "tpu_custom_call" in _compile(fn, a, b, e, off), (k, n)


@pytest.mark.parametrize("window", [1024, None], ids=["sliding", "full"])
def test_banded_attention_compiles(one_chip, window):
    """Causal integer attention at Mellum2's S=8192, 32 query / 4 KV heads
    of 128, int16, forward and backward: the k-block index maps read the
    query offsets from the scalar prefetch, and the backward takes each
    dS tile's exponent in-kernel."""
    Bm, Sm, KV, G, hd = 2, 8192, 4, 8, 128
    q = _s((3, Bm, Sm, KV, G, hd), jnp.int8, one_chip)
    kv = _s((3, Bm, Sm, KV, hd), jnp.int8, one_chip)
    e = _s((), jnp.int32, one_chip)
    off = _s((Bm,), jnp.int32, one_chip)

    def fwd(q, k, v, e, off):
        return ops.attention_fwd(q, e, k, e, v, e, off, 16, causal=True,
                                 window=window, interpret=False)

    assert "tpu_custom_call" in _compile(fwd, q, kv, kv, e, off)

    def bwd(q, k, v, lse, delta, e, off):
        return ops.attention_bwd(q, e, k, e, v, e, q, e, lse, delta, None,
                                 off, 16, 16, causal=True, window=window,
                                 interpret=False)

    lse = _s((Bm, KV, G, Sm), jnp.float32, one_chip)
    delta = _s((Bm, Sm, KV, G), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compile(bwd, q, kv, kv, lse, delta, e, off)
