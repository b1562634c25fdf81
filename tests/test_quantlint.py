"""quantlint acceptance: every golden broken-graph fixture triggers exactly
its QL code, every clean graph is silent, and the walker's counting
primitives behave (dict-params recursion, scan-effective multiplication).

The broken fixtures are the invariant violations the repo has actually
shipped or nearly shipped: the XLA-side rsqrt statistics recompute (norm
layers pre-PR 3), the direct int16 ``Σx²`` at D=768 (the PR 3 hole), a
reused stochastic-rounding key, and dead/shadowed policy rules.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import budget, count_eqns, count_pallas_calls, rules, \
    walker
from repro.core import dfx, int_ops, qpolicy, qtensor
from repro.core.qconfig import QuantConfig
from repro.core.qpolicy import QuantPolicy, ScopeRule
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)


def _codes(findings):
    return sorted({f.code for f in findings})


# =========================================================================
# walker
# =========================================================================

def test_walker_recurses_dict_valued_params():
    """cond stores its branches in params — the hand-rolled recursion this
    replaced missed dict/tuple-valued params entirely."""
    def f(x):
        return jax.lax.cond(x.sum() > 0, lambda v: jnp.exp(v),
                            lambda v: jnp.log1p(jnp.abs(v)), x)
    jx = jax.make_jaxpr(f)(jnp.ones((4,)))
    assert count_eqns(jx, "exp") == 1
    assert count_eqns(jx, "log1p") == 1


def test_walker_effective_counts_multiply_scan_trips():
    def f(x):
        def body(c, _):
            return jnp.sin(c), ()
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out
    jx = jax.make_jaxpr(f)(jnp.ones((4,)))
    assert count_eqns(jx, "sin") == 1
    assert count_eqns(jx, "sin", effective=True) == 7


def test_walker_effective_cond_takes_max_not_sum():
    def f(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jnp.sin(jnp.sin(v)),
                            lambda v: jnp.sin(v), x)
    jx = jax.make_jaxpr(f)(jnp.ones((4,)))
    assert count_eqns(jx, "sin") == 3
    assert count_eqns(jx, "sin", effective=True) == 2


def test_walker_pallas_boundary_flag():
    pal = dataclasses.replace(QuantConfig.int8(), backend="pallas",
                              stochastic_grad=False)
    jx = jax.make_jaxpr(
        lambda x: int_ops.int_linear(x, jnp.ones((32, 16)), None, None, pal)
    )(jnp.ones((4, 32)))
    inside = [s for s in walker.iter_eqns(jx) if s.inside_pallas]
    outside = [s for s in walker.iter_eqns(jx) if not s.inside_pallas]
    assert inside and outside
    # kernel bodies contain the dot_general; the XLA side must not
    assert any(s.prim == "dot_general" for s in inside)
    assert not any(s.prim == "dot_general" for s in outside)


# =========================================================================
# QL001 — integer closure
# =========================================================================

def test_ql001_flags_xla_rsqrt():
    """The pre-PR 3 norm shape: statistics recomputed in XLA from the
    dequantized activations."""
    def broken(x):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-6)
    f = rules.check_integer_closure(jax.make_jaxpr(broken)(jnp.ones((4, 8))))
    assert _codes(f) == ["QL001"]
    assert any("rsqrt" in x.message for x in f)


def test_ql001_flags_limb_split_chain_on_mantissas():
    """The removed XLA ``_split_limbs``: integer rem/div chains on
    quantized mantissas."""
    def broken(x):
        m = jnp.clip(jnp.round(x * 127.0), -127, 127).astype(jnp.int32)
        lo = jax.lax.rem(m, 16)
        hi = jax.lax.div(m, 16)
        return (lo + hi * 16).astype(jnp.float32)
    f = rules.check_integer_closure(jax.make_jaxpr(broken)(jnp.ones((8,))))
    assert _codes(f) == ["QL001"]
    assert len(f) == 2                                      # rem AND div


def test_ql001_exempts_iota_index_arithmetic():
    """The MoE routing idiom ``arange(T*K) // K`` is index bookkeeping, not
    mantissa arithmetic — must NOT be flagged."""
    def routing(x):
        tok = jax.lax.div(jax.lax.iota(jnp.int32, 32), 4)
        return x + tok.astype(jnp.float32)
    assert not rules.check_integer_closure(
        jax.make_jaxpr(routing)(jnp.ones((32,))))


def test_ql001_flags_sim_mantissa_dot():
    """The sim backend contracts int-storage mantissas through an XLA
    dot_general — on a pallas-backend graph that is the fallback leak."""
    qa = dfx.quantize(jax.random.normal(KEY, (8, 16)), 8)
    qb = dfx.quantize(jax.random.normal(jax.random.fold_in(KEY, 1),
                                        (16, 4)), 8)
    def sim_dot(x):
        return dfx.dfx_dot_general(
            dfx.DfxTensor(m=jnp.clip(jnp.round(x * 127.0), -127, 127)
                          .astype(jnp.int8), exp=qa.exp),
            qb, (((1,), (0,)), ((), ())))
    jx = jax.make_jaxpr(sim_dot)(jnp.ones((8, 16)))
    f = rules.check_integer_closure(jx)
    assert "QL001" in _codes(f)
    assert any("dot_general" in x.message for x in f)


def test_ql001_walks_qtensor_ops_clean():
    """The state plane's container ops — quantize (grouped, stochastic),
    dequantize, the SR-EMA moment update, the straight-through fake quant —
    build mantissas with the exact-f32 balanced split (floor-based), never
    integer div/rem chains or an XLA integer dot: QL001 must stay silent
    over the whole QTensor surface (DESIGN.md §7)."""
    def state_ops(x, key):
        t = qtensor.quantize(x, 16, group_axis=0)
        t = qtensor.ema_update(t, x * 0.5, 0.9, key)
        return qtensor.dequantize(t) + qtensor.fake_quant_ste(x, 8)
    jx = jax.make_jaxpr(state_ops)(jnp.ones((4, 8)), KEY)
    assert not rules.check_integer_closure(jx)
    # the full graph-rule battery is silent too (one SR draw per key; no
    # reductions near an accumulator budget; no f32 collective)
    assert not rules.run_rules(jx)


# =========================================================================
# QL002 — PRNG key discipline
# =========================================================================

def test_ql002_flags_reused_stochastic_key():
    def broken(x):
        a = dfx.quantize(x, 8, stochastic=True, key=KEY)
        b = dfx.quantize(x * 2, 8, stochastic=True, key=KEY)
        return dfx.dequantize(a) + dfx.dequantize(b)
    f = rules.check_key_discipline(jax.make_jaxpr(broken)(jnp.ones((8,))))
    assert _codes(f) == ["QL002"]


def test_ql002_flags_key_threaded_through_scan_without_fold_in():
    def broken(x):
        def body(c, _):
            q = dfx.quantize(c, 8, stochastic=True, key=KEY)
            return dfx.dequantize(q), ()
        out, _ = jax.lax.scan(body, x, None, length=4)
        return out
    f = rules.check_key_discipline(jax.make_jaxpr(broken)(jnp.ones((8,))))
    assert _codes(f) == ["QL002"]
    assert any("scan" in x.message for x in f)


def test_ql002_accepts_split_and_fold_in():
    def clean(x):
        k1, k2 = jax.random.split(KEY)
        a = dfx.quantize(x, 8, stochastic=True, key=k1)
        def body(c, i):
            q = dfx.quantize(c, 8, stochastic=True,
                             key=jax.random.fold_in(k2, i))
            return dfx.dequantize(q), ()
        out, _ = jax.lax.scan(body, dfx.dequantize(a), jnp.arange(4))
        return out
    assert not rules.check_key_discipline(
        jax.make_jaxpr(clean)(jnp.ones((8,))))


# =========================================================================
# QL003 / QL005 — policy hygiene and stability
# =========================================================================

def _resolved_paths(policy, paths):
    recs = []
    with qpolicy.record_resolutions() as recs:
        for p in paths:
            policy.resolve(p)
    return [t for pol, t in recs if pol == policy]


def test_ql003_flags_dead_rule():
    policy = QuantPolicy(base=QuantConfig.int8(), rules=(
        ScopeRule("*embed*", (("weight_bits", 16),)),
        ScopeRule("tower.*", (("weight_bits", 16),)),      # matches nothing
    ))
    paths = _resolved_paths(policy, ["embed", "blocks.0.attn.wq", "head"])
    f = rules.check_policy_hygiene(policy, paths)
    assert _codes(f) == ["QL003"]
    assert any("dead rule" in x.message and "tower.*" in x.where for x in f)


def test_ql003_flags_shadowed_rule():
    """A broad rule whose every field a more specific rule overrides on
    every resolved path changes nothing — it is dead weight."""
    policy = QuantPolicy(base=QuantConfig.int8(), rules=(
        ScopeRule("embed*", (("weight_bits", 12),)),       # shadowed below
        ScopeRule("embed", (("weight_bits", 16),)),
    ))
    paths = _resolved_paths(policy, ["embed", "blocks.0.attn.wq"])
    f = rules.check_policy_hygiene(policy, paths)
    assert any("shadowed rule" in x.message and x.where == "embed*"
               for x in f), f


def test_ql003_flags_unscoped_call_site():
    policy = QuantPolicy(base=QuantConfig.int8(), rules=(
        ScopeRule("*embed*", (("weight_bits", 16),)),))
    paths = _resolved_paths(policy, ["embed", ""])        # "" = root
    f = rules.check_policy_hygiene(policy, paths)
    assert any("root path" in x.message for x in f), f


def test_ql003_clean_policy_is_silent():
    policy = QuantPolicy(base=QuantConfig.int8(),
                         rules=qpolicy.preset_rules("int8_embed16"))
    paths = _resolved_paths(policy, ["embed", "head", "blocks.0.attn.wq"])
    assert not rules.check_policy_hygiene(policy, paths)


def test_ql005_flags_divergence_regime_scope():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        policy = QuantPolicy(base=QuantConfig.int8(), rules=(
            ScopeRule("blocks.*", (("act_bits", 8),)),))   # w8/a8: Fig. 4
        paths = _resolved_paths(policy, ["blocks.0.attn.wq", "embed"])
        f = rules.check_stability(policy, paths)
    assert _codes(f) == ["QL005"]
    assert any("divergence regime" in x.message for x in f)


# =========================================================================
# QL006 — accumulator budget
# =========================================================================

def test_ql006_direct_form_reproduces_pr3_hole():
    """The seed-style norm moment: direct int16 ``Σx²`` at D=768 needs
    ~40 bits against int32's 31 — the exact bug PR 3 fixed."""
    site = budget.check_sum_site(16, 768, squared=True)
    assert site is not None
    assert site.bits_needed > 31
    # int8 at the same width fits comfortably — no site
    assert budget.check_sum_site(8, 768, squared=True) is None
    # and the digit-split partials the kernels use fit for any D < 2^17
    assert budget.sum_bits_needed(8, 768, squared=True) <= 31


def test_ql006_flags_overbudget_int16_reduction_in_jaxpr():
    """Jaxpr-level reconstruction: quantize to an int16 mantissa, square,
    reduce in f32 — integer-valued sum past 2^24.  Bounds originate at the
    ``lax.clamp`` primitive (the quantizer-clip idiom the interval model
    recognizes; ``jnp.clip`` lowers to max/min and stays unbounded)."""
    def broken(x):
        m = jax.lax.clamp(-32767.0, jnp.round(x * 32767.0), 32767.0) \
            .astype(jnp.int16)
        mf = m.astype(jnp.float32)
        return jnp.sum(mf * mf, axis=-1)
    f = rules.check_accum_budget(jax.make_jaxpr(broken)(jnp.ones((4, 768))))
    assert _codes(f) == ["QL006"]
    assert any("float32" in x.message for x in f)


def test_ql006_int32_accumulator_is_clean_at_same_width():
    def fixed(x):
        m = jax.lax.clamp(-127.0, jnp.round(x * 127.0), 127.0) \
            .astype(jnp.int32)
        return jnp.sum(m * m, axis=-1)                     # 24 bits < 31
    assert not rules.check_accum_budget(
        jax.make_jaxpr(fixed)(jnp.ones((4, 768))))


def test_ql006_conv_bwd_digit_split_is_clean():
    """Regression for the hole this PR closed: the depthwise-conv dw
    reduction at 16-bit gradients now accumulates digit-split int32
    partials instead of rounding in f32."""
    cfg = dataclasses.replace(QuantConfig.int16(), backend="pallas",
                              stochastic_grad=False)
    x = jax.random.normal(KEY, (2, 32, 16))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 16)) * 0.1
    jx = jax.make_jaxpr(jax.grad(
        lambda w: jnp.sum(int_ops.int_conv1d_depthwise(x, w, None, cfg) ** 2)
    ))(w)
    assert not rules.check_accum_budget(jx)


def test_ql006_kernel_sites_are_found_by_their_pinned_names():
    """The structural kernel checks key on each ``pallas_call``'s pinned
    ``name=``: a limb matmul contracting past K = 524 287 (64² · K over
    int32) is a site; the same kernel at K = 256 is not."""
    S = jax.ShapeDtypeStruct

    def sites(K):
        return budget.check_jaxpr(jax.make_jaxpr(
            lambda x, w: ops.dfx_matmul_tiled(x, jnp.int32(0), 16,
                                              w, jnp.int32(0), 16))(
            S((3, 128, K), jnp.int8), S((3, K, 128), jnp.int8)))

    (site,) = sites(1 << 20)
    assert site.where == "bfp_matmul"
    assert sites(256) == []


# =========================================================================
# QL007 — wire format
# =========================================================================

def test_ql007_flags_quantize_after_f32_gather():
    """The wasteful order: gather full-width bytes, then quantize the
    gathered copy — the b-bit form exists, so the wire should have carried
    it (sharding.quantized_all_gather's whole point)."""
    def broken(x):
        g = jax.lax.all_gather(x, "data")                  # f32 on the wire
        m = jnp.clip(jnp.round(g * 127.0), -127, 127).astype(jnp.int8)
        return m.astype(jnp.float32) / 127.0
    jx = jax.make_jaxpr(broken, axis_env=[("data", 4)])(jnp.ones((8,)))
    f = rules.check_wire_format(jx)
    assert _codes(f) == ["QL007"]
    assert any("all_gather" in x.message for x in f)


def test_ql007_flags_f32_gather_of_elsewhere_quantized_tensor():
    """Order-independent: an f32 gather of a tensor the graph quantizes in
    another branch is the same waste."""
    def broken(x):
        m = jnp.clip(jnp.round(x * 127.0), -127, 127).astype(jnp.int8)
        g = jax.lax.all_gather(x, "data")
        return g.sum() + m.astype(jnp.float32).sum()
    jx = jax.make_jaxpr(broken, axis_env=[("data", 4)])(jnp.ones((8,)))
    assert _codes(rules.check_wire_format(jx)) == ["QL007"]


def test_ql007_quantized_gather_is_clean():
    """The shipped shape: the collective moves int8 limb planes and the
    per-shard exponent; no full-width tensor crosses the wire."""
    def clean(x):
        t = qtensor.quantize(x, 8)
        m = jax.lax.all_gather(t.m, "data")                # int8 planes
        e = jax.lax.all_gather(t.exp, "data")              # int32 exponents
        shards = jax.vmap(
            lambda mm, ee: qtensor.dequantize(
                qtensor.QTensor(m=mm, exp=ee, bits=8)))(m, e)
        return shards.reshape(-1)
    jx = jax.make_jaxpr(clean, axis_env=[("data", 4)])(jnp.ones((8,)))
    assert not rules.check_wire_format(jx)


def test_ql007_plain_f32_gather_without_qtensor_form_is_clean():
    """An f32 gather alone is legitimate (nothing proves a quantized form
    exists) — QL007 only fires on the contradiction."""
    def clean(x):
        return jax.lax.all_gather(x, "data").sum() * 2.0
    jx = jax.make_jaxpr(clean, axis_env=[("data", 4)])(jnp.ones((8,)))
    assert not rules.check_wire_format(jx)


# =========================================================================
# QL008 — kept-op escape
# =========================================================================

def test_ql008_flags_every_kept_prim_escape():
    """Golden broken fixture: all five kept transcendentals on real data
    outside any kernel — exactly QL008, one finding per primitive."""
    def broken(x):
        return (jnp.exp(x) + jax.lax.erf(x) + jax.nn.sigmoid(x)
                + jnp.tanh(x) + jax.lax.rsqrt(jnp.abs(x) + 1.0))
    f = rules.check_kept_ops(jax.make_jaxpr(broken)(jnp.ones((8,))))
    assert _codes(f) == ["QL008"]
    prims = sorted(x.message.split(" ")[0] for x in f)
    assert prims == ["erf", "exp", "logistic", "rsqrt", "tanh"]


def test_ql008_exempts_iota_constant_tables():
    """Rope builds its frequency table as ``exp`` over scaled iota — a
    data-independent constant, not an escaped kept op."""
    def rope_table(x):
        freqs = jnp.exp(jnp.arange(8, dtype=jnp.float32) * -0.3)
        return x * jnp.cos(freqs)[None, :]
    assert not rules.check_kept_ops(
        jax.make_jaxpr(rope_table)(jnp.ones((4, 8))))


def test_ql008_integer_kept_ops_graph_is_clean():
    """The iapprox forms trace to shifts/multiplies/exact exp2 scalings —
    no kept primitive appears, so the swapped graph is silent."""
    from repro.core import iapprox
    def swapped(x):
        return (iapprox.i_exp(x) + iapprox.i_gelu(x) + iapprox.i_silu(x)
                + iapprox.i_tanh(x) + iapprox.i_rsqrt(jnp.abs(x) + 1.0)
                + iapprox.i_softmax(x))
    assert not rules.check_kept_ops(jax.make_jaxpr(swapped)(jnp.ones((8,))))


def test_ql008_gated_on_policy_kept_ops():
    """run_rules only activates QL008 when the policy carries
    ``kept_ops="integer"`` somewhere — an FP32-kept trace legitimately
    keeps its float transcendentals."""
    jx = jax.make_jaxpr(lambda x: jnp.tanh(x))(jnp.ones((4,)))
    fp32_base = dataclasses.replace(QuantConfig.int8(), kept_ops="fp32")
    fp32_pol = QuantPolicy(base=fp32_base)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        int_base = QuantPolicy(base=dataclasses.replace(
            fp32_base, kept_ops="integer"))
        int_rule = QuantPolicy(base=fp32_base, rules=(
            ScopeRule("blocks.*", (("kept_ops", "integer"),)),))
    assert "QL008" not in _codes(rules.run_rules(jx, policy=fp32_pol))
    assert "QL008" in _codes(rules.run_rules(jx, policy=int_base))
    assert "QL008" in _codes(rules.run_rules(
        jx, policy=int_rule, resolutions=[("blocks.0.mlp.act",)]))
    # explicit override beats the policy-derived gate
    assert "QL008" not in _codes(rules.run_rules(
        jx, policy=int_base, kept_ops=False))


# =========================================================================
# clean-graph acceptance (the full config × preset sweep runs in CI via
# ``python -m repro.analysis.lint --config all --preset all``)
# =========================================================================

@pytest.mark.parametrize("config,preset", [
    ("bert_base", "int8"),
    ("bert_base", "int8_embed16"),
    ("mamba2-370m", "int16"),
])
def test_lint_clean_on_registry_configs(config, preset):
    from repro.analysis import lint
    cell = lint.lint_cell(config, preset)
    assert cell["findings"] == [], cell["findings"]
    assert cell["pallas_calls"]["effective"] >= cell["pallas_calls"]["traced"]
    assert cell["resolutions"] > 0
