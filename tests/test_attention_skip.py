"""Causal and windowed attention visits only the key blocks its mask can
reach, and gets the full grid's results bit for bit; the encoders' call
(neither causal nor windowed) is the full-grid ``pallas_call`` it always
was.

Equality is checked against the same kernels with the band forced to every
block (``_k_band``/``_q_band`` returning the whole range, one grid step per
block): a skipped block is wholly masked, and a wholly masked block leaves
the running max, the normaliser and every accumulator exactly as they
were, so the two agree to the bit.  Interpret mode, small shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import walker
from repro.kernels import int_attention as ia
from repro.kernels import ops as kops

BITS = 16


def _planes(rng, shape):
    m = rng.integers(-2 ** 15 + 1, 2 ** 15, shape)
    return kops.split_limbs_stacked(jnp.asarray(m), BITS)


def _attention(case):
    """(o, lse, dq, dk, dv) of one call, forward and backward."""
    Sq, Sk, off, causal, window = case
    B, KV, G, hd = 2, 1, 2, 32
    rng = np.random.default_rng(Sq * 7 + Sk)
    q = _planes(rng, (B, Sq, KV, G, hd))
    k = _planes(rng, (B, Sk, KV, hd))
    v = _planes(rng, (B, Sk, KV, hd))
    g = _planes(rng, (B, Sq, KV, G, hd))
    off = jnp.asarray(off, jnp.int32)
    e = jnp.int32(-19)
    o, lse = kops.attention_fwd(q, e, k, e, v, e, off, BITS, causal=causal,
                                window=window)
    delta = jnp.sum(o * jax.random.normal(jax.random.PRNGKey(0), o.shape),
                    axis=-1)
    dq, dk, dv = kops.attention_bwd(q, e, k, e, v, e, g, jnp.int32(-20),
                                    lse, delta, None, off, BITS,
                                    BITS, causal=causal, window=window)
    return [np.asarray(a) for a in (o, lse, dq, dk, dv)]


CASES = {
    # (Sq, Sk, per-row query offsets, causal, window)
    "causal": (640, 640, [0, 0], True, None),
    "window": (640, 640, [0, 0], True, 64),
    "ragged_tail": (300, 300, [0, 0], True, 100),
    "prefill_offsets": (40, 640, [500, 130], True, 200),
    "decode": (1, 640, [639, 17], True, 64),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_band_equals_full_grid(case, monkeypatch):
    banded = _attention(case)
    monkeypatch.setattr(ia, "_k_band", lambda off, qi, **kw: (0, kw["n_k"] - 1))
    monkeypatch.setattr(ia, "_q_band", lambda off, kj, **kw: (0, kw["nqb"] - 1))
    monkeypatch.setattr(ia, "_band_steps", lambda n, *a: n)
    jax.clear_caches()
    try:
        full = _attention(case)
    finally:
        jax.clear_caches()
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), banded, full):
        assert np.array_equal(a, b), name


def _pallas_calls(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return {s.eqn.params["name"]: s.eqn.params["grid_mapping"]
            for s in walker.iter_eqns(jaxpr, recurse_pallas=False)
            if s.prim == "pallas_call"}


def _block(bmap, *idx):
    j = bmap.index_map_jaxpr
    return tuple(int(x) for x in jax.core.eval_jaxpr(j.jaxpr, j.consts, *idx))


def _operands(B, S, KV, G, hd):
    q = jnp.zeros((3, B, S, KV, G, hd), jnp.int8)
    k = jnp.zeros((3, B, S, KV, hd), jnp.int8)
    return q, k, jnp.zeros((B,), jnp.int32), jnp.int32(0)


def test_windowed_call_visits_a_band():
    """At S=640 (5 key blocks) a window of 64 visits 3 key blocks per query
    block and 3 query blocks per key block in each GQA group."""
    q, k, off, e = _operands(1, 640, 1, 2, 32)
    lse, d = jnp.zeros((1, 1, 2, 640)), jnp.zeros((1, 640, 1, 2))
    fwd = _pallas_calls(lambda q, k: kops.attention_fwd(
        q, e, k, e, k, e, off, BITS, causal=True, window=64), q, k)
    bwd = _pallas_calls(lambda q, k: kops.attention_bwd(
        q, e, k, e, k, e, q, e, lse, d, None, off, BITS, BITS, causal=True,
        window=64), q, k)
    assert fwd["int_attn_fwd"].grid == (1, 10, 3)
    assert bwd["int_attn_bwd_dq"].grid == (1, 10, 3)
    assert bwd["int_attn_bwd_dkv"].grid == (1, 5, 2 * 3)
    assert all(gm.num_index_operands == 1
               for gm in (*fwd.values(), *bwd.values()))


def test_encoder_call_is_todays():
    """``causal=False, window=None`` (bert, vit): the full grid, the
    BlockSpecs and index maps the kernels always had, and no scalar
    prefetch (the query offsets stay an SMEM operand)."""
    B, S, KV, G, hd = 2, 200, 2, 2, 64          # S_p 256, hd_p 128
    q, k, off, e = _operands(B, S, KV, G, hd)
    lse, d = jnp.zeros((B, KV, G, S)), jnp.zeros((B, S, KV, G))
    calls = _pallas_calls(lambda q, k: kops.attention_fwd(
        q, e, k, e, k, e, off, BITS, causal=False), q, k)
    calls.update(_pallas_calls(lambda q, k: kops.attention_bwd(
        q, e, k, e, k, e, q, e, lse, d, e, off, BITS, BITS, causal=False),
        q, k))
    BH, nq, nk = B * KV, G * 2, 2
    tile, kvt, row = (3, 1, 128, 128), (3, 1, 128, 128), (1, 128, 1)
    want = {
        "int_attn_fwd": ((BH, nq, nk), [tile, kvt, kvt],
                         lambda h, i, j: [(0, h, i, 0), (0, h, j, 0),
                                          (0, h, j, 0)]),
        "int_attn_bwd_dq": ((BH, nq, nk), [tile, kvt, kvt, tile, row, row],
                            lambda h, i, j: [(0, h, i, 0), (0, h, j, 0),
                                             (0, h, j, 0), (0, h, i, 0),
                                             (h, i, 0), (h, i, 0)]),
        "int_attn_bwd_dkv": ((BH, nk, nq), [tile, kvt, kvt, tile, row, row],
                             lambda h, j, i: [(0, h, i, 0), (0, h, j, 0),
                                              (0, h, j, 0), (0, h, i, 0),
                                              (h, i, 0), (h, i, 0)]),
    }
    assert set(calls) == set(want)
    for name, (grid, shapes, blocks) in want.items():
        gm = calls[name]
        assert gm.grid == grid, name
        assert gm.num_index_operands == 0, name
        # the tensor operands come first, the two SMEM operands after them
        assert gm.num_inputs == len(shapes) + 2, name
        tensors = gm.block_mappings[:len(shapes)]
        got = [tuple(getattr(s, "block_size", s) for s in b.block_shape)
               for b in tensors]
        assert got == shapes, name
        for idx in [(0, 0, 0), (1, 3, 1), (3, 2, 0)]:
            assert [_block(b, *idx) for b in tensors] == blocks(*idx), (
                name, idx)
