"""Causal and windowed attention visits only the key blocks its mask can
reach, and gets the full grid's results bit for bit; a grid step that walks
a block of score tiles gets the one-tile-a-step grid's results bit for bit;
the encoders' call (neither causal nor windowed) has no scalar prefetch.

Band equality is checked against the same kernels with the band forced to
every block (``_k_band``/``_q_band`` returning the whole range, one grid
step per block): a skipped block is wholly masked, and a wholly masked
block leaves the running max, the normaliser and every accumulator exactly
as they were, so the two agree to the bit.  Block equality is checked
against the chooser forced to one score tile a step (``ops._attn_blocks``
returning the tile): the walk inside a block visits the same tiles in the
same order per row (forward, dq) or per key (dkv).  Interpret mode, small
shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import walker
from repro.kernels import int_attention as ia
from repro.kernels import ops as kops
from repro.kernels.dfx_quant import n_limbs

BITS = 16


def _planes(rng, shape, bits=BITS):
    m = rng.integers(-2 ** (bits - 1) + 1, 2 ** (bits - 1), shape)
    return kops.split_limbs_stacked(jnp.asarray(m), bits)


def _attention(case, B=2, KV=1, G=2, hd=32, bits=(BITS, BITS)):
    """(o, lse, dq, dk, dv) of one call, forward and backward, with
    ``bits`` = (q/k/v and P, dO and dS) mantissa widths."""
    Sq, Sk, off, causal, window = case
    ab, gb = bits
    rng = np.random.default_rng(Sq * 7 + Sk)
    q = _planes(rng, (B, Sq, KV, G, hd), ab)
    k = _planes(rng, (B, Sk, KV, hd), ab)
    v = _planes(rng, (B, Sk, KV, hd), ab)
    g = _planes(rng, (B, Sq, KV, G, hd), gb)
    off = jnp.asarray(off, jnp.int32)
    e = jnp.int32(-ab - 3)
    o, lse = kops.attention_fwd(q, e, k, e, v, e, off, ab, causal=causal,
                                window=window)
    delta = jnp.sum(o * jax.random.normal(jax.random.PRNGKey(0), o.shape),
                    axis=-1)
    ds_exp = None if causal or window is not None else jnp.int32(-12)
    dq, dk, dv = kops.attention_bwd(q, e, k, e, v, e, g, jnp.int32(-gb - 4),
                                    lse, delta, ds_exp, off, ab,
                                    gb, causal=causal, window=window)
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


#: (Sq, Sk, per-row query offsets, causal, window) and the call's shape:
#: each case's chosen blocks span several score tiles in some kernel
BLOCK_CASES = {
    "encoder_gqa": ((200, 200, [0, 0], False, None), dict(KV=2, hd=64)),
    "causal": ((512, 512, [0, 0], True, None), {}),
    "window_unaligned": ((512, 512, [0, 0], True, 200), {}),
    "prefill_offsets": ((256, 640, [300, 130], True, 200), {}),
    "decode": ((1, 640, [639, 17], True, None), {}),
    "ragged_kv": ((300, 300, [0, 0], True, 100), {}),
    "int8_preset_causal": ((256, 256, [0, 0], True, None),
                           dict(bits=(12, 8))),
    "int8_preset_encoder": ((200, 200, [0, 0], False, None),
                            dict(KV=2, bits=(12, 8))),
    "eight_bit": ((384, 384, [0, 0], True, 200), dict(bits=(8, 8))),
}


@pytest.mark.parametrize("case", BLOCK_CASES.values(), ids=BLOCK_CASES.keys())
def test_blocks_equal_one_tile_a_step(case, monkeypatch):
    (Sq, Sk, off, causal, window), shape = case
    hd, bits = shape.get("hd", 32), shape.get("bits", (BITS, BITS))
    plan = kops.attention_grid_plan(Sq, Sk, hd, n_limbs(max(bits)), causal,
                                    window)
    bq, _, bk, _, _ = kops._attn_dims(Sq, Sk, hd)
    assert any(p["blocks"] != (bq, bk) for p in plan.values()), plan
    blocked = _attention(case[0], **shape)
    monkeypatch.setattr(kops, "_attn_blocks", lambda *a: a[-2:])
    tiled = _attention(case[0], **shape)
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), blocked, tiled):
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


def _block_shapes(gm, n):
    return [tuple(getattr(s, "block_size", s) for s in b.block_shape)
            for b in gm.block_mappings[:n]]


#: the chooser's grid block (rows, keys) and the grid of each kernel of a
#: windowed call at S=4096 (two GQA groups), and its block for an encoder
#: call at S=200
WINDOWED = {"fwd": ((512, 1024), (1, 16, 2)),
            "dq": ((512, 1024), (1, 16, 2)),
            "dkv": ((1024, 512), (1, 8, 2 * 2))}
ENCODER_BLOCK = (256, 256)


def test_windowed_call_visits_a_band():
    """At S=4096 a window of 64 visits two key blocks per query block and
    two query blocks per key block in each GQA group, at the chooser's
    grid blocks: each kernel's grid runs the plan's steps, its BlockSpecs
    carry the plan's blocks, and the plan's live tiles are the window's
    (two key tiles a query tile, one for the first)."""
    S, G = 4096, 2
    q, k, off, e = _operands(1, S, 1, G, 32)
    lse, d = jnp.zeros((1, 1, G, S)), jnp.zeros((1, S, 1, G))
    calls = _pallas_calls(lambda q, k: kops.attention_fwd(
        q, e, k, e, k, e, off, BITS, causal=True, window=64), q, k)
    calls.update(_pallas_calls(lambda q, k: kops.attention_bwd(
        q, e, k, e, k, e, q, e, lse, d, None, off, BITS, BITS, causal=True,
        window=64), q, k))
    plan = kops.attention_grid_plan(S, S, 32, 3, True, 64)
    for n, (blocks, grid) in WINDOWED.items():
        gm = calls["int_attn_fwd" if n == "fwd" else f"int_attn_bwd_{n}"]
        rows, keys = blocks
        assert plan[n]["blocks"] == blocks, n
        assert gm.grid == grid and np.prod(grid) == G * plan[n]["steps"], n
        assert gm.num_index_operands == 1, n
        assert _block_shapes(gm, 2) == [(3, 1, rows, 128),
                                        (3, 1, keys, 128)], n
        assert plan[n]["tiles"] == 1 + 2 * (S // 128 - 1), n


def test_encoder_call_is_todays():
    """``causal=False, window=None`` (bert, vit): the full grid at the
    chooser's grid block — every score tile of a head in one step here —
    its BlockSpecs and index maps, and no scalar prefetch (the query
    offsets stay an SMEM operand)."""
    B, S, KV, G, hd = 2, 200, 2, 2, 64          # S_p 256, hd_p 128
    q, k, off, e = _operands(B, S, KV, G, hd)
    lse, d = jnp.zeros((B, KV, G, S)), jnp.zeros((B, S, KV, G))
    calls = _pallas_calls(lambda q, k: kops.attention_fwd(
        q, e, k, e, k, e, off, BITS, causal=False), q, k)
    calls.update(_pallas_calls(lambda q, k: kops.attention_bwd(
        q, e, k, e, k, e, q, e, lse, d, e, off, BITS, BITS, causal=False),
        q, k))
    plan = kops.attention_grid_plan(S, S, hd, 3, False)
    assert all(p["blocks"] == ENCODER_BLOCK for p in plan.values()), plan
    rows, keys = ENCODER_BLOCK
    BH, nq, nk = B * KV, G * 256 // rows, 256 // keys
    tile, kvt, row = (3, 1, rows, 128), (3, 1, keys, 128), (1, rows, 1)
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
        assert _block_shapes(gm, len(shapes)) == shapes, name
        for idx in [(0, 0, 0), (1, 1, 0), (3, 1, 0)]:
            assert [_block(b, *idx) for b in tensors] == blocks(*idx), (
                name, idx)


#: a training step's attention calls per query head of one batch row:
#: the forward twice (once more under remat), dq and dkv
PASSES = {"fwd": 2, "dq": 1, "dkv": 1}

#: each cell's layers (S, head dim, causal, window), batch, query heads;
#: then the most grid steps a training step may take, its live score
#: tiles, and the grid steps of one tile a step
CELLS = {
    "mellum": ([(8192, 128, True, 1024)] * 3 + [(8192, 128, True, None)],
               2, 32, 300_000, 947_200, 1_540_096),
    "bert": ([(384, 64, False, None)] * 12, 32, 12, 20_000, 165_888,
             165_888),
    "vit": ([(197, 64, False, None)] * 12, 64, 12, 40_000, 147_456,
            147_456),
}

#: the chooser's picks (fwd, dq, dkv) for each distinct layer of the cells
MELLUM_WINDOW = ((1024, 1024),) * 3
MELLUM_FULL = ((2048, 2048),) * 3
CELL_BLOCKS = {
    (8192, 128, True, 1024): MELLUM_WINDOW,
    (8192, 128, True, None): MELLUM_FULL,
    (384, 64, False, None): ((384, 384),) * 3,
    (197, 64, False, None): ((256, 256),) * 3,
}


def _step_totals(layers, B, H):
    steps = tiles = 0
    for S, hd, causal, window in layers:
        plan = kops.attention_grid_plan(S, S, hd, 3, causal, window)
        for kernel, n in PASSES.items():
            steps += n * B * H * plan[kernel]["steps"]
            tiles += n * B * H * plan[kernel]["tiles"]
    return steps, tiles


@pytest.mark.parametrize("cell", CELLS)
def test_training_step_grid_totals(cell, monkeypatch):
    """The cells' attention grid steps a training step fall at least four-
    (encoders) or five-fold (Mellum) from one tile a step, and the live
    score tiles stay exactly as many."""
    layers, B, H, most, tiles, tile_steps = CELLS[cell]
    steps, live = _step_totals(layers, B, H)
    assert steps <= most and live == tiles, (steps, live)
    for S, hd, causal, window in set(layers):
        plan = kops.attention_grid_plan(S, S, hd, 3, causal, window)
        assert tuple(p["blocks"] for p in plan.values()) == CELL_BLOCKS[
            S, hd, causal, window]
    monkeypatch.setattr(kops, "_attn_blocks", lambda *a: a[-2:])
    assert _step_totals(layers, B, H) == (tile_steps, tiles)


PLAN_SHAPES = [
    # (Sq, Sk, hd, limbs, causal, window)
    (8192, 8192, 128, 3, True, 1024), (8192, 8192, 128, 3, True, None),
    (8192, 8192, 128, 2, True, None), (384, 384, 64, 3, False, None),
    (384, 384, 64, 1, False, None), (197, 197, 64, 2, False, None),
    (1, 8192, 128, 3, True, None), (1, 640, 64, 3, True, 64),
    (40, 640, 32, 3, True, 200), (1500, 300, 64, 3, False, None),
    (4096, 4096, 256, 3, True, None),
]


@pytest.mark.parametrize("shape", PLAN_SHAPES, ids=str)
def test_grid_blocks_tile_the_call(shape):
    """Every grid block is a multiple of the score tile that divides the
    padded extents (a block of rows never straddles a GQA group), its
    modelled VMEM is within the budget, and decode keeps its 8-row tile."""
    Sq, Sk, hd, limbs, causal, window = shape
    bq, sq_p, bk, sk_p, hd_p = kops._attn_dims(Sq, Sk, hd)
    for kernel, p in kops.attention_grid_plan(*shape).items():
        rows, keys = p["blocks"]
        assert rows % bq == 0 and sq_p % rows == 0, (kernel, p)
        assert keys % bk == 0 and sk_p % keys == 0, (kernel, p)
        assert ia.attention_vmem_bytes(
            kernel, rows, keys, hd_p, limbs, (bq, bk),
            not causal and window is None) <= kops._ATTN_VMEM_BUDGET
        if Sq == 1:
            assert rows == bq == 8, (kernel, p)
