"""Prefill attention over packed prompts: rotary frequencies, the live-pair
table, and the Pallas kernel (interpret mode) against a dense masked float32
reference at the highest matmul precision."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dnng import Rope
from repro.kernels import (attention_table, live_blocks, packed_positions,
                           prefill_attention, rope_inv_freq)
from repro.kernels.ref import attention_ref

D = 128
SEQ = (300, 100, 37)          # unequal prompts, packed
BQ, BK = 64, 128
DEFAULT = Rope("default", theta=500000.0)
YARN = Rope("yarn", theta=500000.0, factor=16.0,
            original_max_position_embeddings=8192, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.2772588722239782)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_default_inverse_frequencies():
    inv, scale = rope_inv_freq(DEFAULT, D)
    assert inv.dtype == np.float32 and inv.shape == (64,) and scale == 1.0
    for i in (0, 1, 31, 63):
        assert inv[i] == np.float32(500000.0 ** (-2 * i / D))


def test_yarn_inverse_frequencies_by_hand():
    """Mellum2's full layers: the correction range is floor/ceil of
    128 ln(8192 / (2 pi r)) / (2 ln 500000) at r = 32 and r = 1, i.e.
    [18, 35]; below it the frequencies stay, above it they are divided by
    16, and within it they blend linearly."""
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(500000)))
    high = math.ceil(128 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(500000)))
    assert (low, high) == (18, 35)
    inv, scale = rope_inv_freq(YARN, D)
    assert scale == 1.2772588722239782

    def base(i):
        return 500000.0 ** (-2 * i / D)

    for i in (0, 10, 17, 18):
        assert inv[i] == np.float32(base(i))
    for i in (35, 40, 63):
        assert inv[i] == np.float32(base(i) / 16)
    t = (25 - 18) / (35 - 18)
    assert inv[25] == pytest.approx(base(25) / 16 * t + base(25) * (1 - t),
                                    rel=1e-7)


def test_packed_positions_restart_in_each_prompt():
    seg, pos = packed_positions([3, 2, 1])
    assert seg.tolist() == [0, 0, 0, 1, 1, 2]
    assert pos.tolist() == [0, 1, 2, 0, 1, 0]


@pytest.mark.parametrize("window", [0, 100, 1000])
def test_the_table_holds_exactly_the_pairs_with_a_visible_key(window):
    seg, pos = packed_positions(SEQ)
    table = attention_table(SEQ, window, block_q=BQ, block_k=BK)
    vis = (seg[:, None] == seg[None, :]) & (pos[None, :] <= pos[:, None])
    if window:
        vis &= pos[:, None] - pos[None, :] < window
    nq, nk = -(-len(seg) // BQ), -(-len(seg) // BK)
    want = [(i, j) for i in range(nq) for j in range(nk)
            if vis[i * BQ:(i + 1) * BQ, j * BK:(j + 1) * BK].any()]
    assert list(zip(table[0].tolist(), table[1].tolist())) == want
    # each query block's run of key blocks is flagged at its ends
    for f, la, i in zip(table[2], table[3], range(table.shape[1])):
        assert f == (i == 0 or table[0, i - 1] != table[0, i])
        assert la == (i == table.shape[1] - 1 or table[0, i + 1]
                      != table[0, i])


def test_a_window_skips_blocks():
    full = attention_table([4096, 2048], 0)
    windowed = attention_table([4096, 2048], 1024)
    assert windowed.shape[1] < 0.7 * full.shape[1]
    assert attention_table([300], 1000).shape == attention_table(
        [300], 0).shape


@pytest.fixture(scope="module")
def qkv():
    n = sum(SEQ)
    ks = jax.random.split(jax.random.key(0), 3)
    return (jax.random.normal(ks[0], (n, 4 * D), jnp.bfloat16),
            jax.random.normal(ks[1], (n, 2 * D), jnp.bfloat16),
            jax.random.normal(ks[2], (n, 2 * D), jnp.bfloat16))


def _call(qkv, rope, window, rows=None):
    q, k, v = qkv
    seg, pos = packed_positions(SEQ)
    r0, r1 = rows or (0, len(seg))
    inv, scale = rope_inv_freq(rope, D)
    table = live_blocks(seg[r0:r1], pos[r0:r1], seg, pos, window,
                        block_q=BQ, block_k=BK)
    args = (jnp.asarray(pos[r0:r1]), jnp.asarray(seg[r0:r1]),
            jnp.asarray(pos), jnp.asarray(seg), jnp.asarray(inv),
            jnp.float32(scale), np.int32(window))
    got = prefill_attention(q[r0:r1], k, v, *args, table, block_q=BQ,
                            block_k=BK, interpret=True)
    ref = attention_ref(q[r0:r1], k, v, *args[:5], scale, window)
    return got, ref


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("rope", [DEFAULT, YARN], ids=["default", "yarn"])
@pytest.mark.parametrize("window", [0, 64, 1000],
                         ids=["full", "window-below", "window-above"])
def test_prefill_attention_matches_the_reference(qkv, rope, window):
    got, ref = _call(qkv, rope, window)
    assert got.shape == ref.shape == (sum(SEQ), 4 * D)
    assert got.dtype == jnp.float32
    # scores are exact from the bf16 operands and the softmax stays in
    # f32; the probabilities' second bf16 part leaves 2^-16 of them, and
    # exp and summation order the rest (1e-4); in bf16 the error is 1e-2
    assert _rel(got, ref) < 3e-4


def test_a_window_above_every_prompt_is_no_window(qkv):
    np.testing.assert_array_equal(_call(qkv, DEFAULT, 1000)[0],
                                  _call(qkv, DEFAULT, 0)[0])


def test_query_rows_cut_in_two_slices_give_the_whole(qkv):
    whole, _ = _call(qkv, YARN, 64)
    cut = 150                  # inside the first prompt, off a block edge
    a, ref_a = _call(qkv, YARN, 64, (0, cut))
    b, ref_b = _call(qkv, YARN, 64, (cut, sum(SEQ)))
    assert _rel(a, ref_a) < 3e-4 and _rel(b, ref_b) < 3e-4
    np.testing.assert_allclose(jnp.concatenate([a, b]), whole, rtol=0,
                               atol=1e-6 * float(jnp.max(jnp.abs(whole))))


def test_the_window_is_honoured(qkv):
    """A windowed result is not the full one: the window changes what the
    late rows of the long prompt see."""
    assert _rel(_call(qkv, DEFAULT, 64)[0], _call(qkv, DEFAULT, 0)[0]) > 0.05
