"""Routed experts: the router, the experts as ragged tenants of the fused
call, and a chip's share of a sparse MLP layer, against float32 references
at the highest matmul precision (Pallas kernels in interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fused_tenant_gemm, moe_block, route, routed_experts
from repro.kernels.ref import expert_ref, moe_block_ref, route_ref

HIDDEN, WIDTH, EXPERTS, TOP_K = 128, 128, 8, 2


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _bf16(key, shape, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale
            ).astype(jnp.bfloat16)


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _experts(key, n):
    k1, k2 = jax.random.split(key)
    return ([_bf16(k, (HIDDEN, 2 * WIDTH), HIDDEN ** -0.5)
             for k in jax.random.split(k1, n)],
            [_bf16(k, (WIDTH, HIDDEN), WIDTH ** -0.5)
             for k in jax.random.split(k2, n)])


def test_route_matches_the_reference():
    logits = jax.random.normal(jax.random.key(0), (64, 64), jnp.float32)
    w, e = route(logits, 8)
    w_ref, e_ref = route_ref(logits, 8)
    np.testing.assert_array_equal(e, e_ref)
    np.testing.assert_allclose(w, w_ref, rtol=1e-6)
    np.testing.assert_allclose(w.sum(axis=1), 1.0, rtol=1e-6)
    assert w.dtype == jnp.float32 and e.dtype == jnp.int32


def test_route_breaks_a_tie_at_the_eighth_expert_to_the_lower_index():
    # experts 5 and 40 tie for the last of eight places
    logits = np.full((2, 64), -1.0, np.float32)
    logits[:, [1, 2, 3, 4, 9, 11, 13]] = [7, 6, 5, 4, 3, 2, 1.5]
    logits[:, [5, 40]] = 0.5
    logits[1, 40] = 0.5000001      # no tie: 40 wins
    w, e = route(jnp.asarray(logits), 8)
    assert sorted(np.asarray(e[0]).tolist()) == [1, 2, 3, 4, 5, 9, 11, 13]
    assert 40 in np.asarray(e[1]).tolist() and 5 not in np.asarray(e[1])
    np.testing.assert_array_equal(e, route_ref(jnp.asarray(logits), 8)[1])
    # renormalised over the eight, not the 64
    p = np.exp(logits[0] - logits[0].max())
    kept = p[[1, 2, 3, 4, 5, 9, 11, 13]]
    np.testing.assert_allclose(np.sort(np.asarray(w[0])),
                               np.sort(kept / kept.sum()), rtol=1e-5)


def test_routed_experts_on_ragged_rows_and_an_empty_expert():
    rows = [1, 7, 40, 0]
    kx, kw = jax.random.split(jax.random.key(1))
    xs = [_bf16(k, (r, HIDDEN)) for k, r in
          zip(jax.random.split(kx, len(rows)), rows)]
    gus, ds = _experts(kw, len(rows))
    outs = routed_experts(xs, gus, ds, interpret=True)
    assert [o.shape for o in outs] == [(r, HIDDEN) for r in rows]
    for x, gu, d, out in zip(xs, gus, ds, outs):
        assert out.dtype == jnp.float32
        if x.shape[0]:
            # bf16 products are exact in f32 and the activation rounds to
            # bf16 in both, so only f32 summation order differs, and with it
            # now and then an activation's bf16 rounding (1e-4 of the
            # output's scale); left in f32, the reference is 1e-3 away
            assert _rel(out, expert_ref(x, gu, d)) < 1e-3


def test_routed_experts_refuses_mismatched_pieces():
    gus, ds = _experts(jax.random.key(2), 2)
    with pytest.raises(ValueError, match="expert 1"):
        routed_experts([jnp.zeros((3, HIDDEN), jnp.bfloat16),
                        jnp.zeros((3, HIDDEN + 1), jnp.bfloat16)], gus, ds,
                       interpret=True)


def test_routed_experts_with_no_rows_at_all_returns_empty_outputs():
    gus, ds = _experts(jax.random.key(3), 2)
    outs = routed_experts([jnp.zeros((0, HIDDEN), jnp.bfloat16)] * 2, gus,
                          ds, interpret=True)
    assert [o.shape for o in outs] == [(0, HIDDEN), (0, HIDDEN)]


@pytest.mark.parametrize("grid_mode", ["compact", "dense", "auto"])
def test_a_tenant_with_no_rows_is_a_dead_slice_of_the_fused_call(grid_mode):
    xs = [jnp.ones((5, 128), jnp.bfloat16), jnp.ones((0, 128), jnp.bfloat16),
          jnp.ones((3, 128), jnp.bfloat16)]
    ws = [jnp.ones((128, 256), jnp.bfloat16)] * 3
    outs = fused_tenant_gemm(xs, ws, grid_mode=grid_mode, interpret=True)
    assert [o.shape for o in outs] == [(5, 256), (0, 256), (3, 256)]
    assert float(outs[0].min()) == float(outs[2].max()) == 128.0


def test_a_call_whose_tenants_all_have_no_rows_fails_clearly():
    with pytest.raises(ValueError, match="every tenant has 0 rows"):
        fused_tenant_gemm([jnp.ones((0, 128), jnp.bfloat16)],
                          [jnp.ones((128, 128), jnp.bfloat16)],
                          interpret=True)


@pytest.fixture(scope="module")
def layer():
    ks = jax.random.split(jax.random.key(4), 3)
    x = _bf16(ks[0], (64, HIDDEN))
    router = _bf16(ks[1], (HIDDEN, EXPERTS), HIDDEN ** -0.5)
    gus, ds = _experts(ks[2], EXPERTS)
    return x, router, gus, ds


def test_moe_block_matches_the_dense_reference(layer):
    x, router, gus, ds = layer
    got = moe_block(x, router, gus, ds, top_k=TOP_K, interpret=True)
    ref = moe_block_ref(x, router, gus, ds, top_k=TOP_K)
    assert got.shape == x.shape and _rel(got, ref) < 1e-3


def test_disjoint_held_shares_add_up_to_the_uncut_layer(layer):
    """Two chips, each told which four of the eight experts it holds, both
    routing over all eight: their parts sum to the whole layer."""
    x, router, gus, ds = layer
    whole = moe_block(x, router, gus, ds, top_k=TOP_K, interpret=True)
    parts = [moe_block(x, router, [gus[e] for e in held],
                       [ds[e] for e in held], top_k=TOP_K, held=held,
                       interpret=True)
             for held in ([0, 2, 4, 6], [7, 5, 3, 1])]
    assert _rel(parts[0] + parts[1], whole) < 1e-6
    ref = moe_block_ref(x, router, [gus[e] for e in (0, 2, 4, 6)],
                        [ds[e] for e in (0, 2, 4, 6)], top_k=TOP_K,
                        held=[0, 2, 4, 6])
    assert _rel(parts[0], ref) < 1e-3
    # each share is a real part: neither is zero nor the whole
    assert _rel(parts[0], whole) > 0.1 and _rel(parts[1], whole) > 0.1
