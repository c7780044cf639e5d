"""Pallas partitioned-WS GEMM vs the pure-jnp oracle (interpret mode)."""

import contextlib
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# only the property tests need hypothesis — the deterministic compact-grid
# / dtype / VMEM / autotune coverage always runs
from _hypothesis_compat import given, settings, st

from repro.kernels import (
    autotune_blocks,
    block_vmem_bytes,
    build_owner_map,
    fused_tenant_gemm,
    grid_accounting,
    live_block_tables,
    partitioned_matmul,
    partitioned_matmul_ref,
)


def _mk(key, E, T, K, N, n_blocks, dtype, seed_valid=None):
    k1, k2, k3 = jax.random.split(key, 3)
    xs = jax.random.normal(k1, (E, T, K), jnp.float32)
    valid_t = (jnp.full((E,), T, jnp.int32) if seed_valid is None
               else seed_valid)
    rows = jnp.arange(T)[None, :, None]
    xs = jnp.where(rows < valid_t[:, None, None], xs, 0.0).astype(dtype)
    w = jax.random.normal(k2, (K, N), jnp.float32).astype(dtype)
    owner = jax.random.randint(k3, (n_blocks,), 0, E)
    return xs, w, owner, valid_t


TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


class TestPartitionedMatmul:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [
        (1, 128, 128, 128),    # single tenant, single block
        (2, 128, 256, 512),    # multi-block N
        (3, 256, 128, 384),    # 3 tenants
        (4, 128, 384, 1024),   # K folds
    ])
    def test_allclose_vs_oracle(self, dtype, shape):
        E, T, K, N = shape
        bn = 128
        xs, w, owner, valid_t = _mk(jax.random.key(0), E, T, K, N,
                                    N // bn, dtype)
        out = partitioned_matmul(xs, w, owner, valid_t, interpret=True)
        ref = partitioned_matmul_ref(xs, w, owner, valid_t, bn)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   **TOL[dtype])

    def test_ragged_valid_t_masks_rows(self):
        E, T, K, N = 2, 256, 128, 256
        valid = jnp.array([100, 256], jnp.int32)
        xs, w, owner, valid_t = _mk(jax.random.key(1), E, T, K, N, 2,
                                    jnp.float32, seed_valid=valid)
        owner = jnp.array([0, 1], jnp.int32)
        out = partitioned_matmul(xs, w, owner, valid_t, interpret=True)
        # tenant 0 owns cols [0,128): rows >= 100 are zero (skipped blocks)
        np.testing.assert_array_equal(np.asarray(out[128:, :128]), 0.0)
        # tenant 1 rows all live
        assert np.abs(np.asarray(out[200:, 128:])).sum() > 0

    def test_block_shape_sweep(self):
        E, T, K, N = 2, 256, 256, 256
        xs, w, owner, valid_t = _mk(jax.random.key(2), E, T, K, N, 2,
                                    jnp.float32)
        ref = partitioned_matmul_ref(xs, w, owner, valid_t, 128)
        for bt, bk in [(128, 128), (64, 128), (128, 64), (256, 256)]:
            out = partitioned_matmul(xs, w, owner, valid_t, block_t=bt,
                                     block_k=bk, block_n=128,
                                     interpret=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-4, atol=1e-4)

    def test_indivisible_shapes_rejected(self):
        xs = jnp.zeros((1, 100, 128))
        w = jnp.zeros((128, 128))
        with pytest.raises(ValueError, match="not divisible"):
            partitioned_matmul(xs, w, jnp.zeros((1,), jnp.int32),
                               jnp.array([100]), interpret=True)

    def test_owner_shape_checked(self):
        xs = jnp.zeros((1, 128, 128))
        w = jnp.zeros((128, 256))
        with pytest.raises(ValueError, match="owner"):
            partitioned_matmul(xs, w, jnp.zeros((5,), jnp.int32),
                               jnp.array([128]), interpret=True)


def _mk_int(seed, E, T, K, N, n_blocks, valid_t, valid_k):
    """Integer-valued f32 operands honouring the zero-padding contract.

    Small-integer entries keep every product and partial sum exactly
    representable in f32, so dense, compact and the oracle must agree
    BIT-exactly regardless of accumulation grouping.
    """
    rng = np.random.default_rng(seed)
    xs = rng.integers(-4, 5, (E, T, K)).astype(np.float32)
    for e in range(E):
        xs[e, valid_t[e]:, :] = 0.0
        xs[e, :, valid_k[e]:] = 0.0
    w = rng.integers(-4, 5, (K, N)).astype(np.float32)
    owner = rng.integers(0, E, n_blocks).astype(np.int32)
    return (jnp.asarray(xs), jnp.asarray(w), jnp.asarray(owner),
            jnp.asarray(valid_t, jnp.int32), jnp.asarray(valid_k, jnp.int32))


@contextlib.contextmanager
def _counting_lowerings():
    """Collect JAX's lowerings to MLIR while the block runs."""
    events = []

    def listen(event, _secs, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield events
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


class TestCompactGrid:
    """grid_mode='compact' — live blocks only, same numerics as dense."""

    @given(seed=st.integers(0, 2**31 - 1),
           dims=st.tuples(st.integers(1, 3),      # E
                          st.integers(1, 3),      # t blocks
                          st.integers(1, 3),      # k blocks
                          st.integers(1, 4)),     # n blocks
           data=st.data())
    @settings(max_examples=10, deadline=None)
    def test_matches_dense_and_oracle_bit_exactly(self, seed, dims, data):
        E, tb, kb, nb = dims
        B = 64
        T, K, N = tb * B, kb * B, nb * B
        valid_t = data.draw(st.lists(st.integers(0, T), min_size=E,
                                     max_size=E))
        valid_k = data.draw(st.lists(st.integers(0, K), min_size=E,
                                     max_size=E))
        xs, w, owner, vt, vk = _mk_int(seed, E, T, K, N, nb,
                                       valid_t, valid_k)
        kw = dict(block_t=B, block_k=B, block_n=B, interpret=True)
        dense = partitioned_matmul(xs, w, owner, vt, vk,
                                   grid_mode="dense", **kw)
        compact = partitioned_matmul(xs, w, owner, vt, vk,
                                     grid_mode="compact", **kw)
        np.testing.assert_array_equal(np.asarray(compact), np.asarray(dense))
        # the oracle masks by valid_t only; valid_k exactness comes from
        # the zero-padded K columns contributing exact zeros
        ref = partitioned_matmul_ref(xs, w, owner, vt, B)
        np.testing.assert_array_equal(np.asarray(compact), np.asarray(ref))

    def test_compact_schedules_exactly_the_live_blocks(self):
        owner = np.array([0, 1, 1, 2], np.int32)
        vt, vk = np.array([100, 256, 7]), np.array([384, 130, 40])
        nidx, tidx, kidx, last = live_block_tables(
            owner, vt, vk, T=256, K=384, block_t=128, block_k=128)
        acc = grid_accounting(T=256, K=384, N=512, owner=owner, valid_t=vt,
                              valid_k=vk, grid_mode="compact")
        assert acc.blocks_scheduled == nidx.size == acc.blocks_live
        assert acc.blocks_skipped == 0
        # tenant0: 1x3 blocks; tenant1 (2 cols): 2*(2x2); tenant2: 1x1
        assert acc.blocks_live == 3 + 2 * 4 + 1
        # K-runs contiguous, drain flagged on the run's last step
        runs = np.flatnonzero(kidx == 0)
        for s, e in zip(runs, list(runs[1:]) + [nidx.size]):
            assert (nidx[s:e] == nidx[s]).all() and (tidx[s:e] == tidx[s]).all()
            assert list(kidx[s:e]) == list(range(e - s))
            assert last[e - 1] == 1 and not last[s:e - 1].any()

    def test_dense_accounting_counts_gated_steps(self):
        owner = np.array([0, 1], np.int32)
        acc = grid_accounting(T=256, K=256, N=256, owner=owner,
                              valid_t=np.array([128, 256]),
                              valid_k=np.array([256, 128]),
                              grid_mode="dense")
        assert acc.blocks_total == acc.blocks_scheduled == 2 * 2 * 2
        assert acc.blocks_live == 2 + 2          # t0: 1x2, t1: 2x1
        assert acc.blocks_skipped == 4
        # fetch model: every scheduled step pulls one x and one w tile
        assert acc.x_bytes_fetched == 8 * 128 * 128 * 4
        assert acc.w_bytes_fetched == 8 * 128 * 128 * 4
        assert acc.schedule_efficiency == 0.5

    def test_zero_live_blocks_returns_zeros(self):
        xs = jnp.ones((1, 128, 128), jnp.float32)
        out = partitioned_matmul(xs, jnp.ones((128, 128), jnp.float32),
                                 jnp.zeros((1,), jnp.int32),
                                 jnp.array([0], jnp.int32),
                                 grid_mode="compact", interpret=True)
        np.testing.assert_array_equal(np.asarray(out), 0.0)

    def test_compact_rejects_traced_partition_state(self):
        xs = jnp.zeros((1, 128, 128), jnp.float32)
        w = jnp.zeros((128, 128), jnp.float32)

        @jax.jit
        def f(owner, vt):
            return partitioned_matmul(xs, w, owner, vt,
                                      grid_mode="compact", interpret=True)

        with pytest.raises(ValueError, match="concrete"):
            f(jnp.zeros((1,), jnp.int32), jnp.array([128], jnp.int32))

    def test_repeated_compact_call_lowers_nothing(self):
        key = jax.random.key(11)
        xs = [jax.random.normal(jax.random.fold_in(key, i), (t, k))
              for i, (t, k) in enumerate([(256, 256), (40, 60)])]
        ws = [jax.random.normal(jax.random.fold_in(key, 10 + i), (k, 128))
              for i, k in enumerate([256, 60])]
        kw = dict(block_t=128, block_k=128, block_n=128, interpret=True,
                  return_stats=True)
        first, stats = fused_tenant_gemm(xs, ws, **kw)
        assert stats.grid_mode == "compact"
        with _counting_lowerings() as lowerings:
            second, _ = fused_tenant_gemm(xs, ws, **kw)
            jax.block_until_ready(second)
        assert lowerings == []
        for a, b in zip(first, second):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_cached_grid_takes_its_tables_at_run_time(self):
        """Layouts of one live-step count share a program and each gets
        its own tables; a new count compiles a grid of its own."""
        B, T, K, N = 64, 128, 128, 128
        owner = jnp.array([0, 1], jnp.int32)
        kw = dict(block_t=B, block_k=B, block_n=B, grid_mode="compact",
                  interpret=True)

        def run(valid_t):
            xs, w, _, vt, vk = _mk_int(sum(valid_t), 2, T, K, N, 2,
                                       valid_t, [K, K])
            out = partitioned_matmul(xs, w, owner, vt, vk, **kw)
            ref = partitioned_matmul_ref(xs, w, owner, vt, B)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

        # the short tenant swaps: 2·2 + 1·2 live steps either way
        assert (live_block_tables([0, 1], [128, 30], [K, K], T=T, K=K,
                                  block_t=B, block_k=B)[0].size ==
                live_block_tables([0, 1], [30, 128], [K, K], T=T, K=K,
                                  block_t=B, block_k=B)[0].size == 6)
        run([128, 30])
        with _counting_lowerings() as lowerings:
            run([30, 128])
        assert lowerings == []
        run([128, 128])  # 8 live steps: a grid of another length

    def test_bad_grid_mode_rejected(self):
        xs = jnp.zeros((1, 128, 128), jnp.float32)
        with pytest.raises(ValueError, match="grid_mode"):
            partitioned_matmul(xs, jnp.zeros((128, 128)),
                               jnp.zeros((1,), jnp.int32),
                               jnp.array([128]), grid_mode="sparse",
                               interpret=True)


class TestOperandContract:
    """Explicit dtype validation/promotion + the VMEM block budget."""

    def test_int_operands_rejected(self):
        xs = jnp.zeros((1, 128, 128), jnp.int32)
        with pytest.raises(TypeError, match="bfloat16 or float32"):
            partitioned_matmul(xs, jnp.zeros((128, 128), jnp.float32),
                               jnp.zeros((1,), jnp.int32),
                               jnp.array([128]), interpret=True)

    def test_f16_weights_rejected(self):
        xs = jnp.zeros((1, 128, 128), jnp.float32)
        with pytest.raises(TypeError, match="bfloat16 or float32"):
            partitioned_matmul(xs, jnp.zeros((128, 128), jnp.float16),
                               jnp.zeros((1,), jnp.int32),
                               jnp.array([128]), interpret=True)

    def test_mixed_bf16_f32_promotes(self):
        key = jax.random.key(3)
        x = jax.random.normal(key, (64, 64), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (64, 64),
                              jnp.float32)
        out = fused_tenant_gemm([x.astype(jnp.bfloat16)], [w],
                                block_t=64, block_k=64, block_n=64,
                                interpret=True)[0]
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32) @ w),
            rtol=1e-5, atol=1e-5)

    def test_vmem_budget_enforced(self):
        xs = jnp.zeros((1, 1024, 1024), jnp.float32)
        w = jnp.zeros((1024, 1024), jnp.float32)
        with pytest.raises(ValueError, match="VMEM"):
            partitioned_matmul(xs, w, jnp.zeros((1,), jnp.int32),
                               jnp.array([1024]), block_t=1024,
                               block_k=1024, block_n=1024, interpret=True)

    def test_mixed_dtype_autotune_budgets_for_the_promoted_type(self):
        # regression: the autotuner must budget/account for the PROMOTED
        # operand dtypes (bf16 × f32 → f32), exactly like the kernel does
        key = jax.random.key(11)
        x = jax.random.normal(key, (64, 64), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (64, 64),
                              jnp.float32)
        budget = block_vmem_bytes(128, 128, 128, "float32", "float32")
        _, stats = fused_tenant_gemm(
            [x.astype(jnp.bfloat16)], [w], vmem_budget_bytes=budget,
            interpret=True, return_stats=True)
        assert (stats.block_t, stats.block_k, stats.block_n) == \
            (128, 128, 128)
        # byte accounting reflects the f32 fetch, not the bf16 source
        acc = stats.accounting
        assert acc.x_bytes_fetched == acc.blocks_scheduled * 128 * 128 * 4

    def test_vmem_budget_is_dtype_aware(self):
        f32 = block_vmem_bytes(256, 256, 256, jnp.float32, jnp.float32)
        bf16 = block_vmem_bytes(256, 256, 256, jnp.bfloat16, jnp.bfloat16)
        assert bf16 < f32  # narrower operands buy headroom


class TestAutotune:
    def test_fits_budget_and_caches(self):
        shapes = ((512, 363, 96), (512, 147, 64), (54, 512, 100))
        before = autotune_blocks.cache_info().hits
        bt, bk, bn = autotune_blocks(shapes)
        assert autotune_blocks(shapes) == (bt, bk, bn)
        assert autotune_blocks.cache_info().hits == before + 1
        assert block_vmem_bytes(bt, bk, bn, "float32", "float32") <= \
            16 * 2 ** 20

    def test_prefers_fewer_fetched_bytes(self):
        # tiny tenants: any block over 128 only adds padding fetch traffic
        assert autotune_blocks(((64, 64, 64), (32, 48, 64))) == \
            (128, 128, 128)

    def test_respects_tight_budget(self):
        budget = block_vmem_bytes(128, 128, 128, "float32", "float32")
        bt, bk, bn = autotune_blocks(((512, 512, 512),),
                                     vmem_budget_bytes=budget)
        assert (bt, bk, bn) == (128, 128, 128)
        with pytest.raises(ValueError, match="fits the VMEM budget"):
            autotune_blocks(((512, 512, 512),),
                            vmem_budget_bytes=budget - 1)

    def test_auto_mode_picks_compact_iff_ragged(self):
        key = jax.random.key(7)
        def mk(t, k, n, s):
            return (jax.random.normal(jax.random.fold_in(key, s), (t, k)),
                    jax.random.normal(jax.random.fold_in(key, s + 100),
                                      (k, n)))
        # tenant 1 is >1 block smaller on T and K: its padding tiles are
        # dead blocks in the shared dense grid
        ragged = [mk(256, 256, 128, 0), mk(40, 60, 128, 1)]
        _, stats = fused_tenant_gemm(
            [x for x, _ in ragged], [w for _, w in ragged],
            block_t=128, block_k=128, block_n=128, interpret=True,
            return_stats=True)
        assert stats.grid_mode == "compact"
        assert stats.accounting.blocks_skipped == 0
        uniform = [mk(128, 128, 128, 2), mk(128, 128, 128, 3)]
        _, stats = fused_tenant_gemm(
            [x for x, _ in uniform], [w for _, w in uniform],
            block_t=128, block_k=128, block_n=128, interpret=True,
            return_stats=True)
        assert stats.grid_mode == "dense"
        assert stats.accounting.schedule_efficiency == 1.0


class TestFusedTenantGemm:
    @given(st.lists(
        st.tuples(st.integers(1, 150), st.integers(1, 150),
                  st.integers(1, 150)),
        min_size=1, max_size=4), st.integers(0, 2**31 - 1))
    @settings(max_examples=12, deadline=None)
    def test_ragged_matches_per_tenant_matmul(self, shapes, seed):
        key = jax.random.key(seed)
        xs, ws = [], []
        for i, (t, k, n) in enumerate(shapes):
            k1, k2 = jax.random.split(jax.random.fold_in(key, i))
            xs.append(jax.random.normal(k1, (t, k), jnp.float32))
            ws.append(jax.random.normal(k2, (k, n), jnp.float32))
        outs = fused_tenant_gemm(xs, ws, block_t=64, block_k=64, block_n=64,
                                 interpret=True)
        for x, w, o in zip(xs, ws, outs):
            assert o.shape == (x.shape[0], w.shape[1])
            np.testing.assert_allclose(np.asarray(o), np.asarray(x @ w),
                                       rtol=1e-4, atol=1e-4)

    def test_owner_map_is_vertical_partitioning(self):
        owner = build_owner_map([100, 300, 128], 128)
        # ceil(100/128)=1, ceil(300/128)=3, ceil(128/128)=1 blocks
        assert owner.tolist() == [0, 1, 1, 1, 2]
        # contiguous runs — the paper's vertical slices
        runs = [owner[0]]
        for o in owner[1:]:
            if o != runs[-1]:
                runs.append(o)
        assert runs == sorted(runs)

    def test_mismatched_pairs_rejected(self):
        with pytest.raises(ValueError):
            fused_tenant_gemm([jnp.zeros((4, 8))], [], interpret=True)
        with pytest.raises(ValueError):
            fused_tenant_gemm([jnp.zeros((4, 8))], [jnp.zeros((9, 4))],
                              interpret=True)

    @pytest.mark.parametrize("grid_mode, spans", [
        ("dense", {"plan": 2, "pack": 1, "kernel": 1, "unpack": 1}),
        ("compact", {"plan": 2, "pack": 1, "tables": 1, "kernel": 1,
                     "unpack": 2}),
    ])
    def test_host_phases_are_spans_on_the_profiler(self, tmp_path,
                                                   grid_mode, spans):
        """Each phase of one call is a flat span in the profiler's trace
        (``plan`` twice, around packing; ``unpack`` in both layers of the
        compact grid), and ``packed_bytes`` counts the padded operands the
        kernel gets."""
        key = jax.random.key(3)
        shapes = [(150, 130, 100), (40, 60, 200)]
        xs = [jax.random.normal(jax.random.fold_in(key, i), (t, k),
                                jnp.bfloat16)
              for i, (t, k, _) in enumerate(shapes)]
        ws = [jax.random.normal(jax.random.fold_in(key, 10 + i), (k, n),
                                jnp.float32)
              for i, (_, k, n) in enumerate(shapes)]
        jax.profiler.start_trace(str(tmp_path))
        try:
            outs = fused_tenant_gemm(xs, ws, grid_mode=grid_mode,
                                     interpret=True)
            jax.block_until_ready(outs)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)
        data = jax.profiler.ProfileData.from_file(path[0])
        found = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                  dict(e.stats))
                 for plane in data.planes for line in plane.lines
                 for e in line.events if e.name.startswith("tenant_gemm.")]
        counts = {}
        for _, _, name, _ in found:
            phase = name.removeprefix("tenant_gemm.")
            counts[phase] = counts.get(phase, 0) + 1
        assert counts == spans
        found.sort()
        assert all(a[1] <= b[0] for a, b in zip(found, found[1:])), \
            "spans overlap"
        stats = {name: st for _, _, name, st in found}
        assert stats["tenant_gemm.kernel"]["grid_mode"] == grid_mode
        bt, bk, bn = autotune_blocks(tuple(shapes), "float32", "float32",
                                     grid_mode=grid_mode)
        T = -(-max(t for t, _, _ in shapes) // bt) * bt
        K = -(-max(k for _, k, _ in shapes) // bk) * bk
        N = sum(-(-n // bn) * bn for _, _, n in shapes)
        xs_pad = jnp.zeros((len(shapes), T, K), jnp.bfloat16)
        w_pad = jnp.zeros((K, N), jnp.float32)
        assert stats["tenant_gemm.pack"]["packed_bytes"] == \
            xs_pad.nbytes + w_pad.nbytes
        for (t, _, n), o in zip(shapes, outs):
            assert o.shape == (t, n)


class TestKernelAlgorithmIntegration:
    """The fused kernel driven by Algorithm 1's partition state — the
    kernel-level realisation of the paper's dynamic partitioning."""

    def test_partition_calculation_drives_owner_map(self):
        from repro.core.partition import ArrayShape, partition_calculation
        # 4 tenants on a 512-lane "array" with 128-lane blocks: Algorithm 1
        # gives each tenant 128 lanes -> owner blocks [0,1,2,3]
        parts = partition_calculation(ArrayShape(rows=128, cols=512), 4)
        owner = []
        for i, p in enumerate(sorted(parts, key=lambda p: p.col_start)):
            assert p.cols % 128 == 0
            owner += [i] * (p.cols // 128)
        assert owner == [0, 1, 2, 3]
        # and the fused kernel computes exactly those tenants' GEMMs
        key = jax.random.key(9)
        xs = jax.random.normal(key, (4, 128, 128), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (128, 512),
                              jnp.float32)
        out = partitioned_matmul(xs, w, jnp.asarray(owner, jnp.int32),
                                 jnp.full((4,), 128, jnp.int32),
                                 interpret=True)
        ref = partitioned_matmul_ref(xs, w, jnp.asarray(owner, jnp.int32),
                                     jnp.full((4,), 128, jnp.int32), 128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    @given(n_tenants=st.integers(1, 4), seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=8, deadline=None)
    def test_merge_then_regrant_still_exact(self, n_tenants, seed):
        """Merging partitions (tenant drains) and re-granting produces a
        new owner map; the SAME kernel stays exact for any layout."""
        from repro.core.partition import ArrayShape, PartitionSet
        key = jax.random.key(seed)
        pset = PartitionSet(ArrayShape(rows=128, cols=128 * 4))
        widths = [128] * n_tenants
        for i, wd in enumerate(widths):
            pset.allocate(f"t{i}", wd)
        if n_tenants > 1:
            pset.free("t0")  # drain one -> merge
        busy = sorted(pset.busy_partitions.items(),
                      key=lambda kv: kv[1].col_start)
        if not busy:
            return
        owner = np.zeros(4, np.int32)
        live = {}
        for rank, (name, part) in enumerate(busy):
            live[rank] = name
            for b in range(part.col_start // 128, part.col_end // 128):
                owner[b] = rank
        E = len(busy)
        xs = jax.random.normal(key, (E, 128, 128), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (128, 512),
                              jnp.float32)
        vt = jnp.full((E,), 128, jnp.int32)
        out = partitioned_matmul(xs, w, jnp.asarray(owner), vt,
                                 interpret=True)
        ref = partitioned_matmul_ref(xs, w, jnp.asarray(owner), vt, 128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
