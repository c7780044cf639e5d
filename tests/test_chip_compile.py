"""The chip path, rehearsed without a chip.

Ahead-of-time compiles of the partitioned-WS kernel for a described TPU v5e
at the real shapes ``chip_smoke.py`` replays, and a CPU run of that
script's replay in interpret mode.  A compile that passes is not a chip
run: it proves only that Mosaic accepts the tiling and the VMEM working
set.
"""

import importlib.util
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    VMEM_BUDGET_BYTES,
    autotune_blocks,
    block_vmem_bytes,
    build_owner_map,
    partitioned_matmul,
)
from repro.kernels.ops import _round_up
from repro.launch import cache

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def heavy_rounds():
    return chip_smoke.schedule_rounds("heavy")


def _compile(one_chip, gemms, dtype, *, grid_mode, blocks=None,
             vmem_budget_bytes=VMEM_BUDGET_BYTES):
    """Compile ``partitioned_matmul`` for one v5e chip at the padded
    geometry ``fused_tenant_gemm`` builds for ``gemms``."""
    dt = str(jnp.dtype(dtype))
    bt, bk, bn = blocks or autotune_blocks(tuple(gemms), dt, dt,
                                           grid_mode="compact")
    T = _round_up(max(m for m, _, _ in gemms), bt)
    K = _round_up(max(k for _, k, _ in gemms), bk)
    owner = np.asarray(build_owner_map([n for _, _, n in gemms], bn))
    valid_t = np.asarray([m for m, _, _ in gemms], np.int32)
    valid_k = np.asarray([k for _, k, _ in gemms], np.int32)
    xs = jax.ShapeDtypeStruct((len(gemms), T, K), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((K, owner.size * bn), dtype, sharding=one_chip)

    def call(xs, w):
        return partitioned_matmul(xs, w, owner, valid_t, valid_k,
                                  block_t=bt, block_k=bk, block_n=bn,
                                  grid_mode=grid_mode,
                                  vmem_budget_bytes=vmem_budget_bytes)

    compiled = jax.jit(call).lower(xs, w).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("grid_mode", ["dense", "compact"])
def test_six_tenant_heavy_round_compiles(one_chip, heavy_rounds, grid_mode):
    six = [r for r in heavy_rounds if len(r) == 6]
    assert six, "the heavy schedule has no 6-tenant round"
    gemms = max(six, key=chip_smoke._footprint)
    compiled = _compile(one_chip, gemms, jnp.bfloat16, grid_mode=grid_mode)
    assert compiled.memory_analysis().argument_size_in_bytes > 0


@pytest.mark.parametrize("grid_mode", ["dense", "compact"])
def test_kernel_keeps_its_name_in_the_compiled_program(one_chip, grid_mode):
    # the device trace finds each kernel by this name
    gemms = [(200, 300, 256), (40, 60, 384)]
    compiled = _compile(one_chip, gemms, jnp.bfloat16, grid_mode=grid_mode)
    assert f"tenant_gemm_{grid_mode}" in compiled.as_text()


def test_alexnet_fc_compiles(one_chip):
    gemms = [chip_smoke.FC_GEMM]
    compiled = _compile(one_chip, gemms, jnp.bfloat16, grid_mode="dense")
    # the 9216x4096 bf16 weight (75 MB) is one argument of the call
    assert compiled.memory_analysis().argument_size_in_bytes >= 9216 * 4096 * 2


def test_largest_block_candidate_fits_v5e_vmem(one_chip):
    assert block_vmem_bytes(512, 512, 512, jnp.float32,
                            jnp.float32) <= VMEM_BUDGET_BYTES
    _compile(one_chip, [(512, 512, 512)], jnp.float32, grid_mode="dense",
             blocks=(512, 512, 512))


def test_block_over_budget_is_refused_by_v5e_compiler(one_chip):
    # the budget is Mosaic's own scoped-VMEM limit: a working set past it
    # is refused by the chip's compiler, not just by partitioned_matmul
    need = block_vmem_bytes(1024, 1024, 1024, jnp.float32, jnp.float32)
    assert need > VMEM_BUDGET_BYTES
    with pytest.raises(Exception, match="(?i)vmem|scoped|limit"):
        _compile(one_chip, [(1024, 1024, 1024)], jnp.float32,
                 grid_mode="dense", blocks=(1024, 1024, 1024),
                 vmem_budget_bytes=need)


# ---------------------------------------------------------------------------
# Mellum2's new kernels at its published widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [1024, 0], ids=["windowed", "full"])
def test_mellum_prefill_attention_compiles(one_chip, window):
    from repro.kernels.attention import _attention_call, attention_table
    from repro.sim import mellum

    table = attention_table(mellum.PROMPTS, window)
    n = sum(mellum.PROMPTS)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda *a: _attention_call(*a, block_q=256, block_k=256,
                                   interpret=False)).lower(
        s(table.shape, jnp.int32), s((), jnp.int32),
        s((n, 4096), jnp.bfloat16), s((n, 512), jnp.bfloat16),
        s((n, 512), jnp.bfloat16), *[s((n,), jnp.int32)] * 4,
        s((64,), jnp.float32), s((), jnp.float32)).compile()
    assert "attention_prefill" in compiled.as_text()


@pytest.mark.parametrize("proj", ["gate_up", "down"])
def test_mellum_routed_experts_compile(one_chip, proj):
    """The dense grid of each of ``routed_experts``' two fused calls, at the
    row counts of the layer with the busiest expert."""
    from repro.sim import mellum

    rows = max((mellum.routed_rows(i) for i in range(mellum.LAYERS)),
               key=max)
    k, n = {"gate_up": (2304, 1792), "down": (896, 2304)}[proj]
    gemms = [(r, k, n) for r in rows if r]
    dt = str(jnp.dtype(jnp.bfloat16))
    compiled = _compile(one_chip, gemms, jnp.bfloat16, grid_mode="dense",
                        blocks=autotune_blocks(tuple(gemms), dt, dt,
                                               grid_mode="dense"))
    assert "tenant_gemm_dense" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes >= \
        64 * k * n * 2


# ---------------------------------------------------------------------------
# chip_smoke.py's host logic and replay, on the CPU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("index", [0, 1])
def test_replay_light_round_interpret(index):
    gemms = chip_smoke.schedule_rounds("light")[index]
    r = chip_smoke.replay_round(gemms, chip_smoke.SEED + index,
                                interpret=True)
    assert r["gemms"] == gemms
    assert r["grid_mode"] in ("dense", "compact")
    assert 0.0 <= r["max_rel_err"] <= chip_smoke.REL_TOL


def test_replay_catches_a_wrong_output(monkeypatch):
    real = chip_smoke.fused_tenant_gemm

    def off_by_one_block(xs, ws, **kw):
        outs, stats = real(xs, ws, **kw)
        return [o.at[:, :1].add(1.0) for o in outs], stats

    monkeypatch.setattr(chip_smoke, "fused_tenant_gemm", off_by_one_block)
    gemms = chip_smoke.schedule_rounds("light")[0]
    with pytest.raises(AssertionError, match="fused tenant 0"):
        chip_smoke.replay_round(gemms, chip_smoke.SEED, interpret=True)


def test_heavy_selection_covers_crowded_and_fc_rounds(heavy_rounds):
    picked = chip_smoke.select_heavy_rounds(heavy_rounds)
    most = max(len(r) for r in heavy_rounds)
    assert most == 6
    assert len(picked) == (chip_smoke.HEAD_ROUNDS
                           + chip_smoke.CROWDED_ROUNDS + 1)
    assert set(range(chip_smoke.HEAD_ROUNDS)) <= set(picked)
    assert {i for i, r in enumerate(heavy_rounds) if len(r) == most} \
        <= set(picked)
    first_fc = next(i for i, r in enumerate(heavy_rounds)
                    if chip_smoke.FC_GEMM in r)
    assert first_fc in picked


def test_light_schedule_rounds():
    rounds = chip_smoke.schedule_rounds("light")
    assert len(rounds) == 31
    assert max(len(r) for r in rounds) == 4


def test_main_refuses_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.strip().splitlines()[-1] if out.strip() else "")


def test_compile_cache_dir(monkeypatch):
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == old
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cache.use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
