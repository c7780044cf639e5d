"""The benchmark's own tests, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

They cover the round plan, the operation and byte count, the trace
reduction, the refusal to run without a TPU, a rehearsal of the harness in
interpret mode with the faults its check has to catch and the int8 control,
the seam that lets a layer kind of another shape or program entry join a
round (with two kinds that live beside these tests), and an ahead-of-time
compile of the largest ``heavy-closed`` rounds for a described TPU v5e.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import (catalog, devtrace, kinds, operands, plan, replay,
                       run, work)
from chipbench.kinds import gemm
from chipbench.tests import kind_attention, kind_experts

ROOT = Path(__file__).resolve().parents[2]
# the Table-1 mixes as (configuration, traffic), with (rounds a pass,
# tenant slices a pass) as reckoned from the schedule; heavy-closed is not a
# cell yet (PERF.md, Open questions) but its plan is the largest
MIXES = {"heavy-closed": ("table1-heavy", "closed-equal", 437, 1050),
         "light-closed": ("table1-light", "closed-equal", 42, 67),
         "heavy-solo": ("table1-heavy", "closed-solo", 237, 237)}
# round signatures warm-up runs (PERF.md, Cells)
WARMED = {"heavy-closed": 424, "light-closed": 30, "heavy-solo": 100}


def _load(kind: str, name: str) -> dict:
    return json.loads((ROOT / "chipbench" / kind / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def plans():
    return {name: plan.build(_load("configs", cfg), _load("traffic", tr))[0]
            for name, (cfg, tr, _, _) in MIXES.items()}


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", MIXES)
def test_row_split_covers_every_layer_once(plans, name):
    p = plans[name]
    rows: dict[int, list[tuple[int, int]]] = {}
    for rnd in p.rounds:
        assert rnd, "an empty round was kept"
        assert len({s.layer for s in rnd}) == len(rnd)
        for s in rnd:
            assert s.rows > 0
            rows.setdefault(s.layer, []).append((s.row0, s.row1))
    assert sorted(rows) == list(range(len(p.layers)))
    for li, spans in rows.items():
        # rounds run in time order, so a layer's slices come in row order
        assert spans[0][0] == 0 and spans[-1][1] == p.layers[li].rows
        assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
    assert (len(p.rounds), sum(map(len, p.rounds))) == MIXES[name][2:]
    useful = sum(2 * m * k * n for t in _load("configs", MIXES[name][0])
                 ["tenants"] for _, m, k, n in t["layers"])
    assert sum(work.flops(p, r) for r in p.rounds) == useful


@pytest.mark.parametrize("name", MIXES)
def test_warm_up_runs_one_round_of_each_signature(plans, name):
    """Counted on the operands' shapes alone, with no array made."""
    p = plans[name]
    xs, ws, cut = jax.eval_shape(operands.generator(p), operands.key(1))
    calls = replay.calls(p, {gemm.ENTRY: "fused"}, cut, ws)
    assert len({rnd.signature() for rnd in calls}) == WARMED[name]
    assert all(len(rnd.calls) == 1 for rnd in calls)


def test_solo_rounds_hold_one_tenant_in_schedule_order(plans):
    p = plans["heavy-solo"]
    assert [r[0].layer for r in p.rounds] == list(range(len(p.layers)))


def test_rounds_from_a_small_trace():
    part = SimpleNamespace
    layers = (plan.layer("a", ["l0", 10, 4, 4]),
              plan.layer("b", ["l0", 3, 4, 4]))
    trace = [SimpleNamespace(tenant="a", layer_index=0, compute_start=0.0,
                             compute_end=4.0, partition=part(col_start=64)),
             SimpleNamespace(tenant="b", layer_index=0, compute_start=1.0,
                             compute_end=2.0, partition=part(col_start=0))]
    rounds = plan.rounds_from_trace(trace, {("a", 0): 0, ("b", 0): 1},
                                    layers)
    # bounds 0, 1, 2, 4: a streams 10 rows over [0, 4), b 3 rows in [1, 2)
    assert rounds == ((plan.Slice(0, 0, 2),),
                      (plan.Slice(1, 0, 3), plan.Slice(0, 2, 5)),
                      (plan.Slice(0, 5, 10),))


def test_a_layer_in_two_segments_is_refused():
    layers = (plan.layer("a", ["l0", 4, 4, 4]),)
    ev = dict(tenant="a", layer_index=0,
              partition=SimpleNamespace(col_start=0))
    trace = [SimpleNamespace(compute_start=0.0, compute_end=1.0, **ev),
             SimpleNamespace(compute_start=2.0, compute_end=3.0, **ev)]
    with pytest.raises(ValueError, match="more than one segment"):
        plan.rounds_from_trace(trace, {("a", 0): 0}, layers)


def test_a_config_the_program_disagrees_with_is_refused():
    cell = catalog.cell("light-closed")
    cfg = json.loads(json.dumps(cell.config))
    cfg["tenants"][0]["layers"][0][1] += 1
    with pytest.raises(ValueError, match="differ from configuration"):
        plan.build(cfg, cell.traffic)


# ---------------------------------------------------------------------------
# the count and the peaks
# ---------------------------------------------------------------------------

def test_flop_and_byte_count_of_a_known_shape():
    layers = (plan.layer("a", ["l0", 2, 3, 4]),
              plan.layer("b", ["l0", 9, 7, 11]))
    both = (plan.Slice(0, 0, 2), plan.Slice(1, 2, 7))
    p = plan.Plan(layers, (both, both[:1]))
    assert work.flops(p, both) == 2 * 2 * 3 * 4 + 2 * 5 * 7 * 11
    one = p.rounds[1]
    assert work.bytes_moved(p, one) == 2 * 3 * 2 + 3 * 4 * 2 + 2 * 4 * 4
    # memory bound at these peaks: 68 bytes / 1 B/s beats 48 flops / 1e3
    assert work.least_seconds(p, one, 1e3, 1.0) == 68.0
    assert work.least_seconds(p, one, 1.0, 1e3) == 48.0


def test_peaks_are_keyed_by_device_kind():
    p = catalog.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        catalog.peaks("TPU v99")


def test_every_cell_resolves_to_its_files():
    bench = catalog.benchmark()
    files = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = catalog.cell(w["name"], bench)
        assert cell.config["name"] == w["config"]
        assert cell.config["source"] == files[w["config"]]["source"]
        assert cell.config["reduced"] == files[w["config"]]["reduced"]
        assert cell.traffic["name"] == w["traffic"]
        names = {m.name for m in cell.metrics}
        assert {"setup_s", "mix_s", "round_p95_ms"} <= names
        assert any(not m.end_to_end for m in cell.metrics)


def test_list_finds_the_cells_without_a_device():
    out = subprocess.run([sys.executable, "chipbench/run.py", "--list"],
                         cwd=ROOT, capture_output=True, text=True, check=True)
    assert [ln.split(":")[0] for ln in out.stdout.splitlines()] == \
        [w["name"] for w in catalog.benchmark()["workloads"]]


def test_a_row_names_its_kind():
    assert kinds.of_row(["l0", 2, 3, 4]) is gemm
    assert kinds.of_row({"kind": "gemm"}) is gemm
    with pytest.raises(KeyError, match="no layer kind 'no_such'"):
        kinds.of_row({"kind": "no_such"})
    with pytest.raises(ValueError, match="identifier"):
        kinds.load("../gemm")


def test_seeds_far_apart_give_other_operands():
    a, b = operands.key(5), operands.key(5 + 2 ** 32)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    assert np.array_equal(jax.random.key_data(operands.key(2 ** 31 + 9)),
                          jax.random.key_data(operands.key(2 ** 31 + 9)))


# ---------------------------------------------------------------------------
# the refusal
# ---------------------------------------------------------------------------

def test_refuses_a_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert run.main(["--workload", "light-closed", "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "light-closed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": ""})
    assert out.returncode != 0 and out.stdout == ""


# ---------------------------------------------------------------------------
# the trace reduction
# ---------------------------------------------------------------------------

def test_reduction_of_a_known_trace():
    trace = {"host": [(0, 100, "window", None), (0, 30, "launch", 4),
                      (30, 60, "wait", 4), (60, 100, "launch", 5)],
             "device": [(35, 45, "fusion"), (40, 50, "kernel"),
                        (55, 58, "kernel")]}
    red = devtrace.reduce(trace)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(18e-9)       # [35, 50) + [55, 58)
    assert red["idle_gaps"][0] == ["launch round 5", pytest.approx(42e-9)]
    assert red["idle_gaps"][1] == ["launch round 4", pytest.approx(35e-9)]
    assert red["idle_gaps"][2] == ["wait round 4", pytest.approx(5e-9)]
    assert red["device_ops"][0] == ["kernel", pytest.approx(13e-9)]
    assert red["idle_by_span"] == pytest.approx(
        {"launch": 77e-9, "wait": 5e-9})


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    x = jnp.ones((256, 256), jnp.float32)
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for r in range(3):
            with jax.profiler.TraceAnnotation("launch", round=r):
                y = f(x)
            with jax.profiler.TraceAnnotation("wait", round=r):
                y.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    trace = devtrace.load(path[0])
    assert {n for _, _, n, _ in trace["host"]} >= {"window", "launch",
                                                   "wait"}
    assert {r for _, _, n, r in trace["host"] if n == "launch"} == {0, 1, 2}
    red = devtrace.reduce(trace)
    assert 0 < red["busy_s"] < red["window_s"]
    assert any("dot" in name for name, _ in red["device_ops"])
    assert {n.split(" ")[0] for n, _ in red["idle_gaps"]} <= {
        "launch", "wait", "other"}


# ---------------------------------------------------------------------------
# the harness in interpret mode, its faults and its control
# ---------------------------------------------------------------------------

def _two_light_rounds(plans) -> plan.Plan:
    """Light rounds 2 (one tenant, dense grid) and 12 (three tenants,
    compact grid), each slice made a whole layer of its own."""
    full = plans["light-closed"]
    layers, rounds = [], []
    for r in (2, 12):
        rnd = []
        for s in full.rounds[r]:
            la = full.layers[s.layer]
            layers.append(dataclasses.replace(la, rows=s.rows))
            rnd.append(plan.Slice(len(layers) - 1, 0, s.rows))
        rounds.append(tuple(rnd))
    return plan.Plan(tuple(layers), tuple(rounds))


@pytest.fixture(scope="module")
def small(plans):
    return _two_light_rounds(plans)


def _interpret_gemm():
    from repro.kernels import fused_tenant_gemm
    return functools.partial(fused_tenant_gemm, interpret=True)


def _run(small, fused, trace=False, seconds=0.5, **given):
    cell = catalog.cell("light-closed")
    return run.run_cell(cell, 2 ** 31 + 17, seconds, trace,
                        given={gemm.ENTRY: fused, **given}, plan=small)


# sha256 of the shapes, types and bytes of every array ``operands.make``
# returned for the two light rounds, in order, recorded from the harness
# that knew only GEMM layers
DRAWN = {2 ** 31 + 17:
         "1e81d81f218a9b8aed07dfedc8e326f703f59b48641c6bf0f4c9e91a6df71d6e",
         7: "e54d15926a5c9d110be9ba774a5df3b9cad9946fa00899085a2204f5e6840677"}


@pytest.mark.parametrize("seed", DRAWN)
def test_gemm_operands_are_the_draw_they_were(small, seed):
    leaves = jax.tree_util.tree_leaves(operands.make(small, seed))
    h = hashlib.sha256()
    for a in map(np.asarray, leaves):
        h.update(str((a.shape, a.dtype.name)).encode())
        h.update(a.tobytes())
    assert len(leaves) == 12 and h.hexdigest() == DRAWN[seed]


def test_rehearsal_of_two_light_rounds(small):
    res = _run(small, _interpret_gemm())
    assert res["correct"] is True
    assert res["attempted"] == 4 and res["failed"] == 0
    assert res["checks"]["rows_off"]["value"] == 0
    assert 0 < res["checks"]["worst_rel_err"]["value"] < 1e-5
    assert set(res["metrics"]) == {"mix_s", "round_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_traced_rehearsal_reads_the_per_layer_metrics(small):
    res = _run(small, _interpret_gemm(), trace=True)
    assert res["correct"] is True
    # the peaks exist only for a TPU, so the shares of peaks stay silent
    assert set(res["metrics"]) == {"schedule_ms", "launch_host_ms",
                                   "lowerings_in_window", "idle_share"}
    assert res["metrics"]["lowerings_in_window"]["value"] == 0
    assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]


def _altered(outs):
    """One answer altered where it is produced."""
    return [outs[0].at[0, 0].add(1.0)] + list(outs[1:])


def _half_rows(outs):
    """Half of the streamed rows of the last tenant left out."""
    last = outs[-1]
    return list(outs[:-1]) + [last[: max(1, last.shape[0] // 2)]]


def _tenant_dropped(outs):
    """A round returns without its last tenant."""
    return list(outs[:-1])


def _zeros(outs):
    """A round that leaves its outputs as they were before it ran."""
    return [jnp.zeros_like(o) for o in outs]


@pytest.mark.parametrize("fault", [_altered, _half_rows, _tenant_dropped,
                                   _zeros])
def test_a_broken_timed_path_is_not_correct(small, fault):
    good = _interpret_gemm()

    def broken(xs, ws, **kw):
        outs = good(xs, ws, **kw)
        return fault(outs) if len(xs) > 1 else outs

    res = _run(small, broken)
    assert res["correct"] is False and res["failed"] >= 1


def test_the_int8_control_is_not_correct(small):
    res = _run(small, gemm.control())
    assert res["correct"] is False
    assert res["checks"]["worst_rel_err"]["value"] > 3 * gemm.REL_ERR_LIMIT


# ---------------------------------------------------------------------------
# the seam: kinds of another shape and another program entry in one round
# ---------------------------------------------------------------------------

def _layer(kind, tenant, row):
    name, rows, spec = kind.parse(row)
    return plan.Layer(tenant, name, kind, rows, spec)


@pytest.fixture(scope="module")
def experts():
    """A GEMM tenant beside routed experts of 1, 7 and 40 rows, all three
    in one slice; then a second expert layer whose slices split experts."""
    layers = (plan.layer("a", ["fc", 16, 256, 128]),
              _layer(kind_experts, "b", {"kind": "kind_experts",
                                         "name": "moe", "experts": [1, 7, 40],
                                         "k": 128, "n": 384}),
              _layer(kind_experts, "b", {"kind": "kind_experts",
                                         "name": "moe2", "experts": [3, 2, 9],
                                         "k": 384, "n": 128}))
    return plan.Plan(layers, ((plan.Slice(0, 0, 16), plan.Slice(1, 0, 48)),
                              (plan.Slice(2, 0, 4),),
                              (plan.Slice(2, 4, 14),)))


def test_expert_pieces_share_the_fused_call(experts):
    xs, ws, cut = operands.make(experts, 3)
    calls = replay.calls(experts, {gemm.ENTRY: "fused"}, cut, ws)
    first = calls[0].calls
    assert len(first) == 1 and first[0][0] == "fused"
    assert [x.shape[0] for x in first[0][1][0]] == [16, 1, 7, 40]
    assert [x.shape[0] for x in calls[1].calls[0][1][0]] == [3, 1]
    assert [x.shape[0] for x in calls[2].calls[0][1][0]] == [1, 9]
    assert calls[0].slots == ((0, 0, 1), (0, 1, 3))
    assert work.flops(experts, experts.rounds[0]) == \
        2 * 16 * 256 * 128 + 2 * 48 * 128 * 384


def test_rehearsal_of_routed_experts(experts):
    res = _run(experts, _interpret_gemm())
    assert res["correct"] is True and res["attempted"] == 3
    assert list(res["checks"]) == ["worst_rel_err", "rows_off",
                                   "layers_compared"]
    assert 0 < res["checks"]["worst_rel_err"]["value"] < 1e-5


def test_an_altered_expert_piece_is_caught(experts):
    good = _interpret_gemm()

    def broken(xs, ws, **kw):
        outs = list(good(xs, ws, **kw))
        if len(xs) == 4:       # the 7-row expert, third piece of round 0
            outs[2] = outs[2].at[3, 5].add(1.0)
        return outs

    res = _run(experts, broken)
    assert res["correct"] is False and res["failed"] == 1
    assert res["checks"]["rows_off"]["value"] == 0


@pytest.fixture(scope="module")
def mixed():
    """Attention slices, through their own entry, in the rounds of GEMM
    slices: a round with both, then one with attention alone."""
    attn = {"kind": "kind_attention", "name": "core", "t": 24, "s": 40,
            "d": 128}
    layers = (plan.layer("a", ["fc", 16, 256, 128]),
              _layer(kind_attention, "b", attn),
              plan.layer("c", ["fc", 8, 128, 256]))
    return plan.Plan(layers, ((plan.Slice(1, 0, 10), plan.Slice(0, 0, 16),
                               plan.Slice(2, 0, 8)),
                              (plan.Slice(1, 10, 24),)))


def test_rehearsal_with_a_second_entry(mixed):
    res = _run(mixed, _interpret_gemm())
    assert res["correct"] is True and res["attempted"] == 3
    assert list(res["checks"]) == ["worst_rel_err", "worst_rel_err.attention",
                                   "rows_off", "layers_compared"]
    assert 0 < res["checks"]["worst_rel_err.attention"]["value"] < 1e-5
    xs, ws, cut = operands.make(mixed, 3)
    calls = replay.calls(mixed, {gemm.ENTRY: "fused", kind_attention.ENTRY:
                                 "attention"}, cut, ws)
    # the attention slice comes first by its column, so its call does
    assert [fn for fn, _ in calls[0].calls] == ["attention", "fused"]
    assert calls[0].slots == ((0, 0, 1), (1, 0, 1), (1, 1, 1))


@pytest.mark.parametrize("fault", ["altered", "control"])
def test_a_broken_second_entry_is_caught(mixed, fault):
    def altered(qs, ks, vs):
        outs = kind_attention.attention(qs, ks, vs)
        return [outs[0].at[2, 3].add(0.5)] + outs[1:]

    res = _run(mixed, _interpret_gemm(), **{kind_attention.ENTRY: {
        "altered": altered, "control": kind_attention.control()}[fault]})
    assert res["correct"] is False and res["failed"] == 1
    assert res["checks"]["worst_rel_err.attention"]["value"] > \
        3 * kind_attention.REL_ERR_LIMIT
    assert res["checks"]["worst_rel_err"]["value"] < gemm.REL_ERR_LIMIT


# ---------------------------------------------------------------------------
# the largest heavy-closed rounds, compiled for a described v5e
# ---------------------------------------------------------------------------

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
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("grid_mode", ["dense", "compact"])
@pytest.mark.parametrize("which", ["most_bytes", "most_tenants"])
def test_largest_heavy_closed_round_compiles_for_v5e(plans, one_chip,
                                                     which, grid_mode):
    from repro.kernels import (autotune_blocks, build_owner_map,
                               partitioned_matmul)

    p = plans["heavy-closed"]
    key = {"most_bytes": lambda r: work.bytes_moved(p, r),
           "most_tenants": lambda r: (len(r), work.bytes_moved(p, r))
           }[which]
    shapes = tuple(gemm.shape(p.layers[s.layer], s.row0, s.row1)
                   for s in max(p.rounds, key=key))
    bt, bk, bn = autotune_blocks(shapes, "bfloat16", "bfloat16",
                                 grid_mode="compact")
    T = -(-max(t for t, _, _ in shapes) // bt) * bt
    K = -(-max(k for _, k, _ in shapes) // bk) * bk
    owner = np.asarray(build_owner_map([n for _, _, n in shapes], bn))
    valid_t = np.asarray([t for t, _, _ in shapes], np.int32)
    valid_k = np.asarray([k for _, k, _ in shapes], np.int32)
    xs = jax.ShapeDtypeStruct((len(shapes), T, K), jnp.bfloat16,
                              sharding=one_chip)
    w = jax.ShapeDtypeStruct((K, owner.size * bn), jnp.bfloat16,
                             sharding=one_chip)

    def call(xs, w):
        return partitioned_matmul(xs, w, owner, valid_t, valid_k,
                                  block_t=bt, block_k=bk, block_n=bn,
                                  grid_mode=grid_mode)

    compiled = jax.jit(call).lower(xs, w).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes >= \
        sum(k * n * 2 for _, k, n in shapes)
