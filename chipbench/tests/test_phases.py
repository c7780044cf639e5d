"""The reduction of the program's spans, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

from __future__ import annotations

import functools
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from chipbench import catalog, devtrace, phases, plan
from chipbench.kinds import gemm
from chipbench.tests.test_chipbench import _two_light_rounds

ROOT = Path(__file__).resolve().parents[2]

# a window of two rounds: round 4 dense, round 5 compact, the program's
# spans nested in the benchmark's launch spans
KNOWN = {
    "host": [(0, 200, "window", None), (0, 90, "launch", 4),
             (90, 120, "wait", 4), (120, 200, "launch", 5)],
    "device": [(25, 35, "pad.1"), (62, 100, "tenant_gemm_dense.1"),
               (95, 110, "fusion"), (192, 198, "tenant_gemm_compact.3")],
}
KNOWN_PROGRAM = {"device_plane": True, "program": [
    (5, 20, "tenant_gemm.plan", {}),
    (20, 30, "tenant_gemm.pack", {"packed_bytes": 1000}),
    (30, 60, "tenant_gemm.kernel", {"grid_mode": "dense"}),
    (60, 70, "tenant_gemm.unpack", {}),
    (125, 135, "tenant_gemm.plan", {}),
    (135, 140, "tenant_gemm.pack", {"packed_bytes": 500}),
    (140, 150, "tenant_gemm.tables", {}),
    (150, 190, "tenant_gemm.kernel", {"grid_mode": "compact"}),
    (190, 195, "tenant_gemm.unpack", {})]}


def _ctx(trace, rounds=2):
    return SimpleNamespace(trace=trace,
                           window=SimpleNamespace(round_ids=list(range(
                               rounds))))


def test_reduction_of_known_program_spans():
    red = phases.reduce(KNOWN, KNOWN_PROGRAM)
    ns = pytest.approx
    assert red["span_s"] == ns({
        "tenant_gemm.plan": 25e-9, "tenant_gemm.pack": 15e-9,
        "tenant_gemm.kernel.dense": 30e-9, "tenant_gemm.unpack": 15e-9,
        "tenant_gemm.tables": 10e-9, "tenant_gemm.kernel.compact": 40e-9})
    assert red["packed_bytes"] == 1500
    # [62, 100) and [192, 198); the fusion beside the kernel is not one
    assert red["kernel_busy_s"] == ns(44e-9)
    assert [n for n, _ in red["kernel_ops"]] == [
        "tenant_gemm_dense.1", "tenant_gemm_compact.3"]
    # busy [25, 35) [62, 110) [192, 198): idle 136 of 200; glue is [0, 5),
    # [120, 125) and [198, 200) under launch outside every program span
    assert red["idle_by_phase"] == ns({
        "tenant_gemm.plan": 25e-9, "tenant_gemm.pack": 10e-9,
        "tenant_gemm.kernel.dense": 25e-9, "tenant_gemm.unpack": 4e-9,
        "tenant_gemm.tables": 10e-9, "tenant_gemm.kernel.compact": 40e-9,
        "launch.glue": 12e-9, "wait": 10e-9})
    assert sum(red["idle_by_phase"].values()) == ns(136e-9)
    assert red["idle_gaps"] == [
        ["tenant_gemm.kernel round 5", ns(82e-9)],
        ["tenant_gemm.kernel round 4", ns(27e-9)],
        ["tenant_gemm.plan round 4", ns(25e-9)],
        ["launch round 5", ns(2e-9)]]


def test_readers_of_known_program_spans():
    red = phases.reduce(KNOWN, KNOWN_PROGRAM)
    ctx = _ctx({"busy_s": 64e-9, **red})
    read = {m: catalog._reader(m)(ctx) for m in phases.PHASE_METRICS}
    assert read == pytest.approx({
        "plan_host_ms": 35e-9 / 2 * 1e3, "pack_host_ms": 30e-9 / 2 * 1e3,
        "kernel_host_ms": 70e-9 / 2 * 1e3, "packed_mb_per_round": 1500e-6 / 2,
        "kernel_device_share": 100 * 44 / 64})


def test_a_program_without_spans_reads_nothing():
    """The same trace from a program that names neither spans nor kernels,
    or from the CPU, which has no device plane."""
    bare = {"host": KNOWN["host"],
            "device": [(s, e, "custom-call.1" if "tenant" in n else n)
                       for s, e, n in KNOWN["device"]]}
    red = phases.reduce(bare, {"program": [], "device_plane": True})
    assert red["span_s"] == {} and red["kernel_busy_s"] == 0
    # gaps are then named for the host spans, as devtrace names them
    assert red["idle_gaps"] == devtrace.reduce(bare)["idle_gaps"]
    ctx = _ctx({"busy_s": 64e-9, **red})
    assert all(catalog._reader(m)(ctx) is None for m in phases.PHASE_METRICS)
    cpu = phases.reduce(KNOWN, {**KNOWN_PROGRAM, "device_plane": False})
    ctx = _ctx({"busy_s": 64e-9, **cpu})
    assert catalog._reader("kernel_device_share")(ctx) is None
    assert catalog._reader("plan_host_ms")(ctx) is not None


def test_refuses_a_cpu(capsys):
    assert phases.main(["--workload", "light-closed", "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


@pytest.fixture(scope="module")
def small():
    cfg, tr = (json.loads((ROOT / "chipbench" / kind / f"{name}.json")
                          .read_text())
               for kind, name in (("configs", "table1-light"),
                                  ("traffic", "closed-equal")))
    return _two_light_rounds({"light-closed": plan.build(cfg, tr)[0]})


def test_a_traced_window_on_the_cpu_reads_the_phases(small):
    from repro.kernels import fused_tenant_gemm

    res = phases.measure(catalog.cell("light-closed"), 2 ** 31 + 17, 0.5,
                         given={gemm.ENTRY: functools.partial(
                             fused_tenant_gemm, interpret=True)},
                         plan=small)
    assert res["correct"] is True
    m = res["metrics"]
    # no device plane on the CPU, so the kernels' share stays silent
    assert set(phases.PHASE_METRICS) - set(m) == {"kernel_device_share"}
    assert {ph.rsplit(".", 1)[-1] for ph in res["phases"]["span_s"]} == {
        "plan", "pack", "tables", "dense", "compact", "unpack"}
    # the spans cover the launch and do not overlap
    inside = m["plan_host_ms"] + m["pack_host_ms"] + m["kernel_host_ms"]
    assert 0.7 * m["launch_host_ms"] < inside <= 1.02 * m["launch_host_ms"]
    idle = res["device"]["window_s"] - res["device"]["busy_s"]
    assert sum(res["phases"]["idle_by_phase"].values()) == \
        pytest.approx(idle, rel=1e-6)
    assert any(n.startswith("tenant_gemm.")
               for n, _ in res["phases"]["idle_gaps"])
    assert jax.devices()[0].platform == "cpu"
