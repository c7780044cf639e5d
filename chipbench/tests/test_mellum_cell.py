"""The ``mellum-prefill`` cell on the CPU: its configuration against the
program, a rehearsal of its two layer kinds at tiny widths through
``run_cell`` in interpret mode, the faults and lower-precision controls
each kind's check must catch, and the warm-up signatures of the real plan.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

from __future__ import annotations

import functools
import json

import jax
import pytest

from chipbench import catalog, kinds, operands, plan, replay, run
from chipbench.kinds import attention, experts, gemm

CELL = "mellum-prefill"


@pytest.fixture(scope="module")
def cell():
    return catalog.cell(CELL)


@pytest.fixture(scope="module")
def real_plan(cell):
    return plan.build(cell.config, cell.traffic)[0]


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------

def test_the_config_rows_match_the_program(cell):
    rows = cell.config["tenants"][0]["layers"]
    assert [kinds.of_row(r).__name__.rsplit(".", 1)[-1] for r in rows] == \
        ["gemm", "attention", "gemm", "gemm", "experts"] * 4
    plan._dnngs(cell.config, 0.0)       # raises where any row differs
    # the config states the published widths it was cut from
    assert cell.config["num_hidden_layers"] == 4
    assert cell.config["reduced"] == ["num_hidden_layers"]
    assert (cell.config["hidden_size"], cell.config["num_experts"],
            cell.config["moe_intermediate_size"],
            cell.config["sliding_window"]) == (2304, 64, 896, 1024)


@pytest.mark.parametrize("edit", ["expert_rows", "window", "rope"])
def test_a_config_the_program_disagrees_with_is_refused(cell, edit):
    cfg = json.loads(json.dumps(cell.config))
    rows = cfg["tenants"][0]["layers"]
    if edit == "expert_rows":
        rows[4]["rows"][0] += 1
        rows[4]["rows"][1] -= 1
    elif edit == "window":
        rows[1]["window"] = 512
    else:
        rows[16]["rope"]["factor"] = 8.0
    with pytest.raises(ValueError, match="differ from configuration"):
        plan._dnngs(cfg, 0.0)


# ---------------------------------------------------------------------------
# the warm-up of the real plan
# ---------------------------------------------------------------------------

def test_the_real_plan_runs_each_layer_once_in_twenty_rounds(real_plan):
    assert len(real_plan.rounds) == 20
    assert all(len(r) == 1 and r[0].row0 == 0 for r in real_plan.rounds)
    assert [real_plan.layers[r[0].layer].name for r in real_plan.rounds] == \
        [f"L{i}.{n}" for i in range(4)
         for n in ("qkv", "attn", "o", "router", "experts")]


def test_warm_up_tells_every_attention_and_expert_layer_apart(real_plan):
    """Signatures from the operands' shapes alone: the three windowed
    layers share one, the full layer has its own (its live-block table is
    longer), and every expert layer has its own (its experts' rows)."""
    xs, ws, cut = jax.eval_shape(operands.generator(real_plan),
                                 operands.key(1))
    calls = replay.calls(real_plan, {gemm.ENTRY: "gemm",
                                     attention.ENTRY: "attention",
                                     experts.ENTRY: "experts"}, cut, ws)
    sig = {real_plan.layers[r[0].layer].name: c.signature()
           for r, c in zip(real_plan.rounds, calls)}
    assert sig["L0.attn"] == sig["L1.attn"] == sig["L2.attn"] != \
        sig["L3.attn"]
    assert len({sig[f"L{i}.experts"] for i in range(4)}) == 4
    assert len({c.signature() for c in calls}) == 9
    # each expert with rows is one piece of the one call
    (fn, args), = calls[4].calls
    assert fn == "experts" and len(args) == 3 and len(args[0]) == \
        sum(1 for r in real_plan.layers[4].spec[0] if r)


# ---------------------------------------------------------------------------
# a rehearsal at tiny widths, in interpret mode
# ---------------------------------------------------------------------------

ATTN = {"kind": "attention", "heads": 2, "kv_heads": 1, "head_dim": 128,
        "seq_lens": [40, 24, 9]}
YARN = {"kind": "yarn", "theta": 500000.0, "factor": 16.0,
        "original_max_position_embeddings": 8192, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.2772588722239782}


@pytest.fixture(scope="module")
def tiny():
    """A GEMM beside a windowed attention layer in one round; experts of
    1, 7, 40 and 0 rows; a full (yarn) layer and a second expert layer,
    each cut into two slices over two rounds."""
    experts0 = {"kind": "experts", "name": "L0.experts", "hidden": 128,
                "width": 128, "rows": [1, 7, 40, 0]}
    layers = (
        plan.layer("m", ["L0.qkv", 16, 256, 128]),
        plan.layer("m", {**ATTN, "name": "L0.attn", "window": 16,
                         "rope": {"kind": "default", "theta": 500000.0}}),
        plan.layer("m", experts0),
        plan.layer("m", {**ATTN, "name": "L1.attn", "window": 0,
                         "rope": YARN}),
        plan.layer("m", {**experts0, "name": "L1.experts",
                         "rows": [3, 2, 9]}),
    )
    s = plan.Slice
    return plan.Plan(layers, ((s(1, 0, 73), s(0, 0, 16)), (s(2, 0, 48),),
                              (s(3, 0, 30),), (s(3, 30, 73),),
                              (s(4, 0, 4),), (s(4, 4, 14),)))


def _attention_interpret(*columns):
    from repro.kernels import prefill_attention
    return [prefill_attention(*p, interpret=True) for p in zip(*columns)]


def _entries():
    from repro.kernels import fused_tenant_gemm, routed_experts
    return {gemm.ENTRY: functools.partial(fused_tenant_gemm, interpret=True),
            experts.ENTRY: functools.partial(routed_experts, interpret=True),
            attention.ENTRY: _attention_interpret}


def _run(cell, tiny, trace=False, **given):
    return run.run_cell(cell, 2 ** 31 + 23, 1.0, trace,
                        given={**_entries(), **given}, plan=tiny)


def test_rehearsal_of_both_kinds(cell, tiny):
    res = _run(cell, tiny)
    assert res["correct"] is True
    assert res["attempted"] == 5 and res["failed"] == 0
    assert list(res["checks"]) == ["worst_rel_err", "worst_rel_err.attention",
                                   "worst_rel_err.experts", "rows_off",
                                   "layers_compared"]
    assert res["checks"]["rows_off"]["value"] == 0
    assert 0 < res["checks"]["worst_rel_err.attention"]["value"] < \
        attention.REL_ERR_LIMIT / 3
    assert 0 < res["checks"]["worst_rel_err.experts"]["value"] < \
        experts.REL_ERR_LIMIT / 3
    assert set(res["metrics"]) == {"mix_s", "round_p95_ms", "setup_s"}
    assert res["info"]["lowerings_in_window"] == 0
    assert res["info"]["warmed_shapes"] == 6


def test_traced_rehearsal_leaves_the_rooflines_to_a_chip(cell, tiny):
    res = _run(cell, tiny, trace=True)
    assert res["correct"] is True
    # the shares of the peaks need a TPU's peaks
    assert res["metrics"] == {}
    assert res["info"]["lowerings_in_window"] == 0


def test_the_rooflines_read_their_own_kinds_rounds(tiny):
    from types import SimpleNamespace

    from chipbench import kind_roofline, work
    win = SimpleNamespace(round_ids=[0, 1, 2, 3, 4, 5, 1],
                          latency_s=[1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 2.0])
    peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    ctx = SimpleNamespace(plan=tiny, window=win, peaks=peaks)

    def least(r):
        return work.least_seconds(tiny, tiny.rounds[r], 1e9, 1e6)

    assert kind_roofline.round_share(ctx, "experts") == pytest.approx(
        100 * (2 * least(1) + least(4) + least(5)) / (2 + 5 + 6 + 2))
    # round 0 mixes a GEMM with attention, so it is neither kind's
    assert kind_roofline.round_share(ctx, "attention") == pytest.approx(
        100 * (least(2) + least(3)) / (3 + 4))
    assert kind_roofline.round_share(SimpleNamespace(
        plan=tiny, window=win, peaks=None), "experts") is None


def _zero_a_row(outs):
    outs = list(outs)
    outs[-1] = outs[-1].at[1].set(0.0)
    return outs


@pytest.mark.parametrize("fault", ["row_zeroed", "experts_swapped"])
def test_a_broken_expert_layer_is_caught(cell, tiny, fault):
    from repro.kernels import routed_experts

    def broken(xs, gus, ds, **kw):
        if fault == "experts_swapped" and len(xs) > 1:
            gus = [gus[1], gus[0], *gus[2:]]
        outs = routed_experts(xs, gus, ds, interpret=True, **kw)
        return _zero_a_row(outs) if fault == "row_zeroed" else outs

    res = _run(cell, tiny, **{experts.ENTRY: broken})
    assert res["correct"] is False and res["failed"] >= 1
    assert res["checks"]["worst_rel_err.experts"]["value"] > \
        3 * experts.REL_ERR_LIMIT
    assert res["checks"]["worst_rel_err.attention"]["value"] < \
        attention.REL_ERR_LIMIT


@pytest.mark.parametrize("fault", ["window_ignored", "row_zeroed"])
def test_a_broken_attention_layer_is_caught(cell, tiny, fault):
    def broken(*columns):
        if fault == "window_ignored":
            columns = list(columns)
            columns[9] = [w * 0 for w in columns[9]]
            columns[10] = [None] * len(columns[10])
        outs = _attention_interpret(*columns)
        return _zero_a_row(outs) if fault == "row_zeroed" else outs

    res = _run(cell, tiny, **{attention.ENTRY: broken})
    assert res["correct"] is False and res["failed"] >= 1
    assert res["checks"]["worst_rel_err.attention"]["value"] > \
        3 * attention.REL_ERR_LIMIT
    assert res["checks"]["worst_rel_err.experts"]["value"] < \
        experts.REL_ERR_LIMIT


@pytest.mark.parametrize("kind", [experts, attention],
                         ids=["experts", "attention"])
def test_the_lower_precision_control_is_caught(cell, tiny, kind):
    res = _run(cell, tiny, **{kind.ENTRY: kind.control()})
    assert res["correct"] is False
    assert res["checks"][kind.CHECK]["value"] > 3 * kind.REL_ERR_LIMIT
    other = attention if kind is experts else experts
    assert res["checks"][other.CHECK]["value"] < other.REL_ERR_LIMIT
