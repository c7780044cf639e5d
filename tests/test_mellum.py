"""Mellum2-12B-A2.5B: its DNNG against the published config, its layers in
the simulator's schedule, the spans of its new ops, and a two-layer block
chained through the program's ops against the plain reference forward."""

import dataclasses
import glob
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Session
from repro.core.dnng import AttentionLayer, ExpertLayer, LayerShape, Rope
from repro.core.partition import Partition
from repro.core.scheduler import StageModel
from repro.kernels import (fused_tenant_gemm, moe_block, packed_positions,
                           prefill_attention, rope_inv_freq, routed_experts)
from repro.kernels.ref import attention_ref, moe_block_ref
from repro.sim import mellum
from repro.sim.systolic import layer_cost, layer_cost_batch
from repro.sim.workloads import MODEL_POOLS, MODELS

# the published values, copied from the model's config.json
PUBLISHED = {
    "hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4,
    "head_dim": 128, "num_experts": 64, "num_experts_per_tok": 8,
    "moe_intermediate_size": 896, "sliding_window": 1024,
    "norm_topk_prob": True, "num_hidden_layers": 28,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 7,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16, "original_max_position_embeddings":
                           8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
}


# ---------------------------------------------------------------------------
# the decoder block, chained through the program's ops and plainly
#
# A layer is pre-norm attention then a pre-norm sparse MLP, each added to
# the residual stream: RMSNorm, the fused q/k/v projection, prefill
# attention (windowed or full by the layer's type, each type with its
# rotary embedding), the output projection; RMSNorm, the router and the
# routed experts.  :func:`prefill` runs it through ``fused_tenant_gemm``,
# ``prefill_attention`` and ``moe_block``; :func:`prefill_ref` is the same
# arithmetic in plain ``jax.numpy`` float32, with the activations rounded
# to bf16 where the program feeds them to a bf16 operation.  Both start
# from the embedded tokens and end in the logits of an LM head.
# ---------------------------------------------------------------------------

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    n_experts: int
    top_k: int
    expert_width: int
    window: int
    layer_types: tuple[str, ...]
    ropes: tuple[tuple[str, Rope], ...]
    vocab: int
    rms_eps: float = 1e-6

    def rope(self, layer_type: str) -> Rope:
        return dict(self.ropes)[layer_type]


def init_params(key: jax.Array, cfg: MellumConfig) -> Params:
    """Random bf16 weights, each scaled by ``1 / sqrt(fan_in)``."""
    def w(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(shape[0])).astype(jnp.bfloat16)

    qkv = (cfg.heads + 2 * cfg.kv_heads) * cfg.head_dim
    layers = []
    for k in jax.random.split(key, len(cfg.layer_types)):
        ks = jax.random.split(k, 5)
        layers.append({
            "ln1": jnp.ones((cfg.hidden,), jnp.float32),
            "ln2": jnp.ones((cfg.hidden,), jnp.float32),
            "qkv": w(ks[0], (cfg.hidden, qkv)),
            "o": w(ks[1], (cfg.heads * cfg.head_dim, cfg.hidden)),
            "router": w(ks[2], (cfg.hidden, cfg.n_experts)),
            "gate_up": [w(kk, (cfg.hidden, 2 * cfg.expert_width))
                        for kk in jax.random.split(ks[3], cfg.n_experts)],
            "down": [w(kk, (cfg.expert_width, cfg.hidden))
                     for kk in jax.random.split(ks[4], cfg.n_experts)],
        })
    head_key = jax.random.fold_in(key, len(cfg.layer_types))
    return {"layers": layers, "ln_f": jnp.ones((cfg.hidden,), jnp.float32),
            "head": w(head_key, (cfg.hidden, cfg.vocab))}


def _rmsnorm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    """RMSNorm in float32, rounded to bf16 for the next projection."""
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g
    return y.astype(jnp.bfloat16)


def _split_qkv(qkv: jax.Array, cfg: MellumConfig):
    q_w = cfg.heads * cfg.head_dim
    kv_w = cfg.kv_heads * cfg.head_dim
    qkv = qkv.astype(jnp.bfloat16)
    return qkv[:, :q_w], qkv[:, q_w:q_w + kv_w], qkv[:, q_w + kv_w:]


def prefill(params: Params, x: jax.Array, seq_lens: Sequence[int],
            cfg: MellumConfig, *, interpret: bool = False) -> jax.Array:
    """Logits ``(tokens, vocab)`` float32 of the packed prompts
    ``seq_lens`` from their embeddings ``x`` ``(tokens, hidden)``, through
    the program's ops."""
    seg, pos = (jnp.asarray(a) for a in packed_positions(seq_lens))
    x = x.astype(jnp.float32)
    for p, kind in zip(params["layers"], cfg.layer_types):
        inv, scale = rope_inv_freq(cfg.rope(kind), cfg.head_dim)
        window = cfg.window if kind == "sliding_attention" else 0
        (qkv,) = fused_tenant_gemm([_rmsnorm(x, p["ln1"], cfg.rms_eps)],
                                   [p["qkv"]], interpret=interpret)
        q, k, v = _split_qkv(qkv, cfg)
        a = prefill_attention(q, k, v, pos, seg, pos, seg, jnp.asarray(inv),
                              jnp.float32(scale), jnp.int32(window),
                              interpret=interpret)
        (o,) = fused_tenant_gemm([a.astype(jnp.bfloat16)], [p["o"]],
                                 interpret=interpret)
        x = x + o
        x = x + moe_block(_rmsnorm(x, p["ln2"], cfg.rms_eps), p["router"],
                          p["gate_up"], p["down"], top_k=cfg.top_k,
                          interpret=interpret)
    (logits,) = fused_tenant_gemm([_rmsnorm(x, params["ln_f"], cfg.rms_eps)],
                                  [params["head"]], interpret=interpret)
    return logits


def prefill_ref(params: Params, x: jax.Array, seq_lens: Sequence[int],
                cfg: MellumConfig) -> jax.Array:
    """:func:`prefill` in plain ``jax.numpy`` float32: dense masked
    attention, every expert on every token weighted by its gate."""
    seg, pos = (jnp.asarray(a) for a in packed_positions(seq_lens))

    def mm(a, w):
        return a.astype(jnp.float32) @ w.astype(jnp.float32)

    x = x.astype(jnp.float32)
    for p, kind in zip(params["layers"], cfg.layer_types):
        inv, scale = rope_inv_freq(cfg.rope(kind), cfg.head_dim)
        window = cfg.window if kind == "sliding_attention" else 0
        q, k, v = _split_qkv(mm(_rmsnorm(x, p["ln1"], cfg.rms_eps),
                                p["qkv"]), cfg)
        a = attention_ref(q, k, v, pos, seg, pos, seg, jnp.asarray(inv),
                          scale, window)
        x = x + mm(a.astype(jnp.bfloat16), p["o"])
        x = x + moe_block_ref(_rmsnorm(x, p["ln2"], cfg.rms_eps),
                              p["router"], p["gate_up"], p["down"],
                              top_k=cfg.top_k)
    return mm(_rmsnorm(x, params["ln_f"], cfg.rms_eps), params["head"])


@pytest.fixture(scope="module")
def dnng():
    return MODELS[mellum.NAME]()


def test_the_dnng_has_the_published_widths(dnng):
    p = PUBLISHED
    assert len(dnng.layers) == 5 * 4
    tokens = sum(mellum.PROMPTS)
    assert tokens == 8192
    q_w = p["num_attention_heads"] * p["head_dim"]
    kv_w = p["num_key_value_heads"] * p["head_dim"]
    for i in range(4):
        qkv, attn, o, router, experts = dnng.layers[5 * i:5 * i + 5]
        assert [la.name for la in (qkv, attn, o, router, experts)] == [
            f"L{i}.{n}" for n in ("qkv", "attn", "o", "router", "experts")]
        assert (qkv.gemm_m, qkv.gemm_k, qkv.gemm_n) == (
            tokens, p["hidden_size"], q_w + 2 * kv_w)
        assert (o.gemm_m, o.gemm_k, o.gemm_n) == (tokens, q_w,
                                                  p["hidden_size"])
        assert (router.gemm_m, router.gemm_k, router.gemm_n) == (
            tokens, p["hidden_size"], p["num_experts"])
        assert isinstance(attn, AttentionLayer)
        assert (attn.heads, attn.kv_heads, attn.head_dim) == (
            p["num_attention_heads"], p["num_key_value_heads"],
            p["head_dim"])
        kind = p["layer_types"][i]
        rope = p["rope_parameters"][kind]
        assert attn.window == (p["sliding_window"]
                               if kind == "sliding_attention" else 0)
        assert attn.rope.kind == rope["rope_type"]
        assert attn.rope.theta == rope["rope_theta"]
        if kind == "full_attention":
            assert (attn.rope.factor, attn.rope.original_max_position_embeddings,
                    attn.rope.beta_fast, attn.rope.beta_slow,
                    attn.rope.attention_factor) == (
                rope["factor"], rope["original_max_position_embeddings"],
                rope["beta_fast"], rope["beta_slow"],
                rope["attention_factor"])
        assert isinstance(experts, ExpertLayer)
        assert (experts.hidden, experts.width, experts.n_experts,
                experts.top_k) == (p["hidden_size"],
                                   p["moe_intermediate_size"],
                                   p["num_experts"], p["num_experts_per_tok"])
        assert experts.held == tuple(range(p["num_experts"]))
    # the published pattern: three windowed layers, then one full
    assert [la.window for la in dnng.layers if isinstance(
        la, AttentionLayer)] == [1024, 1024, 1024, 0]


def test_each_expert_layer_routes_every_token_to_eight_experts(dnng):
    for la in dnng.layers:
        if isinstance(la, ExpertLayer):
            assert sum(la.rows) == 8192 * 8
            busiest = max(la.rows) / (sum(la.rows) / len(la.rows))
            assert 1.8 < busiest < 3.0
    assert len({la.rows for la in dnng.layers
                if isinstance(la, ExpertLayer)}) == 4


def test_mellum_is_in_no_table1_pool():
    assert all(mellum.NAME not in pool for pool in MODEL_POOLS.values())
    assert len(MODEL_POOLS["all"]) == 12


def test_a_layer_of_several_gemms_costs_their_sum(dnng):
    part = Partition(rows=128, col_start=16, cols=64)
    experts = dnng.layers[4]
    assert len(experts.gemms) == 2 * 64
    cost = layer_cost(experts, part)
    assert cost.macs == experts.macs == sum(t * k * n
                                            for t, k, n in experts.gemms)
    attn = dnng.layers[1]
    assert layer_cost(attn, part).macs == attn.macs
    # the batch oracle sums the same GEMMs
    batch = layer_cost_batch([dnng.layers[0], experts, attn],
                             [part] * 3)
    assert [batch.row(i) for i in range(3)] == [
        layer_cost(la, part) for la in (dnng.layers[0], experts, attn)]
    # a plain layer's stage times are as they were
    fc = LayerShape.fc("fc", 300, 500, batch=7)
    stage = StageModel()
    assert stage.stage_in_s(fc) == (300 * 500 + 7 * 300) * 2 / 64e9



def test_a_plain_layer_costs_its_one_gemm():
    """The sum over ``gemms`` is the one lowered GEMM's cost, scalar and
    batched, with and without bandwidth shares."""
    from repro.core.dataflow import GEMM, ws_cost, ws_cost_batch
    part = Partition(rows=128, col_start=0, cols=48)
    layers = [LayerShape.fc("fc", 300, 500, batch=7),
              LayerShape.conv("c", 64, 3, 11, 11, 56, 56, stride=4)]
    for la in layers:
        assert layer_cost(la, part) == ws_cost(GEMM.of_layer(la), part)
    got = layer_cost_batch(layers, [part] * 2, bw_shares=[0.5, 1.0])
    want = ws_cost_batch([GEMM.of_layer(la) for la in layers], [part] * 2,
                         bw_shares=[0.5, 1.0])
    for f in dataclasses.fields(got):
        assert np.array_equal(getattr(got, f.name), getattr(want, f.name))


def test_an_expert_layer_needs_a_routed_row():
    with pytest.raises(ValueError, match="routed row"):
        ExpertLayer("e", 64, 32, rows=(0, 0))

def test_attention_lowers_the_visible_band():
    la = AttentionLayer("a", heads=8, kv_heads=2, head_dim=128,
                        seq_lens=(10, 4), window=3)
    # per prompt, min(i + 1, 3) keys for query i
    assert la.pairs == (1 + 2 + 3 * 8) + (1 + 2 + 3 + 3)
    assert la.macs == sum(t * k * n for t, k, n in la.gemms)
    with pytest.raises(ValueError, match="evenly"):
        AttentionLayer("a", heads=6, kv_heads=4, head_dim=128,
                       seq_lens=(4,))


@pytest.mark.parametrize("beside", [False, True], ids=["alone", "alexnet"])
@pytest.mark.parametrize("run", ["coresident", "baseline"])
def test_session_runs_every_layer_once_in_one_interval(dnng, beside, run):
    dnngs = [dnng] + ([MODELS["AlexNet"]()] if beside else [])
    s = Session(policy="equal", backend="sim")
    trace = (s.run(dnngs, compare_baseline=False).partitioned.trace
             if run == "coresident" else s.run_baseline(dnngs).schedule.trace)
    seen = [(e.tenant, e.layer_index) for e in trace]
    assert len(seen) == len(set(seen)) == sum(len(g) for g in dnngs)
    ends = {}
    for e in trace:
        assert e.fraction == 1.0 and e.compute_end > e.compute_start
        ends[e.tenant, e.layer_index] = e
    # a chain: each layer computes after its predecessor
    for i in range(1, len(dnng)):
        assert ends[dnng.name, i].compute_start >= \
            ends[dnng.name, i - 1].compute_end


def _spans(path):
    data = jax.profiler.ProfileData.from_file(path)
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name,
                   dict(e.stats))
                  for plane in data.planes for line in plane.lines
                  for e in line.events
                  if e.name.split(".")[0] in ("routed_experts", "attention",
                                              "tenant_gemm"))


def test_the_new_ops_mark_flat_spans(tmp_path):
    ks = jax.random.split(jax.random.key(0), 6)
    rows = [3, 0, 9]
    xs = [jax.random.normal(k, (r, 128), jnp.bfloat16)
          for k, r in zip(jax.random.split(ks[0], 3), rows)]
    gus = [jax.random.normal(k, (128, 256), jnp.bfloat16)
           for k in jax.random.split(ks[1], 3)]
    ds = [jax.random.normal(k, (128, 128), jnp.bfloat16)
          for k in jax.random.split(ks[2], 3)]
    seg, pos = (jnp.asarray(a) for a in packed_positions([20, 12]))
    inv, scale = rope_inv_freq(Rope(), 128)
    q = jax.random.normal(ks[3], (32, 256), jnp.bfloat16)
    k = jax.random.normal(ks[4], (32, 128), jnp.bfloat16)
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(routed_experts(xs, gus, ds, interpret=True))
        jax.block_until_ready(prefill_attention(
            q, k, k, pos, seg, pos, seg, jnp.asarray(inv),
            jnp.float32(scale), np.int32(8), interpret=True))
    finally:
        jax.profiler.stop_trace()
    found = _spans(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                             recursive=True)[0])
    names = [n for _, _, n, _ in found]
    assert names.count("routed_experts.plan") == 1
    assert names.count("routed_experts.act") == 1
    assert names.count("attention.plan") == names.count(
        "attention.kernel") == 1
    assert names.count("tenant_gemm.kernel") == 2
    # flat: no span encloses another
    assert all(a[1] <= b[0] for a, b in zip(found, found[1:]))
    stats = {n: st for _, _, n, st in found}
    assert stats["routed_experts.plan"] == {"experts_live": 2,
                                            "routed_rows": 12, "rows_max": 9}
    assert stats["attention.plan"]["window"] == 8
    assert stats["attention.plan"]["live_blocks"] == 1


def test_a_two_layer_block_matches_the_reference_forward():
    """One windowed and one full layer at hidden 256, heads of 128, eight
    experts top-2, chained through the program's ops, against the plain
    float32 forward on the logits of a small head."""
    cfg = MellumConfig(
        hidden=256, heads=4, kv_heads=2, head_dim=128, n_experts=8,
        top_k=2, expert_width=128, window=16,
        layer_types=("sliding_attention", "full_attention"),
        ropes=tuple(mellum.ROPE.items()), vocab=64)
    params = init_params(jax.random.key(7), cfg)
    seq = [40, 24, 9]
    x = jax.random.normal(jax.random.key(8), (sum(seq), cfg.hidden),
                          jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        got = prefill(params, x, seq, cfg, interpret=True)
        ref = prefill_ref(params, x, seq, cfg)
    assert got.shape == (sum(seq), cfg.vocab)
    # both round to bf16 at the same points; what differs is f32 summation
    # order, which now and then moves a bf16 rounding one step (4e-3 of
    # the logits' scale here); a dropped window, rotary
    # embedding or expert moves them by 1e-1 or more
    rel = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 1e-2
