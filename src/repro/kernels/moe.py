"""Routed experts on the partitioned array: a sparse MLP layer's experts as
co-resident tenants of one fused call.

A token-choice router scores every expert and sends each token to its
``top_k`` best; each expert is a SiLU-gated MLP.  On the paper's array the
experts a chip holds are tenants: each owns a column slice (its weights
stay), and its routed rows stream through it.  :func:`routed_experts` runs
every held expert that has rows in one :func:`fused_tenant_gemm` call for
gate/up and one for down, so the rows of each expert are ragged tenants of
one grid.

:func:`route` is the router, :func:`moe_block` the whole layer (route,
dispatch, :func:`routed_experts`, gate-weighted combine) as a served
forward pass runs it.  A chip that holds only some experts is told which
(``held``); it routes over all of them and returns its own experts' part of
the output, so the parts of disjoint holdings add up to the whole layer.

Host phases are marked for the profiler like ``fused_tenant_gemm``'s:
``routed_experts.plan`` (checks and the choice of pieces; stats
``experts_live``, ``routed_rows``, ``rows_max``) and ``routed_experts.act``
(the gated activation between the two calls), flat, beside the calls'
own ``tenant_gemm.*`` spans.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import fused_tenant_gemm


@functools.partial(jax.jit, static_argnames=("top_k",))
def route(logits: jax.Array, top_k: int) -> tuple[jax.Array, jax.Array]:
    """Token-choice routing: ``(weights, experts)``, each ``(tokens,
    top_k)``.

    Softmax over every expert in float32, the ``top_k`` largest logits per
    token (ties go to the lower expert index, as ``lax.top_k`` breaks
    them), their probabilities renormalised to sum to 1.
    """
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, experts = jax.lax.top_k(logits, top_k)
    weights = jnp.take_along_axis(probs, experts, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


@jax.jit
def _gated(gate_ups: list[jax.Array]) -> list[jax.Array]:
    """``silu(gate) * up`` in float32, rounded to bf16, per expert; each
    input holds gate then up, side by side."""
    out = []
    for gu in gate_ups:
        g, u = jnp.split(gu.astype(jnp.float32), 2, axis=1)
        out.append((jax.nn.silu(g) * u).astype(jnp.bfloat16))
    return out


def routed_experts(xs: Sequence[jax.Array], w_gate_ups: Sequence[jax.Array],
                   w_downs: Sequence[jax.Array], *, interpret: bool = False
                   ) -> list[jax.Array]:
    """Each held expert's SiLU-gated MLP on its routed rows, all experts in
    one fused call per projection.

    ``xs[e]``: ``(r_e, hidden)`` bf16 rows routed to expert ``e``;
    ``w_gate_ups[e]``: ``(hidden, 2 * width)``, gate then up;
    ``w_downs[e]``: ``(width, hidden)``.  Returns per expert the float32
    ``(r_e, hidden)`` down projection of ``silu(x @ gate) * (x @ up)``,
    the activation rounded to bf16 as the down call's operand.  Experts
    with no rows are passed to neither call; their outputs are empty.

    Both calls use the dense grid: at a layer's real widths the ragged rows
    leave about half the blocks dead, which the dense grid gates on the
    device, whereas the compact grid's host-built mask of a
    ``(max rows, experts * hidden)`` output would be rebuilt and uploaded,
    with a second output, on every call.
    """
    rows = [int(x.shape[0]) for x in xs]
    live = [e for e, r in enumerate(rows) if r]
    with jax.profiler.TraceAnnotation(
            "routed_experts.plan", experts_live=len(live),
            routed_rows=sum(rows), rows_max=max(rows, default=0)):
        if not (len(xs) == len(w_gate_ups) == len(w_downs)) or not xs:
            raise ValueError("need one (rows, gate/up, down) triple per "
                             "expert")
        for e, (x, gu, d) in enumerate(zip(xs, w_gate_ups, w_downs)):
            if x.ndim != 2 or gu.shape != (x.shape[1], 2 * d.shape[0]) \
                    or d.shape[1] != x.shape[1]:
                raise ValueError(
                    f"expert {e}: rows {x.shape}, gate/up {gu.shape} and "
                    f"down {d.shape} are not (r, h), (h, 2f), (f, h)")
    hidden = int(xs[0].shape[1])
    if not live:
        return [jnp.zeros((0, hidden), jnp.float32) for _ in xs]
    gate_ups = fused_tenant_gemm([xs[e] for e in live],
                                 [w_gate_ups[e] for e in live],
                                 grid_mode="dense", interpret=interpret)
    with jax.profiler.TraceAnnotation("routed_experts.act"):
        hs = _gated(gate_ups)
    del gate_ups
    downs = fused_tenant_gemm(hs, [w_downs[e] for e in live],
                              grid_mode="dense", interpret=interpret)
    out = iter(downs)
    return [next(out) if r else jnp.zeros((0, hidden), jnp.float32)
            for r in rows]


def moe_block(x: jax.Array, w_router: jax.Array,
              w_gate_ups: Sequence[jax.Array], w_downs: Sequence[jax.Array],
              *, top_k: int, held: Sequence[int] | None = None,
              interpret: bool = False) -> jax.Array:
    """A sparse MLP layer on ``x`` ``(tokens, hidden)`` bf16: route over
    every expert (``w_router`` ``(hidden, experts)``), dispatch each token
    to its experts, :func:`routed_experts`, and sum the experts' outputs
    weighted by their gates.  Returns float32 ``(tokens, hidden)``.

    ``held`` names the experts this chip holds (default: all), in the order
    of ``w_gate_ups`` and ``w_downs``; tokens routed to other experts get
    nothing from them here.  Dispatch reads the routing back to the host.
    """
    held = list(range(w_router.shape[1])) if held is None else list(held)
    if len(held) != len(w_gate_ups) or len(held) != len(w_downs):
        raise ValueError(f"{len(held)} held experts but {len(w_gate_ups)} "
                         f"gate/up and {len(w_downs)} down weights")
    (logits,) = fused_tenant_gemm([x], [w_router], interpret=interpret)
    weights, experts = route(logits, top_k)
    experts, weights = np.asarray(experts), np.asarray(weights)
    picks = [np.nonzero(experts == e) for e in held]   # (tokens, slots)
    outs = routed_experts([x[tok] for tok, _ in picks], w_gate_ups, w_downs,
                          interpret=interpret)
    y = jnp.zeros((x.shape[0], x.shape[1]), jnp.float32)
    for (tok, slot), out in zip(picks, outs):
        if tok.size:
            y = y.at[tok].add(weights[tok, slot][:, None] * out)
    return y
