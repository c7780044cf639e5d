"""A cell's operands, made on the device from the seed in one jitted call.

One bf16 activation ``x`` of ``(t, k)`` and one bf16 weight ``w`` of
``(k, n)`` per layer, and each round's row slices of the activations, cut
in the same call so that the measured window slices nothing.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp

from chipbench.plan import Plan


def key(seed: int) -> jax.Array:
    """A key that tells apart every whole-number seed, not only 32 bits."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def make(plan: Plan, seed: int):
    """``(xs, ws, cut)``: per-layer activations and weights, and per round
    the list of its tenants' activation row slices, in call order."""
    shapes = [(layer.t, layer.k, layer.n) for layer in plan.layers]
    rounds = [[(s.layer, s.row0, s.row1) for s in rnd] for rnd in plan.rounds]
    sizes = [t * k for t, k, _ in shapes] + [k * n for _, k, n in shapes]
    offsets = [0, *itertools.accumulate(sizes)]

    @jax.jit
    def generate(k):
        # one draw for every operand, then cut: one random-number kernel
        # compiles far faster than one per operand
        flat = jax.random.normal(k, (offsets[-1],), jnp.bfloat16)
        parts = [flat[offsets[i]:offsets[i + 1]] for i in range(len(sizes))]
        xs = [parts[i].reshape(t, kk) for i, (t, kk, _) in enumerate(shapes)]
        ws = [parts[len(shapes) + i].reshape(kk, n)
              for i, (_, kk, n) in enumerate(shapes)]
        cut = [[xs[li][r0:r1] for li, r0, r1 in rnd] for rnd in rounds]
        return xs, ws, cut

    return jax.block_until_ready(generate(key(seed)))
