"""A cell's operands, made on the device from the seed in one jitted call.

Each layer's bf16 activations and weights, of the shapes its kind names,
and each round's slices cut from the activations as the slices' kinds cut
them, in the same call so that the measured window slices nothing.
"""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp

from chipbench.plan import Plan


def key(seed: int) -> jax.Array:
    """A key that tells apart every whole-number seed, not only 32 bits."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def generator(plan: Plan):
    """The jitted call that makes ``make``'s operands from a key."""
    shapes = [layer.kind.operands(layer) for layer in plan.layers]
    # every layer's activations, then every layer's weights, each in order
    flat_shapes = [s for xsh, _ in shapes for s in xsh] + \
        [s for _, wsh in shapes for s in wsh]
    offsets = [0, *itertools.accumulate(math.prod(s) for s in flat_shapes)]
    rounds = [[(s.layer, s.row0, s.row1) for s in rnd] for rnd in plan.rounds]

    @jax.jit
    def generate(k):
        # one draw for every operand, then cut: one random-number kernel
        # compiles far faster than one per operand
        flat = jax.random.normal(k, (offsets[-1],), jnp.bfloat16)
        parts = iter(flat[offsets[i]:offsets[i + 1]].reshape(s)
                     for i, s in enumerate(flat_shapes))
        xs = [[next(parts) for _ in xsh] for xsh, _ in shapes]
        ws = [[next(parts) for _ in wsh] for _, wsh in shapes]
        cut = [[plan.layers[li].kind.cut(plan.layers[li], r0, r1, xs[li])
                for li, r0, r1 in rnd] for rnd in rounds]
        return xs, ws, cut

    return generate


def make(plan: Plan, seed: int):
    """``(xs, ws, cut)``: per layer the list of its activations and the list
    of its weights, and per round the activation pieces of each slice, in
    call order."""
    return jax.block_until_ready(generator(plan)(key(seed)))
