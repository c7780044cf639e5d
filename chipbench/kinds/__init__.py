"""Layer kinds: everything the harness does with a layer, by its kind.

A configuration's layer row names its kind: a JSON object row carries it
under ``"kind"``; a list row names none and is a plain GEMM,
``[name, t, k, n]``.  Kind ``<kind>`` is the module
``chipbench/kinds/<kind>.py``, found by that name; a later kind is a new
file there and configuration rows that name it, with no edit to the
harness.  A kind module holds:

``ENTRY``
    The name of the program entry its slices run through.  In a round,
    the slices whose kinds name the same entry share one call, in the order
    of their first array column.
``program()``, ``control()``
    That entry's callable, and the reference in the next lower precision
    with the same call shape.  An entry is called as ``entry(*columns)``:
    each slice passes argument pieces, tuples of one length, and the
    call's ``i``-th argument is the list of every piece's ``i``-th member.
    It returns one output per piece, in piece order.
``parse(row)``
    The row as ``(name, rows, spec)``: the layer's name, the rows it
    streams (``plan.row_at`` cuts them into slices across the rounds) and
    whatever else the kind reads back, kept as ``Layer.spec``.
``matches(row, program_layer)``
    Whether the row states the program's own layer (a layer of
    ``repro.sim.workloads``' DNNG), so that the program cannot change the
    work without the configuration file.
``operands(layer)``
    ``(x_shapes, w_shapes)``: the shapes of its activations and weights,
    all bf16, drawn from the seed.
``cut(layer, row0, row1, xs)``
    Traced, in set-up's one jitted call: the activation pieces that rows
    ``[row0, row1)`` need, cut from the layer's activations ``xs``.
``pieces(layer, row0, row1, cut, ws)``
    In set-up: the argument pieces of the slice of rows ``[row0, row1)``
    for the entry, from its ``cut`` and the layer's whole weights ``ws``.
``out_shape(layer)``
    The shape of the layer's assembled output, float32.  A slice's output is
    its pieces' outputs stacked on the first axis, and the layer's output its
    slices' outputs stacked the same way, in the order the pass computed
    them, so the first axis is the layer's streamed rows.
``reference(layer, xs, ws)``
    The plain float32 NumPy reference of the layer's output from its host
    operands; it imports nothing of the program.
``CHECK``, ``REL_ERR_LIMIT``
    The name under which the result line reports the largest
    ``max|out - ref| / max|ref|`` over the kind's layers, and its limit;
    kinds that share a name share its limit.  The module's docstring gives
    the reason for the limit.
``work(layer, row0, row1)``
    ``(operations, bytes)`` of rows ``[row0, row1)``, from the unpadded
    shapes.
"""

from __future__ import annotations

import importlib
from types import ModuleType

DEFAULT = "gemm"


def load(name: str) -> ModuleType:
    """The kind ``name``: the module ``chipbench/kinds/<name>.py``."""
    if not name.isidentifier():
        raise ValueError(f"a kind's name is a Python identifier, got "
                         f"{name!r}")
    try:
        return importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise KeyError(f"no layer kind {name!r}: no file "
                       f"chipbench/kinds/{name}.py") from None


def of_row(row) -> ModuleType:
    """The kind a configuration row names; a list row is a plain GEMM."""
    return load(row["kind"] if isinstance(row, dict) else DEFAULT)
