"""Deep Neural Network Graph (DNNG) — the paper's workload abstraction (§2.1).

A DNNG is a weighted DAG ``G(V, E)`` whose vertices are layers and whose edges
encode execution precedence.  Each layer carries the 9 convolution shape
parameters ``{M, N, C, R, S, H, W, P, Q}`` (paper Eq. 1):

    FW    ∈ R^{M×C×R×S}   — filter weights   (M filters, C channels, R×S kernel)
    IFMap ∈ R^{N×C×H×W}   — input feature map (N batch, H×W spatial)
    OFMap ∈ R^{N×M×P×Q}   — output feature map (P×Q output spatial)

``Opr(l) = M·N·C·R·S·H·W`` (paper Eq. 2) estimates the MAC count and is the
priority key of the Task_Assignment step of Algorithm 1.

Every layer lowers to a GEMM for the weight-stationary systolic array:

    stationary (weights):  K × M   with K = C·R·S   (K on PE rows, M on PE cols)
    streamed  (im2col):    T × K   with T = N·P·Q   (T input rows streamed)

Fully connected / recurrent layers are expressed with R=S=1, H=W=P=Q=1 and the
batch/time steps folded into N.

Two layers of a transformer block are not one GEMM: a routed-expert layer
(:class:`ExpertLayer`) and an attention core (:class:`AttentionLayer`).  Each
lowers to several weight-stationary GEMMs (``gemms``), which the simulator
prices one by one and sums, so such a layer still runs in one compute
interval; each also answers the aggregate ``gemm_m``/``gemm_k``/``gemm_n``,
``macs``, ``opr`` and ``weight_bytes`` that the policies read.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterator, Sequence


@functools.lru_cache(maxsize=4096)
def _chain_edges(n_layers: int) -> tuple[tuple[int, int], ...]:
    """Edge list of the default linear chain (shared across the thousands
    of per-job DNNG clones the open-loop traffic generator stamps out)."""
    return tuple((i, i + 1) for i in range(n_layers - 1))


@functools.lru_cache(maxsize=4096)
def _pred_table(edges: tuple[tuple[int, int], ...],
                n_layers: int) -> tuple[tuple[int, ...], ...]:
    """Predecessor indices per layer, precomputed once per graph shape.

    The dynamic scheduler asks for predecessors on every ready-set update;
    rebuilding the edge scan per query was the single hottest line of the
    serving hot path before this cache (see benchmarks/scale_bench.py).
    """
    preds: list[list[int]] = [[] for _ in range(n_layers)]
    for s, d in edges:
        preds[d].append(s)
    return tuple(tuple(p) for p in preds)


@dataclasses.dataclass(frozen=True)
class LayerShape:
    """The 9 shape parameters of one DNN layer (paper Eq. 1)."""

    M: int  # number of filters (output channels)
    N: int  # batch size
    C: int  # input channels
    R: int  # filter height
    S: int  # filter width
    H: int  # input height
    W: int  # input width
    P: int  # output height
    Q: int  # output width
    name: str = ""

    def __post_init__(self) -> None:
        for f in ("M", "N", "C", "R", "S", "H", "W", "P", "Q"):
            v = getattr(self, f)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"LayerShape.{f} must be a positive int, got {v!r}")

    def __hash__(self) -> int:
        # memoized: LayerShape keys every hot cost-oracle memo (ws_cost /
        # layer_cost LRUs, stage-cost dicts), and the generated dataclass
        # hash re-tuples all 10 fields per lookup.  Frozen blocks setattr
        # but not __dict__ writes; equal instances hash equal because the
        # memo is derived from the same field tuple eq compares.
        h = self.__dict__.get("_hash")
        if h is None:
            self.__dict__["_hash"] = h = hash(
                (self.M, self.N, self.C, self.R, self.S,
                 self.H, self.W, self.P, self.Q, self.name))
        return h

    # -- paper Eq. 2 ------------------------------------------------------
    @property
    def opr(self) -> int:
        """MAC-operation count ``Opr(l) = M·N·C·R·S·H·W``.

        Note: the paper uses H·W (input spatial) rather than P·Q; we keep the
        paper's formula for priority ordering and expose :meth:`macs` as the
        exact count used by the cycle/energy models.  Memoized like
        ``__hash__``: it is the sort key of every Task_Assignment round.
        """
        v = self.__dict__.get("_opr")
        if v is None:
            self.__dict__["_opr"] = v = (self.M * self.N * self.C * self.R
                                         * self.S * self.H * self.W)
        return v

    @property
    def macs(self) -> int:
        """Exact MAC count of the lowered GEMM: M·N·C·R·S·P·Q."""
        return self.M * self.N * self.C * self.R * self.S * self.P * self.Q

    # -- GEMM lowering (weight stationary) --------------------------------
    @property
    def gemm_k(self) -> int:
        """Reduction dim = C·R·S (maps to PE rows; weights are stationary)."""
        return self.C * self.R * self.S

    @property
    def gemm_n(self) -> int:
        """Output-channel dim = M (maps to PE columns — the partitioned dim)."""
        return self.M

    @property
    def gemm_m(self) -> int:
        """Streamed dim = N·P·Q (rows of im2col input fed through the array)."""
        return self.N * self.P * self.Q

    @property
    def gemms(self) -> tuple[tuple[int, int, int], ...]:
        """The layer's GEMMs as ``(T, K, N)``: here the one lowered GEMM."""
        return ((self.gemm_m, self.gemm_k, self.gemm_n),)

    @property
    def weight_bytes(self) -> int:
        return 2 * self.gemm_k * self.gemm_n  # bf16/int16 as in Scale-Sim configs

    @property
    def ifmap_elems(self) -> int:
        return self.N * self.C * self.H * self.W

    @property
    def ofmap_elems(self) -> int:
        return self.N * self.M * self.P * self.Q

    @staticmethod
    def conv(name: str, M: int, C: int, R: int, S: int, H: int, W: int,
             stride: int = 1, pad: int | None = None, N: int = 1) -> "LayerShape":
        """Build a conv layer; output spatial derived from stride/padding."""
        if pad is None:
            pad = R // 2
        P = (H + 2 * pad - R) // stride + 1
        Q = (W + 2 * pad - S) // stride + 1
        return LayerShape(M=M, N=N, C=C, R=R, S=S, H=H, W=W, P=max(P, 1),
                          Q=max(Q, 1), name=name)

    @staticmethod
    def fc(name: str, in_features: int, out_features: int, batch: int = 1) -> "LayerShape":
        """Fully connected layer: GEMM (batch × in) · (in × out)."""
        return LayerShape(M=out_features, N=batch, C=in_features, R=1, S=1,
                          H=1, W=1, P=1, Q=1, name=name)

    @staticmethod
    def lstm_cell(name: str, input_size: int, hidden: int, steps: int,
                  batch: int = 1) -> "LayerShape":
        """LSTM cell unrolled over ``steps``: 4 gate GEMMs of (in+hid)→hid.

        Expressed as one GEMM with K = input_size + hidden, M = 4·hidden and
        the time steps folded into the streamed dimension.
        """
        return LayerShape(M=4 * hidden, N=batch * steps, C=input_size + hidden,
                          R=1, S=1, H=1, W=1, P=1, Q=1, name=name)


def _gemm_macs(gemms) -> int:
    return sum(t * k * n for t, k, n in gemms)


@dataclasses.dataclass(frozen=True)
class ExpertLayer:
    """The routed experts a chip holds of one sparse MLP layer.

    Expert ``held[i]`` is a SiLU-gated MLP, ``hidden -> 2 * width`` (gate and
    up, one weight) then ``width -> hidden`` (down), applied to the
    ``rows[i]`` token rows the router sent it.  The router scores all
    ``n_experts`` and picks ``top_k`` per token; a chip holding only some
    of them computes their share of the layer.  Lowered to two GEMMs per
    held expert with rows, at that expert's rows.  In aggregate the layer
    streams every routed row (``gemm_m``) against the held experts' gate/up
    columns (``gemm_n``, the widest of its GEMMs).
    """

    name: str
    hidden: int
    width: int
    rows: tuple[int, ...]
    held: tuple[int, ...] = ()
    n_experts: int = 0
    top_k: int = 1

    def __post_init__(self) -> None:
        if self.hidden < 1 or self.width < 1 or not any(self.rows):
            raise ValueError(f"ExpertLayer {self.name!r}: needs a hidden "
                             "and expert width, one row count a held "
                             "expert and a routed row")
        if any(not isinstance(r, int) or r < 0 for r in self.rows):
            raise ValueError(f"ExpertLayer {self.name!r}: row counts must "
                             f"be ints >= 0, got {self.rows}")
        held = self.held or tuple(range(len(self.rows)))
        n = self.n_experts or len(held)
        if len(held) != len(self.rows) or len(set(held)) != len(held) \
                or not all(0 <= e < n for e in held):
            raise ValueError(f"ExpertLayer {self.name!r}: held experts "
                             f"{held} do not match {len(self.rows)} row "
                             f"counts among {n} experts")
        object.__setattr__(self, "held", tuple(held))
        object.__setattr__(self, "n_experts", n)

    @property
    def gemms(self) -> tuple[tuple[int, int, int], ...]:
        out = []
        for r in self.rows:
            if r:
                out += [(r, self.hidden, 2 * self.width),
                        (r, self.width, self.hidden)]
        return tuple(out)

    @property
    def gemm_m(self) -> int:
        return sum(self.rows)

    @property
    def gemm_k(self) -> int:
        return self.hidden

    @property
    def gemm_n(self) -> int:
        return len(self.held) * 2 * self.width

    @property
    def macs(self) -> int:
        return _gemm_macs(self.gemms)

    @property
    def opr(self) -> int:
        return self.macs

    @property
    def weight_bytes(self) -> int:
        return 2 * len(self.held) * 3 * self.hidden * self.width

    @property
    def ifmap_elems(self) -> int:
        return self.gemm_m * self.hidden

    @property
    def ofmap_elems(self) -> int:
        return self.gemm_m * self.hidden


@dataclasses.dataclass(frozen=True)
class Rope:
    """Rotary position embedding of an attention layer: ``kind`` "default"
    (inverse frequencies ``theta ** (-2i / head_dim)``) or "yarn" (those,
    blended toward ``1 / factor`` of themselves between ``beta_fast`` and
    ``beta_slow`` rotations over ``original_max_position_embeddings``, with
    ``attention_factor`` multiplying cos and sin)."""

    kind: str = "default"
    theta: float = 10000.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self) -> None:
        if self.kind not in ("default", "yarn"):
            raise ValueError(f"rope kind must be 'default' or 'yarn', got "
                             f"{self.kind!r}")


def visible_pairs(length: int, window: int) -> int:
    """(query, key) pairs a causal prompt of ``length`` tokens attends,
    each query seeing itself and the ``window - 1`` keys before it
    (``window`` 0: every key before it)."""
    if not window or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


@dataclasses.dataclass(frozen=True)
class AttentionLayer:
    """The attention core of a prefill step: ``heads`` query heads sharing
    ``kv_heads`` key/value heads, each ``head_dim`` wide, over packed
    prompts of ``seq_lens`` tokens, causal within each prompt, windowed to
    ``window`` keys (0: full), with rotary embedding ``rope``.

    In the weight-stationary lowering a prompt's keys (for QKᵀ) and values
    (for PV) are the stationary operands and its queries stream: per prompt
    and key/value head, QKᵀ streams the group's ``heads / kv_heads`` query
    heads' rows against the visible keys, and PV the scores against the
    values.  A band of visible keys is lowered at its mean width, the
    visible pairs over the prompt's length rounded up.  In aggregate the
    layer streams every token's query row (``gemm_m``, ``gemm_k`` its
    width) to an output of the same width (``gemm_n``).
    """

    name: str
    heads: int
    kv_heads: int
    head_dim: int
    seq_lens: tuple[int, ...]
    window: int = 0
    rope: Rope = Rope()

    def __post_init__(self) -> None:
        if min(self.heads, self.kv_heads, self.head_dim) < 1 \
                or self.heads % self.kv_heads:
            raise ValueError(f"AttentionLayer {self.name!r}: {self.heads} "
                             f"query heads do not share {self.kv_heads} "
                             "key/value heads evenly")
        if not self.seq_lens or min(self.seq_lens) < 1 or self.window < 0:
            raise ValueError(f"AttentionLayer {self.name!r}: needs prompts "
                             "of at least one token and a window >= 0")

    @property
    def tokens(self) -> int:
        return sum(self.seq_lens)

    @property
    def pairs(self) -> int:
        """Visible (query, key) pairs over every prompt, per head."""
        return sum(visible_pairs(n, self.window) for n in self.seq_lens)

    @property
    def gemms(self) -> tuple[tuple[int, int, int], ...]:
        group = self.heads // self.kv_heads
        out = []
        for n in self.seq_lens:
            keys = -(-visible_pairs(n, self.window) // n)
            out += [(group * n, self.head_dim, keys),
                    (group * n, keys, self.head_dim)] * self.kv_heads
        return tuple(out)

    @property
    def gemm_m(self) -> int:
        return self.tokens

    @property
    def gemm_k(self) -> int:
        return self.heads * self.head_dim

    @property
    def gemm_n(self) -> int:
        return self.heads * self.head_dim

    @property
    def macs(self) -> int:
        return _gemm_macs(self.gemms)

    @property
    def opr(self) -> int:
        return self.macs

    @property
    def weight_bytes(self) -> int:
        """The stationary operands: every key and value, bf16."""
        return 2 * 2 * self.tokens * self.kv_heads * self.head_dim

    @property
    def ifmap_elems(self) -> int:
        return self.tokens * (self.heads + 2 * self.kv_heads) * self.head_dim

    @property
    def ofmap_elems(self) -> int:
        return self.tokens * self.heads * self.head_dim


@dataclasses.dataclass(frozen=True)
class DNNG:
    """A DNN graph: a named chain/DAG of layers with an arrival time (§2.1).

    ``edges`` holds (src, dst) layer-index pairs.  The common case (and all the
    paper's workloads) is a linear chain, which is the default when ``edges``
    is None.  ``arrival_time`` is A_t in cycles (or seconds — units follow the
    simulator's clock).
    """

    name: str
    layers: tuple[LayerShape | ExpertLayer | AttentionLayer, ...]
    arrival_time: float = 0.0
    edges: tuple[tuple[int, int], ...] | None = None

    def __post_init__(self) -> None:
        if not self.layers:
            raise ValueError(f"DNNG {self.name!r} has no layers")
        n = len(self.layers)
        if self.edges is not None:
            for s, d in self.edges:
                if not (0 <= s < n and 0 <= d < n):
                    raise ValueError(f"edge ({s},{d}) out of range for {n} layers")
                if s >= d:
                    raise ValueError(f"edge ({s},{d}) violates topological order")

    @property
    def edge_list(self) -> tuple[tuple[int, int], ...]:
        if self.edges is not None:
            return self.edges
        return _chain_edges(len(self.layers))

    @property
    def pred_table(self) -> tuple[tuple[int, ...], ...]:
        """Predecessors per layer index, cached per graph shape — the
        scheduler's O(1) DAG-readiness lookup."""
        return _pred_table(self.edge_list, len(self.layers))

    def predecessors(self, idx: int) -> list[int]:
        return list(self.pred_table[idx])

    def successors(self, idx: int) -> list[int]:
        return [d for s, d in self.edge_list if s == idx]

    def roots(self) -> list[int]:
        """Layers with no predecessors (ready at arrival)."""
        dsts = {d for _, d in self.edge_list}
        return [i for i in range(len(self.layers)) if i not in dsts]

    def clone(self, name: str | None = None,
              arrival_time: float | None = None) -> "DNNG":
        """Re-stamp a validated template with a new name / arrival.

        The open-loop traffic generator clones one Table-1 template per
        arriving job; this skips ``dataclasses.replace``'s re-validation
        (the layer tuple and edges are shared, already-validated objects)
        — measurably cheaper at thousands of jobs per run.
        """
        g = object.__new__(DNNG)
        d = g.__dict__
        d["name"] = self.name if name is None else name
        d["layers"] = self.layers
        d["arrival_time"] = (self.arrival_time if arrival_time is None
                             else arrival_time)
        d["edges"] = self.edges
        return g

    @property
    def total_macs(self) -> int:
        return sum(layer.macs for layer in self.layers)

    @property
    def total_opr(self) -> int:
        return sum(layer.opr for layer in self.layers)

    def __iter__(self) -> Iterator[LayerShape]:
        return iter(self.layers)

    def __len__(self) -> int:
        return len(self.layers)


def chain(name: str, layers: Sequence[LayerShape], arrival_time: float = 0.0) -> DNNG:
    """Convenience constructor for the (ubiquitous) linear-chain DNNG."""
    return DNNG(name=name, layers=tuple(layers), arrival_time=arrival_time)


def validate_dag(g: DNNG) -> bool:
    """Property-test hook: the edge list must be acyclic & topologically sorted."""
    seen: set[int] = set()
    for s, d in g.edge_list:
        if d in seen and s not in seen:
            return False
        seen.add(s)
        seen.add(d)
    return all(s < d for s, d in g.edge_list)


def estimated_execution_time(g: DNNG, macs_per_cycle: float) -> float:
    """E_t estimate used by Algorithm 1 line 8 (coarse: MACs / throughput)."""
    if macs_per_cycle <= 0:
        raise ValueError("macs_per_cycle must be positive")
    return g.total_macs / macs_per_cycle
