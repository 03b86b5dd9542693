"""Lower a jaxpr into the mapper's operator graph.

Reuses ``repro.core.estimator.iter_eqns`` — the same traversal that prices
op counts — so the graph's op totals reconcile with ``pim_estimate`` by
construction: every costed primitive becomes exactly one node carrying the
same MAC/add/mul count the estimator would have charged.

Node kinds:
  * ``MatmulNode``  — ``dot_general``; the rhs operand is treated as the
    stationary weight (x @ W convention). Backward-pass matmuls therefore
    get their own stationary operand, mirroring FloatPIM's layout which
    keeps a transposed weight copy resident for backprop.
  * ``ConvNode``    — ``conv_general_dilated``; stationary weight is the
    (fan_in, cout) filter matrix (spatially replicated units share it).
  * ``EltwiseNode`` — add/sub/mul/div, priced per element; executed in the
    shared peripheral FP units, so no weight placement.

Dependency edges are recovered by dataflow closure over *all* primitives
(a tanh between two matmuls still links them). Var identity does not cross
sub-jaxpr boundaries (pjit / scan bodies), so edges within an inlined call
are precise while edges across the boundary are dropped — the scheduler
only relies on the topological emission order, which ``iter_eqns``
guarantees.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
from jax.extend.core import Literal, jaxpr_as_fun
import jax.numpy as jnp
import numpy as np

from repro.core import estimator
from repro.core.estimator import OpCounts


@dataclasses.dataclass
class OpNode:
    idx: int
    kind: str                 # matmul | conv | eltwise
    name: str                 # "<primitive>.<idx>"
    repeat: int               # static multiplicity (scan length product)
    deps: list[int]
    out_shape: tuple[int, ...]
    out_elems: int            # per execution
    macs: int = 0             # totals including ``repeat``
    adds: int = 0
    muls: int = 0
    eqn_id: int = 0           # id() of the source eqn (executor lookup key)
    top_eqn: int = 0          # index of the owning *top-level* jaxpr eqn —
                              # partition cuts land on top-eqn boundaries

    @property
    def weight_shape(self) -> tuple[int, int] | None:
        return None

    @property
    def weight_values(self) -> int:
        ws = self.weight_shape
        return ws[0] * ws[1] if ws else 0


@dataclasses.dataclass
class MatmulNode(OpNode):
    batch: int = 1
    m: int = 0
    k: int = 0
    n: int = 0

    @property
    def weight_shape(self) -> tuple[int, int]:
        # batched matmuls (attention scores etc.) hold each batch member's
        # stationary operand; fold batch into the column dimension.
        return (self.k, self.n * self.batch)


@dataclasses.dataclass
class ConvNode(OpNode):
    fan_in: int = 0
    cout: int = 0

    @property
    def weight_shape(self) -> tuple[int, int]:
        return (self.fan_in, self.cout)


@dataclasses.dataclass
class EltwiseNode(OpNode):
    op: str = "add"           # add | sub | mul | div
    n_elems: int = 0          # totals including ``repeat``


@dataclasses.dataclass
class OpGraph:
    """Cost-relevant operator graph of one traced function."""

    nodes: list[OpNode]
    closed_jaxpr: Any                       # jax.core.ClosedJaxpr
    in_tree: Any
    out_tree: Any
    fn: Callable | None = None

    def totals(self) -> OpCounts:
        c = OpCounts()
        for nd in self.nodes:
            c.macs += nd.macs
            c.adds += nd.adds
            c.muls += nd.muls
        return c

    def weight_values(self) -> int:
        return sum(nd.weight_values for nd in self.nodes)

    def weight_bits(self, n_bits: int = 32) -> int:
        return self.weight_values() * n_bits

    def matmul_like(self) -> list[OpNode]:
        return [nd for nd in self.nodes if nd.kind in ("matmul", "conv")]


def _out_elems(eqn) -> int:
    return int(np.prod(eqn.outvars[0].aval.shape, dtype=np.int64))


def build_graph_from_jaxpr(closed_jaxpr, in_tree=None, out_tree=None,
                           fn: Callable | None = None) -> OpGraph:
    nodes: list[OpNode] = []
    origin: dict[int, frozenset[int]] = {}   # id(var) -> producing node idxs

    def read_origin(v) -> frozenset[int]:
        return origin.get(id(v), frozenset())

    top_stream = [(eqn, scale, top_idx)
                  for top_idx, top in enumerate(closed_jaxpr.jaxpr.eqns)
                  for eqn, scale in estimator.iter_eqn(top)]
    for eqn, scale, top_idx in top_stream:
        name = eqn.primitive.name
        src = frozenset().union(*[read_origin(v) for v in eqn.invars]) \
            if eqn.invars else frozenset()
        node: OpNode | None = None
        idx = len(nodes)
        kind = estimator.node_kind(name)
        # some equations have no outputs (no placed kind among them)
        out_shape = tuple(eqn.outvars[0].aval.shape) if eqn.outvars else ()
        if kind == "matmul":
            b, m, n, k = estimator.dot_general_dims(eqn)
            node = MatmulNode(
                idx=idx, kind="matmul", name=f"dot_general.{idx}",
                repeat=scale, deps=sorted(src), out_shape=out_shape,
                out_elems=_out_elems(eqn), macs=scale * b * m * n * k,
                eqn_id=id(eqn), top_eqn=top_idx, batch=b, m=m, k=k, n=n)
        elif kind == "conv":
            out_elems, fan_in, cout = estimator.conv_dims(eqn)
            node = ConvNode(
                idx=idx, kind="conv", name=f"conv.{idx}",
                repeat=scale, deps=sorted(src), out_shape=out_shape,
                out_elems=out_elems, macs=scale * out_elems * fan_in,
                eqn_id=id(eqn), top_eqn=top_idx, fan_in=fan_in, cout=cout)
        elif kind == "eltwise":
            n_el = _out_elems(eqn)
            is_add = name in estimator.ADD_PRIMS
            node = EltwiseNode(
                idx=idx, kind="eltwise", name=f"{name}.{idx}",
                repeat=scale, deps=sorted(src), out_shape=out_shape,
                out_elems=n_el,
                adds=scale * n_el if is_add else 0,
                muls=0 if is_add else scale * n_el,
                eqn_id=id(eqn), top_eqn=top_idx, op=name,
                n_elems=scale * n_el)
        if node is not None:
            nodes.append(node)
            out_origin = frozenset({node.idx})
        else:
            out_origin = src
        for v in eqn.outvars:
            origin[id(v)] = out_origin
    return OpGraph(nodes=nodes, closed_jaxpr=closed_jaxpr,
                   in_tree=in_tree, out_tree=out_tree, fn=fn)


def build_graph(fn: Callable, *args, **kwargs) -> OpGraph:
    """Trace ``fn(*args, **kwargs)`` (ShapeDtypeStructs welcome — no
    allocation) and lower its jaxpr to an ``OpGraph``."""
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args, **kwargs)
    flat, in_tree = jax.tree.flatten((args, kwargs))
    del flat
    out_tree = jax.tree.structure(out_shape)
    return build_graph_from_jaxpr(closed, in_tree=in_tree, out_tree=out_tree,
                                  fn=fn)


# ---------------------------------------------------------------------------
# scan residency: expand repeat=R scans into resident per-layer copies
# ---------------------------------------------------------------------------
#
# A scanned layer stack lowers to ONE top-level ``scan`` equation, so every
# node inside it shares one top_eqn and ``placement.partition`` cannot cut
# the stack — deep models pipeline as a monolith. ``expand_scans`` replays
# the jaxpr with each selected scan unrolled into R resident per-iteration
# copies (or ceil(R/g) chunked scans of length g when the full unroll
# exceeds the subarray budget), then re-traces: the body equations become
# ordinary top-level equations, each copy's weights get their own resident
# block grid, and partition cuts can land between layers. The replay binds
# every other equation verbatim (the ``eval_jaxpr`` idiom), so numerics
# are bit-identical and ``estimator.count_ops_jaxpr`` totals are unchanged
# (R copies counting once each == one copy scaled by R).


def scan_lengths(closed_jaxpr) -> dict[int, int]:
    """Top-level ``scan`` equations by eqn index -> static trip count."""
    return {i: int(eqn.params["length"])
            for i, eqn in enumerate(closed_jaxpr.jaxpr.eqns)
            if eqn.primitive.name == "scan"
            and int(eqn.params["length"]) > 1}


def _unrolled_scan(eqn, invals: list, group: int) -> list:
    """Evaluate one ``scan`` equation as resident copies.

    ``group <= 1`` (or >= length) unrolls fully: the body jaxpr is called
    once per iteration, inlining its equations at top level. ``group = g``
    emits ``ceil(length / g)`` chunked ``scan`` equations of length <= g —
    one resident copy per chunk. ``reverse`` scans thread the carry through
    iterations (and chunks) back to front; stacked ``ys`` keep positional
    order either way, exactly matching ``lax.scan`` semantics.
    """
    p = eqn.params
    length = int(p["length"])
    n_consts, n_carry = int(p["num_consts"]), int(p["num_carry"])
    reverse = bool(p["reverse"])
    body = p["jaxpr"]                       # ClosedJaxpr of the scan body
    body_fn = jaxpr_as_fun(body)
    consts = invals[:n_consts]
    carry = list(invals[n_consts:n_consts + n_carry])
    xs = invals[n_consts + n_carry:]
    n_ys = len(body.jaxpr.outvars) - n_carry

    if group <= 1 or group >= length:
        idxs = range(length - 1, -1, -1) if reverse else range(length)
        ys_by_pos: dict[int, tuple] = {}
        for i in idxs:
            outs = body_fn(*consts, *carry, *[x[i] for x in xs])
            carry = list(outs[:n_carry])
            ys_by_pos[i] = tuple(outs[n_carry:])
        ys = [jnp.stack([ys_by_pos[i][j] for i in range(length)], axis=0)
              for j in range(n_ys)]
        return carry + ys

    def chunk_body(c, x_slice):
        outs = body_fn(*consts, *c, *x_slice)
        return tuple(outs[:n_carry]), tuple(outs[n_carry:])

    chunks = [(lo, min(length, lo + group))
              for lo in range(0, length, group)]
    ys_by_chunk: dict[int, tuple] = {}
    for lo, hi in (reversed(chunks) if reverse else chunks):
        xs_c = tuple(jax.lax.slice_in_dim(x, lo, hi, axis=0) for x in xs)
        carry_t, ys_c = jax.lax.scan(chunk_body, tuple(carry), xs_c,
                                     reverse=reverse)
        carry = list(carry_t)
        ys_by_chunk[lo] = ys_c
    ys = [jnp.concatenate([ys_by_chunk[lo][j] for lo, _ in chunks], axis=0)
          for j in range(n_ys)]
    return carry + ys


def expand_scans(closed_jaxpr, groups: dict[int, int]):
    """Re-trace ``closed_jaxpr`` with the top-level scans named in
    ``groups`` (eqn index -> chunk length ``g``; ``g=1`` = full unroll)
    expanded into resident copies. Every other equation replays verbatim,
    so the returned ``ClosedJaxpr`` has identical invars/outvars avals,
    identical numerics, and identical ``count_ops_jaxpr`` totals."""
    jaxpr = closed_jaxpr.jaxpr

    def replay(*flat_args):
        env: dict = {}

        def read(v):
            return v.val if isinstance(v, Literal) else env[v]

        for cv, c in zip(jaxpr.constvars, closed_jaxpr.consts):
            env[cv] = c
        for iv, a in zip(jaxpr.invars, flat_args):
            env[iv] = a
        for i, eqn in enumerate(jaxpr.eqns):
            invals = [read(v) for v in eqn.invars]
            if i in groups and eqn.primitive.name == "scan":
                outvals = _unrolled_scan(eqn, invals, groups[i])
            else:
                subfuns, bind_params = eqn.primitive.get_bind_params(
                    eqn.params)
                outvals = eqn.primitive.bind(*subfuns, *invals,
                                             **bind_params)
                if not eqn.primitive.multiple_results:
                    outvals = [outvals]
            for v, val in zip(eqn.outvars, outvals):
                if not isinstance(v, jax.core.DropVar):
                    env[v] = val
        return [read(v) for v in jaxpr.outvars]

    avals = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
             for v in jaxpr.invars]
    return jax.make_jaxpr(replay)(*avals)


def _node_blocks(node: OpNode, weight_rows: int, weight_cols: int) -> int:
    """Subarray blocks one resident copy of this node's weight grid takes
    (0 for eltwise — peripheral units, no placement)."""
    ws = node.weight_shape
    if not ws:
        return 0
    return (max(1, math.ceil(ws[0] / weight_rows))
            * max(1, math.ceil(ws[1] / weight_cols)))


def plan_scan_expansion(graph: OpGraph, *, weight_rows: int,
                        weight_cols: int,
                        budget: int) -> dict[int, int]:
    """Capacity-bucketed expansion plan: for each top-level scan owning
    placed weights, the largest copy count the subarray ``budget`` allows.

    Returns ``{eqn_idx: g}`` for :func:`expand_scans` — ``g=1`` when the
    full R-copy unroll fits, ``g>1`` (``ceil(R/g)`` resident copies) when
    it must bucket, and the site omitted entirely (refused) when even two
    resident copies would blow the budget. The budget is counted in
    subarray blocks against every node's weight grid, so un-expanded
    nodes' residency is charged too."""
    lengths = scan_lengths(graph.closed_jaxpr)
    if not lengths:
        return {}
    base = sum(_node_blocks(nd, weight_rows, weight_cols)
               for nd in graph.nodes)
    free = budget - base
    plan: dict[int, int] = {}
    for eqn_idx, length in lengths.items():
        copy_blocks = sum(_node_blocks(nd, weight_rows, weight_cols)
                          for nd in graph.nodes if nd.top_eqn == eqn_idx)
        if copy_blocks == 0:
            continue                       # no resident weights inside
        if (length - 1) * copy_blocks <= free:
            plan[eqn_idx] = 1              # full unroll fits
            free -= (length - 1) * copy_blocks
            continue
        n_copies = 1 + free // copy_blocks
        if n_copies < 2:
            continue                       # refuse: cannot afford a 2nd copy
        g = math.ceil(length / n_copies)
        plan[eqn_idx] = g
        free -= (math.ceil(length / g) - 1) * copy_blocks
    return plan


def expand_graph(graph: OpGraph, *, weight_rows: int, weight_cols: int,
                 budget: int) -> OpGraph:
    """Expand ``graph``'s scanned layer stacks into resident per-layer
    copies where the subarray ``budget`` allows (see
    :func:`plan_scan_expansion`); returns ``graph`` unchanged when no scan
    can be expanded. The rebuilt graph keeps the original ``fn`` and
    arg/out trees — ``jax.jit(fn)`` remains the numerical oracle."""
    plan = plan_scan_expansion(graph, weight_rows=weight_rows,
                               weight_cols=weight_cols, budget=budget)
    if not plan:
        return graph
    expanded = expand_scans(graph.closed_jaxpr, plan)
    return build_graph_from_jaxpr(expanded, in_tree=graph.in_tree,
                                  out_tree=graph.out_tree, fn=graph.fn)
