"""Whole-stage fusion pass: collapse chains of narrow operators into one
jitted computation per stage.

Follows Flare (native compilation for Spark) and the SystemML operator-
fusion-plan work: between exchanges, a run of batch-local narrow operators
— projection, filter, rename, expand, with coalesce-batches as an in-stage
staging point — does no data-dependent control flow and touches each row
once, so the whole run is memory-bound and can execute as ONE XLA
computation instead of one eager dispatch per expression node plus a
compaction kernel per filter. The pass rewrites maximal fusable chains into
:class:`~blaze_tpu.ir.nodes.FusedStage` nodes; ``ops/fused.py`` compiles
each stage's expression chain into a single jitted closure cached by chain
fingerprint across batches AND queries.

Cost model (the SystemML-style cut points, kept deliberately small):

- **Boundaries are structural.** Blocking or exchange operators (sort, agg,
  join, window, shuffle/ipc endpoints, scans) are never crossed — a chain
  runs strictly between them, where shapes stay capacity-bucket compatible.
- **Fuse only what provably traces.** Every expression must pass
  ``fusable_expr`` (pure device path) and every schema in the chain must be
  fully fixed-width; anything else ends the chain. Batches that still show
  host columns at runtime (dictionary-encoded device dtypes) fall back
  per-batch inside the operator.
- **Fuse only when it saves dispatches.** A chain is rewritten when its
  estimated eager dispatch count exceeds the fused dispatch count (one per
  jitted segment) by at least ``conf.fusion_min_saved_dispatches`` — a lone
  column-reference projection or a bare coalesce stays unfused.
- **Leave agg's filter alone.** A filter directly under an Agg is the one
  the device partial-agg kernel absorbs where ``fused_filter_agg`` is on;
  swallowing it here would disengage that path, so the chain may start only
  below it.

The pass runs at operator-build time (``runtime/executor.build_operator``),
not at plan-optimization time, so it sees post-lowering trees (including
driver-inserted CoalesceBatches over IpcReader) and applies identically on
the driver and on pool workers rebuilding plans from shipped proto IR —
FusedStage itself never needs a proto encoding. It is idempotent and pure:
re-running it over an already-fused tree is a no-op.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import List, Tuple

from blaze_tpu.ir import nodes as N

_TRIVIAL = None  # populated lazily: expression types with no eager dispatch


def fuse_plan(node: N.PlanNode, conf) -> N.PlanNode:
    """Rewrite maximal fusable chains in ``node``'s tree into FusedStage
    nodes. Returns the input tree unchanged when ``conf.fusion_enabled`` is
    off (the escape hatch: the built operator tree is then exactly the
    pre-fusion one)."""
    if not getattr(conf, "fusion_enabled", False):
        return node
    return _fuse(node, conf, allow_start=True)


def _fuse(node: N.PlanNode, conf, allow_start: bool) -> N.PlanNode:
    if isinstance(node, N.FusedStage):  # idempotence
        child = _fuse(node.child, conf, allow_start=True)
        if child is node.child:
            return node
        return dataclasses.replace(node, child=child)
    if allow_start and _op_fusable(node, conf):
        from blaze_tpu.obs import attribution as _audit

        chain = [node]  # outermost-first
        cur = node.child
        while _op_fusable(cur, conf):
            chain.append(cur)
            cur = cur.child
        # decision audit: why did the chain stop at ``cur``?
        _audit.note_fusion_break(_op_unfusable_reason(cur, conf)
                                 or "blocking_op")
        if _worth_fusing(chain, conf):
            _audit.note_fusion_chain(len(chain), len(chain))
            fused_child = _fuse(cur, conf, allow_start=True)
            return N.FusedStage(child=fused_child,
                                ops=tuple(reversed(chain)))
        # a maximal chain not worth fusing has no worthwhile subchain
        # (the gain estimate is additive) — recurse past it instead
        _audit.note_fusion_chain(0, len(chain))
        _audit.note_fusion_break("cost_below_min_saved")
    return _recurse(node, conf)


def _recurse(node: N.PlanNode, conf) -> N.PlanNode:
    changed = False

    def fn(child):
        nonlocal changed
        allow = not (isinstance(node, N.Agg) and isinstance(child, N.Filter))
        if not allow and _op_fusable(child, conf):
            from blaze_tpu.obs import attribution as _audit

            _audit.note_fusion_break("agg_filter_guard")
        out = _fuse(child, conf, allow_start=allow)
        changed = changed or out is not child
        return out

    rebuilt = N.map_children(node, fn)
    # identity-preserving: a tree with nothing to fuse passes through
    # untouched (build_operator runs this on every build — and tests pin
    # the escape-hatch contract with ``is``)
    return rebuilt if changed else node


def _all_device(schema) -> bool:
    from blaze_tpu.utils.device import is_device_dtype

    return all(is_device_dtype(f.dtype) for f in schema.fields)


def _op_fusable(node: N.PlanNode, conf) -> bool:
    """Can this node join a fused chain? Structural kind + traceable
    expressions + fully fixed-width schemas on both sides."""
    return _op_unfusable_reason(node, conf) is None


def _contains_pyudf(expr) -> bool:
    from blaze_tpu.ir import exprs as E

    if isinstance(expr, E.PyUDF):
        return True
    try:
        return any(_contains_pyudf(c) for c in expr.children())
    except Exception:
        return False


def _expr_break_reason(exprs) -> str:
    return "pyudf" if any(_contains_pyudf(e) for e in exprs) \
        else "unfusable_expr"


def _op_unfusable_reason(node: N.PlanNode, conf):
    """None when the node can join a fused chain, else the break reason
    (one of ``obs.attribution.FUSION_BREAK_REASONS``) — the decision-audit
    form of ``_op_fusable``, same checks in the same order."""
    from blaze_tpu.exprs.compiler import fusable_expr

    if not isinstance(node, (N.Projection, N.Filter, N.RenameColumns,
                             N.CoalesceBatches, N.Expand)):
        return "blocking_op"
    try:
        in_schema = node.child.output_schema
        if not _all_device(in_schema):
            return "host_schema"
        if isinstance(node, N.Projection):
            if not _all_device(node.output_schema):
                return "host_schema"
            if not all(fusable_expr(e, in_schema) for e in node.exprs):
                return _expr_break_reason(node.exprs)
            return None
        if isinstance(node, N.Filter):
            if not all(fusable_expr(p, in_schema) for p in node.predicates):
                return _expr_break_reason(node.predicates)
            return None
        if isinstance(node, N.Expand):
            if not _all_device(node.schema):
                return "host_schema"
            flat = [e for proj in node.projections for e in proj]
            if not all(fusable_expr(e, in_schema) for e in flat):
                return _expr_break_reason(flat)
            return None
        return None  # rename / coalesce: structural only
    except Exception:
        return "schema_error"


def _nontrivial(exprs) -> int:
    from blaze_tpu.ir import exprs as E

    return sum(1 for e in exprs
               if not isinstance(e, (E.Column, E.BoundReference, E.Literal)))


def _estimated_eager_dispatches(chain: List[N.PlanNode]) -> int:
    """Rough eager cost of the chain: one dispatch per non-trivial
    expression evaluation plus one compaction kernel per filter. (Eager
    expression trees dispatch per jnp op, so this undercounts — fine, the
    estimate only needs to separate "saves work" from "saves nothing".)"""
    est = 0
    for op in chain:
        if isinstance(op, N.Projection):
            est += _nontrivial(op.exprs)
        elif isinstance(op, N.Filter):
            est += _nontrivial(op.predicates) + 1
        elif isinstance(op, N.Expand):
            est += sum(_nontrivial(p) for p in op.projections)
    return est


def _fused_dispatches(chain: List[N.PlanNode]) -> int:
    """Fused cost: one jitted dispatch per contiguous non-coalesce run."""
    segs = 0
    in_run = False
    for op in chain:
        if isinstance(op, N.CoalesceBatches):
            in_run = False
        elif not in_run:
            segs += 1
            in_run = True
    return segs


def _worth_fusing(chain: List[N.PlanNode], conf) -> bool:
    saved = _estimated_eager_dispatches(chain) - _fused_dispatches(chain)
    return saved >= getattr(conf, "fusion_min_saved_dispatches", 1)


# -- steps + fingerprint ------------------------------------------------------


def chain_steps(ops: Tuple[N.PlanNode, ...]) -> Tuple[tuple, ...]:
    """Lower a FusedStage's op tuple (innermost-first) into the neutral step
    format consumed by ``exprs.compiler.build_fused_closure`` and the fused
    operator: ("project", exprs, names) | ("filter", preds) |
    ("rename", names) | ("coalesce", batch_size) | ("expand", projs, schema)."""
    steps = []
    for op in ops:
        if isinstance(op, N.Projection):
            steps.append(("project", tuple(op.exprs), tuple(op.names)))
        elif isinstance(op, N.Filter):
            steps.append(("filter", tuple(op.predicates)))
        elif isinstance(op, N.RenameColumns):
            steps.append(("rename", tuple(op.renamed_names)))
        elif isinstance(op, N.CoalesceBatches):
            steps.append(("coalesce", op.batch_size))
        elif isinstance(op, N.Expand):
            steps.append(("expand",
                          tuple(tuple(p) for p in op.projections), op.schema))
        else:
            raise TypeError(f"unfusable op in FusedStage: {type(op).__name__}")
    return tuple(steps)


def _schema_sig(schema) -> list:
    return [[f.name, repr(f.dtype)] for f in schema.fields]


def fused_fingerprint(input_schema, steps) -> str:
    """Stable identity of one fused segment: input schema + the full step
    list with serialized expressions. Keys the process-global jitted-closure
    cache, so two queries with the same subplan shape share one compiled
    program (per batch-shape bucket) — the jit-cache-reuse contract in the
    fusion tests."""
    from blaze_tpu.ir.serde import expr_to_json

    payload = [_schema_sig(input_schema)]
    for st in steps:
        kind = st[0]
        if kind == "project":
            payload.append([kind, [expr_to_json(e) for e in st[1]],
                            list(st[2])])
        elif kind == "filter":
            payload.append([kind, [expr_to_json(p) for p in st[1]]])
        elif kind == "rename":
            payload.append([kind, list(st[1])])
        elif kind == "coalesce":
            payload.append([kind, st[1]])
        else:  # expand
            payload.append([kind,
                            [[expr_to_json(e) for e in proj] for proj in st[1]],
                            _schema_sig(st[2])])
    blob = json.dumps(payload, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:24]
