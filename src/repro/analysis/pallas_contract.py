"""pallas-contract checker: BlockSpec/grid invariants for every pallas_call.

The GQMV/attention kernels replay the paper's 3-stage pipeline with Pallas
grid pipelining; the contract that keeps the pipeline stall-free (and
CORRECT) is structural and checkable before any kernel runs:

- **index_map arity == grid rank (+ scalar-prefetch args)**: a mismatched
  lambda fails deep inside Mosaic with a shape error far from the bug.
- **block sizes divide their dims, or the tail is provably handled**: our
  grids are built as ``dim // block``; a caller-supplied block that does
  not divide the dim silently TRUNCATES the grid (the tail rows are never
  computed). The checker demands evidence of divisibility per divisor: the
  value comes from ``_pick_block``/a ``*check*`` validator, or a
  ``while dim % blk: blk //= 2`` descent, or an explicit raise/assert on
  ``%``.
- **out_specs/out_shape cardinality agree** when both are lists.
- **estimated VMEM footprint under budget**: sum of block-spec and scratch
  bytes (double-buffered), resolving block names through local assignments
  and module constants (an unknown dim is assumed ``ASSUMED_DIM`` in the
  two minor, tiled dims and ``ASSUMED_LEADING_DIM`` before them) — a
  coarse gate that catches order-of-magnitude mistakes, not a cycle model.
"""

from __future__ import annotations

import ast
from typing import Iterable

from repro.analysis.engine import BaseChecker, Finding, dotted_name

VMEM_BUDGET_BYTES = 16 * 1024 * 1024   # ~16 MB/core (pallas guide)
ASSUMED_DIM = 128                      # fallback for unresolvable minor dims
ASSUMED_LEADING_DIM = 8                # ... and for unresolvable leading dims
ASSUMED_DTYPE_BYTES = 4


def _int_constants(tree: ast.AST) -> dict[str, int]:
    out: dict[str, int] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Constant):
            if isinstance(node.value.value, int):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out[t.id] = node.value.value
    return out


class _FnInfo:
    """Per-function context: local assignments, nested defs, guard names."""

    def __init__(self, fn: ast.FunctionDef):
        self.fn = fn
        self.assigns: dict[str, ast.expr] = {}
        self.defs: dict[str, ast.FunctionDef] = {}
        self.guarded: set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id not in self.assigns:
                        self.assigns[t.id] = node.value
            elif isinstance(node, ast.FunctionDef) and node is not fn:
                self.defs.setdefault(node.name, node)
        self._collect_guards(fn)

    def _collect_guards(self, fn):
        def mod_operands(expr):
            for n in ast.walk(expr):
                if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mod):
                    for side in (n.left, n.right):
                        if isinstance(side, ast.Name):
                            yield side.id

        for node in ast.walk(fn):
            if isinstance(node, ast.While):
                self.guarded.update(mod_operands(node.test))
            elif isinstance(node, ast.Assert):
                self.guarded.update(mod_operands(node.test))
            elif isinstance(node, ast.If) and any(
                    isinstance(s, ast.Raise) for s in node.body):
                self.guarded.update(mod_operands(node.test))
            elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                callee = dotted_name(node.value.func)
                if "pick_block" in callee or "check" in callee:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            self.guarded.add(t.id)
            elif isinstance(node, ast.Call):
                callee = dotted_name(node.func)
                if "check" in callee:
                    for a in node.args:
                        if isinstance(a, ast.Name):
                            self.guarded.add(a.id)


def _resolve(expr: ast.expr, info: _FnInfo, consts: dict[str, int],
             depth: int = 0) -> int | None:
    """Best-effort integer evaluation of a block/shape expression."""
    if depth > 8 or expr is None:
        return None
    if isinstance(expr, ast.Constant):
        return expr.value if isinstance(expr.value, int) else None
    if isinstance(expr, ast.Name):
        if expr.id in consts:
            return consts[expr.id]
        if expr.id in info.assigns:
            return _resolve(info.assigns[expr.id], info, consts, depth + 1)
        return None
    if isinstance(expr, ast.BinOp):
        ln = _resolve(expr.left, info, consts, depth + 1)
        r = _resolve(expr.right, info, consts, depth + 1)
        if ln is None or r is None:
            return None
        try:
            if isinstance(expr.op, ast.FloorDiv):
                return ln // r if r else None
            if isinstance(expr.op, ast.Mult):
                return ln * r
            if isinstance(expr.op, ast.Add):
                return ln + r
            if isinstance(expr.op, ast.Sub):
                return ln - r
        except ZeroDivisionError:
            return None
        return None
    if isinstance(expr, ast.BoolOp) and isinstance(expr.op, ast.Or):
        # `block_m or _pick_block(m, DEFAULT_BM)` — take any resolvable arm
        for v in expr.values:
            got = _resolve(v, info, consts, depth + 1)
            if got is not None:
                return got
        return None
    if isinstance(expr, ast.Call):
        callee = dotted_name(expr.func)
        if "pick_block" in callee and len(expr.args) >= 2:
            return _resolve(expr.args[1], info, consts, depth + 1)
        if callee in ("min", "max") and expr.args:
            vals = [_resolve(a, info, consts, depth + 1) for a in expr.args]
            vals = [v for v in vals if v is not None]
            if vals:
                return min(vals) if callee == "min" else max(vals)
    return None


def _blockspec_parts(call: ast.Call):
    """(shape_tuple_expr, index_map_expr) of a pl.BlockSpec(...) call."""
    shape = call.args[0] if call.args else None
    index_map = call.args[1] if len(call.args) > 1 else None
    for kw in call.keywords:
        if kw.arg == "index_map":
            index_map = kw.value
        elif kw.arg == "block_shape":
            shape = kw.value
    return shape, index_map


def _arity(index_map: ast.expr, info: _FnInfo) -> int | None:
    if isinstance(index_map, ast.Lambda):
        a = index_map.args
        return len(a.posonlyargs) + len(a.args)
    if isinstance(index_map, ast.Name):
        fd = info.defs.get(index_map.id)
        if fd is not None:
            return len(fd.args.posonlyargs) + len(fd.args.args)
        target = info.assigns.get(index_map.id)
        if target is not None and target is not index_map:
            return _arity(target, info)
    return None


def _imap_signature(index_map: ast.expr, info: _FnInfo):
    """(param names, body AST) of an index_map — a Lambda, or a Name bound
    to a lambda/def. None when unresolvable (e.g. built by a factory)."""
    if isinstance(index_map, ast.Lambda):
        a = index_map.args
        return [p.arg for p in (*a.posonlyargs, *a.args)], index_map.body
    if isinstance(index_map, ast.Name):
        fd = info.defs.get(index_map.id)
        if fd is not None:
            a = fd.args
            return [p.arg for p in (*a.posonlyargs, *a.args)], fd
        target = info.assigns.get(index_map.id)
        if target is not None and target is not index_map:
            return _imap_signature(target, info)
    return None


def _spec_list(expr: ast.expr, info: _FnInfo) -> list[ast.Call] | None:
    """Resolve in_specs/out_specs to the list of BlockSpec calls (or a
    single spec as a one-element list). None when unresolvable."""
    if isinstance(expr, ast.Name):
        expr = info.assigns.get(expr.id, expr)
    if isinstance(expr, (ast.List, ast.Tuple)):
        out = []
        for e in expr.elts:
            if isinstance(e, ast.Call) and dotted_name(e.func).endswith("BlockSpec"):
                out.append(e)
            else:
                return None
        return out
    if isinstance(expr, ast.Call) and dotted_name(expr.func).endswith("BlockSpec"):
        return [expr]
    return None


class PallasContractChecker(BaseChecker):
    id = "pallas-contract"
    description = ("pallas_call BlockSpec/grid contracts: index_map arity, "
                   "divisible blocks, out_specs/out_shape cardinality, "
                   "VMEM budget")

    def __init__(self, vmem_budget: int = VMEM_BUDGET_BYTES):
        self.vmem_budget = vmem_budget

    def check_file(self, path, tree, source) -> Iterable[Finding]:
        if "pallas_call" not in source:
            return
        consts = _int_constants(tree)
        for fn in ast.walk(tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            calls = [n for n in ast.walk(fn)
                     if isinstance(n, ast.Call)
                     and dotted_name(n.func).endswith("pallas_call")]
            if not calls:
                continue
            info = _FnInfo(fn)
            for call in calls:
                yield from self._check_call(path, fn, call, info, consts)

    # -- one pallas_call ----------------------------------------------------
    def _check_call(self, path, fn, call, info, consts) -> Iterable[Finding]:
        kws = {kw.arg: kw.value for kw in call.keywords}
        grid_expr = kws.get("grid")
        n_prefetch = 0
        in_specs, out_specs = kws.get("in_specs"), kws.get("out_specs")
        scratch = kws.get("scratch_shapes")

        gs_expr = kws.get("grid_spec")
        if gs_expr is not None:
            if isinstance(gs_expr, ast.Name):
                gs_expr = info.assigns.get(gs_expr.id)
            if isinstance(gs_expr, ast.Call):
                gkws = {kw.arg: kw.value for kw in gs_expr.keywords}
                grid_expr = gkws.get("grid", grid_expr)
                in_specs = gkws.get("in_specs", in_specs)
                out_specs = gkws.get("out_specs", out_specs)
                scratch = gkws.get("scratch_shapes", scratch)
                np_expr = gkws.get("num_scalar_prefetch")
                if isinstance(np_expr, ast.Constant) and isinstance(np_expr.value, int):
                    n_prefetch = np_expr.value

        if isinstance(grid_expr, ast.Name):
            grid_expr = info.assigns.get(grid_expr.id, grid_expr)
        grid_elts: list[ast.expr] | None = None
        if isinstance(grid_expr, (ast.Tuple, ast.List)):
            grid_elts = list(grid_expr.elts)
        elif grid_expr is not None and not isinstance(grid_expr, ast.Name):
            grid_elts = [grid_expr]       # grid=8 scalar form

        # 1. index_map arity -------------------------------------------------
        specs = (_spec_list(in_specs, info) or []) + (_spec_list(out_specs, info) or [])
        if grid_elts is not None:
            want = len(grid_elts) + n_prefetch
            for spec in specs:
                _, imap = _blockspec_parts(spec)
                if imap is None:
                    continue
                got = _arity(imap, info)
                if got is not None and got != want:
                    yield Finding(
                        self.id, path, spec.lineno,
                        f"BlockSpec index_map takes {got} args but the grid "
                        f"rank is {len(grid_elts)}"
                        + (f" + {n_prefetch} scalar-prefetch refs" if n_prefetch else "")
                        + f" = {want} (in `{fn.name}`)", col=spec.col_offset)

        # 1b. declared scalar prefetch must be USED by some index_map --------
        # The prefetch args ride LAST in every index_map signature
        # (index_map(*grid, *prefetch_refs)). Declaring num_scalar_prefetch
        # without any index_map reading the refs means the scalar DMA is
        # dead weight — or, worse, a block-table kernel whose index maps
        # ignore the table and read the same physical blocks at every grid
        # step. Fires only when at least one index_map resolved (factories
        # that build maps dynamically stay out of reach of this rule).
        if n_prefetch > 0 and specs:
            any_resolved = any_used = False
            for spec in specs:
                _, imap = _blockspec_parts(spec)
                if imap is None:
                    continue
                sig = _imap_signature(imap, info)
                if sig is None or len(sig[0]) < n_prefetch:
                    continue
                names, body = sig
                any_resolved = True
                pref = set(names[-n_prefetch:])
                if any(isinstance(n, ast.Name) and n.id in pref
                       for n in ast.walk(body)):
                    any_used = True
                    break
            if any_resolved and not any_used:
                anchor = gs_expr if isinstance(gs_expr, ast.Call) else call
                yield Finding(
                    self.id, path, anchor.lineno,
                    f"num_scalar_prefetch={n_prefetch} declared but no "
                    "index_map reads the prefetched ref(s): the scalar DMA "
                    "is dead weight, or a block-table kernel is ignoring "
                    f"its table (in `{fn.name}`)", col=anchor.col_offset)

        # 2. divisible blocks ------------------------------------------------
        for elt in grid_elts or []:
            if isinstance(elt, ast.Name):
                elt = info.assigns.get(elt.id, elt)
            if isinstance(elt, ast.BinOp) and isinstance(elt.op, ast.FloorDiv):
                div = elt.right
                if isinstance(div, ast.Name) and div.id not in info.guarded:
                    yield Finding(
                        self.id, path, elt.lineno,
                        f"grid dim `{ast.unparse(elt)}` floor-divides by "
                        f"`{div.id}` with no divisibility guard in "
                        f"`{fn.name}`: a non-dividing block silently drops "
                        "the tail rows — validate (raise) or derive the "
                        "block via _pick_block/a % descent",
                        col=elt.col_offset)

        # 3. out_specs/out_shape cardinality ---------------------------------
        out_shape = kws.get("out_shape")
        if isinstance(out_shape, ast.Name):
            out_shape = info.assigns.get(out_shape.id)
        os_specs = _spec_list(out_specs, info)
        if (isinstance(out_shape, (ast.List, ast.Tuple)) and os_specs is not None
                and isinstance(out_specs, (ast.List, ast.Tuple))):
            if len(out_shape.elts) != len(os_specs):
                yield Finding(
                    self.id, path, call.lineno,
                    f"out_shape has {len(out_shape.elts)} entries but "
                    f"out_specs has {len(os_specs)} (in `{fn.name}`)",
                    col=call.col_offset)

        # 4. VMEM footprint estimate -----------------------------------------
        total = 0
        for spec in specs:
            shape, _ = _blockspec_parts(spec)
            total += 2 * self._shape_bytes(shape, info, consts)  # double-buffered
        if isinstance(scratch, ast.Name):
            scratch = info.assigns.get(scratch.id)
        if isinstance(scratch, (ast.List, ast.Tuple)):
            for s in scratch.elts:
                if isinstance(s, ast.Call) and s.args:
                    total += self._shape_bytes(s.args[0], info, consts)
        if total > self.vmem_budget:
            yield Finding(
                self.id, path, call.lineno,
                f"estimated VMEM footprint ~{total / 2**20:.1f} MiB exceeds "
                f"the {self.vmem_budget / 2**20:.0f} MiB budget (blocks "
                f"double-buffered, unknown minor dims assumed {ASSUMED_DIM}) in "
                f"`{fn.name}` — shrink the block sizes",
                severity="warning", col=call.col_offset)

    def _shape_bytes(self, shape, info, consts) -> int:
        if isinstance(shape, ast.Name):
            shape = info.assigns.get(shape.id, shape)
        if not isinstance(shape, (ast.Tuple, ast.List)):
            return 0
        n = 1
        minor = len(shape.elts) - 2
        for k, d in enumerate(shape.elts):
            v = _resolve(d, info, consts)
            if v is None or v <= 0:
                # a whole (sublane, lane) tile in the minor dims; a leading
                # dim of a multi-dim block is a head or row count, not a tile
                v = ASSUMED_DIM if k >= minor else ASSUMED_LEADING_DIM
            n *= v
        return n * ASSUMED_DTYPE_BYTES
