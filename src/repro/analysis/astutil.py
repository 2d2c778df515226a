"""Stdlib-``ast`` plumbing shared by the analysis rule modules.

Pure syntax: nothing here imports jax or executes repo code.  The main
jobs are (a) extracting ``pl.pallas_call`` sites — grid, scalar-prefetch
count, BlockSpec index-map arities, scratch dtypes, kernel body name —
through the local-name indirections the kernel modules actually use
(``grid = (...)``, ``kernel = functools.partial(_kernel, ...)``,
``grid_spec = pltpu.PrefetchScalarGridSpec(...)``), and (b) normalized
function-body comparison for the intentional-duplicate rule (OR03),
which canonicalizes ``pl.cdiv(a, b)`` to ``-(-a // b)`` and strips
docstrings so the two legal spellings of ceil-div compare equal.
"""
from __future__ import annotations

import ast
import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple


@dataclasses.dataclass
class SourceFile:
    """A parsed file: path, raw text, module AST."""

    path: Path
    text: str
    tree: ast.Module


def load(path) -> SourceFile:
    """Parse ``path`` into a :class:`SourceFile`."""
    p = Path(path)
    text = p.read_text()
    return SourceFile(path=p, text=text, tree=ast.parse(text))


def top_level_functions(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
    """Module-level function definitions by name (classes excluded)."""
    return {n.name: n for n in tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


def local_env(fn: ast.AST) -> Dict[str, ast.expr]:
    """name -> value for simple single-target assignments under ``fn``.

    Shallow by design: used to chase the one-hop indirections
    (``grid``/``grid_spec``/``kernel``/dtype aliases) kernel entry
    functions introduce, not to evaluate code.
    """
    env: Dict[str, ast.expr] = {}
    for node in ast.walk(fn):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            env[node.targets[0].id] = node.value
    return env


def resolve(expr: Optional[ast.expr], env: Dict[str, ast.expr],
            depth: int = 4) -> Optional[ast.expr]:
    """Follow Name -> assigned-value links up to ``depth`` hops."""
    while (depth and isinstance(expr, ast.Name) and expr.id in env
           and env[expr.id] is not expr):
        expr = env[expr.id]
        depth -= 1
    return expr


def _called_name(func: ast.expr) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


@dataclasses.dataclass
class BlockSpecInfo:
    """One BlockSpec at a pallas_call site: index-map arity + location.

    ``unblocked`` marks a spec that names only a ``memory_space`` (an
    operand the kernel reaches by DMA): it has no index map by design.
    """

    arity: Optional[int]
    lineno: int
    unblocked: bool = False


@dataclasses.dataclass
class PallasSite:
    """One ``pl.pallas_call`` site, structurally decomposed."""

    entry: str
    entry_node: ast.FunctionDef
    lineno: int
    kernel_body: Optional[str]
    grid: List[ast.expr]
    grid_parsed: bool
    scalar_prefetch: int
    in_specs: List[BlockSpecInfo]
    out_specs: List[BlockSpecInfo]
    scratch_dtypes: List[Optional[str]]


def _spec_list(expr: Optional[ast.expr],
               env: Dict[str, ast.expr]) -> List[ast.expr]:
    expr = resolve(expr, env)
    if expr is None:
        return []
    if isinstance(expr, (ast.List, ast.Tuple)):
        return list(expr.elts)
    return [expr]


def _block_spec_info(expr: ast.expr) -> BlockSpecInfo:
    arity: Optional[int] = None
    unblocked = False
    if isinstance(expr, ast.Call) and _called_name(expr.func) == "BlockSpec":
        index_map: Optional[ast.expr] = None
        if len(expr.args) >= 2:
            index_map = expr.args[1]
        kws = {kw.arg for kw in expr.keywords}
        for kw in expr.keywords:
            if kw.arg == "index_map":
                index_map = kw.value
        if isinstance(index_map, ast.Lambda):
            arity = len(index_map.args.args)
        unblocked = (index_map is None and not expr.args
                     and kws == {"memory_space"})
    return BlockSpecInfo(arity=arity, lineno=expr.lineno,
                         unblocked=unblocked)


def _scratch_dtypes(expr: Optional[ast.expr],
                    env: Dict[str, ast.expr]) -> List[Optional[str]]:
    out: List[Optional[str]] = []
    for item in _spec_list(expr, env):
        if (isinstance(item, ast.Call)
                and isinstance(item.func, ast.Attribute)
                and _called_name(item.func.value) == "SemaphoreType"):
            continue       # a DMA/barrier semaphore is not a buffer
        dtype: Optional[str] = None
        if (isinstance(item, ast.Call) and len(item.args) >= 2
                and _called_name(item.func) in ("VMEM", "SMEM", "ANY")):
            val = resolve(item.args[1], env)
            if isinstance(val, ast.Attribute):
                dtype = val.attr
            elif isinstance(val, ast.Name):
                dtype = val.id
        out.append(dtype)
    return out


def _kernel_body_name(expr: Optional[ast.expr],
                      env: Dict[str, ast.expr]) -> Optional[str]:
    expr = resolve(expr, env)
    if isinstance(expr, ast.Call) and expr.args:
        # functools.partial(_kernel, ...) -> _kernel
        expr = resolve(expr.args[0], env)
    if isinstance(expr, ast.Name):
        return expr.id
    return None


def _extract_site(entry: ast.FunctionDef, call: ast.Call,
                  env: Dict[str, ast.expr]) -> PallasSite:
    kw = {k.arg: k.value for k in call.keywords if k.arg}
    grid_expr = kw.get("grid")
    prefetch = 0
    in_specs_expr = kw.get("in_specs")
    out_specs_expr = kw.get("out_specs")
    scratch_expr = kw.get("scratch_shapes")

    grid_spec = resolve(kw.get("grid_spec"), env)
    if isinstance(grid_spec, ast.Call):
        gs_kw = {k.arg: k.value for k in grid_spec.keywords if k.arg}
        grid_expr = gs_kw.get("grid", grid_expr)
        in_specs_expr = gs_kw.get("in_specs", in_specs_expr)
        out_specs_expr = gs_kw.get("out_specs", out_specs_expr)
        scratch_expr = gs_kw.get("scratch_shapes", scratch_expr)
        npf = gs_kw.get("num_scalar_prefetch")
        if isinstance(npf, ast.Constant) and isinstance(npf.value, int):
            prefetch = npf.value

    grid_expr = resolve(grid_expr, env)
    if isinstance(grid_expr, (ast.Tuple, ast.List)):
        grid, parsed = list(grid_expr.elts), True
    elif grid_expr is not None:
        grid, parsed = [grid_expr], True
    else:
        grid, parsed = [], False

    return PallasSite(
        entry=entry.name,
        entry_node=entry,
        lineno=call.lineno,
        kernel_body=_kernel_body_name(
            call.args[0] if call.args else None, env),
        grid=grid,
        grid_parsed=parsed,
        scalar_prefetch=prefetch,
        in_specs=[_block_spec_info(e)
                  for e in _spec_list(in_specs_expr, env)],
        out_specs=[_block_spec_info(e)
                   for e in _spec_list(out_specs_expr, env)],
        scratch_dtypes=_scratch_dtypes(scratch_expr, env),
    )


def find_pallas_sites(tree: ast.Module) -> List[PallasSite]:
    """Every ``pl.pallas_call`` site under a top-level function."""
    sites = []
    for fn in top_level_functions(tree).values():
        env = local_env(fn)
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                sites.append(_extract_site(fn, node, env))
    return sites


def grid_axis_kind(expr: ast.expr) -> str:
    """'cdiv' | 'floordiv' | 'other' for one grid-axis expression."""
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Call) and _called_name(sub.func) == "cdiv":
            return "cdiv"
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.FloorDiv):
        return "floordiv"
    return "other"


def has_mod_assert(fn: ast.FunctionDef) -> bool:
    """True when ``fn`` contains an assert over a ``%`` expression."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Assert):
            for sub in ast.walk(node.test):
                if isinstance(sub, ast.BinOp) and isinstance(sub.op,
                                                             ast.Mod):
                    return True
    return False


def referenced_names(fn: ast.AST) -> Set[str]:
    """Every name referenced under ``fn``: bare names as ``name``,
    one-level attribute access as ``base.attr`` (plus bare ``attr``
    for deeper chains).  Nested defs/lambdas fold in automatically."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Name):
            out.add(node.id)
        elif isinstance(node, ast.Attribute):
            if isinstance(node.value, ast.Name):
                out.add(f"{node.value.id}.{node.attr}")
            else:
                out.add(node.attr)
    return out


def writes_raw(fn: ast.AST) -> bool:
    """True when ``fn`` performs a raw durable write: ``open`` in a
    writable mode, ``os.replace``, or ``np.savez*``/``np.save``."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if (isinstance(f, ast.Name) and f.id == "open"
                and len(node.args) >= 2):
            mode = node.args[1]
            if (isinstance(mode, ast.Constant)
                    and isinstance(mode.value, str)
                    and any(c in mode.value for c in "wa+x")):
                return True
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            if f.value.id == "os" and f.attr == "replace":
                return True
            if f.value.id in ("np", "numpy") and (
                    f.attr.startswith("savez") or f.attr == "save"):
                return True
    return False


class _CdivNormalizer(ast.NodeTransformer):
    """Rewrite ``cdiv(a, b)`` / ``pl.cdiv(a, b)`` to ``-(-a // b)``."""

    def visit_Call(self, node: ast.Call) -> ast.expr:
        self.generic_visit(node)
        if (_called_name(node.func) == "cdiv" and len(node.args) == 2
                and not node.keywords):
            a, b = node.args
            return ast.UnaryOp(
                op=ast.USub(),
                operand=ast.BinOp(
                    left=ast.UnaryOp(op=ast.USub(), operand=a),
                    op=ast.FloorDiv(), right=b))
        return node


def normalized_body_dump(fn: ast.FunctionDef) -> str:
    """Deterministic dump of ``fn``'s body, docstring stripped and
    ceil-div spellings canonicalized — signatures are NOT compared, so
    duplicates may legally differ in defaults/annotations (OR03)."""
    body = list(fn.body)
    if (body and isinstance(body[0], ast.Expr)
            and isinstance(body[0].value, ast.Constant)
            and isinstance(body[0].value.value, str)):
        body = body[1:]
    module = ast.Module(body=body, type_ignores=[])
    module = _CdivNormalizer().visit(module)
    return ast.dump(module, annotate_fields=False)


@dataclasses.dataclass
class FuncInfo:
    """A function at module or class scope, for call-graph rules."""

    qualname: str
    node: ast.FunctionDef
    cls: Optional[str] = None


def collect_functions(tree: ast.Module) -> Dict[str, FuncInfo]:
    """Module-level functions plus class methods (``Cls.meth``).

    Nested defs are folded into their enclosing function by the
    ``ast.walk``-based predicates, so the graph stays at this
    granularity on purpose.
    """
    out: Dict[str, FuncInfo] = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out[node.name] = FuncInfo(qualname=node.name, node=node)
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = f"{node.name}.{sub.name}"
                    out[q] = FuncInfo(qualname=q, node=sub, cls=node.name)
    return out


def call_edges(funcs: Dict[str, FuncInfo]) -> Dict[str, Set[str]]:
    """qualname -> qualnames it references (module-local resolution:
    bare names to module functions, ``self.x`` to same-class methods)."""
    edges: Dict[str, Set[str]] = {}
    module_level = {q for q, f in funcs.items() if f.cls is None}
    for q, info in funcs.items():
        refs = referenced_names(info.node)
        tgt: Set[str] = set()
        for r in refs:
            if r in module_level:
                tgt.add(r)
            if r.startswith("self."):
                meth = f"{info.cls}.{r[5:]}"
                if meth in funcs:
                    tgt.add(meth)
        tgt.discard(q)
        edges[q] = tgt
    return edges


def transitive_closure(start: str,
                       edges: Dict[str, Set[str]]) -> Set[str]:
    """Every qualname reachable from ``start`` (inclusive)."""
    seen = {start}
    frontier = [start]
    while frontier:
        cur = frontier.pop()
        for nxt in edges.get(cur, ()):
            if nxt not in seen:
                seen.add(nxt)
                frontier.append(nxt)
    return seen


def module_for(root: Path, path: Path) -> str:
    """Dotted module name of ``path`` under ``root/src``."""
    rel = path.resolve().relative_to((root / "src").resolve())
    return ".".join(rel.with_suffix("").parts)


def path_for(root: Path, module: str) -> Path:
    """Source path of dotted ``module`` under ``root/src``."""
    return root / "src" / Path(*module.split(".")).with_suffix(".py")
