"""Rule family (a): kernel contracts vs pallas_call sites (KC01–KC08).

Each check is a pure function over a parsed kernel module plus the
contracts registered for it, so the seeded-violation corpus
(tests/analysis_corpus/) can drive single files through the same code
path the repo-level linter uses.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Optional

from repro.analysis import astutil
from repro.analysis.contracts import OOB_WRITE, KernelContract
from repro.analysis.report import Finding
from repro.analysis.vmem import PIPELINE_BUFFERS, VMEM_BUDGET_BYTES, fits_vmem

# Accumulation dtypes a kernel dot may declare (KC05): int8 operands
# accumulate exactly in int32, everything else in f32.
DOT_ACCUM_DTYPES = ("float32", "int32")

# Scratch accumulator dtypes allowed by KC08.
SCRATCH_DTYPES = ("float32", "int32")

# Callables whose results are approximate on TPU (or contraction-order
# dependent) and therefore banned from exact-parity kernel bodies
# (KC07) — the PR 7 exp2-scale bug class.
APPROX_TRANSCENDENTALS = frozenset({
    "exp", "exp2", "expm1", "log", "log2", "log1p", "logsumexp",
    "tanh", "sigmoid", "softmax", "erf", "erfc", "rsqrt",
})


def _f(rule: str, path: Path, line: int, msg: str) -> Finding:
    return Finding(rule=rule, path=str(path), line=line, message=msg)


def _check_grid_arity(site: astutil.PallasSite, c: KernelContract,
                      path: Path) -> List[Finding]:
    out: List[Finding] = []
    if not site.grid_parsed:
        out.append(_f("KC02", path, site.lineno,
                      f"{site.entry}: could not determine the grid "
                      "statically"))
        return out
    if len(site.grid) != c.grid_rank:
        out.append(_f("KC02", path, site.lineno,
                      f"{site.entry}: grid rank {len(site.grid)} != "
                      f"contract grid_rank {c.grid_rank}"))
    if site.scalar_prefetch != c.scalar_prefetch:
        out.append(_f("KC02", path, site.lineno,
                      f"{site.entry}: num_scalar_prefetch "
                      f"{site.scalar_prefetch} != contract "
                      f"scalar_prefetch {c.scalar_prefetch}"))
    want = len(site.grid) + site.scalar_prefetch
    for kind, specs in (("in_specs", site.in_specs),
                        ("out_specs", site.out_specs)):
        for i, spec in enumerate(specs):
            if spec.unblocked:
                continue
            if spec.arity is None:
                out.append(_f("KC02", path, spec.lineno,
                              f"{site.entry}: {kind}[{i}] has no "
                              "statically-visible index-map lambda"))
            elif spec.arity != want:
                out.append(_f("KC02", path, spec.lineno,
                              f"{site.entry}: {kind}[{i}] index map "
                              f"takes {spec.arity} args, grid rank + "
                              f"scalar prefetch = {want}"))
    return out


def _check_vmem(site: astutil.PallasSite, c: KernelContract,
                path: Path) -> List[Finding]:
    if c.vmem_model is None or c.max_shapes is None:
        return [_f("KC03", path, site.lineno,
                   f"{site.entry}: contract declares no VMEM model / "
                   "max shapes")]
    try:
        used = c.vmem_model(**dict(c.max_shapes))
    except TypeError as e:
        return [_f("KC03", path, site.lineno,
                   f"{site.entry}: vmem_model does not accept the "
                   f"declared max_shapes ({e})")]
    if not fits_vmem(used):
        return [_f("KC03", path, site.lineno,
                   f"{site.entry}: model gives {used} bytes at max "
                   f"shapes {dict(c.max_shapes)}, x{PIPELINE_BUFFERS} "
                   f"pipeline buffers > budget {VMEM_BUDGET_BYTES}")]
    return []


def _check_tails(site: astutil.PallasSite, c: KernelContract,
                 body: Optional[ast.FunctionDef], src: str,
                 path: Path) -> List[Finding]:
    out: List[Finding] = []
    body_src = ast.get_source_segment(src, body) if body is not None else ""
    squashed = "".join((body_src or "").split())
    kinds = [astutil.grid_axis_kind(g) for g in site.grid]
    for axis, kind in enumerate(kinds):
        if kind == "cdiv":
            marker = dict(c.tail).get(axis)
            if marker is None:
                out.append(_f("KC04", path, site.lineno,
                              f"{site.entry}: cdiv grid axis {axis} has "
                              "no declared tail-mask entry"))
            elif marker != OOB_WRITE and \
                    "".join(marker.split()) not in squashed:
                out.append(_f("KC04", path, site.lineno,
                              f"{site.entry}: declared tail marker "
                              f"{marker!r} for axis {axis} not found in "
                              f"kernel body {c.body!r}"))
        elif kind == "floordiv" and not c.divisible:
            out.append(_f("KC04", path, site.lineno,
                          f"{site.entry}: exact-division grid axis "
                          f"{axis} but the contract does not declare "
                          "divisible=True"))
    for axis in dict(c.tail):
        if axis >= len(kinds) or kinds[axis] != "cdiv":
            out.append(_f("KC04", path, site.lineno,
                          f"{site.entry}: stale tail entry for axis "
                          f"{axis} (not a cdiv grid axis)"))
    if c.divisible and not astutil.has_mod_assert(site.entry_node):
        out.append(_f("KC04", path, site.lineno,
                      f"{site.entry}: divisible=True but no "
                      "divisibility assert (`%`) in the entry"))
    return out


def _check_dots(c: KernelContract, body: ast.FunctionDef,
                path: Path) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(body):
        if isinstance(node, ast.BinOp) and isinstance(node.op,
                                                      ast.MatMult):
            out.append(_f("KC05", path, node.lineno,
                          f"{c.body}: `@` matmul in a kernel body has "
                          "no explicit accumulation dtype — use "
                          "dot_general(preferred_element_type=...)"))
        if not isinstance(node, ast.Call):
            continue
        name = node.func.attr if isinstance(node.func, ast.Attribute) \
            else getattr(node.func, "id", None)
        if name not in ("dot_general", "dot"):
            continue
        pet = None
        for kw in node.keywords:
            if kw.arg == "preferred_element_type":
                pet = kw.value
        if pet is None:
            out.append(_f("KC05", path, node.lineno,
                          f"{c.body}: {name} without "
                          "preferred_element_type"))
        else:
            dtype = pet.attr if isinstance(pet, ast.Attribute) \
                else getattr(pet, "id", None)
            if dtype not in DOT_ACCUM_DTYPES:
                out.append(_f("KC05", path, node.lineno,
                              f"{c.body}: {name} accumulates in "
                              f"{dtype!r}, expected one of "
                              f"{DOT_ACCUM_DTYPES}"))
    return out


def _check_transcendentals(c: KernelContract, body: ast.FunctionDef,
                           path: Path) -> List[Finding]:
    if not c.exact_parity:
        return []
    out: List[Finding] = []
    for node in ast.walk(body):
        if not isinstance(node, ast.Call):
            continue
        name = node.func.attr if isinstance(node.func, ast.Attribute) \
            else getattr(node.func, "id", None)
        if name in APPROX_TRANSCENDENTALS:
            out.append(_f("KC07", path, node.lineno,
                          f"{c.body}: approximate transcendental "
                          f"`{name}` in an exact-parity kernel body"))
    return out


def _check_scratch(site: astutil.PallasSite, c: KernelContract,
                   path: Path) -> List[Finding]:
    out: List[Finding] = []
    got = tuple(site.scratch_dtypes)
    if len(got) != len(c.accumulators):
        out.append(_f("KC08", path, site.lineno,
                      f"{site.entry}: {len(got)} scratch buffers, "
                      f"contract declares {len(c.accumulators)}"))
        return out
    for i, (g, want) in enumerate(zip(got, c.accumulators)):
        if g is None:
            out.append(_f("KC08", path, site.lineno,
                          f"{site.entry}: scratch[{i}] dtype not "
                          "statically resolvable"))
        elif g != want:
            out.append(_f("KC08", path, site.lineno,
                          f"{site.entry}: scratch[{i}] is {g}, "
                          f"contract declares {want}"))
        elif want not in SCRATCH_DTYPES:
            out.append(_f("KC08", path, site.lineno,
                          f"{site.entry}: scratch[{i}] dtype {want} is "
                          f"not an allowed accumulator ({SCRATCH_DTYPES})"))
    return out


def check_kernel_file(path: Path, tree: ast.Module, src: str,
                      file_contracts: Dict[str, KernelContract]
                      ) -> List[Finding]:
    """All KC rules over one parsed kernel file.

    ``file_contracts`` maps entry-function name -> contract for this
    file; entries without a contract are KC01, contracts without a
    surviving site are KC01 (stale), and KC06 (no f64) applies to the
    whole module.
    """
    findings: List[Finding] = []
    funcs = astutil.top_level_functions(tree)
    seen = set()
    for site in astutil.find_pallas_sites(tree):
        c = file_contracts.get(site.entry)
        if c is None:
            findings.append(_f("KC01", path, site.lineno,
                               f"pallas_call in `{site.entry}` has no "
                               "registered KernelContract"))
            continue
        seen.add(site.entry)
        body = funcs.get(c.body)
        if body is None:
            findings.append(_f("KC01", path, site.lineno,
                               f"{site.entry}: contract body "
                               f"{c.body!r} not found in module"))
            continue
        if site.kernel_body is not None and site.kernel_body != c.body:
            findings.append(_f("KC01", path, site.lineno,
                               f"{site.entry}: pallas_call body "
                               f"{site.kernel_body!r} != contract body "
                               f"{c.body!r}"))
        findings += _check_grid_arity(site, c, path)
        findings += _check_vmem(site, c, path)
        findings += _check_tails(site, c, body, src, path)
        findings += _check_dots(c, body, path)
        findings += _check_transcendentals(c, body, path)
        findings += _check_scratch(site, c, path)
    for entry, c in file_contracts.items():
        if entry not in seen:
            findings.append(_f("KC01", path, 1,
                               f"contract for `{entry}` has no "
                               "surviving pallas_call site"))
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in (
                "float64", "f64"):
            findings.append(_f("KC06", path, node.lineno,
                               "float64 reference in a kernel module"))
    return findings


def check_kernels(root: Path, registry) -> List[Finding]:
    """KC rules over every file in ``src/repro/kernels/``."""
    findings: List[Finding] = []
    kdir = root / "src" / "repro" / "kernels"
    by_module: Dict[str, Dict[str, KernelContract]] = {}
    for (module, entry), c in registry.items():
        by_module.setdefault(module, {})[entry] = c
    for path in sorted(kdir.glob("*.py")):
        sf = astutil.load(path)
        module = astutil.module_for(root, path)
        findings += check_kernel_file(path, sf.tree, sf.text,
                                      by_module.get(module, {}))
    return findings
