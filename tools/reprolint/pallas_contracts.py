"""Layer 2: Pallas kernel contract checker.

The repo's kernels live as ``kernels/<name>/{kernel,ref,ops}.py`` triplets:
the Pallas TPU kernel, a pure-jnp oracle it must stay bit-comparable with,
and the shape-generic jitted wrapper.  The runtime tests compare numerics;
this checker verifies the *structural* contracts without executing anything
on a TPU:

- ``pallas-triplet``       — all three files exist;
- ``pallas-interpret``     — every ``pallas_call`` threads an ``interpret``
  parameter (compiled on a TPU, interpreted on the CPU that CI and the
  tests run on; ``repro.kernels.interpret_mode`` picks);
- ``pallas-lane``          — every resolvable trailing BlockSpec tile dim
  is 1 (scalar operand) or a multiple of the 128-wide TPU lane;
- ``pallas-divisibility``  — the wrapper guarding a tiled grid asserts the
  padded dims divide by the tile (``x % block == 0`` style);
- ``pallas-vmem``          — the per-program VMEM footprint estimated from
  the default tile sizes (BlockSpec tiles + scratch, f32) fits the budget;
- ``kernel-ref-signature`` — some public oracle in ref.py is call-compatible
  with the kernel entry (required positionals form a prefix of the kernel's
  parameters and every oracle parameter exists on the kernel).

Resolution is static: tile dims are resolved through literal ints, module
constants, and keyword-only defaults; unresolvable dims (e.g. a head dim
taken from the input shape) are skipped for the lane check and assumed
``DEFAULT_UNRESOLVED_DIM`` wide for the VMEM estimate.
"""

from __future__ import annotations

import ast
from pathlib import Path

from tools.reprolint.engine import Finding

LANE = 128
DEFAULT_UNRESOLVED_DIM = 128          # assumed width of e.g. a head dim
BYTES_PER_ELEMENT = 4                 # kernels compute in f32
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024


def _attr_chain(node: ast.AST) -> list[str]:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return []


def _module_constants(tree: ast.Module) -> dict[str, int]:
    consts: dict[str, int] = {}
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, int)):
            consts[node.targets[0].id] = node.value.value
    return consts


def _param_defaults(fn: ast.FunctionDef, consts: dict[str, int]) -> dict[str, int]:
    """Resolvable integer defaults of a function's parameters."""
    out: dict[str, int] = {}
    args = fn.args
    pos = args.posonlyargs + args.args
    for a, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
        v = _resolve(d, consts, {})
        if v is not None:
            out[a.arg] = v
    for a, d in zip(args.kwonlyargs, args.kw_defaults):
        if d is None:
            continue
        v = _resolve(d, consts, {})
        if v is not None:
            out[a.arg] = v
    return out


def _resolve(node: ast.AST, consts: dict[str, int],
             defaults: dict[str, int]) -> int | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.Name):
        if node.id in consts:
            return consts[node.id]
        return defaults.get(node.id)
    return None


def _params_of(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    return [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]


def _required_positionals(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    pos = a.posonlyargs + a.args
    n_required = len(pos) - len(a.defaults)
    return [p.arg for p in pos[:n_required]]


def _has_mod_assert(fn: ast.FunctionDef) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Assert):
            for sub in ast.walk(node.test):
                if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Mod):
                    return True
    return False


def _block_shapes(call: ast.Call):
    """(lineno, [dim nodes]) for every BlockSpec tuple in a pallas_call."""
    for node in ast.walk(call):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if not (chain and chain[-1] == "BlockSpec"):
            continue
        if node.args and isinstance(node.args[0], ast.Tuple):
            yield node.lineno, node.args[0].elts


def _scratch_shapes(call: ast.Call):
    """[dim nodes] per VMEM scratch declaration in a pallas_call."""
    for kw in call.keywords:
        if kw.arg != "scratch_shapes":
            continue
        for node in ast.walk(kw.value):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if chain and chain[-1] in ("VMEM", "MemoryRef"):
                if node.args and isinstance(node.args[0], ast.Tuple):
                    yield node.args[0].elts


def check_kernel_module(path: Path, rel: str, *,
                        vmem_budget: int = DEFAULT_VMEM_BUDGET) -> list[Finding]:
    """Contracts on one kernel.py: interpret, lane, divisibility, VMEM."""
    source = path.read_text()
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding("pallas-interpret", rel, e.lineno or 0,
                        f"kernel module does not parse: {e.msg}")]
    consts = _module_constants(tree)
    out: list[Finding] = []

    for fn in [n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)]:
        calls = [c for c in ast.walk(fn) if isinstance(c, ast.Call)
                 and (ch := _attr_chain(c.func)) and ch[-1] == "pallas_call"]
        if not calls:
            continue
        defaults = _param_defaults(fn, consts)
        if not _has_mod_assert(fn):
            out.append(Finding(
                "pallas-divisibility", rel, fn.lineno,
                f"{fn.name!r} wraps a pallas_call but never asserts that "
                f"the tiled dims divide by the tile (x % block == 0); an "
                f"indivisible input would silently read out of bounds"))
        for call in calls:
            if not any(kw.arg == "interpret" for kw in call.keywords):
                out.append(Finding(
                    "pallas-interpret", rel, call.lineno,
                    f"pallas_call in {fn.name!r} has no interpret= "
                    f"parameter: the kernel cannot run off the TPU "
                    f"(CI and the tests run it in the Pallas interpreter)"))
            vmem_bytes = 0
            for lineno, dims in _block_shapes(call):
                resolved = [_resolve(d, consts, defaults) for d in dims]
                trailing = resolved[-1] if resolved else None
                if trailing is not None and trailing != 1 \
                        and trailing % LANE != 0:
                    out.append(Finding(
                        "pallas-lane", rel, lineno,
                        f"trailing BlockSpec tile dim {trailing} in "
                        f"{fn.name!r} is neither 1 (scalar) nor a multiple "
                        f"of the {LANE}-wide TPU lane"))
                n = 1
                for r in resolved:
                    n *= r if r is not None else DEFAULT_UNRESOLVED_DIM
                vmem_bytes += n * BYTES_PER_ELEMENT
            for dims in _scratch_shapes(call):
                n = 1
                for d in dims:
                    r = _resolve(d, consts, defaults)
                    n *= r if r is not None else DEFAULT_UNRESOLVED_DIM
                vmem_bytes += n * BYTES_PER_ELEMENT
            if vmem_bytes > vmem_budget:
                out.append(Finding(
                    "pallas-vmem", rel, call.lineno,
                    f"estimated VMEM footprint of {fn.name!r} at default "
                    f"tiles is {vmem_bytes / 2**20:.1f} MiB > budget "
                    f"{vmem_budget / 2**20:.1f} MiB (blocks + scratch, "
                    f"f32, unresolved dims assumed "
                    f"{DEFAULT_UNRESOLVED_DIM})"))
    return out


def check_kernel_ref_signatures(kernel_path: Path, ref_path: Path,
                                rel: str) -> list[Finding]:
    """Some oracle in ref.py must be call-compatible with the kernel entry."""
    ktree = ast.parse(kernel_path.read_text())
    rtree = ast.parse(ref_path.read_text())
    entries = []
    for fn in [n for n in ast.walk(ktree) if isinstance(n, ast.FunctionDef)]:
        if any((ch := _attr_chain(c.func)) and ch[-1] == "pallas_call"
               for c in ast.walk(fn) if isinstance(c, ast.Call)):
            entries.append(fn)
    refs = [n for n in rtree.body if isinstance(n, ast.FunctionDef)
            and not n.name.startswith("_")]
    if not entries or not refs:
        return [Finding("kernel-ref-signature", rel, 0,
                        "could not pair a pallas_call entry in kernel.py "
                        "with a public oracle in ref.py")]
    out = []
    for entry in entries:
        kparams = _params_of(entry)
        ok = False
        for ref in refs:
            req = _required_positionals(ref)
            if (req and req == kparams[:len(req)]
                    and set(_params_of(ref)) <= set(kparams)):
                ok = True
                break
        if not ok:
            out.append(Finding(
                "kernel-ref-signature", rel, entry.lineno,
                f"no public oracle in ref.py is call-compatible with "
                f"kernel entry {entry.name}({', '.join(kparams)}): the "
                f"oracle's required positionals must prefix the kernel's "
                f"parameters so the bit-comparability tests can drive "
                f"both with one argument list"))
    return out


def check_kernels_root(root: Path, repo_root: Path, *,
                       vmem_budget: int = DEFAULT_VMEM_BUDGET) -> list[dict]:
    """All pallas-layer checks for one kernels/ directory.

    Returns ``[{path, findings}]`` so the caller can apply each file's own
    suppressions."""
    results = []
    for pkg in sorted(p for p in root.iterdir() if p.is_dir()):
        files = {n: pkg / f"{n}.py" for n in ("kernel", "ref", "ops")}
        missing = [n for n, p in files.items() if not p.exists()]
        rel_pkg = str(pkg.relative_to(repo_root)) if pkg.is_relative_to(
            repo_root) else str(pkg)
        if missing:
            if len(missing) == 3:
                continue                     # not a kernel package at all
            results.append({"path": None, "findings": [Finding(
                "pallas-triplet", rel_pkg, 0,
                f"kernel package is missing {', '.join(sorted(missing))}: "
                f"every kernel ships as a kernel/ref/ops triplet so the "
                f"oracle and wrapper cannot drift away")]})
            continue
        krel = str(files["kernel"].relative_to(repo_root)) \
            if files["kernel"].is_relative_to(repo_root) else str(files["kernel"])
        fnd = check_kernel_module(files["kernel"], krel,
                                  vmem_budget=vmem_budget)
        fnd += check_kernel_ref_signatures(files["kernel"], files["ref"], krel)
        results.append({"path": files["kernel"], "findings": fnd})
    return results
