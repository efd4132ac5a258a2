"""Pallas resource pass (SPF30x).

Statically evaluates every ``pl.pallas_call`` in the kernel modules at
the spec's reference serving shape: BlockSpec block shapes × operand
dtypes × grid → per-kernel VMEM footprint, doubled for Pallas's
double-buffered pipelining.  Scalar-prefetch operands
(``PrefetchScalarGridSpec.num_scalar_prefetch``) live in SMEM and are
excluded — they never appear in ``in_specs``.

Shape symbols are resolved from, in order: the enclosing wrapper's
straight-line integer assignments (``t = p_n // block_p``), its keyword
parameter defaults (``block_q=128``), and the spec bindings.  A symbol
none of those cover is SPF304; a site whose structure the evaluator
does not recognize at all is SPF303 — either way the site is visibly
NOT covered, never silently skipped.

Also flags interpret-only constructs inside kernel bodies (SPF302):
``print``/``breakpoint`` and host ``np.*`` calls trace fine under
``interpret=True`` but have no TPU lowering.
"""
from __future__ import annotations

import ast
import dataclasses

from repro.analysis.common import Finding, Module, enclosing_symbol
from repro.analysis.config import VmemSpec

_DTYPE_BYTES = {
    "float32": 4, "f32": 4, "int32": 4, "i32": 4,
    "bfloat16": 2, "bf16": 2, "float16": 2,
    "int8": 1, "uint8": 1, "bool": 1,
    "float64": 8, "int64": 8,
}


class Unresolved(Exception):
    def __init__(self, symbol: str):
        self.symbol = symbol
        super().__init__(symbol)


class Unanalyzable(Exception):
    pass


@dataclasses.dataclass
class Operand:
    role: str               # "in" | "out"
    shape: tuple[int, ...]
    dtype: str
    nbytes: int


@dataclasses.dataclass
class KernelReport:
    module: str
    file: str
    line: int
    wrapper: str            # enclosing wrapper function qualname
    grid: tuple[int, ...]
    operands: list[Operand]
    vmem_bytes: int         # sum(block bytes) * 2 (double buffering)

    def as_dict(self) -> dict:
        return {
            "kernel": self.wrapper,
            "module": self.module,
            "file": self.file,
            "line": self.line,
            "grid": list(self.grid),
            "operands": [
                {"role": o.role, "shape": list(o.shape), "dtype": o.dtype,
                 "bytes": o.nbytes}
                for o in self.operands
            ],
            "vmem_bytes": self.vmem_bytes,
            "vmem_mib": round(self.vmem_bytes / (1024 * 1024), 3),
        }


# ---------------------------------------------------------------------------
# Symbol environment + expression evaluation
# ---------------------------------------------------------------------------

def _env_for(fn: ast.AST | None, bindings: dict) -> dict[str, int]:
    env = dict(bindings)
    if fn is None:
        return env
    # keyword parameter defaults (block_q=128, ...)
    args = fn.args
    for a, d in zip(args.args[len(args.args) - len(args.defaults):],
                    args.defaults):
        if isinstance(d, ast.Constant) and isinstance(d.value, int):
            env.setdefault(a.arg, d.value)
    for a, d in zip(args.kwonlyargs, args.kw_defaults):
        if d is not None and isinstance(d, ast.Constant) and \
                isinstance(d.value, int):
            env.setdefault(a.arg, d.value)
    # straight-line integer assignments (t = p_n // block_p)
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            try:
                env[node.targets[0].id] = _eval(node.value, env)
            except (Unresolved, Unanalyzable):
                pass
    return env


def _eval(node: ast.AST, env: dict[str, int]) -> int:
    if isinstance(node, ast.Constant):
        if isinstance(node.value, int):
            return node.value
        raise Unanalyzable
    if isinstance(node, ast.Name):
        if node.id in env:
            return int(env[node.id])
        raise Unresolved(node.id)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval(node.operand, env)
    if isinstance(node, ast.BinOp):
        a, b = _eval(node.left, env), _eval(node.right, env)
        if isinstance(node.op, ast.Add):
            return a + b
        if isinstance(node.op, ast.Sub):
            return a - b
        if isinstance(node.op, ast.Mult):
            return a * b
        if isinstance(node.op, (ast.FloorDiv, ast.Div)):
            return a // b
        if isinstance(node.op, ast.Mod):
            return a % b
    raise Unanalyzable


# ---------------------------------------------------------------------------
# pallas_call site parsing
# ---------------------------------------------------------------------------

def _is_pallas_call(node: ast.Call) -> bool:
    f = node.func
    return (
        isinstance(f, ast.Attribute) and f.attr == "pallas_call"
    )


def _kw(node: ast.Call, name: str) -> ast.AST | None:
    for k in node.keywords:
        if k.arg == name:
            return k.value
    return None


def _blockspec_shape(spec: ast.AST) -> ast.AST:
    """The block-shape tuple node of a ``pl.BlockSpec(shape, index_map)``."""
    if isinstance(spec, ast.Call) and isinstance(spec.func, ast.Attribute) \
            and spec.func.attr == "BlockSpec" and spec.args:
        return spec.args[0]
    raise Unanalyzable


def _spec_list(node: ast.AST | None) -> list[ast.AST]:
    if node is None:
        return []
    if isinstance(node, (ast.List, ast.Tuple)):
        return list(node.elts)
    return [node]


def _out_dtypes(node: ast.AST | None) -> list[str]:
    """dtypes from ``jax.ShapeDtypeStruct(shape, jnp.<dtype>)`` entries."""
    out = []
    for e in _spec_list(node):
        if isinstance(e, ast.Call) and len(e.args) >= 2 and isinstance(
            e.args[1], ast.Attribute
        ):
            out.append(e.args[1].attr)
        else:
            out.append("float32")
    return out


def _eval_shape(node: ast.AST, env: dict[str, int]) -> tuple[int, ...]:
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(_eval(e, env) for e in node.elts)
    raise Unanalyzable


def _unwrap_partial(a: ast.AST) -> str | None:
    if isinstance(a, ast.Name):
        return a.id
    if isinstance(a, ast.Call) and a.args and isinstance(a.args[0], ast.Name):
        f = a.func
        is_partial = (isinstance(f, ast.Attribute) and f.attr == "partial") \
            or (isinstance(f, ast.Name) and f.id == "partial")
        if is_partial:
            return a.args[0].id
    return None


def _kernel_fn_name(node: ast.Call, wrapper: ast.AST | None) -> str | None:
    """Resolve the kernel body reference: ``_kernel``,
    ``functools.partial(_kernel, ...)``, or a local variable bound to
    either form inside the wrapper."""
    if not node.args:
        return None
    name = _unwrap_partial(node.args[0])
    if name is None:
        return None
    # chase one level of local binding: `kernel = functools.partial(_k, ...)`
    for n in ast.walk(wrapper) if wrapper is not None else ():
        if isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                isinstance(n.targets[0], ast.Name) and \
                n.targets[0].id == name:
            inner = _unwrap_partial(n.value)
            if inner is not None:
                return inner
    return name


def _interpret_only(mod: Module, kernel: ast.AST, qual: str) -> list[Finding]:
    out = []
    for node in ast.walk(kernel):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id in ("print", "breakpoint"):
            out.append(Finding(
                "SPF302", mod.rel, node.lineno, f"{mod.name}.{qual}",
                f"{f.id}() inside a Pallas kernel body has no TPU "
                "lowering (interpret-only)",
            ))
        elif isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                and f.value.id in ("np", "numpy"):
            out.append(Finding(
                "SPF302", mod.rel, node.lineno, f"{mod.name}.{qual}",
                f"host numpy call np.{f.attr}() inside a Pallas kernel "
                "body (interpret-only; use jnp)",
            ))
    return out


# ---------------------------------------------------------------------------
# The pass
# ---------------------------------------------------------------------------

def _analyze_site(
    mod: Module, call: ast.Call, wrapper: ast.AST | None, wrapper_qual: str,
    spec: VmemSpec,
) -> tuple[KernelReport | None, list[Finding]]:
    findings: list[Finding] = []
    env = _env_for(wrapper, spec.bindings)
    line = call.lineno
    sym = f"{mod.name}.{wrapper_qual}"

    grid_node = _kw(call, "grid")
    in_specs = _kw(call, "in_specs")
    out_specs = _kw(call, "out_specs")
    gs = _kw(call, "grid_spec")
    if gs is not None and isinstance(gs, ast.Name):
        # grid_spec built earlier in the wrapper: find its assignment
        for node in ast.walk(wrapper) if wrapper is not None else ():
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name) and \
                    node.targets[0].id == gs.id:
                gs = node.value
                break
    if isinstance(gs, ast.Call):
        grid_node = _kw(gs, "grid") or grid_node
        in_specs = _kw(gs, "in_specs") or in_specs
        out_specs = _kw(gs, "out_specs") or out_specs

    try:
        if grid_node is None or (in_specs is None and out_specs is None):
            raise Unanalyzable
        grid = _eval_shape(grid_node, env)
        overrides = spec.dtype_overrides.get((mod.name, wrapper_qual), {})
        out_dts = _out_dtypes(_kw(call, "out_shape"))
        operands: list[Operand] = []
        for i, s in enumerate(_spec_list(in_specs)):
            shape = _eval_shape(_blockspec_shape(s), env)
            dt = overrides.get(i, "float32")
            nbytes = _DTYPE_BYTES[dt]
            for d in shape:
                nbytes *= d
            operands.append(Operand("in", shape, dt, nbytes))
        outs = _spec_list(out_specs)
        for i, s in enumerate(outs):
            shape = _eval_shape(_blockspec_shape(s), env)
            dt = out_dts[i] if i < len(out_dts) else "float32"
            nbytes = _DTYPE_BYTES.get(dt, 4)
            for d in shape:
                nbytes *= d
            operands.append(Operand("out", shape, dt, nbytes))
        total = 2 * sum(o.nbytes for o in operands)  # double-buffered
        report = KernelReport(
            module=mod.name, file=mod.rel, line=line, wrapper=wrapper_qual,
            grid=grid, operands=operands, vmem_bytes=total,
        )
        if total > spec.budget_bytes:
            findings.append(Finding(
                "SPF301", mod.rel, line, sym,
                f"kernel VMEM footprint {total / 2**20:.2f} MiB exceeds "
                f"the {spec.budget_bytes / 2**20:.0f} MiB per-core budget "
                "at the reference shape",
            ))
        return report, findings
    except Unresolved as e:
        findings.append(Finding(
            "SPF304", mod.rel, line, sym,
            f"shape symbol {e.symbol!r} has no value in the analysis "
            "bindings (add it to VMEM_BINDINGS)",
        ))
    except Unanalyzable:
        findings.append(Finding(
            "SPF303", mod.rel, line, sym,
            "pallas_call site the resource pass cannot statically "
            "evaluate (unrecognized grid/BlockSpec structure)",
        ))
    return None, findings


def run(
    modules: dict[str, Module], spec: VmemSpec
) -> tuple[list[Finding], list[KernelReport]]:
    findings: list[Finding] = []
    reports: list[KernelReport] = []
    for mod in sorted(modules.values(), key=lambda m: m.name):
        if not mod.name.startswith(spec.module_prefixes):
            continue
        # index module functions so sites map to their enclosing wrapper
        fns = {
            n.name: n for n in mod.tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        for call in ast.walk(mod.tree):
            if not (isinstance(call, ast.Call) and _is_pallas_call(call)):
                continue
            qual = enclosing_symbol(mod, call.lineno).removeprefix(
                mod.name + "."
            )
            wrapper = fns.get(qual)
            report, fs = _analyze_site(mod, call, wrapper, qual, spec)
            findings.extend(fs)
            if report is not None:
                reports.append(report)
            kname = _kernel_fn_name(call, wrapper)
            if kname is not None and kname in fns:
                findings.extend(_interpret_only(mod, fns[kname], kname))
    return findings, reports
