"""J-rules: JAX/Pallas tracer safety for the accelerator hot path.

Applied to ``kernels/`` and ``core/``: the Pallas kernels and the jitted
solve tiers. A host-device sync inside a jitted function (``.item()``,
``float(tracer)``, ``np.asarray`` of a traced value) forces a blocking
transfer on every call; Python ``if``/``while`` on a traced value raises
``TracerBoolConversionError`` at trace time or, worse, bakes one branch in
silently; a ``pl.pallas_call`` whose BlockSpec/grid arities disagree fails
deep inside Mosaic with no source context, and one without an
``interpret=`` escape hatch cannot be debugged off-TPU.

Discovery is intentionally static and conservative:
  - jit functions: ``@jax.jit`` / ``@jit`` decorators,
    ``@functools.partial(jax.jit, ...)``, and module-level
    ``name = jax.jit(fn)`` rebinding of a module function;
  - Pallas kernels: the callee of ``pl.pallas_call`` — given directly, or
    through a local ``functools.partial(kernel_fn, **static_kwargs)``
    binding. Parameters bound via ``partial`` keywords and names listed in
    ``static_argnames`` are treated as Python-static.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .engine import (
    Finding,
    ModuleContext,
    Rule,
    resolved_name,
    terminal_name,
)

_JIT_NAMES = {"jax.jit", "jit", "jax.pjit", "pjit"}
_PARTIAL_NAMES = {"functools.partial", "partial"}
_PALLAS_CALL_NAMES = {"jax.experimental.pallas.pallas_call", "pallas_call"}
_BLOCKSPEC_LEAF = "BlockSpec"


def _is_jit_ref(ctx: ModuleContext, node: ast.AST) -> bool:
    return resolved_name(ctx, node) in _JIT_NAMES


def _static_argnames(call: ast.Call) -> Set[str]:
    """Names in a ``static_argnames=`` kwarg (literal str / tuple / list)."""
    for kw in call.keywords:
        if kw.arg != "static_argnames":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            return {v.value}
        if isinstance(v, (ast.Tuple, ast.List)):
            return {
                e.value for e in v.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            }
    return set()


def _function_defs(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
    return {
        n.name: n
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }


def _partial_bindings(tree: ast.Module, ctx: ModuleContext
                      ) -> Dict[str, Tuple[str, Set[str]]]:
    """``alias -> (function_name, bound_kwarg_names)`` for
    ``alias = functools.partial(fn, kw=...)`` assignments anywhere."""
    out: Dict[str, Tuple[str, Set[str]]] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and isinstance(node.value, ast.Call)):
            continue
        call = node.value
        if resolved_name(ctx, call.func) not in _PARTIAL_NAMES or not call.args:
            continue
        fn_name = terminal_name(call.args[0])
        if fn_name is None:
            continue
        bound = {kw.arg for kw in call.keywords if kw.arg}
        for t in node.targets:
            if isinstance(t, ast.Name):
                out[t.id] = (fn_name, bound)
    return out


def traced_functions(ctx: ModuleContext
                     ) -> List[Tuple[ast.FunctionDef, Set[str]]]:
    """(function, static-param-names) pairs for jit-compiled and Pallas-kernel
    functions in the module."""
    defs = _function_defs(ctx.tree)
    partials = _partial_bindings(ctx.tree, ctx)
    out: Dict[str, Tuple[ast.FunctionDef, Set[str]]] = {}

    def add(fn: ast.FunctionDef, statics: Set[str]) -> None:
        prev = out.get(fn.name)
        out[fn.name] = (fn, statics | (prev[1] if prev else set()))

    for fn in defs.values():
        for dec in fn.decorator_list:
            if _is_jit_ref(ctx, dec):
                add(fn, set())
            elif isinstance(dec, ast.Call):
                if _is_jit_ref(ctx, dec.func):
                    add(fn, _static_argnames(dec))
                elif (resolved_name(ctx, dec.func) in _PARTIAL_NAMES
                      and dec.args and _is_jit_ref(ctx, dec.args[0])):
                    add(fn, _static_argnames(dec))
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            call = node.value
            if _is_jit_ref(ctx, call.func) and call.args:
                name = terminal_name(call.args[0])
                if name in defs:
                    add(defs[name], _static_argnames(call))
        elif isinstance(node, ast.Call) and _is_pallas_call(ctx, node):
            if not node.args:
                continue
            kernel_arg = node.args[0]
            if isinstance(kernel_arg, ast.Call) and resolved_name(
                ctx, kernel_arg.func
            ) in _PARTIAL_NAMES and kernel_arg.args:
                name = terminal_name(kernel_arg.args[0])
                bound = {kw.arg for kw in kernel_arg.keywords if kw.arg}
                if name in defs:
                    add(defs[name], bound)
            else:
                name = terminal_name(kernel_arg)
                if name in partials:
                    fn_name, bound = partials[name]
                    if fn_name in defs:
                        add(defs[fn_name], bound)
                elif name in defs:
                    add(defs[name], set())
    return list(out.values())


def _is_pallas_call(ctx: ModuleContext, node: ast.Call) -> bool:
    full = resolved_name(ctx, node.func)
    if full in _PALLAS_CALL_NAMES:
        return True
    return terminal_name(node.func) == "pallas_call"


class HostSyncInJit(Rule):
    rule_id = "J201"
    title = "host-device sync inside a jit/Pallas-traced function"
    rationale = (
        ".item(), float()/int() on arrays, and np.asarray of traced values "
        "force a blocking device->host transfer per call (or fail under "
        "trace); keep values on-device (jnp) and reduce with lax primitives."
    )
    scope = ("repro/kernels/", "repro/core/")

    _SYNC_METHODS = {"item", "tolist", "numpy", "block_until_ready"}
    _NUMPY_MATERIALIZERS = {"asarray", "array", "copy", "frombuffer", "ascontiguousarray"}

    def check(self, ctx: ModuleContext) -> List[Finding]:
        findings: List[Finding] = []
        for fn, _statics in traced_functions(ctx):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if (isinstance(f, ast.Attribute)
                        and f.attr in self._SYNC_METHODS
                        and not node.args):
                    findings.append(ctx.finding(
                        node, self.rule_id,
                        f".{f.attr}() inside traced function {fn.name!r} forces "
                        f"a host sync; keep the value on-device",
                    ))
                    continue
                if (isinstance(f, ast.Name) and f.id in ("float", "int", "bool")
                        and len(node.args) == 1
                        and not isinstance(node.args[0], ast.Constant)):
                    findings.append(ctx.finding(
                        node, self.rule_id,
                        f"{f.id}(...) on a possibly-traced value inside "
                        f"{fn.name!r} concretizes the tracer; use jnp casts "
                        f"(.astype) or lax ops",
                    ))
                    continue
                full = resolved_name(ctx, f)
                if (full and full.startswith("numpy.")
                        and full.rsplit(".", 1)[1] in self._NUMPY_MATERIALIZERS):
                    findings.append(ctx.finding(
                        node, self.rule_id,
                        f"{full}(...) inside traced function {fn.name!r} "
                        f"materializes on host; use jax.numpy",
                    ))
        return findings


class TracerControlFlow(Rule):
    rule_id = "J202"
    title = "Python control flow on a traced value"
    rationale = (
        "if/while on a tracer either raises TracerBoolConversionError or, "
        "when shapes make it evaluable, silently bakes one branch into the "
        "compiled program. Use jax.lax.cond/select/while_loop, or mark the "
        "argument static (static_argnames)."
    )
    scope = ("repro/kernels/", "repro/core/")

    def check(self, ctx: ModuleContext) -> List[Finding]:
        findings: List[Finding] = []
        for fn, statics in traced_functions(ctx):
            params = {
                a.arg
                for a in (list(fn.args.posonlyargs) + list(fn.args.args)
                          + list(fn.args.kwonlyargs))
            } - statics - {"self"}
            for node in ast.walk(fn):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                traced = self._traced_names_in_test(node.test, params)
                if traced:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    findings.append(ctx.finding(
                        node, self.rule_id,
                        f"Python {kind!r} on possibly-traced parameter(s) "
                        f"{', '.join(sorted(traced))} in {fn.name!r}; use "
                        f"jax.lax.cond/select or declare them static",
                    ))
        return findings

    @staticmethod
    def _traced_names_in_test(test: ast.AST, params: Set[str]) -> Set[str]:
        # `x is None` / `x is not None` checks are static under trace.
        if isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops
        ):
            return set()
        hits: Set[str] = set()
        for node in ast.walk(test):
            if isinstance(node, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
            ):
                continue
            if isinstance(node, ast.Name) and node.id in params:
                hits.add(node.id)
        return hits


class PallasCallContract(Rule):
    rule_id = "J203"
    title = "inconsistent pl.pallas_call BlockSpec/grid or missing interpret="
    rationale = (
        "An index_map whose arity differs from the grid rank, or a block "
        "shape whose length differs from the index_map result, fails inside "
        "Mosaic with no source context; a call without an interpret= escape "
        "hatch cannot be validated on CPU (every kernel here is CI-tested "
        "with interpret=True)."
    )
    scope = ("repro/kernels/", "repro/core/")

    def check(self, ctx: ModuleContext) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and _is_pallas_call(ctx, node)):
                continue
            kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
            has_splat = any(kw.arg is None for kw in node.keywords)
            if "interpret" not in kwargs and not has_splat:
                findings.append(ctx.finding(
                    node, self.rule_id,
                    "pl.pallas_call without an interpret= escape hatch; thread "
                    "an interpret flag through for CPU validation",
                ))
            grid_rank = self._literal_grid_rank(kwargs.get("grid"))
            for spec in self._block_specs(kwargs):
                block_len, im_args, im_ret = self._spec_shape(spec)
                if grid_rank is not None and im_args is not None and im_args != grid_rank:
                    findings.append(ctx.finding(
                        spec, self.rule_id,
                        f"BlockSpec index_map takes {im_args} arg(s) but the "
                        f"grid has rank {grid_rank}",
                    ))
                if (block_len is not None and im_ret is not None
                        and block_len != im_ret):
                    findings.append(ctx.finding(
                        spec, self.rule_id,
                        f"BlockSpec block_shape has {block_len} dim(s) but its "
                        f"index_map returns {im_ret}",
                    ))
        return findings

    @staticmethod
    def _literal_grid_rank(grid: Optional[ast.AST]) -> Optional[int]:
        if grid is None:
            return None
        if isinstance(grid, (ast.Tuple, ast.List)):
            return len(grid.elts)
        if isinstance(grid, ast.Constant) and isinstance(grid.value, int):
            return 1
        return None

    def _block_specs(self, kwargs: Dict[str, ast.AST]) -> List[ast.Call]:
        specs: List[ast.Call] = []
        for key in ("in_specs", "out_specs", "grid_spec"):
            v = kwargs.get(key)
            if v is None:
                continue
            for node in ast.walk(v):
                if (isinstance(node, ast.Call)
                        and terminal_name(node.func) == _BLOCKSPEC_LEAF):
                    specs.append(node)
        return specs

    @staticmethod
    def _spec_shape(spec: ast.Call
                    ) -> Tuple[Optional[int], Optional[int], Optional[int]]:
        """(block_shape length, index_map arg count, index_map return length),
        each None when not statically determinable."""
        block_shape: Optional[ast.AST] = spec.args[0] if spec.args else None
        index_map: Optional[ast.AST] = spec.args[1] if len(spec.args) > 1 else None
        for kw in spec.keywords:
            if kw.arg == "block_shape":
                block_shape = kw.value
            elif kw.arg == "index_map":
                index_map = kw.value
        block_len = (
            len(block_shape.elts)
            if isinstance(block_shape, (ast.Tuple, ast.List))
            else None
        )
        im_args = im_ret = None
        if isinstance(index_map, ast.Lambda):
            im_args = len(index_map.args.posonlyargs) + len(index_map.args.args)
            body = index_map.body
            if isinstance(body, (ast.Tuple, ast.List)):
                im_ret = len(body.elts)
            elif isinstance(body, (ast.Constant, ast.Name, ast.BinOp)):
                im_ret = 1
        return block_len, im_args, im_ret


def rules() -> List[Rule]:
    return [HostSyncInJit(), TracerControlFlow(), PallasCallContract()]
