"""Which code runs under a JAX trace, and which code is the host hot loop.

Everything here is a static over-approximation computed per module:

traced functions
    (a) defs decorated with a tracing wrapper (``@jax.jit``,
        ``@partial(jax.jit, ...)``, ``@jax.remat`` ...);
    (b) defs/lambdas passed by name to a tracing wrapper call
        (``jax.jit(train_step, donate_argnums=(0,))``,
        ``lax.scan(micro_step, ...)``);
    (c) defs nested inside a traced function;
    (d) defs reachable from a traced body through same-module calls
        (``self._finalize_step(...)`` marks method ``_finalize_step``) —
        one fixed point over bare callee names.

hot (step-path) host functions
    functions named in HOT_FUNC_NAMES (the engine's public per-step
    surface) plus any def carrying a ``# graftlint: hotpath`` marker on
    its decorator/def lines. These are NOT traced — they dispatch compiled
    steps — but a host sync inside them stalls the dispatch pipeline the
    same way, so TPU001 checks them at WARNING level.

Aliases are resolved through the module's imports (``import jax.numpy as
jnp`` makes ``jnp.float32`` qualify to ``jax.numpy.float32``), so rules
match on canonical dotted names instead of guessing at spellings.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

# wrappers whose callable argument is traced by JAX (canonical names;
# aliases resolve onto these through the import map)
TRACING_WRAPPERS = {
    "jax.jit", "jax.pjit", "jax.experimental.pjit.pjit",
    "jax.grad", "jax.value_and_grad", "jax.jacfwd", "jax.jacrev",
    "jax.hessian", "jax.vmap", "jax.pmap", "jax.xmap",
    "jax.remat", "jax.checkpoint", "jax.ad_checkpoint.checkpoint",
    "jax.custom_vjp", "jax.custom_jvp", "jax.closure_convert",
    "jax.lax.scan", "jax.lax.cond", "jax.lax.while_loop",
    "jax.lax.fori_loop", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.lax.custom_root",
    "shard_map",
    "jax.experimental.multihost_utils.host_local_array_to_global_array",
    "flax.linen.scan", "flax.linen.remat", "nn.scan", "nn.remat",
}

# wrappers that compile/stage (retrace risk when rebuilt per call) — a
# strict subset of TRACING_WRAPPERS
JIT_WRAPPERS = {
    "jax.jit", "jax.pjit", "jax.experimental.pjit.pjit", "jax.pmap",
}

HOT_FUNC_NAMES = {"train_batch", "eval_batch", "forward", "backward", "step"}

_HOTPATH_MARK = re.compile(r"#\s*graftlint:\s*hotpath\b")

# parameters that are static python values by JAX convention even when the
# wrapper's static_argnums can't be resolved statically
CONVENTIONALLY_STATIC = {"train", "training", "is_training", "deterministic",
                         "mode", "axis", "axis_name"}


class ImportMap:
    """local name -> canonical dotted prefix, from the module's imports."""

    def __init__(self, tree: ast.Module):
        self.aliases: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for a in node.names:
                    self.aliases[a.asname or a.name] = \
                        f"{node.module}.{a.name}"

    def qualify(self, node: ast.AST) -> Optional[str]:
        """Dotted name of a Name/Attribute chain with the root resolved
        through the import table; None for non-name expressions."""
        parts: List[str] = []
        cur = node
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if not isinstance(cur, ast.Name):
            return None
        root = self.aliases.get(cur.id, cur.id)
        return ".".join([root] + list(reversed(parts)))


def unwrap_partial(call: ast.AST, imports: ImportMap) -> Optional[ast.AST]:
    """``partial(jax.jit, ...)`` -> the ``jax.jit`` node; else None."""
    if isinstance(call, ast.Call) and call.args:
        q = imports.qualify(call.func)
        if q in ("functools.partial", "partial"):
            return call.args[0]
    return None


class JitScope:
    def __init__(self, module):
        self.module = module
        tree = module.tree
        self.imports = ImportMap(tree)
        self._defs: List[ast.AST] = [
            n for n in module.all_nodes
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda))]
        # bare name -> defs (for call-graph propagation)
        self._by_name: Dict[str, List[ast.AST]] = {}
        for d in self._defs:
            if isinstance(d, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._by_name.setdefault(d.name, []).append(d)
        self.traced: Set[ast.AST] = set()
        self.static_params: Dict[ast.AST, Set[str]] = {}
        self._traced_effective: Dict[ast.AST, bool] = {}
        self._mark_direct()
        self._propagate_calls()
        self.hot: Set[ast.AST] = {
            d for d in self._defs
            if isinstance(d, (ast.FunctionDef, ast.AsyncFunctionDef))
            and d not in self.traced
            and (d.name in HOT_FUNC_NAMES or self._marked_hotpath(d))}

    # -- queries --------------------------------------------------------------

    def wrapper_name(self, call: ast.Call) -> Optional[str]:
        """Canonical wrapper name of a tracing-wrapper Call, else None."""
        q = self.imports.qualify(call.func)
        if q in TRACING_WRAPPERS:
            return q
        return None

    def is_jit_call(self, call: ast.Call) -> bool:
        return self.imports.qualify(call.func) in JIT_WRAPPERS

    def in_traced(self, node: ast.AST) -> bool:
        fn = self.module.enclosing_function(node)
        chain = []
        while fn is not None:
            if fn in self._traced_effective:
                result = self._traced_effective[fn]
                break
            if fn in self.traced:
                result = True
                break
            chain.append(fn)
            fn = self.module.enclosing_function(fn)
        else:
            result = False
        for f in chain:
            self._traced_effective[f] = result
        return result

    def fn_traced(self, fn: ast.AST) -> bool:
        """Is this def effectively traced — marked itself, or nested under
        a traced def?"""
        return fn in self.traced or self.in_traced(fn)

    def in_hot(self, node: ast.AST) -> bool:
        fn = self.module.enclosing_function(node)
        return fn is not None and fn in self.hot

    def static_param_names(self, fn: ast.AST) -> Set[str]:
        return self.static_params.get(fn, set()) | CONVENTIONALLY_STATIC

    def resolve_local_def(self, node: ast.AST) -> Optional[ast.AST]:
        """A Name/Lambda argument -> the local def it references,
        scope-aware: among same-named defs the one visible from the
        reference wins (innermost enclosing scope outward, Python
        name-resolution order), not whichever the module-walk met last —
        two nested helpers both called ``body`` used to collapse onto
        one of them."""
        if isinstance(node, ast.Lambda):
            return node
        if isinstance(node, ast.Name):
            defs = self._by_name.get(node.id)
            if not defs:
                return None
            if len(defs) == 1:
                return defs[0]
            return self._visible_def(node, defs)
        return None

    def _visible_def(self, node: ast.AST, defs: List[ast.AST]) -> ast.AST:
        """Pick among same-named defs by lexical scope: walk the
        reference's enclosing-function chain innermost-out; the first
        scope that directly owns a candidate wins. Within one scope the
        binding live at the reference is the LAST def at or above the
        reference line (rebinding semantics); a forward reference (a
        closure calling a def that appears later) falls back to the
        scope's last def."""
        enc = self.module.enclosing_function
        owner = {d: enc(d) for d in defs}
        scope = enc(node)
        ref_line = getattr(node, "lineno", 0)
        while True:
            cands = [d for d in defs if owner[d] is scope]
            if cands:
                prior = [d for d in cands if d.lineno <= ref_line]
                pool = prior or cands
                return max(pool, key=lambda d: d.lineno)
            if scope is None:
                return defs[-1]
            scope = enc(scope)

    # -- analysis -------------------------------------------------------------

    def _marked_hotpath(self, d: ast.AST) -> bool:
        lines = self.module.lines
        start = min(getattr(dec, "lineno", d.lineno)
                    for dec in ([d] + list(getattr(d, "decorator_list", []))))
        for ln in range(start, d.lineno + 1):
            if 1 <= ln <= len(lines) and _HOTPATH_MARK.search(lines[ln - 1]):
                return True
        return False

    def _decorator_wrapper(self, dec: ast.AST) -> Optional[str]:
        inner = unwrap_partial(dec, self.imports)
        if inner is not None:
            q = self.imports.qualify(inner)
            return q if q in TRACING_WRAPPERS else None
        target = dec.func if isinstance(dec, ast.Call) else dec
        q = self.imports.qualify(target)
        return q if q in TRACING_WRAPPERS else None

    def _record_static(self, fn: ast.AST, call: Optional[ast.Call]):
        """Map static_argnums/static_argnames from a wrapper call onto the
        wrapped def's parameter names (best effort on literal ints/strs)."""
        if call is None or not isinstance(
                fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        params = [a.arg for a in fn.args.args]
        names = self.static_params.setdefault(fn, set())
        for kw in call.keywords:
            if kw.arg in ("static_argnums", "static_argnames"):
                vals = kw.value.elts if isinstance(
                    kw.value, (ast.Tuple, ast.List)) else [kw.value]
                for v in vals:
                    if isinstance(v, ast.Constant):
                        if isinstance(v.value, int) and \
                                0 <= v.value < len(params):
                            names.add(params[v.value])
                        elif isinstance(v.value, str):
                            names.add(v.value)

    def _mark_direct(self):
        # (a) decorated defs
        for d in self._defs:
            for dec in getattr(d, "decorator_list", []):
                if self._decorator_wrapper(dec) is not None:
                    self.traced.add(d)
                    if isinstance(dec, ast.Call):
                        # covers both jit(...) and partial(jit, ...) forms
                        self._record_static(d, dec)
        # (b) defs/lambdas passed to wrapper calls
        for call in self.module.all_calls:
            if self.wrapper_name(call) is None:
                # partial(jax.jit, ...)(fn) style
                inner = unwrap_partial(call.func, self.imports) \
                    if isinstance(call.func, ast.Call) else None
                if inner is None or \
                        self.imports.qualify(inner) not in TRACING_WRAPPERS:
                    continue
            for arg in call.args:
                target = self.resolve_local_def(arg)
                if target is not None:
                    self.traced.add(target)
                    self._record_static(target, call)
        # (c) is implicit: in_traced() walks the enclosing chain

    def _propagate_calls(self):
        # (d) fixed point over bare callee names inside traced bodies
        # (including bodies of defs nested in traced defs — they run under
        # the same trace). Callee names per def are collected once.
        fn_callees: Dict[ast.AST, Set[str]] = {}

        def callees(d: ast.AST) -> Set[str]:
            if d not in fn_callees:
                names: Set[str] = set()
                for n in self.module.fn_nodes(d, subtree=True):
                    if not isinstance(n, ast.Call):
                        continue
                    if isinstance(n.func, ast.Name):
                        names.add(n.func.id)
                    elif isinstance(n.func, ast.Attribute) and isinstance(
                            n.func.value, ast.Name) and \
                            n.func.value.id == "self":
                        names.add(n.func.attr)
                fn_callees[d] = names
            return fn_callees[d]

        worklist = list(self.traced)
        while worklist:
            d = worklist.pop()
            for name in callees(d):
                for target in self._by_name.get(name, []):
                    if target not in self.traced:
                        self.traced.add(target)
                        worklist.append(target)
