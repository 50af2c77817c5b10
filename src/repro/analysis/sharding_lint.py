"""Sharding/collective contract lint: the shard_map + host-boundary rules.

PR 9 made serving mesh-native; these are the contracts that keep it
correct and retrace-free (docs/distributed.md), none of which jax checks
statically:

========  ===========================================================
 S401     a collective inside a shard_map body names an axis that is
          neither mentioned in the site's ``in_specs``/``out_specs``
          literals nor one of the repo's known mesh axes
          (``registry.KNOWN_MESH_AXES``) — a typo'd axis name fails at
          run time on the first sharded deployment, not in CI.
 S402     ``in_specs`` arity does not match the wrapped function's
          positional signature (after ``functools.partial`` binding),
          or a tuple ``out_specs`` disagrees with the body's returned
          tuple length.
 S403     a host array (``np.*``-derived) is passed straight into a
          cached jit program instead of flowing through the class's
          ``_host`` boundary helper / ``constrain`` — the second
          sharding signature that silently retraces every program.
 S404     a paged cache-pool leaf (``*_pages`` / ``pages/*``) is not
          covered by an explicit ``cache_spec`` placement rule, or a
          literal ``cache_spec(path)`` call falls through to the
          default batch rule.
 S405     deprecated ``set_mesh`` process-global — thread the mesh
          explicitly (``Model(cfg, mesh=...)``, ``constrain(mesh=...)``).
========  ===========================================================

Like every pass here the analysis is source-level and best-effort: axis
names and spec arities are checked where they resolve to literals (via
one level of local assignment and ``partial`` keyword binding) and
skipped where they stay symbolic.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Set, Tuple

from repro.analysis._astutil import (FuncInfo, ModuleInfo, Project,
                                     call_keywords, dotted_name)
from repro.analysis.findings import Finding
from repro.analysis.registry import KNOWN_MESH_AXES

_SHARD_MAP_NAMES = ("shard_map", "jax.shard_map",
                    "shmap")
_PARTIAL_NAMES = ("functools.partial", "partial")

#: collective name -> positional index of its axis-name argument
_COLLECTIVES: Dict[str, int] = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "all_gather": 1,
    "all_to_all": 1, "ppermute": 1, "pshuffle": 1, "pswapaxes": 1,
    "axis_index": 0, "psum_scatter": 1,
}

#: argument expressions S403 accepts at a cached-program boundary; anything
#: demonstrably numpy-derived must cross through one of these instead
_HOST_BOUNDARY_CALLS = ("_host", "constrain", "device_put")

_NP_PREFIXES = ("np.", "numpy.")


def _own_nodes(fi: FuncInfo) -> Iterator[ast.AST]:
    """Nodes of ``fi``'s own body, not descending into nested defs."""
    stack: List[ast.AST] = list(fi.body())
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                yield child
                continue
            stack.append(child)


def _module_scope_nodes(mod: ModuleInfo) -> Iterator[ast.AST]:
    """Top-level nodes (module pseudo-scope), not descending into defs."""
    stack: List[ast.AST] = list(mod.tree.body)
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                continue
            stack.append(child)


class ShardingLint:
    def __init__(self, project: Project):
        self.project = project
        self.findings: List[Finding] = []
        self._seen: Set[Tuple[str, int, str]] = set()

    def emit(self, mod: ModuleInfo, line: int, code: str, msg: str) -> None:
        key = (mod.rel, line, code)
        if key not in self._seen:
            self._seen.add(key)
            self.findings.append(Finding(mod.rel, line, code, msg))

    def run(self) -> List[Finding]:
        for mod in self.project.modules.values():
            self._check_set_mesh(mod)
            self._check_cache_spec_calls(mod)
            for fi in mod.functions.values():
                for node in _own_nodes(fi):
                    if isinstance(node, ast.Call) and self._is_shard_map(node):
                        self._check_shard_map_site(mod, fi, node)
            for node in _module_scope_nodes(mod):
                if isinstance(node, ast.Call) and self._is_shard_map(node):
                    self._check_shard_map_site(mod, None, node)
            self._check_host_boundaries(mod)
        self._check_cache_spec_rules()
        self.findings.sort(key=lambda f: (f.path, f.line, f.code))
        return self.findings

    # ----------------------------------------------------------------- S405
    def _check_set_mesh(self, mod: ModuleInfo) -> None:
        if mod.rel.endswith("distributed/constraints.py"):
            return
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                dn = dotted_name(node.func)
                if dn and (dn == "set_mesh" or dn.endswith(".set_mesh")):
                    self.emit(mod, node.lineno, "S405",
                              "set_mesh is a removed process-global; thread "
                              "the mesh explicitly (Model(cfg, mesh=...))")

    # ------------------------------------------------------- shard_map sites
    def _is_shard_map(self, call: ast.Call) -> bool:
        dn = dotted_name(call.func)
        return bool(dn) and (dn in _SHARD_MAP_NAMES
                             or dn.endswith(".shard_map"))

    def _resolve_local(self, scope: Optional[FuncInfo],
                       name: str) -> Optional[ast.expr]:
        """Last ``name = <expr>`` assignment in the scope's own body."""
        if scope is None:
            return None
        found = None
        for node in _own_nodes(scope):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id == name:
                found = node.value
        return found

    def _spec_expr(self, scope: Optional[FuncInfo],
                   expr: Optional[ast.expr]) -> Optional[ast.expr]:
        if isinstance(expr, ast.Name):
            return self._resolve_local(scope, expr.id)
        return expr

    def _check_shard_map_site(self, mod: ModuleInfo,
                              scope: Optional[FuncInfo],
                              call: ast.Call) -> None:
        kws = call_keywords(call)
        fn_expr = call.args[0] if call.args else kws.get("f")
        if fn_expr is None:
            return
        in_specs = self._spec_expr(scope, kws.get("in_specs"))
        out_specs = self._spec_expr(scope, kws.get("out_specs"))

        # axes mentioned as string literals anywhere in the spec exprs
        spec_axes: Set[str] = set()
        for spec in (in_specs, out_specs):
            if spec is not None:
                for node in ast.walk(spec):
                    if isinstance(node, ast.Constant) \
                            and isinstance(node.value, str):
                        spec_axes.add(node.value)
        allowed = spec_axes | set(KNOWN_MESH_AXES)

        bound_kw: Dict[str, ast.expr] = {}
        n_bound_pos = 0
        body_expr = fn_expr
        if isinstance(fn_expr, ast.Call):
            dn = dotted_name(fn_expr.func)
            if dn in _PARTIAL_NAMES and fn_expr.args:
                body_expr = fn_expr.args[0]
                n_bound_pos = len(fn_expr.args) - 1
                bound_kw = call_keywords(fn_expr)
        candidates = self._resolve_fn(mod, scope, body_expr)

        for body in candidates:
            self._check_collective_axes(mod, body, bound_kw, allowed)
            self._check_spec_arity(mod, call, body, n_bound_pos, bound_kw,
                                   in_specs, out_specs)

    def _resolve_fn(self, mod: ModuleInfo, scope: Optional[FuncInfo],
                    expr: ast.expr) -> List[FuncInfo]:
        if isinstance(expr, ast.Name):
            return self.project.resolve_name(expr.id, mod, scope)
        if isinstance(expr, ast.Attribute):
            return self.project.resolve_attr_call(expr.value, expr.attr, mod)
        if isinstance(expr, ast.Lambda):
            return [FuncInfo(expr, mod, "<lambda>", scope)]
        return []

    # ----------------------------------------------------------------- S401
    def _collective_calls(self, body: FuncInfo
                          ) -> Iterator[Tuple[ast.Call, str, int]]:
        for node in ast.walk(body.node):
            if not isinstance(node, ast.Call):
                continue
            dn = dotted_name(node.func)
            if not dn:
                continue
            tail = dn.rsplit(".", 1)[-1]
            if tail not in _COLLECTIVES:
                continue
            # jax.lax.psum / lax.psum / a `from jax import lax` alias; a
            # bare name must import from jax.lax to count
            if "." not in dn:
                target = body.module.imports.get(dn, "")
                if not target.startswith("jax.lax"):
                    continue
            elif not (dn.startswith("jax.lax.") or dn.startswith("lax.")):
                continue
            yield node, tail, _COLLECTIVES[tail]

    def _check_collective_axes(self, mod: ModuleInfo, body: FuncInfo,
                               bound_kw: Dict[str, ast.expr],
                               allowed: Set[str]) -> None:
        for call, name, axis_pos in self._collective_calls(body):
            kws = call_keywords(call)
            axis_expr = kws.get("axis_name")
            if axis_expr is None and len(call.args) > axis_pos:
                axis_expr = call.args[axis_pos]
            for axis in self._axis_strings(body, bound_kw, axis_expr):
                if axis not in allowed:
                    self.emit(mod, call.lineno, "S401",
                              f"{name} over axis {axis!r}: not in the "
                              f"shard_map site's specs or the known mesh "
                              f"axes {sorted(allowed)}")

    def _axis_strings(self, body: FuncInfo, bound_kw: Dict[str, ast.expr],
                      expr: Optional[ast.expr]) -> List[str]:
        """Statically-known axis names in an ``axis_name`` argument:
        string literals, tuples of them, or a parameter bound to a string
        constant by the site's ``partial``."""
        if expr is None:
            return []
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            return [expr.value]
        if isinstance(expr, (ast.Tuple, ast.List)):
            out: List[str] = []
            for e in expr.elts:
                out.extend(self._axis_strings(body, bound_kw, e))
            return out
        if isinstance(expr, ast.Name):
            bound = bound_kw.get(expr.id)
            if isinstance(bound, ast.Constant) \
                    and isinstance(bound.value, str):
                return [bound.value]
            local = self._resolve_local(body, expr.id)
            if isinstance(local, ast.Constant) \
                    and isinstance(local.value, str):
                return [local.value]
        return []

    # ----------------------------------------------------------------- S402
    def _check_spec_arity(self, mod: ModuleInfo, site: ast.Call,
                          body: FuncInfo, n_bound_pos: int,
                          bound_kw: Dict[str, ast.expr],
                          in_specs: Optional[ast.expr],
                          out_specs: Optional[ast.expr]) -> None:
        if body.node.args.vararg is None \
                and isinstance(in_specs, (ast.Tuple, ast.List)):
            pos = body.positional_params()
            n_defaults = len(body.node.args.defaults)
            bound = n_bound_pos + sum(1 for k in bound_kw if k in pos)
            required = len(pos) - bound
            n_specs = len(in_specs.elts)
            if not (required - n_defaults <= n_specs <= required):
                self.emit(mod, site.lineno, "S402",
                          f"in_specs has {n_specs} entr(ies) but "
                          f"{body.name}() takes {required} positional "
                          f"arg(s) after partial binding")
        if isinstance(out_specs, ast.Tuple):
            want = len(out_specs.elts)
            for node in ast.walk(body.node):
                if isinstance(node, ast.Return) and node.value is not None:
                    got = (len(node.value.elts)
                           if isinstance(node.value, ast.Tuple) else 1)
                    if got != want:
                        self.emit(mod, site.lineno, "S402",
                                  f"out_specs is a {want}-tuple but "
                                  f"{body.name}() returns {got} value(s)")

    # ----------------------------------------------------------------- S403
    def _check_host_boundaries(self, mod: ModuleInfo) -> None:
        for ci in mod.classes.values():
            if "_host" not in ci.methods:
                continue
            builders = {name for name, fi in ci.methods.items()
                        if self._contains_jit(fi)}
            if not builders:
                continue
            for name, fi in ci.methods.items():
                if name in builders:
                    continue
                self._check_boundary_method(mod, fi, builders)

    def _contains_jit(self, fi: FuncInfo) -> bool:
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Call):
                dn = dotted_name(node.func)
                if dn in ("jax.jit", "jit", "api.jit"):
                    return True
        return False

    def _check_boundary_method(self, mod: ModuleInfo, fi: FuncInfo,
                               builders: Set[str]) -> None:
        # one statement-ordered sweep: assigns reclassify names as they
        # execute (``toks = np.full(...)`` then ``toks = self._host(toks)``
        # is clean), calls are checked against the state at their line —
        # a call embedded in an assignment sees the pre-assignment state.
        events: List[ast.AST] = []
        for node in _own_nodes(fi):
            if isinstance(node, ast.Assign):
                events.append(node)
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name):
                events.append(node)
        events.sort(key=lambda n: (n.lineno, isinstance(n, ast.Assign)))
        program_vars: Set[str] = set()
        np_locals: Set[str] = set()
        for node in events:
            if isinstance(node, ast.Call):
                if node.func.id not in program_vars:
                    continue
                for arg in list(node.args) + [kw.value for kw in
                                              node.keywords]:
                    bad = self._host_arg(arg, np_locals)
                    if bad:
                        self.emit(mod, node.lineno, "S403",
                                  f"{bad} passed into cached program "
                                  f"{node.func.id}() without the _host/"
                                  f"constrain boundary — second sharding "
                                  f"signature, silent retrace")
                continue
            value = node.value
            if isinstance(value, ast.Call) \
                    and isinstance(value.func, ast.Attribute) \
                    and isinstance(value.func.value, ast.Name) \
                    and value.func.value.id == "self" \
                    and value.func.attr in builders:
                for t in node.targets:
                    for n in _flat_names(t):
                        program_vars.add(n)
                        np_locals.discard(n)
                continue
            if self._is_np_expr(value):
                for t in node.targets:
                    for n in _flat_names(t):
                        np_locals.add(n)
                        program_vars.discard(n)
                continue
            for t in node.targets:
                for n in _flat_names(t):
                    np_locals.discard(n)
                    program_vars.discard(n)

    def _is_np_expr(self, expr: ast.expr) -> bool:
        if isinstance(expr, ast.Call):
            dn = dotted_name(expr.func) or ""
            return dn.startswith(_NP_PREFIXES)
        return False

    def _host_arg(self, arg: ast.expr,
                  np_locals: Set[str]) -> Optional[str]:
        if isinstance(arg, ast.Call):
            dn = dotted_name(arg.func) or ""
            tail = dn.rsplit(".", 1)[-1]
            if tail in _HOST_BOUNDARY_CALLS:
                return None
            if dn.startswith(_NP_PREFIXES):
                return f"host array {dn}(...)"
        if isinstance(arg, ast.Name) and arg.id in np_locals:
            return f"host array {arg.id!r}"
        return None

    # ----------------------------------------------------------------- S404
    def _cache_spec_patterns(self) -> Optional[List[str]]:
        """Ordered ``re.search`` pattern literals inside the scanned tree's
        ``cache_spec`` definition (None when no definition is in scope)."""
        for mod in self.project.modules.values():
            fi = mod.functions.get("cache_spec")
            if fi is None:
                continue
            pats: List[str] = []
            for node in ast.walk(fi.node):
                if isinstance(node, ast.Call):
                    dn = dotted_name(node.func)
                    if dn and dn.endswith("search") and node.args \
                            and isinstance(node.args[0], ast.Constant) \
                            and isinstance(node.args[0].value, str):
                        pats.append(node.args[0].value)
            return pats
        return None

    def _covered(self, leaf: str, patterns: List[str]) -> bool:
        return any(re.search(p, leaf) for p in patterns)

    def _check_cache_spec_rules(self) -> None:
        patterns = self._cache_spec_patterns()
        if patterns is None:
            return
        for mod in self.project.modules.values():
            if mod.rel.endswith("distributed/sharding.py"):
                continue
            # only dict literals built inside cache constructors count as
            # pool pytrees; a config dict elsewhere may reuse leaf-ish keys
            for fi in mod.functions.values():
                if "cache" not in fi.name.lower():
                    continue
                for node in ast.walk(fi.node):
                    if not isinstance(node, ast.Dict):
                        continue
                    for key in node.keys:
                        if isinstance(key, ast.Constant) \
                                and isinstance(key.value, str) \
                                and key.value.endswith("_pages") \
                                and not self._covered(key.value, patterns):
                            self.emit(mod, key.lineno, "S404",
                                      f"paged pool leaf {key.value!r} "
                                      f"matches no cache_spec placement "
                                      f"rule — it would fall through to "
                                      f"the default batch rule")

    def _check_cache_spec_calls(self, mod: ModuleInfo) -> None:
        patterns = self._cache_spec_patterns()
        if patterns is None or mod.rel.endswith("distributed/sharding.py"):
            return
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                dn = dotted_name(node.func)
                if not (dn and dn.rsplit(".", 1)[-1] == "cache_spec"):
                    continue
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    path = node.args[0].value
                    if ("pages" in path or path.endswith("_pages")) \
                            and not self._covered(path, patterns):
                        self.emit(mod, node.lineno, "S404",
                                  f"cache_spec({path!r}) matches no paged "
                                  f"placement rule — check the path "
                                  f"spelling against _PARAM/cache rules")


def _flat_names(target: ast.expr) -> List[str]:
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in target.elts:
            out.extend(_flat_names(e))
        return out
    return []


def run(project: Project) -> List[Finding]:
    """Entry point: S4xx findings over the project."""
    return ShardingLint(project).run()
