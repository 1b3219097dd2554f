"""AST analysis: TPU tracing-hazard detection for this codebase.

One pass per module, stdlib-``ast`` only (no jax import, no execution).
The analysis is deliberately *heuristic* — it approximates at trace
time what ``utils.tracing.RecompilationSentinel`` measures at run time
— and it is tuned to this repo's idioms:

* **Traced contexts.**  A function is "traced" when it is decorated
  with ``jax.jit``/``pjit`` (directly or via ``functools.partial``),
  passed to a tracing entry point (``jax.jit(fn)``, ``lax.scan(body,
  ...)``, ``vmap``/``grad``/``remat``/...) anywhere in the module —
  including through wrapper calls like ``jax.jit(instrument(fn))`` —
  nested inside a traced function, or called by name from one
  (intra-module fixpoint).  Cross-module reachability is not modeled;
  the runtime sentinel covers that half.
* **Device-flavored expressions.**  An expression is treated as living
  on device when its subtree mentions a ``jnp``/``jax.lax``/
  ``jax.nn``/``jax.random`` call, or a local name assigned from one
  (single forward pass), or — inside a traced function — a parameter.
  ``.shape``/``.ndim``/``.dtype``/``len()`` prune the subtree (static
  metadata, legal to branch on), as does ``jax.device_get`` (the one
  sanctioned host-transfer idiom: batch a pytree, sync once).

Findings (rule ids in ``rules.py``) carry file:line, rule id, and a
fix hint; ``# lint: disable=FTL00x — why`` suppresses with an inline
justification, and the checked-in baseline absorbs accepted history
(``findings.py``).
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set

from fedtorch_tpu.lint.findings import (
    Finding, apply_suppressions, suppressions_for_source,
)
from fedtorch_tpu.lint.rules import hint_for

# canonical jax entry points whose function-valued arguments get
# traced.  Deliberately NOT ``jax.tree.map`` and friends — tree
# mapping executes its function eagerly, it does not trace it.
_TRACING_CANON = {
    "jax.jit", "jax.pjit", "jax.vmap", "jax.pmap", "jax.grad",
    "jax.value_and_grad", "jax.checkpoint", "jax.remat",
    "jax.named_call", "jax.custom_jvp", "jax.custom_vjp",
    "jax.lax.scan", "jax.lax.map", "jax.lax.cond",
    "jax.lax.while_loop", "jax.lax.fori_loop", "jax.lax.switch",
    "jax.lax.associative_scan", "jax.experimental.pjit.pjit",
}

# jax.random.* that DERIVE or inspect keys (never consume a stream)
_KEY_DERIVERS = {"split", "fold_in", "key", "PRNGKey", "clone",
                 "wrap_key_data", "key_data", "key_impl"}

# host scalar coercions (FTL001)
_COERCIONS = {"float", "int", "bool"}

# attribute accesses that are static metadata, not device reads
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "at",
                 "aval", "weak_type"}

# calls whose RESULT is host/static even with device args: dtype
# predicates, metadata probes, python introspection
_HOST_RESULT_CALLS = {
    "jax.numpy.issubdtype", "jax.numpy.isdtype", "jax.numpy.iinfo",
    "jax.numpy.finfo", "jax.numpy.result_type",
    "jax.numpy.promote_types", "jax.numpy.ndim", "jax.numpy.shape",
    "jax.numpy.dtype", "jax.dtypes.issubdtype",
    "jax.dtypes.result_type", "jax.random.key_impl",
    "jax.device_get", "jax.eval_shape", "jax.typeof",
}
_HOST_RESULT_NAMES = {"isinstance", "issubclass", "len", "getattr",
                      "hasattr", "type", "repr", "str", "callable",
                      # parallel.evaluate: the evaluation's ONE
                      # jax.device_get is inside it
                      "evaluate_to_host"}

# methods whose RESULT is host values whatever they are handed: the
# trainer's sanctioned batched fetches, one jax.device_get inside each
# (FederatedTrainer.round_host_fetch / round_host_scalars)
_HOST_RESULT_METHODS = {"round_host_fetch", "round_host_scalars"}

# device-returning jax namespaces (callable prefixes)
_DEVICE_CALL_PREFIXES = (
    "jax.numpy.", "jax.lax.", "jax.nn.", "jax.random.", "jax.scipy.",
    "jax.tree.", "jax.tree_util.", "jax.device_put", "jax.ops.")


def _attr_path(node: ast.AST) -> Optional[str]:
    """Dotted path of a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _Aliases:
    """What this module calls jax / jax.numpy / numpy / functools."""

    def __init__(self, tree: ast.Module):
        self.jax: Set[str] = set()
        self.jnp: Set[str] = set()
        self.np: Set[str] = set()
        self.partial: Set[str] = set()
        # names bound by `from jax import jit, vmap, lax, random, ...`
        self.jax_members: Dict[str, str] = {}
        # names bound by `from numpy import asarray, ...`
        self.np_members: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = a.asname or a.name
                    if a.name == "jax":
                        self.jax.add(name)
                    elif a.name in ("jax.numpy",):
                        self.jnp.add(name)
                    elif a.name == "numpy":
                        self.np.add(name)
                    elif a.name == "functools":
                        self.partial.add(name + ".partial")
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                for a in node.names:
                    name = a.asname or a.name
                    if mod == "jax" and a.name == "numpy":
                        self.jnp.add(name)
                    elif mod == "jax":
                        self.jax_members[name] = a.name
                    elif mod.startswith("jax."):
                        self.jax_members[name] = \
                            mod.split(".", 1)[1] + "." + a.name
                    elif mod == "functools" and a.name == "partial":
                        self.partial.add(name)
                    elif mod == "numpy":
                        # `from numpy import asarray` — the bare name
                        # canonicalizes to numpy.<member>
                        self.np_members[name] = a.name

    def canon(self, path: Optional[str]) -> Optional[str]:
        """Canonicalize a dotted path against the aliases:
        'jnp.sum' -> 'jax.numpy.sum', 'lax.scan' (from jax import lax)
        -> 'jax.lax.scan', 'np.dot' -> 'numpy.dot'."""
        if not path:
            return None
        head, _, rest = path.partition(".")
        if head in self.jnp:
            return "jax.numpy" + ("." + rest if rest else "")
        if head in self.np:
            return "numpy" + ("." + rest if rest else "")
        if head in self.jax:
            return "jax" + ("." + rest if rest else "")
        if head in self.jax_members:
            return "jax." + self.jax_members[head] + \
                ("." + rest if rest else "")
        if head in self.np_members:
            return "numpy." + self.np_members[head] + \
                ("." + rest if rest else "")
        return path


def _copy_state(state: Dict[str, dict]) -> Dict[str, dict]:
    """Branch-local copy of the PRNG walker state — the inner per-key
    dicts are mutable and must not be shared across branches."""
    return {k: dict(v) for k, v in state.items()}


def _set_parents(tree: ast.AST) -> None:
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            child._lint_parent = parent  # type: ignore[attr-defined]


def _enclosing_function(node: ast.AST):
    cur = getattr(node, "_lint_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return cur
        cur = getattr(cur, "_lint_parent", None)
    return None


class ModuleAnalysis:
    """Single-module pass producing findings for all rules."""

    def __init__(self, src: str, path: str):
        self.src = src
        self.path = path
        self.lines = src.splitlines()
        self.tree = ast.parse(src, filename=path)
        _set_parents(self.tree)
        self.aliases = _Aliases(self.tree)
        self.findings: List[Finding] = []
        self.functions = [n for n in ast.walk(self.tree)
                          if isinstance(n, (ast.FunctionDef,
                                            ast.AsyncFunctionDef,
                                            ast.Lambda))]
        self._fn_by_name: Dict[str, List[ast.AST]] = {}
        for fn in self.functions:
            if not isinstance(fn, ast.Lambda):
                self._fn_by_name.setdefault(fn.name, []).append(fn)
        self._fn_by_binding = self._collect_fn_bindings()
        self.traced: Set[ast.AST] = set()
        # (fn_node, has_donate, site_node): site is where a
        # donate_argnums= would be written — the decorator/jit call
        self._jit_bindings: List[tuple] = []
        self._static_params: Dict[ast.AST, Set[str]] = {}
        self._mark_traced()
        self._device_vars: Dict[ast.AST, Set[str]] = {}
        for fn in self.functions:
            self._device_vars[fn] = self._collect_device_vars(fn)
        self._claimed_tests: Set[ast.AST] = set()

    # -- traced-context discovery -------------------------------------

    def _canon_call(self, call: ast.Call) -> Optional[str]:
        return self.aliases.canon(_attr_path(call.func))

    def _is_tracing_entry(self, canon: Optional[str]) -> bool:
        return canon in _TRACING_CANON

    def _jit_has_donate(self, call: ast.Call) -> bool:
        return any(kw.arg in ("donate_argnums", "donate_argnames")
                   for kw in call.keywords)

    def _static_names_from_call(self, call: ast.Call, fn) -> Set[str]:
        """Parameter names pinned static by static_argnums/argnames."""
        out: Set[str] = set()
        if isinstance(fn, ast.Lambda):
            return out
        pos = [a.arg for a in (fn.args.posonlyargs + fn.args.args)]
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and \
                            isinstance(n.value, str):
                        out.add(n.value)
            elif kw.arg == "static_argnums":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and \
                            isinstance(n.value, int) and \
                            0 <= n.value < len(pos):
                        out.add(pos[n.value])
        return out

    def _collect_fn_bindings(self) -> Dict[str, List[ast.AST]]:
        """Names bound to function values by ASSIGNMENT — the
        local-closure idiom ``lax.scan``/``while_loop`` bodies are
        built with (``round_program.py``): ``step = _make_body(t)``,
        ``body = lambda s: ...``, ``fn = a_body if flag else b_body``.
        Chased to a fixpoint so chains of rebindings resolve. Without
        this map, a closure bound to a local before the tracing call
        was invisible to traced-context discovery (the gap pinned by
        tests/test_lint_analyzer.py's scan-closure fixtures)."""
        bindings: Dict[str, List[ast.AST]] = {}

        def refs_of(expr: ast.AST) -> List[ast.AST]:
            """Function nodes a deliberately-function-valued RHS
            denotes. Deliberate forms only — a general result-of-call
            binding would mark every helper traced and cascade false
            positives through the intra-module call graph."""
            if isinstance(expr, ast.Lambda):
                return [expr]
            if isinstance(expr, ast.Name):
                return list(self._fn_by_name.get(expr.id, [])) \
                    + list(bindings.get(expr.id, []))
            if isinstance(expr, ast.Attribute):
                return list(self._fn_by_name.get(expr.attr, []))
            if isinstance(expr, ast.IfExp):
                return refs_of(expr.body) + refs_of(expr.orelse)
            if isinstance(expr, ast.Call) and \
                    isinstance(expr.func, ast.Name):
                # closure factory: `step = _make_body(t)` resolves to
                # the function(s) the factory RETURNS — not the
                # factory itself, so helpers that merely return call
                # results don't get wrongly marked traced
                out: List[ast.AST] = []
                for cand in self._fn_by_name.get(expr.func.id, []):
                    out.extend(self._returned_fns(cand))
                return out
            return []

        changed = True
        while changed:
            changed = False
            for node in ast.walk(self.tree):
                if not isinstance(node, ast.Assign) \
                        or len(node.targets) != 1 \
                        or not isinstance(node.targets[0], ast.Name):
                    continue
                refs = refs_of(node.value)
                if not refs:
                    continue
                known = bindings.setdefault(node.targets[0].id, [])
                for r in refs:
                    if r not in known:
                        known.append(r)
                        changed = True
        return bindings

    def _returned_fns(self, fndef: ast.AST) -> List[ast.AST]:
        """Function nodes ``fndef`` returns (lambdas, nested-def
        names, conditional expressions of either) — what a closure
        factory hands its caller."""
        out: List[ast.AST] = []

        def resolve(expr: ast.AST) -> None:
            if isinstance(expr, ast.Lambda):
                out.append(expr)
            elif isinstance(expr, ast.Name):
                out.extend(self._fn_by_name.get(expr.id, []))
            elif isinstance(expr, ast.IfExp):
                resolve(expr.body)
                resolve(expr.orelse)

        for sub in ast.walk(fndef):
            if isinstance(sub, ast.Return) and sub.value is not None \
                    and _enclosing_function(sub) is fndef:
                resolve(sub.value)
        return out

    def _resolve_fn_refs(self, node: ast.AST) -> List[ast.AST]:
        """Function defs referenced by name (or trailing attribute —
        ``self.round_fn`` resolves to the method ``round_fn``) anywhere
        inside ``node``, plus inline lambdas/defs and names BOUND to
        function values by assignment (``_collect_fn_bindings`` — the
        closure-factory / name-assigned-lambda idioms)."""
        out: List[ast.AST] = []
        for sub in ast.walk(node):
            if isinstance(sub, ast.Lambda):
                out.append(sub)
            elif isinstance(sub, ast.Name):
                out.extend(self._fn_by_name.get(sub.id, []))
                out.extend(self._fn_by_binding.get(sub.id, []))
            elif isinstance(sub, ast.Attribute):
                out.extend(self._fn_by_name.get(sub.attr, []))
        return out

    def _mark_traced(self) -> None:
        # 1) decorators
        for fn in self.functions:
            for dec in getattr(fn, "decorator_list", []):
                canon = self.aliases.canon(_attr_path(dec))
                if canon and self._is_tracing_entry(canon):
                    self.traced.add(fn)
                    if canon.endswith(("jit", "pjit")):
                        self._jit_bindings.append((fn, False, dec))
                elif isinstance(dec, ast.Call):
                    dcanon = self._canon_call(dec)
                    if dcanon and self._is_tracing_entry(dcanon):
                        self.traced.add(fn)
                        if dcanon.endswith(("jit", "pjit")):
                            self._jit_bindings.append(
                                (fn, self._jit_has_donate(dec), dec))
                        self._static_params.setdefault(
                            fn, set()).update(
                            self._static_names_from_call(dec, fn))
                    elif dcanon and (dcanon in self.aliases.partial
                                     or dcanon.endswith(".partial")
                                     or dcanon == "partial"):
                        # @partial(jax.jit, static_argnames=...)
                        if dec.args:
                            inner = self.aliases.canon(
                                _attr_path(dec.args[0]))
                            if inner and self._is_tracing_entry(inner):
                                self.traced.add(fn)
                                if inner.endswith(("jit", "pjit")):
                                    self._jit_bindings.append(
                                        (fn, self._jit_has_donate(dec),
                                         dec))
                                self._static_params.setdefault(
                                    fn, set()).update(
                                    self._static_names_from_call(
                                        dec, fn))
        # 2) calls to tracing entry points with function-valued args
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            canon = self._canon_call(node)
            if not self._is_tracing_entry(canon):
                continue
            refs = []
            for arg in node.args:
                refs.extend(self._resolve_fn_refs(arg))
            for ref in refs:
                self.traced.add(ref)
                self._static_params.setdefault(ref, set()).update(
                    self._static_names_from_call(node, ref))
            if canon and canon.rsplit(".", 1)[-1] in ("jit", "pjit") \
                    and refs:
                has_donate = self._jit_has_donate(node)
                for ref in refs:
                    if not isinstance(ref, ast.Lambda):
                        self._jit_bindings.append(
                            (ref, has_donate, node))
        # 3) nesting: functions defined inside traced functions
        changed = True
        while changed:
            changed = False
            for fn in self.functions:
                if fn in self.traced:
                    continue
                anc = _enclosing_function(fn)
                while anc is not None:
                    if anc in self.traced:
                        self.traced.add(fn)
                        changed = True
                        break
                    anc = _enclosing_function(anc)
            # 4) intra-module call graph: f traced => callees traced
            for fn in list(self.traced):
                for sub in ast.walk(fn):
                    if isinstance(sub, ast.Call):
                        p = _attr_path(sub.func)
                        if p is None:
                            continue
                        tail = p.rsplit(".", 1)[-1]
                        if self.aliases.canon(p) != p:
                            continue  # library call, not local
                        for ref in self._fn_by_name.get(tail, []):
                            if ref not in self.traced:
                                self.traced.add(ref)
                                changed = True

    def _in_traced(self, node: ast.AST) -> bool:
        fn = _enclosing_function(node)
        while fn is not None:
            if fn in self.traced:
                return True
            fn = _enclosing_function(fn)
        return False

    # -- device-flavored expressions ----------------------------------

    @staticmethod
    def _target_names(tgt: ast.AST) -> List[str]:
        """Plain names bound by an assignment target: ``x`` or the
        Name elements of ``a, b = ...``.  Attribute targets
        (``self.x = ...``) bind no trackable local — crucially they
        must NOT mark ``self`` device-flavored."""
        if isinstance(tgt, ast.Name):
            return [tgt.id]
        if isinstance(tgt, (ast.Tuple, ast.List)):
            out = []
            for e in tgt.elts:
                out.extend(ModuleAnalysis._target_names(e))
            return out
        return []

    def _collect_device_vars(self, fn) -> Set[str]:
        """Names assigned from jnp/jax calls inside ``fn`` (single
        forward pass), plus — when ``fn`` is traced — its non-static
        parameters."""
        out: Set[str] = set()
        if fn in self.traced:
            # lambdas share ast.arguments with defs, so traced
            # name-assigned lambda bodies get device-flavored params
            # too (the while_loop/scan local-closure idiom)
            static = self._static_params.get(fn, set())
            for a in (fn.args.posonlyargs + fn.args.args
                      + fn.args.kwonlyargs):
                if a.arg not in ("self", "cls") and a.arg not in static:
                    out.add(a.arg)
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            for sub in ast.walk(stmt if isinstance(stmt, ast.AST)
                                else ast.Expr(stmt)):
                if isinstance(sub, ast.Assign) and \
                        self._expr_is_device(sub.value, out):
                    for tgt in sub.targets:
                        out.update(self._target_names(tgt))
                elif isinstance(sub, (ast.AugAssign, ast.AnnAssign)) \
                        and sub.value is not None \
                        and self._expr_is_device(sub.value, out):
                    if isinstance(sub.target, ast.Name):
                        out.add(sub.target.id)
        return out

    def _expr_is_device(self, node: ast.AST,
                        device_vars: Set[str]) -> bool:
        """Does this expression's value (heuristically) live on device?"""
        if isinstance(node, ast.Attribute) and \
                node.attr in _STATIC_ATTRS:
            return False  # x.shape / x.ndim / x.dtype: static metadata
        if isinstance(node, ast.Compare) and \
                all(isinstance(op, (ast.Is, ast.IsNot))
                    for op in node.ops):
            return False  # `x is None` — host identity check
        if isinstance(node, ast.Call):
            canon = self._canon_call(node)
            if canon in _HOST_RESULT_CALLS:
                return False  # dtype predicates / sanctioned transfer
            if isinstance(node.func, ast.Name) and \
                    node.func.id in _HOST_RESULT_NAMES:
                return False
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _HOST_RESULT_METHODS:
                return False
            if canon and (canon.startswith(_DEVICE_CALL_PREFIXES)
                          or canon == "jax.numpy"):
                return True
        if isinstance(node, ast.Name) and node.id in device_vars:
            return True
        for child in ast.iter_child_nodes(node):
            if self._expr_is_device(child, device_vars):
                return True
        return False

    def _device_ctx(self, node: ast.AST) -> Set[str]:
        fn = _enclosing_function(node)
        return self._device_vars.get(fn, set()) if fn is not None \
            else set()

    # -- emit -----------------------------------------------------------

    def _emit(self, node: ast.AST, rule: str, message: str) -> None:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        text = self.lines[line - 1] if 0 < line <= len(self.lines) \
            else ""
        self.findings.append(Finding(
            path=self.path, line=line, col=col, rule=rule,
            message=message, hint=hint_for(rule),
            source_line=text))

    # -- rules -----------------------------------------------------------

    def run(self) -> List[Finding]:
        self._rule_branching()      # claims If/While tests first
        self._rule_host_sync()
        self._rule_numpy_in_jit()
        self._rule_prng_discipline()
        self._rule_missing_donation()
        by_line = suppressions_for_source(self.src)
        return apply_suppressions(
            sorted(self.findings,
                   key=lambda f: (f.line, f.col, f.rule)), by_line)

    # FTL005 — Python branching on traced values ------------------------
    def _rule_branching(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.If, ast.While, ast.Assert,
                                     ast.IfExp)):
                continue
            test = node.test
            dv = self._device_ctx(node)
            traced = self._in_traced(node)
            if traced and self._expr_is_device(test, dv):
                self._claimed_tests.add(test)
                self._emit(
                    test, "FTL005",
                    "Python branch on a traced value inside jitted "
                    "code — this concretizes at trace time")
                continue
            # host-side: branching via a scalar-coercion idiom on a
            # device value (`if float(jnp...) > t:`) — a per-iteration
            # sync when it sits in a round loop
            for sub in ast.walk(test):
                if isinstance(sub, ast.Call) and self._is_host_sync(
                        sub, dv):
                    self._claimed_tests.add(test)
                    self._emit(
                        test, "FTL005",
                        "Python branch on a host-coerced device value "
                        "— a device sync per evaluation")
                    break

    def _under_claimed_test(self, node: ast.AST) -> bool:
        cur = node
        while cur is not None:
            if cur in self._claimed_tests:
                return True
            cur = getattr(cur, "_lint_parent", None)
        return False

    # FTL001 — host syncs ----------------------------------------------
    def _is_host_sync(self, call: ast.Call, device_vars: Set[str]) \
            -> bool:
        func = call.func
        if isinstance(func, ast.Name) and func.id in _COERCIONS \
                and len(call.args) == 1 and not call.keywords:
            return self._expr_is_device(call.args[0], device_vars)
        if isinstance(func, ast.Attribute) and func.attr == "item" \
                and not call.args:
            return self._expr_is_device(func.value, device_vars)
        canon = self._canon_call(call)
        if canon in ("numpy.asarray", "numpy.array") and call.args:
            return self._expr_is_device(call.args[0], device_vars)
        return False

    def _rule_host_sync(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            if self._under_claimed_test(node):
                continue  # FTL005 already owns this site
            dv = self._device_ctx(node)
            if not self._is_host_sync(node, dv):
                continue
            if self._in_traced(node):
                self._emit(node, "FTL001",
                           "host sync / concretization of a traced "
                           "value inside jitted code")
            else:
                self._emit(node, "FTL001",
                           "host sync on a device value — a blocking "
                           "device->host transfer per call")

    # FTL002 — numpy on traced values inside jit ------------------------
    def _rule_numpy_in_jit(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            canon = self._canon_call(node)
            if not canon or not canon.startswith("numpy."):
                continue
            if canon in ("numpy.asarray", "numpy.array"):
                continue  # FTL001's (host sync flavor)
            if not self._in_traced(node):
                continue  # numpy at setup time is legal
            dv = self._device_ctx(node)
            if any(self._expr_is_device(a, dv) for a in node.args) or \
                    any(self._expr_is_device(kw.value, dv)
                        for kw in node.keywords):
                self._emit(node, "FTL002",
                           f"{canon.replace('numpy', 'np')} applied to "
                           "a traced value inside jitted code — the "
                           "result is a trace-time constant (or a "
                           "TracerArrayConversionError)")

    # FTL003 — PRNG key discipline --------------------------------------
    def _rule_prng_discipline(self) -> None:
        for fn in self.functions:
            if isinstance(fn, ast.Lambda):
                continue
            # parameters are keys bound OUTSIDE any loop in the body:
            # consuming one inside a loop is the classic reuse bug
            state = {a.arg: {"used": False, "loop_depth": 0}
                     for a in (fn.args.posonlyargs + fn.args.args
                               + fn.args.kwonlyargs)}
            self._prng_walk(fn.body, state, loop_depth=0)

    def _random_call_kind(self, call: ast.Call) -> Optional[str]:
        canon = self._canon_call(call)
        if not canon or not canon.startswith("jax.random."):
            return None
        tail = canon.rsplit(".", 1)[-1]
        return "derive" if tail in _KEY_DERIVERS else "consume"

    def _prng_uses_in(self, node: ast.AST, state: Dict[str, dict],
                      loop_depth: int) -> None:
        """Record key consumptions inside one expression subtree.
        Names bound by comprehension generators within the subtree are
        exempt (fresh per element — ``for kk in keys``)."""
        comp_targets: Set[str] = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.comprehension):
                comp_targets.update(self._target_names(sub.target))
            elif isinstance(sub, ast.Lambda):
                comp_targets.update(a.arg for a in sub.args.args)
        for sub in ast.walk(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested defs get their own walk
            if not isinstance(sub, ast.Call):
                continue
            if self._random_call_kind(sub) != "consume":
                continue
            arg = sub.args[0] if sub.args else None
            if not isinstance(arg, ast.Name) or \
                    arg.id in comp_targets:
                continue
            name = arg.id
            st = state.get(name)
            if st is None:
                state[name] = {"used": True, "loop_depth": loop_depth}
            elif st["used"]:
                self._emit(sub, "FTL003",
                           f"PRNG key '{name}' consumed again without "
                           "an intervening split/fold_in")
            elif loop_depth > st["loop_depth"]:
                self._emit(sub, "FTL003",
                           f"PRNG key '{name}' bound outside this "
                           "loop is consumed every iteration — same "
                           "stream each time")
            else:
                st["used"] = True

    def _derives_key(self, expr: ast.AST) -> bool:
        """Does this RHS derive fresh key(s)?  Covers direct calls,
        ``split(...)[0]`` subscripts, and generator/tuple expressions
        of fold_in/split calls — but not mixed consume exprs."""
        derive = consume = False
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Call):
                kind = self._random_call_kind(sub)
                derive |= kind == "derive"
                consume |= kind == "consume"
        return derive and not consume

    def _prng_walk(self, stmts, state: Dict[str, dict],
                   loop_depth: int) -> None:
        """Forward pass over a statement list in source order.
        ``state[name]`` is {"used": bool, "loop_depth": bound-at}.
        Compound statements contribute only their header expressions
        here; their bodies are recursed into exactly once."""
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                continue  # analyzed as its own function
            if isinstance(stmt, (ast.For, ast.AsyncFor)):
                self._prng_uses_in(stmt.iter, state, loop_depth)
                # loop targets rebind fresh each iteration (e.g.
                # `for kk in jax.random.split(key, n)`)
                for n in self._target_names(stmt.target):
                    state[n] = {"used": False,
                                "loop_depth": loop_depth + 1}
                self._prng_walk(stmt.body, state, loop_depth + 1)
                self._prng_walk(stmt.orelse, state, loop_depth)
            elif isinstance(stmt, ast.While):
                self._prng_uses_in(stmt.test, state, loop_depth)
                self._prng_walk(stmt.body, state, loop_depth + 1)
                self._prng_walk(stmt.orelse, state, loop_depth)
            elif isinstance(stmt, ast.If):
                self._prng_uses_in(stmt.test, state, loop_depth)
                # branch-local DEEP copies: the per-key value dicts are
                # mutated in place, so a shallow dict(state) would leak
                # one branch's consumption into its exclusive sibling
                self._prng_walk(stmt.body, _copy_state(state),
                                loop_depth)
                self._prng_walk(stmt.orelse, _copy_state(state),
                                loop_depth)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    self._prng_uses_in(item.context_expr, state,
                                       loop_depth)
                self._prng_walk(stmt.body, state, loop_depth)
            elif isinstance(stmt, ast.Try):
                self._prng_walk(stmt.body, state, loop_depth)
                for h in stmt.handlers:
                    self._prng_walk(h.body, _copy_state(state),
                                    loop_depth)
                self._prng_walk(stmt.orelse, _copy_state(state),
                                loop_depth)
                self._prng_walk(stmt.finalbody, state, loop_depth)
            else:
                self._prng_uses_in(stmt, state, loop_depth)
                # rebinding from a deriving expr refreshes the name(s)
                if isinstance(stmt, ast.Assign) and \
                        self._derives_key(stmt.value):
                    for tgt in stmt.targets:
                        for n in self._target_names(tgt):
                            state[n] = {"used": False,
                                        "loop_depth": loop_depth}

    # FTL004 — missing donation -----------------------------------------
    def _rule_missing_donation(self) -> None:
        seen: Set[ast.AST] = set()
        for fn, has_donate, site in self._jit_bindings:
            if fn in seen or has_donate or isinstance(fn, ast.Lambda):
                continue
            seen.add(fn)
            params = {a.arg for a in (fn.args.posonlyargs + fn.args.args)
                      if a.arg not in ("self", "cls")}
            if not params:
                continue
            derived = set(params)
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Assign):
                    if any(isinstance(n, ast.Name) and n.id in derived
                           for n in ast.walk(sub.value)):
                        for tgt in sub.targets:
                            for n in ast.walk(tgt):
                                if isinstance(n, ast.Name):
                                    derived.add(n.id)
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Return) and sub.value is not None:
                    if _enclosing_function(sub) is not fn:
                        continue
                    if any(isinstance(n, ast.Name) and n.id in derived
                           for n in ast.walk(sub.value)):
                        self._emit(
                            site, "FTL004",
                            f"jitted '{fn.name}' returns arrays "
                            "derived from its arguments but the jit "
                            "has no donate_argnums — input and "
                            "output buffers stay live together")
                        break


def analyze_source(src: str, path: str = "<string>") -> List[Finding]:
    """Findings for one module's source text (sorted by line)."""
    return ModuleAnalysis(src, path).run()


def iter_py_files(root: str, targets) -> List[str]:
    out = []
    for t in targets:
        full = os.path.join(root, t)
        if os.path.isfile(full) and full.endswith(".py"):
            out.append(full)
        elif os.path.isdir(full):
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", ".git",
                                            ".jax_cache")]
                for f in sorted(filenames):
                    if f.endswith(".py"):
                        out.append(os.path.join(dirpath, f))
    return sorted(set(out))


def analyze_paths(root: str, targets) -> List[Finding]:
    """Findings for every .py under ``targets`` (repo-relative)."""
    findings: List[Finding] = []
    for full in iter_py_files(root, targets):
        rel = os.path.relpath(full, root).replace(os.sep, "/")
        try:
            src = open(full, encoding="utf-8").read()
            findings.extend(analyze_source(src, rel))
        except (SyntaxError, UnicodeDecodeError) as e:
            findings.append(Finding(
                path=rel, line=getattr(e, "lineno", 1) or 1, col=0,
                rule="FTL000", message=f"could not analyze: {e}",
                hint="", source_line=""))
    return findings
