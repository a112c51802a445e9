"""tpulint JAX/TPU rules (TPU1xx) — the bug classes round 5 paid for.

All four rules hinge on knowing which functions are *traced*: decorated
with ``jax.jit``/``pjit`` (directly or via ``functools.partial``),
passed to ``jax.jit``/``pjit`` as a value, used as a ``jax.lax.scan``
body, or lexically nested inside any of those. ``_traced_functions``
computes that set once per module; each rule then walks only the traced
bodies (or, for TPU103, only the import-time surface).
"""

from __future__ import annotations

import ast
import builtins
from typing import Iterator

from kubeflow_tpu.analysis.core import (
    Finding, Module, Rule, call_name, dotted, register,
)

_JITS = {"jax.jit", "jit", "pjit", "jax.pjit", "jax.experimental.pjit.pjit"}
_SCANS = {"jax.lax.scan", "lax.scan"}
_PARTIALS = {"functools.partial", "partial"}
_BUILTINS = frozenset(dir(builtins))

# module roots whose calls build arrays (device or host) when executed
_ARRAY_ROOTS = ("jnp.", "np.", "numpy.", "jax.numpy.")
# ...except pure metadata helpers, which return dtypes/scalars, not buffers
_META_TAILS = {"finfo", "iinfo", "dtype", "shape", "ndim", "result_type",
               "issubdtype", "promote_types"}
_ARRAY_EXACT = {"jax.device_put"}
_ARRAY_PREFIX = ("jax.random.",)

# enclosing-scope parameter names that conventionally hold weight trees
_PARAMISH = ("params", "variables", "weights", "state", "cache")


def _is_array_call(call: ast.Call) -> bool:
    name = call_name(call)
    if not name:
        return False
    if name in _ARRAY_EXACT or name.startswith(_ARRAY_PREFIX):
        return True
    if any(name.startswith(r) for r in _ARRAY_ROOTS):
        return name.rsplit(".", 1)[-1] not in _META_TAILS
    return False


def _paramish(name: str) -> bool:
    return name in _PARAMISH or name.endswith(
        ("_params", "_vars", "_variables", "_weights", "_state", "_cache"))


def _jit_decorator(fn: ast.FunctionDef) -> ast.expr | None:
    for dec in fn.decorator_list:
        if dotted(dec) in _JITS:
            return dec
        if isinstance(dec, ast.Call):
            name = call_name(dec)
            if name in _JITS:
                return dec
            if (name in _PARTIALS and dec.args
                    and dotted(dec.args[0]) in _JITS):
                return dec
    return None


def _scope_of(module: Module, node: ast.AST) -> ast.AST:
    for anc in module.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef, ast.Module)):
            return anc
    return module.tree


def _callable_args(call: ast.Call) -> list[ast.expr]:
    """First-positional-argument expressions that may name a function
    (unwrapping conditional selection like ``a if cond else b``)."""
    if not call.args:
        return []
    head = call.args[0]
    if isinstance(head, ast.IfExp):
        return [head.body, head.orelse]
    return [head]


def _static_names(fn: ast.FunctionDef, jit_node: ast.expr | None) -> set[str]:
    """Names the jit treats as static (static_argnames/static_argnums)."""
    if not isinstance(jit_node, ast.Call):
        return set()
    out: set[str] = set()
    pos = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    for kw in jit_node.keywords:
        val = kw.value
        items = val.elts if isinstance(val, (ast.Tuple, ast.List)) else [val]
        if kw.arg == "static_argnames":
            out |= {e.value for e in items
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)}
        elif kw.arg == "static_argnums":
            for e in items:
                if (isinstance(e, ast.Constant) and isinstance(e.value, int)
                        and e.value < len(pos)):
                    out.add(pos[e.value])
    return out


def _traced_functions(module: Module) -> dict[ast.FunctionDef, dict]:
    """Map every traced FunctionDef to {'jit': node|None, 'kind': str},
    computed once per module (memoized — TPU101 and TPU102 share it).

    kind is 'jit' (the jit root), 'scan' (a lax.scan body), or 'nested'
    (lexically inside another traced function, hence traced with it).
    """
    cached = getattr(module, "_tpulint_traced", None)
    if cached is not None:
        return cached
    defs: list[ast.FunctionDef] = [
        n for n in ast.walk(module.tree) if isinstance(n, ast.FunctionDef)]
    by_scope: dict[ast.AST, dict[str, ast.FunctionDef]] = {}
    for fn in defs:
        by_scope.setdefault(_scope_of(module, fn), {})[fn.name] = fn

    def resolve(call: ast.Call, name: str) -> ast.FunctionDef | None:
        scope: ast.AST | None = _scope_of(module, call)
        while scope is not None:
            fn = by_scope.get(scope, {}).get(name)
            if fn is not None:
                return fn
            scope = (None if isinstance(scope, ast.Module)
                     else _scope_of(module, scope))
        return None

    traced: dict[ast.FunctionDef, dict] = {}
    for fn in defs:
        dec = _jit_decorator(fn)
        if dec is not None:
            traced[fn] = {"jit": dec, "kind": "jit"}
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if name in _JITS or name in _SCANS:
            for arg in _callable_args(node):
                target = dotted(arg)
                fn = resolve(node, target) if target else None
                if fn is not None and fn not in traced:
                    traced[fn] = {
                        "jit": node if name in _JITS else None,
                        "kind": "jit" if name in _JITS else "scan"}
    # closure: nested defs trace with their parent
    for fn in defs:
        if fn in traced:
            continue
        for anc in module.ancestors(fn):
            if isinstance(anc, ast.FunctionDef) and anc in traced:
                traced[fn] = {"jit": traced[anc]["jit"], "kind": "nested"}
                break
    module._tpulint_traced = traced
    return traced


def _own_nodes(fn: ast.FunctionDef) -> Iterator[ast.AST]:
    """Walk fn's body without descending into nested function defs
    (those are traced entries of their own)."""
    stack: list[ast.AST] = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _param_names(fn: ast.FunctionDef) -> set[str]:
    a = fn.args
    names = {x.arg for x in a.posonlyargs + a.args + a.kwonlyargs}
    if a.vararg:
        names.add(a.vararg.arg)
    if a.kwarg:
        names.add(a.kwarg.arg)
    return names


def _bound_names(fn: ast.FunctionDef) -> set[str]:
    """Names assigned anywhere inside fn (its locals)."""
    out = _param_names(fn)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(
                node.ctx, (ast.Store, ast.Del)):
            out.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)) and node is not fn:
            out.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                out.add((alias.asname or alias.name).split(".")[0])
    return out


def _module_globals(module: Module) -> set[str]:
    out: set[str] = set()
    for node in module.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            out.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                out.add((alias.asname or alias.name).split(".")[0])
        else:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
                    out.add(sub.id)
    return out


@register
class ClosureCapturedArray(Rule):
    """TPU101: array built in an enclosing scope, captured by a traced
    function. The capture is serialized into the jitted program as an
    inline constant — the 700MB-MLIR / retrace-per-swap bug class.
    Arrays must flow through jit arguments."""

    id = "TPU101"
    name = "closure-captured-array"
    short = "traced function closes over an array built outside its jit root"

    def check(self, module: Module) -> Iterator[Finding]:
        traced = _traced_functions(module)
        g = _module_globals(module)
        for fn in traced:
            root = self._jit_root(module, traced, fn)
            if root is None:
                continue  # scan body with no jit boundary in this module:
                # captures stay inside whatever trace invokes it
            if module.enclosing_function(root) is None:
                continue  # module-level jit root: no function closure
            local = _bound_names(fn)
            reported: set[str] = set()
            for node in _own_nodes(fn):
                if not (isinstance(node, ast.Name)
                        and isinstance(node.ctx, ast.Load)):
                    continue
                name = node.id
                if (name in local or name in g or name in _BUILTINS
                        or name in reported):
                    continue
                verdict = self._classify(module, traced, fn, root, name)
                if verdict:
                    reported.add(name)
                    yield self.finding(module, node, verdict)

    @staticmethod
    def _jit_root(module: Module, traced: dict,
                  fn: ast.FunctionDef) -> ast.FunctionDef | None:
        """Outermost enclosing-or-self traced function entered via
        jax.jit/pjit. Bindings inside it are tracers (same trace);
        bindings *outside* it are host values a capture would bake in."""
        root = fn if traced[fn]["kind"] == "jit" else None
        for anc in module.ancestors(fn):
            if (isinstance(anc, ast.FunctionDef) and anc in traced
                    and traced[anc]["kind"] == "jit"):
                root = anc
        return root

    def _classify(self, module: Module, traced: dict, fn: ast.FunctionDef,
                  root: ast.FunctionDef, name: str) -> str | None:
        """Walk enclosing function scopes for name's binding; report iff
        the binding is array-valued evidence AND lives outside the jit
        root (a host value serialized into the program)."""
        host_scopes = {anc for anc in module.ancestors(root)
                       if isinstance(anc, ast.FunctionDef)}
        scope = module.enclosing_function(fn)
        while scope is not None:
            if scope not in host_scopes:
                # scopes at or inside the jit root are part of the same
                # trace — captures there are tracers, not constants
                if name in _param_names(scope) or any(
                        isinstance(t, ast.Name) and t.id == name
                        for sub in ast.walk(scope)
                        if isinstance(sub, ast.Assign) for t in sub.targets):
                    return None
                scope = module.enclosing_function(scope)
                continue
            if name in _param_names(scope):
                if scope not in traced and _paramish(name):
                    return (f"traced function '{fn.name}' closes over "
                            f"'{name}', a parameter of '{scope.name}' that "
                            "by name holds arrays; the tree is inlined into "
                            "the jitted program as constants — pass it as a "
                            "jit argument")
                return None
            for sub in ast.walk(scope):
                if (isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and sub is not scope):
                    continue
                if isinstance(sub, ast.Assign):
                    targets = sub.targets
                elif isinstance(sub, ast.AnnAssign) and sub.value is not None:
                    targets = [sub.target]
                else:
                    continue
                for t in targets:
                    if (isinstance(t, ast.Name) and t.id == name
                            and isinstance(sub.value, ast.Call)
                            and _is_array_call(sub.value)):
                        return (f"traced function '{fn.name}' closes over "
                                f"array '{name}' built at line "
                                f"{sub.value.lineno} "
                                f"({call_name(sub.value)}); it is baked into "
                                "the jitted program as a constant — pass it "
                                "as a jit argument instead")
                    if isinstance(t, ast.Name) and t.id == name:
                        return None  # bound, but not to array evidence
            scope = module.enclosing_function(scope)
        return None


@register
class HostSyncInJit(Rule):
    """TPU102: host-synchronizing call inside a traced function. These
    either fail at trace time (``.item``/``float`` on tracers) or, via
    callbacks, serialize device and host per step — the dispatch-bound
    decode-loop class."""

    id = "TPU102"
    name = "host-sync-in-jit"
    short = "host-synchronizing call inside a traced function"

    _SYNC_DOTTED = {"jax.device_get", "np.asarray", "np.array",
                    "numpy.asarray", "numpy.array"}

    def check(self, module: Module) -> Iterator[Finding]:
        traced = _traced_functions(module)
        for fn, info in traced.items():
            static = _static_names(fn, info.get("jit"))
            params = _param_names(fn)
            for node in _own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                if isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "item" and not node.args:
                    yield self.finding(
                        module, node,
                        f".item() inside traced '{fn.name}' forces a "
                        "device->host sync (or a tracer error); return the "
                        "array and read it outside the jit")
                elif name in self._SYNC_DOTTED:
                    yield self.finding(
                        module, node,
                        f"{name}() inside traced '{fn.name}' pulls the value "
                        "to host; use jnp ops (or move the conversion "
                        "outside the jit)")
                elif name == "print":
                    yield self.finding(
                        module, node,
                        f"print() inside traced '{fn.name}' runs at trace "
                        "time only; use jax.debug.print for runtime values")
                elif name in ("float", "int") and len(node.args) == 1:
                    arg = node.args[0]
                    if (isinstance(arg, ast.Name) and arg.id in params
                            and arg.id not in static):
                        yield self.finding(
                            module, node,
                            f"{name}() on traced argument '{arg.id}' in "
                            f"'{fn.name}' concretizes a tracer (host sync "
                            "or trace error); keep it as an array or mark "
                            "it static")


@register
class JnpAtImport(Rule):
    """TPU103: jnp/jax array construction at import time. Import-time
    device work breaks JAX_PLATFORMS selection, initializes the backend
    before the mesh exists, and runs on every process that so much as
    imports the module (controllers included)."""

    id = "TPU103"
    name = "jnp-at-import"
    short = "jnp/jax array construction executed at module import"

    def check(self, module: Module) -> Iterator[Finding]:
        for call in self._import_time_calls(module.tree.body):
            yield self.finding(
                module, call,
                f"{call_name(call)}() runs at module import; build the "
                "array lazily (inside the function that uses it) so "
                "importing never touches the backend")

    def _import_time_calls(self, stmts) -> Iterator[ast.Call]:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # bodies are lazy, but decorators and defaults evaluate now
                eager = (stmt.decorator_list + stmt.args.defaults
                         + [d for d in stmt.args.kw_defaults if d])
                for expr in eager:
                    yield from self._calls_in(expr)
            elif isinstance(stmt, ast.ClassDef):
                yield from self._import_time_calls(stmt.body)
                for expr in stmt.decorator_list:
                    yield from self._calls_in(expr)
            else:
                yield from self._calls_in(stmt)

    def _calls_in(self, node: ast.AST) -> Iterator[ast.Call]:
        stack = [node]
        while stack:
            cur = stack.pop()
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue  # lazy bodies
            if isinstance(cur, ast.Call) and _is_array_call(cur) \
                    and call_name(cur).split(".")[0] not in ("np", "numpy"):
                yield cur  # host numpy at import is cheap: allowed
            stack.extend(ast.iter_child_nodes(cur))


@register
class MissingDonate(Rule):
    """TPU104: a train/update-step jit without buffer donation. The
    threaded state (params+opt) is then copied every step — 2x HBM for
    the largest live tree and measurable step-time tax at scale."""

    id = "TPU104"
    name = "missing-donate"
    short = "train-step jit without donate_argnums"

    _STEPPISH = ("train_step", "update_step")

    def _steppish(self, name: str | None) -> bool:
        return bool(name) and any(s in name for s in self._STEPPISH)

    def _has_donate(self, call: ast.Call) -> bool:
        return any(kw.arg in ("donate_argnums", "donate_argnames")
                   for kw in call.keywords)

    def check(self, module: Module) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.FunctionDef) and self._steppish(node.name):
                dec = _jit_decorator(node)
                if isinstance(dec, ast.Call) and not self._has_donate(dec):
                    yield self._emit(module, dec, node.name)
                elif dec is not None and not isinstance(dec, ast.Call):
                    yield self._emit(module, dec, node.name)  # bare @jax.jit
            elif isinstance(node, ast.Call) and call_name(node) in _JITS \
                    and not self._has_donate(node):
                for arg in _callable_args(node):
                    target = dotted(arg)
                    if self._steppish(target):
                        yield self._emit(module, node, target)
                        break

    def _emit(self, module: Module, node: ast.AST, name: str) -> Finding:
        return self.finding(
            module, node,
            f"jit of '{name}' without donate_argnums/donate_argnames: the "
            "threaded train state is copied instead of donated, doubling "
            "its HBM footprint every step")
