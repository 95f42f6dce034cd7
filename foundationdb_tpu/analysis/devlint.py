"""devlint rules DEV001..DEV008: JAX/device discipline for the hot path.

The conflict kernel's throughput story (docs/performance.md) died a dozen
small deaths before this existed: a re-traced jit in the rebalance path, an
eager un-donated state rebase, raw device transfers scattered outside the
jaxenv choke points. Each rule encodes one of those bug classes; like the
flow family they are static approximations tuned to never miss the
exemplar shape (tests/test_devlint.py pins both directions per rule).

DEV001 and DEV006 are interprocedural: they consume the PackageContext
call graph (callgraph.py) and per-function summaries, so a coroutine that
calls a blocking helper defined two modules away is flagged at the call
site. Resolution is conservative — an attribute call on an arbitrary
receiver only counts when EVERY same-named method in the package shares
the property, and unresolvable calls are assumed fine — so the family
under-approximates rather than spray false positives.
"""

from __future__ import annotations

import ast
from typing import Iterable

from foundationdb_tpu.analysis.callgraph import FunctionInfo, PackageContext
from foundationdb_tpu.analysis.flowlint import (
    Finding, ModuleContext, Rule, register)

# device→host synchronization points (DEV001)
_ALWAYS_BLOCKING = {"jax.block_until_ready", "jax.device_get"}
# host materializers: blocking only when fed a device-tainted value
_HOST_MATERIALIZERS = {"numpy.asarray", "numpy.array"}
# tracing wrappers whose per-call construction costs a re-trace (DEV002)
_TRACE_CTORS = {"jax.jit", "jax.vmap", "jax.pmap"}
# jnp constructors whose size argument bakes into the compiled program (DEV005)
_JNP_SIZED_CTORS = {
    "jax.numpy.zeros", "jax.numpy.ones", "jax.numpy.full", "jax.numpy.empty",
    "jax.numpy.arange", "jax.numpy.eye", "jax.numpy.linspace",
    "jax.numpy.tri", "jax.numpy.broadcast_to",
}
# raw transfer entry points (DEV007); jaxenv.py is the sanctioned home
_TRANSFER_FNS = {
    "jax.device_put", "jax.device_get", "jax.device_put_sharded",
    "jax.device_put_replicated",
}
_SANCTIONED_TRANSFER_MODULE = "foundationdb_tpu/utils/jaxenv.py"
# np.random.* entry points that do NOT share the module-global PRNG (DEV008)
_NP_RANDOM_OK = {
    "numpy.random.RandomState", "numpy.random.default_rng",
    "numpy.random.Generator", "numpy.random.SeedSequence",
    "numpy.random.PCG64", "numpy.random.Philox", "numpy.random.MT19937",
}
# jax.random.* that produce/derive keys rather than consuming one (DEV008)
_JAX_RANDOM_NONCONSUMING = {"split", "PRNGKey", "key", "fold_in",
                            "wrap_key_data", "key_data", "clone"}


def _origin(mod: ModuleContext, node: ast.AST) -> str | None:
    return mod.resolve_dotted(node)


def _owned(mod: ModuleContext, fn: ast.AST):
    """Nodes whose nearest enclosing def is `fn` (lambda bodies included,
    nested defs excluded)."""
    for node in ast.walk(fn):
        if mod.enclosing_function(node) is fn:
            yield node


def _module_level(mod: ModuleContext):
    for node in ast.walk(mod.tree):
        if mod.enclosing_function(node) is None:
            yield node


def _jax_rooted(mod: ModuleContext, expr: ast.AST) -> bool:
    """Expression contains a call/attribute chain resolving into jax.*."""
    for node in ast.walk(expr):
        if isinstance(node, (ast.Attribute, ast.Name)):
            origin = _origin(mod, node)
            if origin and (origin == "jax" or origin.startswith("jax.")):
                return True
    return False


def _sanctioned_offload(mod: ModuleContext, node: ast.AST) -> bool:
    """Inside an argument handed to `*.run_blocking(...)` — the loop's
    worker-thread offload, where blocking on the device is the point."""
    for anc in mod.ancestors(node):
        if isinstance(anc, ast.Call) \
                and isinstance(anc.func, ast.Attribute) \
                and anc.func.attr == "run_blocking" \
                and not any(node is n for n in ast.walk(anc.func)):
            return True
    return False


def _self_attr(node: ast.AST) -> str | None:
    """"X" for `self.X`, else None."""
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


def _same_target(a: ast.AST, b: ast.AST) -> bool:
    if isinstance(a, ast.Name) and isinstance(b, ast.Name):
        return a.id == b.id
    sa, sb = _self_attr(a), _self_attr(b)
    return sa is not None and sa == sb


# ---------------------------------------------------------------------------
# shared package analysis (computed once, cached on the PackageContext)
# ---------------------------------------------------------------------------

class _DevAnalysis:
    """Call-graph summaries every DEV rule shares: device taint, the
    blocks-on-host fixpoint, jit targets and trace reachability."""

    def __init__(self, pkg: PackageContext):
        self.pkg = pkg
        self._taint: dict[str, set[str]] = {}
        self._compute_blocking()
        self._compute_jit_targets()

    # ---------------------------------------------------------- device taint

    def tainted_names(self, fn: FunctionInfo) -> set[str]:
        """Local names assigned from jnp/jax-rooted expressions (two
        propagation passes: tainted = device value until proven host)."""
        cached = self._taint.get(fn.fqname)
        if cached is not None:
            return cached
        tainted: set[str] = set()
        assigns = [n for n in _owned(fn.mod, fn.node)
                   if isinstance(n, ast.Assign) and len(n.targets) == 1
                   and isinstance(n.targets[0], ast.Name)]
        for _ in range(2):
            for n in assigns:
                name = n.targets[0].id
                if name in tainted:
                    continue
                if _jax_rooted(fn.mod, n.value) or any(
                        isinstance(x, ast.Name) and x.id in tainted
                        for x in ast.walk(n.value)):
                    tainted.add(name)
        self._taint[fn.fqname] = tainted
        return tainted

    def _is_tainted_expr(self, fn: FunctionInfo, expr: ast.AST) -> bool:
        if _jax_rooted(fn.mod, expr):
            return True
        tainted = self.tainted_names(fn)
        return any(isinstance(x, ast.Name) and x.id in tainted
                   for x in ast.walk(expr))

    # ------------------------------------------------- blocks-on-host summary

    def _direct_blocks(self, fn: FunctionInfo) -> list[tuple[ast.AST, str]]:
        out: list[tuple[ast.AST, str]] = []
        mod = fn.mod
        for node in _owned(mod, fn.node):
            if not isinstance(node, ast.Call):
                continue
            if _sanctioned_offload(mod, node):
                continue
            func = node.func
            if isinstance(func, ast.Attribute) \
                    and func.attr == "block_until_ready":
                out.append((node, "block_until_ready"))
                continue
            if isinstance(func, ast.Attribute) and func.attr == "item" \
                    and node.args == [] \
                    and self._is_tainted_expr(fn, func.value):
                out.append((node, ".item"))
                continue
            origin = _origin(mod, func)
            if origin in _ALWAYS_BLOCKING:
                out.append((node, origin))
            elif origin in _HOST_MATERIALIZERS and node.args \
                    and self._is_tainted_expr(fn, node.args[0]):
                out.append((node, origin))
            elif isinstance(func, ast.Name) and func.id in ("float", "int") \
                    and len(node.args) == 1 \
                    and self._is_tainted_expr(fn, node.args[0]):
                out.append((node, func.id))
        return out

    def _compute_blocking(self) -> None:
        """Fixpoint: a function blocks on host if it contains a blocking
        primitive, or if every candidate of one of its (non-offloaded)
        calls blocks. Call sites that introduced blocking are recorded for
        DEV001's at-the-call-site reporting."""
        for fn in self.pkg.iter_functions():
            direct = self._direct_blocks(fn)
            fn.summary["direct_blocks"] = direct
            fn.summary["blocks"] = bool(direct)
            fn.summary["blocking_calls"] = []
            fn.summary["calls"] = [
                n for n in _owned(fn.mod, fn.node)
                if isinstance(n, ast.Call)
                and not _sanctioned_offload(fn.mod, n)]
        changed = True
        while changed:
            changed = False
            for fn in self.pkg.iter_functions():
                if fn.summary["blocks"] and not fn.summary["calls"]:
                    continue
                for call in fn.summary["calls"]:
                    cands = self.pkg.resolve_call(fn.mod, call)
                    cands = [c for c in cands if c.fqname != fn.fqname]
                    if not cands or not all(c.summary["blocks"]
                                            for c in cands):
                        continue
                    rec = (call, cands[0].qualname)
                    if rec not in fn.summary["blocking_calls"]:
                        fn.summary["blocking_calls"].append(rec)
                    if not fn.summary["blocks"]:
                        fn.summary["blocks"] = True
                        changed = True

    # --------------------------------------------- jit targets & reachability

    def _partial_of_jit(self, mod: ModuleContext,
                        call: ast.Call) -> ast.Call | None:
        """The inner functools.partial(f, ...) of jax.jit(partial(f, ...))."""
        if call.args and isinstance(call.args[0], ast.Call) \
                and _origin(mod, call.args[0].func) == "functools.partial":
            return call.args[0]
        return None

    def _static_argnum_names(self, fnnode, call: ast.Call) -> set[str]:
        names: set[str] = set()
        params = [a.arg for a in fnnode.args.posonlyargs + fnnode.args.args]
        for kw in call.keywords:
            if kw.arg == "static_argnums":
                vals = (kw.value.elts
                        if isinstance(kw.value, (ast.Tuple, ast.List))
                        else [kw.value])
                for v in vals:
                    if isinstance(v, ast.Constant) and isinstance(v.value, int) \
                            and v.value < len(params):
                        names.add(params[v.value])
            elif kw.arg == "static_argnames":
                for x in ast.walk(kw.value):
                    if isinstance(x, ast.Constant) and isinstance(x.value, str):
                        names.add(x.value)
        return names

    def _target_entry(self, info: FunctionInfo,
                      static_extra: set[str]) -> None:
        """Mark `info` as a direct trace target; traced params = positional
        params minus static ones. Keyword-only params count as static: in
        this codebase they are partial-bound or defaulted config (shapes,
        intra_rounds, ...), never runtime arrays."""
        args = info.node.args
        positional = [a.arg for a in args.posonlyargs + args.args]
        static = set(static_extra) | {a.arg for a in args.kwonlyargs}
        traced = {p for p in positional if p not in static and p != "self"}
        prev = self.jit_targets.get(info.fqname)
        if prev is not None:
            traced &= prev  # multiple jit sites: traced where ALL agree
        self.jit_targets[info.fqname] = traced

    def _jit_arg_candidates(self, mod, name: str) -> list[FunctionInfo]:
        """Functions a Name handed to jax.jit/shard_map may denote: normal
        resolution first, then a unique same-module NESTED def (factories
        like _build_sharded_step jit a closure-local step function)."""
        cands = self.pkg.resolve_call(
            mod, ast.Call(func=ast.Name(id=name), args=[], keywords=[]))
        if cands:
            return cands
        nested = [f for f in self.pkg.functions.values()
                  if f.relpath == mod.relpath and f.name == name]
        return nested if len(nested) == 1 else []

    def _compute_jit_targets(self) -> None:
        self.jit_targets: dict[str, set[str]] = {}
        for mod in self.pkg.modules:
            # decorated defs
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    info = self.pkg.functions.get(
                        f"{mod.relpath}::{mod.qualname(node)}")
                    if info is None:
                        continue
                    for dec in node.decorator_list:
                        static: set[str] = set()
                        target = None
                        if _origin(mod, dec) == "jax.jit":
                            target = info
                        elif isinstance(dec, ast.Call):
                            o = _origin(mod, dec.func)
                            if o == "jax.jit":
                                target = info
                                static = self._static_argnum_names(node, dec)
                            elif o == "functools.partial" and dec.args \
                                    and _origin(mod, dec.args[0]) == "jax.jit":
                                target = info
                                static = self._static_argnum_names(node, dec)
                        if target is not None:
                            self._target_entry(target, static)
                # functions passed to jax.jit(...) / shard_map(...)
                if not isinstance(node, ast.Call):
                    continue
                origin = _origin(mod, node.func)
                is_shard_map = (isinstance(node.func, ast.Name)
                                and node.func.id == "shard_map") \
                    or (origin or "").endswith(".shard_map")
                if origin != "jax.jit" and not is_shard_map:
                    continue
                if not node.args:
                    continue
                fn_arg = node.args[0]
                static = set()
                partial = self._partial_of_jit(mod, node)
                if partial is not None:
                    static = {kw.arg for kw in partial.keywords
                              if kw.arg is not None}
                    fn_arg = partial.args[0] if partial.args else None
                if isinstance(fn_arg, ast.Name):
                    for info in self._jit_arg_candidates(mod, fn_arg.id):
                        static |= self._static_argnum_names(info.node, node)
                        self._target_entry(info, static)

        # trace reachability: BFS from direct targets through resolvable
        # calls (a helper called from inside a jitted function runs traced,
        # so its shapes are static by construction)
        self.trace_reachable: set[str] = set(self.jit_targets)
        frontier = [self.pkg.functions[fq] for fq in self.jit_targets
                    if fq in self.pkg.functions]
        while frontier:
            fn = frontier.pop()
            for node in ast.walk(fn.node):
                if not isinstance(node, ast.Call):
                    continue
                for cand in self.pkg.resolve_call(fn.mod, node):
                    if cand.fqname not in self.trace_reachable:
                        self.trace_reachable.add(cand.fqname)
                        frontier.append(cand)


def _analysis(pkg: PackageContext) -> _DevAnalysis:
    a = pkg.caches.get("devlint")
    if a is None:
        a = _DevAnalysis(pkg)
        pkg.caches["devlint"] = a
    return a


# -------------------------------------------------------------- DEV001

@register
class ImplicitReadbackInActor(Rule):
    code = "DEV001"
    summary = ("device→host readback (block_until_ready / device_get / "
               "np.asarray / float() / .item() on device values) inside a "
               "sim-visible coroutine — blocks the event loop; offload via "
               "loop.run_blocking. Interprocedural: a helper that blocks is "
               "flagged at the coroutine's call site.")

    def check_package(self, pkg: PackageContext) -> Iterable[Finding]:
        ana = _analysis(pkg)
        for fn in pkg.iter_functions():
            if not fn.is_async or not fn.mod.sim_visible:
                continue
            for node, detail in fn.summary.get("direct_blocks", ()):
                yield self.finding(
                    fn.mod, node, detail,
                    f"{detail} synchronizes device→host on the event-loop "
                    f"thread inside coroutine {fn.qualname}; move it into "
                    f"loop.run_blocking(...)")
            for call, callee in fn.summary.get("blocking_calls", ()):
                yield self.finding(
                    fn.mod, call, callee,
                    f"{callee}() blocks on a device→host sync (possibly "
                    f"transitively) and is called from coroutine "
                    f"{fn.qualname} on the event-loop thread; wrap the call "
                    f"in loop.run_blocking(...)")


# -------------------------------------------------------------- DEV002

@register
class JitConstructedPerCall(Rule):
    code = "DEV002"
    summary = ("jax.jit/vmap/pmap constructed per call (immediately invoked "
               "or built inside a loop) — re-traces and re-compiles every "
               "invocation; hoist to a cached factory")

    def check(self, mod: ModuleContext) -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            origin = _origin(mod, node.func)
            if origin not in _TRACE_CTORS:
                continue
            parent = mod.parents.get(node)
            if isinstance(parent, ast.Call) and parent.func is node:
                yield self.finding(
                    mod, parent, origin,
                    f"{origin}(...)(...) builds a fresh traced callable and "
                    f"invokes it once — every call re-traces (and for jit, "
                    f"re-compiles); bind it once in a cached factory")
                continue
            for anc in mod.ancestors(node):
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    break
                if isinstance(anc, (ast.For, ast.While, ast.AsyncFor)):
                    yield self.finding(
                        mod, node, origin,
                        f"{origin}(...) constructed inside a loop — one "
                        f"re-trace per iteration; hoist the wrapper out of "
                        f"the loop")
                    break


# -------------------------------------------------------------- DEV003

@register
class TracedValueBranch(Rule):
    code = "DEV003"
    summary = ("Python if/while on a traced parameter inside a jit target — "
               "ConcretizationTypeError at trace time (or a silently baked-"
               "in constant); use lax.cond/jnp.where")

    def check_package(self, pkg: PackageContext) -> Iterable[Finding]:
        ana = _analysis(pkg)
        for fqname, traced in ana.jit_targets.items():
            fn = pkg.functions.get(fqname)
            if fn is None or not traced:
                continue
            for node in _owned(fn.mod, fn.node):
                if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                    hits = sorted({x.id for x in ast.walk(node.test)
                                   if isinstance(x, ast.Name)
                                   and x.id in traced})
                    if hits:
                        yield self.finding(
                            fn.mod, node, hits[0],
                            f"Python branch on traced parameter "
                            f"'{hits[0]}' inside jit target {fn.qualname}; "
                            f"use lax.cond / jnp.where (static config "
                            f"belongs in keyword-only/static args)")


# -------------------------------------------------------------- DEV004

@register
class BadStaticArgnums(Rule):
    code = "DEV004"
    summary = ("static_argnums that are not integer constants, or a static "
               "position fed an array/unhashable value at a call site — "
               "TypeError (unhashable) or a retrace per distinct value")

    def check(self, mod: ModuleContext) -> Iterable[Finding]:
        static_positions: dict[str, tuple[int, ...]] = {}
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            origin = _origin(mod, node.func)
            is_jit = origin == "jax.jit" or (
                origin == "functools.partial" and node.args
                and _origin(mod, node.args[0]) == "jax.jit")
            if not is_jit:
                continue
            positions: list[int] = []
            for kw in node.keywords:
                if kw.arg != "static_argnums":
                    continue
                vals = (kw.value.elts
                        if isinstance(kw.value, (ast.Tuple, ast.List))
                        else [kw.value])
                for v in vals:
                    if isinstance(v, ast.Constant) \
                            and isinstance(v.value, int):
                        positions.append(v.value)
                    else:
                        yield self.finding(
                            mod, kw.value, "static_argnums",
                            "static_argnums must be integer constants — a "
                            "computed/array value makes the cache key "
                            "unhashable or unstable")
            if not positions:
                continue
            # g = jax.jit(f, static_argnums=(k,)) — remember g's positions
            parent = mod.parents.get(node)
            tgt = node
            if isinstance(parent, ast.Call):  # functools.partial wrapper
                tgt = parent
                parent = mod.parents.get(parent)
            if isinstance(parent, ast.Assign) and len(parent.targets) == 1 \
                    and isinstance(parent.targets[0], ast.Name) \
                    and parent.value is tgt:
                static_positions[parent.targets[0].id] = tuple(positions)
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)):
                continue
            positions = static_positions.get(node.func.id)
            if not positions:
                continue
            for k in positions:
                if k >= len(node.args):
                    continue
                arg = node.args[k]
                if isinstance(arg, (ast.List, ast.Dict, ast.Set,
                                    ast.ListComp, ast.DictComp, ast.SetComp)) \
                        or _jax_rooted(mod, arg):
                    yield self.finding(
                        mod, node, node.func.id,
                        f"static position {k} of {node.func.id}() receives "
                        f"an array/unhashable value — static args are "
                        f"hashed into the compile-cache key; pass arrays "
                        f"as traced operands")


# -------------------------------------------------------------- DEV005

@register
class ShapeDependentConstructor(Rule):
    code = "DEV005"
    summary = ("jnp constructor sized by len()/.shape-derived host "
               "arithmetic outside any traced context — a new compiled "
               "program per batch size; pad to bucketed shapes")

    def check_package(self, pkg: PackageContext) -> Iterable[Finding]:
        ana = _analysis(pkg)
        for fn in pkg.iter_functions():
            if fn.fqname in ana.trace_reachable:
                continue  # shapes are static under trace by construction
            shape_locals = self._shape_derived_locals(fn)
            for node in _owned(fn.mod, fn.node):
                if not isinstance(node, ast.Call):
                    continue
                origin = _origin(fn.mod, node.func)
                if origin not in _JNP_SIZED_CTORS:
                    continue
                exprs = list(node.args) + [kw.value for kw in node.keywords]
                for e in exprs:
                    if self._shape_dependent(e, shape_locals):
                        yield self.finding(
                            fn.mod, node, origin,
                            f"{origin}() sized by data-dependent host "
                            f"arithmetic in {fn.qualname} — every distinct "
                            f"size compiles a fresh program; pad to the "
                            f"bucketed shapes (BatchEncoder.bucket_shapes)")
                        break

    @staticmethod
    def _shape_derived_locals(fn: FunctionInfo) -> set[str]:
        out: set[str] = set()
        for node in _owned(fn.mod, fn.node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and ShapeDependentConstructor._shape_dependent(
                        node.value, out):
                out.add(node.targets[0].id)
        return out

    @staticmethod
    def _shape_dependent(expr: ast.AST, shape_locals: set[str]) -> bool:
        for x in ast.walk(expr):
            if isinstance(x, ast.Attribute) and x.attr == "shape":
                return True
            if isinstance(x, ast.Call) and isinstance(x.func, ast.Name) \
                    and x.func.id == "len":
                return True
            if isinstance(x, ast.Name) and x.id in shape_locals:
                return True
        return False


# -------------------------------------------------------------- DEV006

@register
class MissingDonation(Rule):
    code = "DEV006"
    summary = ("state-overwrite call `x = f(x, ...)` through a jit with no "
               "donate_argnums (or an eager un-jitted device function) — "
               "the dead input buffer doubles HBM traffic/footprint")

    def check_package(self, pkg: PackageContext) -> Iterable[Finding]:
        for mod in pkg.modules:
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.value, ast.Call)):
                    continue
                target, call = node.targets[0], node.value
                if not call.args or not _same_target(target, call.args[0]):
                    continue
                yield from self._check_site(pkg, mod, node, call)

    def _check_site(self, pkg, mod, node, call) -> Iterable[Finding]:
        func = call.func
        jit_vars = self._jit_vars(mod)
        if isinstance(func, ast.Name):
            donated = jit_vars.get(func.id)
            if donated is False:
                yield self.finding(
                    mod, node, func.id,
                    f"{func.id}() is a jit with no donate_argnums but its "
                    f"first operand is overwritten by the result — donate "
                    f"it (see _donate_state_argnums) to halve state "
                    f"traffic")
                return
            if donated is None:
                for cand in pkg.resolve_call(mod, call):
                    fac = self._factory_donation(cand)
                    if fac is False:
                        yield self.finding(
                            mod, node, func.id,
                            f"{func.id}() returns a jit with no "
                            f"donate_argnums; its first operand is "
                            f"overwritten by the result — add "
                            f"donate_argnums to the factory's jit")
                    elif fac is None and self._touches_device(cand):
                        yield self.finding(
                            mod, node, func.id,
                            f"{func.id}() runs device ops eagerly (op-by-op "
                            f"dispatch, no donation) and its result "
                            f"overwrites its first operand — wrap it in a "
                            f"cached jit with donate_argnums")
        elif isinstance(func, ast.Call) and isinstance(func.func, ast.Name):
            # factory invocation: _compiled_rebase()(state, delta)
            for cand in pkg.resolve_call(
                    mod, ast.Call(func=func.func, args=[], keywords=[])):
                if self._factory_donation(cand) is False:
                    yield self.finding(
                        mod, node, func.func.id,
                        f"{func.func.id}() returns a jit with no "
                        f"donate_argnums; its first operand is overwritten "
                        f"by the result — add donate_argnums to the "
                        f"factory's jit")

    @staticmethod
    def _jit_vars(mod: ModuleContext) -> dict[str, bool]:
        """name -> has donate_argnums, for `g = jax.jit(...)` assignments.
        Cached on the ModuleContext (never keyed by relpath: tests reuse
        one snippet path across many distinct parses)."""
        got = getattr(mod, "_dev_jit_vars", None)
        if got is not None:
            return got
        out: dict[str, bool] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and _origin(mod, node.value.func) == "jax.jit":
                out[node.targets[0].id] = any(
                    kw.arg in ("donate_argnums", "donate_argnames")
                    for kw in node.value.keywords)
        mod._dev_jit_vars = out
        return out

    @staticmethod
    def _factory_donation(fn: FunctionInfo) -> bool | None:
        """True/False when `fn` returns a jax.jit(...) with/without
        donation; None when it is not a jit factory."""
        for node in _owned(fn.mod, fn.node):
            if isinstance(node, ast.Return) and node.value is not None \
                    and isinstance(node.value, ast.Call) \
                    and _origin(fn.mod, node.value.func) == "jax.jit":
                return any(kw.arg in ("donate_argnums", "donate_argnames")
                           for kw in node.value.keywords)
        return None

    @staticmethod
    def _touches_device(fn: FunctionInfo) -> bool:
        for node in _owned(fn.mod, fn.node):
            if isinstance(node, ast.Call):
                origin = _origin(fn.mod, node.func)
                if origin and origin.startswith(("jax.numpy.", "jax.lax.")):
                    return True
        return False


# -------------------------------------------------------------- DEV007

@register
class RawDeviceTransfer(Rule):
    code = "DEV007"
    summary = ("jax.device_put/device_get outside the utils/jaxenv.py choke "
               "points — moves bytes the resolver's transfer counters never "
               "see; use jaxenv.device_put/device_get")

    def check(self, mod: ModuleContext) -> Iterable[Finding]:
        if mod.relpath == _SANCTIONED_TRANSFER_MODULE:
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            origin = _origin(mod, node.func)
            if origin in _TRANSFER_FNS:
                yield self.finding(
                    mod, node, origin,
                    f"raw {origin}() outside utils/jaxenv.py — transfers "
                    f"must go through the jaxenv choke points so every "
                    f"one of them is counted")


# -------------------------------------------------------------- DEV008

@register
class PRNGDiscipline(Rule):
    code = "DEV008"
    summary = ("module-global numpy PRNG use, or a jax.random key consumed "
               "more than once without split — cross-instance coupling / "
               "identical draws")

    def check(self, mod: ModuleContext) -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            origin = _origin(mod, node.func)
            if origin and origin.startswith("numpy.random.") \
                    and origin not in _NP_RANDOM_OK:
                yield self.finding(
                    mod, node, origin,
                    f"{origin}() mutates/draws from numpy's module-global "
                    f"PRNG — seed a local RandomState/default_rng instead "
                    f"(global state couples every engine instance and "
                    f"breaks seed replay)")
        for fn in ast.walk(mod.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_key_reuse(mod, fn)

    def _check_key_reuse(self, mod: ModuleContext,
                         fn: ast.AST) -> Iterable[Finding]:
        rotated: set[str] = set()
        uses: dict[str, list[ast.Call]] = {}
        for node in _owned(mod, fn):
            if isinstance(node, ast.Assign):
                if any(isinstance(x, ast.Call)
                       and (_origin(mod, x.func) or "").endswith(
                           "random.split")
                       for x in ast.walk(node.value)):
                    for t in node.targets:
                        for x in ast.walk(t):
                            if isinstance(x, ast.Name):
                                rotated.add(x.id)
            if not isinstance(node, ast.Call):
                continue
            origin = _origin(mod, node.func)
            if not origin or not origin.startswith("jax.random."):
                continue
            if origin.rsplit(".", 1)[1] in _JAX_RANDOM_NONCONSUMING:
                continue
            if node.args and isinstance(node.args[0], ast.Name):
                uses.setdefault(node.args[0].id, []).append(node)
        for name, calls in sorted(uses.items()):
            if name in rotated or len(calls) < 2:
                continue
            for call in calls[1:]:
                yield self.finding(
                    mod, call, f"key:{name}",
                    f"jax.random key '{name}' is consumed by more than one "
                    f"draw without jax.random.split — identical randomness "
                    f"on every reuse; split the key per draw")
