"""JAX dispatch-path rules: jit-in-loop, donated reuse, host syncs.

The steady-state laws behind a steady microbatch cadence: tracing
is for setup (a `jax.jit` inside a per-microbatch loop recompiles or at
best re-hashes every iteration, PL301); a donated buffer belongs to XLA
the moment the jitted call runs (touching it after is undefined, PL302);
and the dispatch path stays ASYNC — one `np.asarray`/`float()` on a
device array in the hot loop serializes host and device and the overlap
window (DCN_STAGE_DEPTH) collapses (PL303).
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Set

from .lint import (Finding, Module, Rule, SEVERITY_WARNING, dotted,
                   walk_excluding_nested_functions)


def _is_jit_call(node: ast.Call) -> bool:
    name = dotted(node.func)
    if name in ("jax.jit", "jit"):
        return True
    # functools.partial(jax.jit, ...) — the decorator-factory idiom
    if name.endswith("partial") and node.args:
        return dotted(node.args[0]) in ("jax.jit", "jit")
    return False


class JitInLoop(Rule):
    id = "PL301"
    name = "jit-in-loop"
    severity = SEVERITY_WARNING
    fix_hint = ("hoist the jax.jit out of the loop (module level, setup "
                "path, or a keyed cache like spmd_decode's _cache_init)")
    rationale = ("jax.jit inside a per-microbatch/per-round loop pays "
                 "wrapper construction and cache lookup every iteration — "
                 "and a capture-varying signature recompiles every time")

    def check(self, module: Module) -> Iterator[Finding]:
        for loop in ast.walk(module.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in walk_excluding_nested_functions(loop.body):
                if isinstance(node, ast.Call) and _is_jit_call(node):
                    yield self.finding(
                        module, node,
                        "jax.jit constructed inside a loop body")


class DonatedArgReuse(Rule):
    id = "PL302"
    name = "donated-arg-reuse"
    severity = SEVERITY_WARNING
    fix_hint = ("a donated argument's buffer belongs to XLA after the "
                "call: use the call's RESULT, or stop donating "
                "(donate_argnums) if the input must stay live")
    rationale = ("reading a donated jax.Array after the jitted call is "
                 "undefined behavior — deleted-buffer errors on CPU, "
                 "silent garbage on TPU with buffer reuse")

    def __init__(self):
        # per-module donating callee names, filled by collect():
        # `fn = jax.jit(step, donate_argnums=(1,))` -> "fn";
        # `self._fn = jax.jit(...)` -> "_fn"
        self._donating: Dict[str, Set[str]] = {}

    @staticmethod
    def _donates(call: ast.Call) -> bool:
        if not _is_jit_call(call):
            return False
        for kw in call.keywords:
            if kw.arg in ("donate_argnums", "donate_argnames"):
                v = kw.value
                # an empty literal tuple/list donates nothing; anything
                # computed is conservatively treated as donating
                if isinstance(v, (ast.Tuple, ast.List)) and not v.elts:
                    return False
                return True
        return False

    def collect(self, module: Module) -> None:
        names: Set[str] = set()
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Assign):
                continue
            if not isinstance(node.value, ast.Call) \
                    or not self._donates(node.value):
                continue
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
                elif isinstance(t, ast.Attribute):
                    names.add(t.attr)
        self._donating[module.path] = names

    def check(self, module: Module) -> Iterator[Finding]:
        donating = self._donating.get(module.path, set())
        if not donating:
            return
        for fn in ast.walk(module.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield from self._check_function(module, fn, donating)

    def _check_function(self, module: Module, fn: ast.AST,
                        donating: Set[str]) -> Iterator[Finding]:
        body = list(walk_excluding_nested_functions(fn.body))
        calls = []
        for node in body:
            if not isinstance(node, ast.Call):
                continue
            callee = None
            if isinstance(node.func, ast.Name) and node.func.id in donating:
                callee = node.func.id
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in donating:
                callee = node.func.attr
            if callee is not None:
                args = [a.id for a in node.args if isinstance(a, ast.Name)]
                if args:
                    calls.append((node.lineno, callee, args))
        if not calls:
            return
        loads: List = [n for n in body if isinstance(n, ast.Name)
                       and isinstance(n.ctx, ast.Load)]
        stores = [n for n in body if isinstance(n, ast.Name)
                  and isinstance(n.ctx, ast.Store)]
        for call_line, callee, args in calls:
            for arg in args:
                for use in loads:
                    if use.id != arg or use.lineno <= call_line:
                        continue
                    # re-assignment between the call and the use makes the
                    # later load a DIFFERENT value (x = fn(x) idiom)
                    if any(s.id == arg and call_line <= s.lineno
                           <= use.lineno for s in stores):
                        continue
                    yield self.finding(
                        module, use,
                        f"{arg} may be donated to {callee}() on line "
                        f"{call_line} and is read again afterwards")
                    break    # one finding per (call, arg)


# the steady-state dispatch surface, by function name: the hot path the
# overlap design (DCN_STAGE_DEPTH, PendingWire) keeps asynchronous
_DISPATCH_NAME_RE = re.compile(r"dispatch|steady|(^|_)emit(_|$)")

# host-sync primitives: each forces a device->host round trip (or a
# blocking wait) when applied to a device array
_SYNC_DOTTED = frozenset((
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get",
))
_SYNC_ATTRS = frozenset(("block_until_ready", "tolist", "item"))
_SYNC_BUILTINS = frozenset(("float", "int", "bytes"))


class HostSyncInDispatchPath(Rule):
    id = "PL303"
    name = "host-sync-in-dispatch-path"
    severity = SEVERITY_WARNING
    fix_hint = ("keep the dispatch path async: move the sync to the "
                "readback/retire side (PendingWire.finalize idiom), or "
                "suppress with a comment naming why the sync is safe here")
    rationale = ("np.asarray/float()/block_until_ready on a device array "
                 "in the steady dispatch path serializes host and device "
                 "and collapses the pipelined overlap window")

    def check(self, module: Module) -> Iterator[Finding]:
        for fn in ast.walk(module.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not _DISPATCH_NAME_RE.search(fn.name):
                continue
            for node in walk_excluding_nested_functions(fn.body):
                if not isinstance(node, ast.Call):
                    continue
                desc = self._sync_desc(node)
                if desc is not None:
                    yield self.finding(
                        module, node,
                        f"host-sync {desc} inside dispatch-path "
                        f"function {fn.name}()")

    @staticmethod
    def _sync_desc(node: ast.Call) -> Optional[str]:
        name = dotted(node.func)
        if name in _SYNC_DOTTED:
            return f"{name}()"
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _SYNC_ATTRS:
            return f".{node.func.attr}()"
        if isinstance(node.func, ast.Name) \
                and node.func.id in _SYNC_BUILTINS:
            # only when converting a variable (a potential device array);
            # float("1.5") / int(os.getenv(...)) conversions are host data
            if len(node.args) == 1 and isinstance(
                    node.args[0], (ast.Name, ast.Attribute)):
                return f"{node.func.id}()"
        return None


RULES = (JitInLoop, DonatedArgReuse, HostSyncInDispatchPath)
