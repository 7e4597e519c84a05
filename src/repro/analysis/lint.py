"""jaxlint: repo-specific JAX static analysis (the hot-path guard, static side).

StreamBrain's value is that the BCPNN hot loops run as fast as the hardware
allows — and the failure modes that silently regress that are not syntax
errors: a host sync inside a scan body, a buffer read after donation, a
Python mutable reaching a trace as a baked-in constant, an unlocked write to
state the async engine's executor thread shares.  This module is a pure-AST
lint pass (stdlib only — no jax import, so the CI lint job runs it without
installing jax) with four repo-specific rules:

JL001  host-sync / host-transfer call in traced code or a hot module.
       ``np.asarray``, ``np.array``, ``jax.device_get``, ``.item()``,
       ``.tolist()``, ``block_until_ready`` and jax-valued ``float()`` /
       ``int()`` casts are flagged (a) inside any function passed to
       ``jax.jit`` / ``lax.scan`` / ``vmap`` / ``shard_map`` / ``grad`` or
       decorated with them — where they either break tracing or force a
       device sync per call — and (b) ANYWHERE in the designated hot-path
       modules (:data:`DEFAULT_HOT_MODULES`), so every host transfer in the
       serving/training dispatch loops is either removed or carries an
       explicit waiver documenting why it is load-bearing.
JL002  donation-after-use: a buffer passed at a ``donate_argnums`` position
       of a jitted callable is read again after the call — donation
       invalidates the buffer, so the read returns garbage (or errors) on
       accelerators while silently "working" on CPU.
JL003  recompile hazards: a ``jax.jit`` (or other trace wrapper) constructed
       inside a loop (a fresh trace cache per iteration), an unhashable
       literal (list/dict/set) passed at a ``static_argnums``/``argnames``
       position, or a traced function closing over an enclosing scope's
       mutable literal (the trace bakes it in as a constant; later mutation
       is silently ignored).
JL004  unlocked shared-state mutation: in a class that owns a
       ``threading.Lock`` / ``RLock`` / ``Condition``, any write to a
       ``self.*`` attribute outside ``__init__`` that is not lexically under
       ``with self.<lock>:`` — the discipline ``repro.runtime.metrics``
       follows, enforced everywhere the AsyncEngine's executor thread (or
       the Router's scheduler thread) can race a caller thread.  A class
       whose lock arrives indirectly (constructor parameter, shared bundle
       lock) registers it via a class attribute so coverage never silently
       lapses::

           class Counter:
               _JAXLINT_LOCKS = ("_lock",)   # JL004 registration
               def __init__(self, lock=None):
                   self._lock = lock if lock is not None else threading.Lock()

       Methods named ``*_locked`` are exempt: the suffix is a naming
       contract (the CPython convention) that the CALLER holds the lock —
       the ``with`` block lives one frame up where a lexical check cannot
       see it.

Waivers
-------
The ONLY suppression mechanism is an inline waiver comment with a reason::

    nxt = np.asarray(nxt)  # jaxlint: allow[JL001] reason=tokens steer EOS host-side

A waiver on its own line covers the next code line; several rules may be
listed (``allow[JL001,JL004]``).  A waiver without a reason, and a waiver
that matches no finding, are themselves findings (JL000) — waivers never rot.

CLI: ``tools/jaxlint [paths...]`` (or ``python -m repro.analysis.lint``);
exits non-zero when findings remain.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import io
import os
import re
import sys
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

RULES = {
    "JL000": "malformed or unused waiver",
    "JL001": "host sync / transfer on a hot path",
    "JL002": "buffer used after donation",
    "JL003": "recompile hazard",
    "JL004": "unlocked shared-state mutation",
}

# Modules whose WHOLE body is a hot path: every host transfer here must be
# deliberate, so JL001 applies module-wide (not just inside traced code).
DEFAULT_HOT_MODULES: Tuple[str, ...] = (
    "repro/runtime/service.py",
    "repro/runtime/engine.py",
    "repro/runtime/router.py",
    "repro/runtime/continual.py",
    "repro/runtime/trace.py",
    "repro/runtime/export.py",
    "repro/runtime/plans.py",
    "repro/runtime/epoch_engine.py",
    "repro/runtime/program.py",
    "repro/core/compiled.py",
    "repro/kernels/ops.py",
    "repro/kernels/bcpnn_phase.py",
)

# Dotted-call suffixes that enter a trace; their first positional argument is
# traced Python code.
_TRACE_WRAPPERS = {
    "jax.jit", "jit",
    "jax.vmap", "vmap",
    "jax.pmap", "pmap",
    "jax.lax.scan", "lax.scan",
    "jax.grad", "grad",
    "jax.value_and_grad", "value_and_grad",
    "jax.checkpoint", "jax.remat",
    "shard_map", "jax.shard_map",
    "checkify.checkify",
}

# Host-sync / host-transfer calls (JL001).
_SYNC_DOTTED = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get", "device_get",
    "jax.block_until_ready",
}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_CAST_BUILTINS = {"float", "int", "bool"}

_LOCK_FACTORIES = {
    "threading.Lock", "threading.RLock", "threading.Condition",
    "Lock", "RLock", "Condition",
}

_MUTABLE_LITERALS = (
    ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp,
)

_WAIVER_RE = re.compile(
    r"#\s*jaxlint:\s*allow\[([A-Za-z0-9,\s]+)\]\s*(?:reason=(.+))?$"
)


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


@dataclasses.dataclass
class _Waiver:
    line: int          # comment's own line
    covers: Set[int]   # code lines the waiver applies to
    rules: Set[str]
    reason: str
    used: bool = False


# --------------------------------------------------------------------------
# Small AST helpers.
# --------------------------------------------------------------------------
def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _matches(dotted: Optional[str], suffixes: Set[str]) -> bool:
    if dotted is None:
        return False
    return dotted in suffixes or any(
        dotted.endswith("." + s) for s in suffixes
    )


def _trace_call(call: ast.Call) -> Optional[ast.Call]:
    """The trace-wrapper call underlying ``call`` — handles the direct form
    and ``functools.partial(jax.jit, ...)``."""
    dotted = _dotted(call.func)
    if _matches(dotted, _TRACE_WRAPPERS):
        return call
    if _matches(dotted, {"functools.partial", "partial"}) and call.args:
        inner = _dotted(call.args[0])
        if _matches(inner, _TRACE_WRAPPERS):
            return call
    return None


def _mentions_jax(node: ast.AST) -> bool:
    return any(
        isinstance(n, ast.Name) and n.id in ("jax", "jnp", "lax")
        for n in ast.walk(node)
    )


def _static_looking(node: ast.AST) -> bool:
    """Casts of shapes/lengths/constants are static under trace — skip."""
    if isinstance(node, ast.Constant):
        return True
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in ("shape", "ndim", "size"):
            return True
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) and n.func.id == "len":
            return True
    return False


def _int_or_ints(node: ast.AST) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
        return out
    return []


class _Parents(ast.NodeVisitor):
    """parent map + per-node enclosing statement."""

    def __init__(self, tree: ast.AST):
        self.parent: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node

    def ancestors(self, node: ast.AST) -> Iterable[ast.AST]:
        while node in self.parent:
            node = self.parent[node]
            yield node

    def statement(self, node: ast.AST) -> ast.AST:
        last = node
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda, ast.Module, ast.ClassDef)):
                return last
            last = anc
        return last


# --------------------------------------------------------------------------
# The per-file linter.
# --------------------------------------------------------------------------
class _FileLint:
    def __init__(self, src: str, path: str, hot: Sequence[str]):
        self.src = src
        self.path = path
        self.findings: List[Finding] = []
        self.tree = ast.parse(src, filename=path)
        self.parents = _Parents(self.tree)
        norm = path.replace(os.sep, "/")
        self.is_hot = any(norm.endswith(h) for h in hot)
        self.waivers = self._parse_waivers(src)

    # ------------------------------------------------------------- waivers
    def _parse_waivers(self, src: str) -> List[_Waiver]:
        waivers: List[_Waiver] = []
        code_tokens_on: Set[int] = set()
        comments: List[Tuple[int, str]] = []
        try:
            for tok in tokenize.generate_tokens(io.StringIO(src).readline):
                if tok.type == tokenize.COMMENT:
                    comments.append((tok.start[0], tok.string))
                elif tok.type not in (
                    tokenize.NL, tokenize.NEWLINE, tokenize.INDENT,
                    tokenize.DEDENT, tokenize.ENCODING, tokenize.ENDMARKER,
                ):
                    for ln in range(tok.start[0], tok.end[0] + 1):
                        code_tokens_on.add(ln)
        except tokenize.TokenError:
            return waivers
        for line, text in comments:
            m = _WAIVER_RE.search(text)
            if m is None:
                if re.search(r"jaxlint\s*:", text):
                    self._emit("JL000", line, 0,
                               "unparseable jaxlint comment (want "
                               "'# jaxlint: allow[JLxxx] reason=...')")
                continue
            rules = {r.strip().upper() for r in m.group(1).split(",") if r.strip()}
            reason = (m.group(2) or "").strip()
            bad = rules - set(RULES)
            if bad:
                self._emit("JL000", line, 0,
                           f"waiver names unknown rule(s) {sorted(bad)}")
                continue
            if not reason:
                self._emit("JL000", line, 0,
                           "waiver without a reason= — document why the "
                           "transfer/mutation is load-bearing")
                continue
            covers = {line}
            if line not in code_tokens_on:  # comment-only line: covers next
                covers.add(line + 1)
            waivers.append(_Waiver(line, covers, rules, reason))
        return waivers

    def _emit(self, rule: str, line: int, col: int, message: str) -> None:
        self.findings.append(Finding(self.path, line, col, rule, message))

    # ---------------------------------------------------------------- run
    def run(self) -> List[Finding]:
        traced = self._traced_functions()
        self._check_sync_calls(traced)
        self._check_donation_and_static()
        self._check_jit_in_loop()
        self._check_closure_mutables(traced)
        self._check_lock_discipline()
        return self._apply_waivers()

    def _apply_waivers(self) -> List[Finding]:
        kept: List[Finding] = []
        for f in self.findings:
            if f.rule == "JL000":
                kept.append(f)
                continue
            waived = False
            for w in self.waivers:
                if f.line in w.covers and f.rule in w.rules:
                    w.used = True
                    waived = True
                    break
            if not waived:
                kept.append(f)
        for w in self.waivers:
            if not w.used:
                kept.append(Finding(
                    self.path, w.line, 0, "JL000",
                    f"waiver allow[{','.join(sorted(w.rules))}] matches no "
                    "finding — delete it",
                ))
        kept.sort(key=lambda f: (f.line, f.col, f.rule))
        return kept

    # ----------------------------------------------------- traced regions
    def _traced_functions(self) -> Set[ast.AST]:
        """Function nodes (def/lambda) whose bodies execute under a trace."""
        traced: Set[ast.AST] = set()

        def resolve_name(name: str, from_node: ast.AST) -> Optional[ast.AST]:
            # Nearest enclosing scope defining a function with this name.
            scopes = [self.tree] + [
                a for a in self.parents.ancestors(from_node)
                if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef))
            ]
            for scope in scopes:
                for child in ast.walk(scope):
                    if (isinstance(child, (ast.FunctionDef,
                                           ast.AsyncFunctionDef))
                            and child.name == name):
                        return child
            return None

        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and _trace_call(node) is not None:
                args = node.args
                # partial(jax.jit, f, ...) puts the fn at index 1.
                dotted = _dotted(node.func)
                if _matches(dotted, {"functools.partial", "partial"}):
                    args = node.args[1:]
                if not args:
                    continue
                fn = args[0]
                if isinstance(fn, ast.Lambda):
                    traced.add(fn)
                elif isinstance(fn, ast.Name):
                    target = resolve_name(fn.id, node)
                    if target is not None:
                        traced.add(target)
                elif isinstance(fn, ast.Attribute):
                    target = resolve_name(fn.attr, node)
                    if target is not None:
                        traced.add(target)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    d = dec.func if isinstance(dec, ast.Call) else dec
                    if _matches(_dotted(d), _TRACE_WRAPPERS) or (
                        isinstance(dec, ast.Call)
                        and _trace_call(dec) is not None
                    ):
                        traced.add(node)
        return traced

    def _in_traced(self, node: ast.AST, traced: Set[ast.AST]) -> bool:
        if node in traced:
            return True
        return any(a in traced for a in self.parents.ancestors(node))

    # ------------------------------------------------------------- JL001
    def _check_sync_calls(self, traced: Set[ast.AST]) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            in_trace = self._in_traced(node, traced)
            if not in_trace and not self.is_hot:
                continue
            where = (
                "inside traced code (breaks tracing or syncs per call)"
                if in_trace else "on a hot-path module"
            )
            dotted = _dotted(node.func)
            if _matches(dotted, _SYNC_DOTTED):
                self._emit("JL001", node.lineno, node.col_offset,
                           f"host transfer `{dotted}` {where}")
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _SYNC_METHODS
                    and not node.args):
                self._emit("JL001", node.lineno, node.col_offset,
                           f"host sync `.{node.func.attr}()` {where}")
                continue
            if (isinstance(node.func, ast.Name)
                    and node.func.id in _CAST_BUILTINS
                    and len(node.args) == 1):
                arg = node.args[0]
                if _static_looking(arg):
                    continue
                # In a hot module (but outside traced code) only flag casts
                # of jax-valued expressions — host bookkeeping ints are fine.
                if in_trace or _mentions_jax(arg):
                    self._emit(
                        "JL001", node.lineno, node.col_offset,
                        f"`{node.func.id}()` of a device value {where}",
                    )

    # ------------------------------------------------- JL002/JL003 (calls)
    def _function_scopes(self) -> List[ast.AST]:
        return [self.tree] + [
            n for n in ast.walk(self.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]

    def _check_donation_and_static(self) -> None:
        for scope in self._function_scopes():
            donated: Dict[str, List[int]] = {}
            statics: Dict[str, Tuple[List[int], List[str]]] = {}
            body = scope.body if hasattr(scope, "body") else []
            # Pass 1: jitted-callable bindings in this scope.
            for stmt in body if isinstance(body, list) else []:
                if not isinstance(stmt, ast.Assign) or len(stmt.targets) != 1:
                    continue
                target = stmt.targets[0]
                if not isinstance(target, ast.Name):
                    continue
                call = stmt.value
                if not isinstance(call, ast.Call) or _trace_call(call) is None:
                    continue
                for kw in call.keywords:
                    if kw.arg == "donate_argnums":
                        pos = _int_or_ints(kw.value)
                        if pos:
                            donated[target.id] = pos
                    elif kw.arg == "static_argnums":
                        pos = _int_or_ints(kw.value)
                        if pos:
                            statics.setdefault(target.id, ([], []))[0].extend(pos)
                    elif kw.arg == "static_argnames":
                        names = []
                        if isinstance(kw.value, ast.Constant):
                            names = [str(kw.value.value)]
                        elif isinstance(kw.value, (ast.Tuple, ast.List)):
                            names = [
                                str(e.value) for e in kw.value.elts
                                if isinstance(e, ast.Constant)
                            ]
                        if names:
                            statics.setdefault(target.id, ([], []))[1].extend(names)
            if not donated and not statics:
                continue
            # Pass 2: call sites within this scope (nested defs excluded from
            # the "after" analysis but included as uses).
            events = self._name_events(scope)
            for node in ast.walk(scope):
                if not isinstance(node, ast.Call):
                    continue
                fname = node.func.id if isinstance(node.func, ast.Name) else None
                if fname in statics:
                    pos, names = statics[fname]
                    for p in pos:
                        if p < len(node.args) and isinstance(
                            node.args[p], _MUTABLE_LITERALS
                        ):
                            self._emit(
                                "JL003", node.lineno, node.col_offset,
                                f"unhashable literal at static_argnums[{p}] "
                                f"of `{fname}` — every call re-traces (or "
                                "TypeErrors)",
                            )
                    for kw in node.keywords:
                        if kw.arg in names and isinstance(
                            kw.value, _MUTABLE_LITERALS
                        ):
                            self._emit(
                                "JL003", node.lineno, node.col_offset,
                                f"unhashable literal for static arg "
                                f"`{kw.arg}` of `{fname}`",
                            )
                if fname in donated:
                    stmt = self.parents.statement(node)
                    end = getattr(stmt, "end_lineno", node.lineno)
                    for p in donated[fname]:
                        if p >= len(node.args):
                            continue
                        arg = node.args[p]
                        if not isinstance(arg, ast.Name):
                            continue
                        # `state, xs = epoch(state, xs)` rebinds the donated
                        # name in the same statement — the post-call buffer
                        # replaces the dead one, so later reads are fine.
                        if isinstance(stmt, (ast.Assign, ast.AugAssign)) and any(
                            isinstance(t, ast.Name)
                            and t.id == arg.id
                            and isinstance(t.ctx, ast.Store)
                            for tgt in getattr(stmt, "targets", [stmt])
                            for t in ast.walk(tgt)
                        ):
                            continue
                        use = self._first_use_after(events, arg.id, end)
                        if use is not None:
                            self._emit(
                                "JL002", use, node.col_offset,
                                f"`{arg.id}` read after being donated to "
                                f"`{fname}` (line {node.lineno}) — donation "
                                "invalidates the buffer on accelerators",
                            )

    def _name_events(self, scope: ast.AST) -> List[Tuple[int, str, str]]:
        """(line, name, 'load'|'store') events in statement order."""
        events: List[Tuple[int, str, str]] = []
        for node in ast.walk(scope):
            if isinstance(node, ast.Name):
                kind = "store" if isinstance(
                    node.ctx, (ast.Store, ast.Del)
                ) else "load"
                events.append((node.lineno, node.id, kind))
        events.sort(key=lambda e: e[0])
        return events

    @staticmethod
    def _first_use_after(
        events: List[Tuple[int, str, str]], name: str, after_line: int
    ) -> Optional[int]:
        """First load of ``name`` strictly after ``after_line`` that is not
        preceded by a re-binding store."""
        for line, nm, kind in events:
            if nm != name or line <= after_line:
                continue
            return line if kind == "load" else None
        return None

    # ------------------------------------------------------------- JL003
    def _check_jit_in_loop(self) -> None:
        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Call) and _trace_call(node) is not None):
                continue
            for anc in self.parents.ancestors(node):
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda)):
                    break  # loops outside the defining function don't apply
                if isinstance(anc, (ast.For, ast.While)):
                    dotted = _dotted(node.func) or "trace wrapper"
                    self._emit(
                        "JL003", node.lineno, node.col_offset,
                        f"`{dotted}` constructed inside a loop — a fresh "
                        "trace cache every iteration (hoist it)",
                    )
                    break

    def _check_closure_mutables(self, traced: Set[ast.AST]) -> None:
        for fn in traced:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Lambda)):
                continue
            enclosing = next(
                (a for a in self.parents.ancestors(fn)
                 if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef))),
                None,
            )
            if enclosing is None:
                continue
            bound = self._bound_names(fn)
            free = {
                n.id for n in ast.walk(fn)
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
                and n.id not in bound
            }
            for stmt in ast.walk(enclosing):
                if not isinstance(stmt, ast.Assign):
                    continue
                if not isinstance(stmt.value, _MUTABLE_LITERALS):
                    continue
                for t in stmt.targets:
                    if isinstance(t, ast.Name) and t.id in free:
                        self._emit(
                            "JL003", fn.lineno, fn.col_offset,
                            f"traced function closes over mutable `{t.id}` "
                            f"(bound line {stmt.lineno}) — baked in as a "
                            "constant at trace time; later mutation is "
                            "silently ignored",
                        )

    @staticmethod
    def _bound_names(fn: ast.AST) -> Set[str]:
        bound: Set[str] = set()
        args = fn.args
        for a in (
            list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
        ):
            bound.add(a.arg)
        if args.vararg:
            bound.add(args.vararg.arg)
        if args.kwarg:
            bound.add(args.kwarg.arg)
        for n in ast.walk(fn):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                bound.add(n.id)
            elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                bound.add(n.name)
        return bound

    # ------------------------------------------------------------- JL004
    def _check_lock_discipline(self) -> None:
        classes = {
            n.name: n for n in ast.walk(self.tree)
            if isinstance(n, ast.ClassDef)
        }
        lock_attrs: Dict[str, Set[str]] = {}

        def own_locks(cls: ast.ClassDef) -> Set[str]:
            attrs: Set[str] = set()
            for node in ast.walk(cls):
                if not isinstance(node, ast.Assign):
                    continue
                # Explicit registration: `_JAXLINT_LOCKS = ("_lock", ...)` as
                # a class attribute — for locks that arrive indirectly (a
                # constructor parameter, a bundle-shared lock) where no
                # factory call is visible to the pattern below.
                if (len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and node.targets[0].id == "_JAXLINT_LOCKS"
                        and isinstance(node.value, (ast.Tuple, ast.List))):
                    for e in node.value.elts:
                        if isinstance(e, ast.Constant) and isinstance(
                            e.value, str
                        ):
                            attrs.add(e.value)
                    continue
                if not (isinstance(node.value, ast.Call)
                        and _matches(_dotted(node.value.func), _LOCK_FACTORIES)):
                    continue
                for t in node.targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        attrs.add(t.attr)
            return attrs

        def all_locks(name: str, seen: Set[str]) -> Set[str]:
            if name in lock_attrs:
                return lock_attrs[name]
            if name in seen or name not in classes:
                return set()
            seen.add(name)
            cls = classes[name]
            attrs = set(own_locks(cls))
            for base in cls.bases:
                if isinstance(base, ast.Name):
                    attrs |= all_locks(base.id, seen)
            lock_attrs[name] = attrs
            return attrs

        for name, cls in classes.items():
            locks = all_locks(name, set())
            if not locks:
                continue
            for method in cls.body:
                if not isinstance(method, (ast.FunctionDef,
                                           ast.AsyncFunctionDef)):
                    continue
                if method.name in ("__init__", "__new__"):
                    continue
                if method.name.endswith("_locked"):
                    # Naming contract: a `*_locked` method documents that
                    # its CALLER holds the lock (the CPython convention);
                    # the with-block lives one frame up where the linter
                    # cannot see it.
                    continue
                self._check_method_writes(method, locks)

    def _check_method_writes(self, method: ast.AST, locks: Set[str]) -> None:
        for node in ast.walk(method):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            else:
                continue
            for t in targets:
                if not (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    continue
                if t.attr in locks:
                    continue
                if self._under_lock(node, locks):
                    continue
                self._emit(
                    "JL004", node.lineno, node.col_offset,
                    f"write to `self.{t.attr}` outside `with self."
                    f"{'/'.join(sorted(locks))}` in a lock-owning class — "
                    "the executor thread can race this",
                )

    def _under_lock(self, node: ast.AST, locks: Set[str]) -> bool:
        for anc in self.parents.ancestors(node):
            if isinstance(anc, ast.With):
                for item in anc.items:
                    e = item.context_expr
                    if (isinstance(e, ast.Attribute)
                            and isinstance(e.value, ast.Name)
                            and e.value.id == "self" and e.attr in locks):
                        return True
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
        return False


# --------------------------------------------------------------------------
# Public API + CLI.
# --------------------------------------------------------------------------
def lint_source(
    src: str, path: str = "<string>",
    hot: Sequence[str] = DEFAULT_HOT_MODULES,
) -> List[Finding]:
    """Lint one source string; ``path`` decides hot-module status."""
    try:
        return _FileLint(src, path, hot).run()
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, e.offset or 0, "JL000",
                        f"syntax error: {e.msg}")]


def lint_paths(
    paths: Sequence[str], hot: Sequence[str] = DEFAULT_HOT_MODULES,
) -> List[Finding]:
    """Lint files and directory trees (``*.py``)."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _, names in os.walk(p):
                files.extend(
                    os.path.join(root, n) for n in names if n.endswith(".py")
                )
        else:
            files.append(p)
    findings: List[Finding] = []
    for f in sorted(files):
        with open(f, encoding="utf-8") as fh:
            findings.extend(lint_source(fh.read(), f, hot))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="jaxlint", description="repo-specific JAX static analysis"
    )
    ap.add_argument("paths", nargs="+", help="files or directories to lint")
    ap.add_argument(
        "--hot", action="append", default=None,
        help="extra hot-path module suffix (repeatable); defaults to the "
        "serving/training dispatch modules",
    )
    args = ap.parse_args(argv)
    hot = list(DEFAULT_HOT_MODULES) + (args.hot or [])
    findings = lint_paths(args.paths, hot=hot)
    for f in findings:
        print(f.render())
    if findings:
        print(f"jaxlint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
