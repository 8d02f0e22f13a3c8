"""donation-safety: donated buffers must not be touched after the call.

``jax.jit(..., donate_argnums=(0,))`` hands the argument's device buffer
to XLA for reuse: after the call the caller's array is logically dead —
touching it raises on strict backends and silently reads reused memory on
others (the "donated-buffer fix (fresh_state per phase)" of PR 5 was
exactly this bug).  The pass enforces the contract statically:

  - **registration**: a def decorated ``@jax.jit(donate_argnums=...)`` or
    ``@functools.partial(jax.jit, donate_argnums=...)``, or a module/local
    binding ``f = jax.jit(g, donate_argnums=...)``, registers a donating
    callable with its donated positions/names.
  - **call sites**: at every call of a registered callable inside the same
    module, each donated argument that is a plain variable is tracked
    forward through the enclosing function: a LOAD of that variable after
    the call, before any rebinding STORE, is a finding.  The idiomatic
    consume-and-rebind loop (``state, loss = step(state, ...)``) stores on
    the same statement and passes.
  - **arity**: ``donate_argnums`` out of range of the wrapped function's
    positional signature is reported directly (a latent TypeError that
    only fires on the first real call).

Resolution is intra-module and name-based — builders that RETURN jitted
closures (this codebase's dominant pattern) are checked at their
definition site (the decorated def), while their dynamic call sites in
runner.py are out of static reach.  That boundary is deliberate: the
pass stays exact (near-zero false positives) and the donation contract
is still pinned where the donation is declared.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import (
    SEVERITY_ERROR,
    AnalysisContext,
    AnalysisPass,
    Finding,
    SourceModule,
    dotted_name,
    iter_child_statements,
)

__all__ = ["DonationSafetyPass"]


def _last(name: Optional[str]) -> str:
    return name.rsplit(".", 1)[-1] if name else ""


class _Donor:
    def __init__(self, argnums: Tuple[int, ...], argnames: Tuple[str, ...], line: int):
        self.argnums = argnums
        self.argnames = argnames
        self.line = line


def _literal_ints(node: ast.AST) -> Optional[Tuple[int, ...]]:
    """(0, 1) / [0] / 0 -> tuple of ints; None when not statically known."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                out.append(elt.value)
            else:
                return None
        return tuple(out)
    return None


def _literal_strs(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                out.append(elt.value)
            else:
                return None
        return tuple(out)
    return None


def _donation_kwargs(call: ast.Call) -> Optional[_Donor]:
    argnums: Tuple[int, ...] = ()
    argnames: Tuple[str, ...] = ()
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            argnums = _literal_ints(kw.value) or ()
        elif kw.arg == "donate_argnames":
            argnames = _literal_strs(kw.value) or ()
    if argnums or argnames:
        return _Donor(argnums, argnames, call.lineno)
    return None


def _jit_call(node: ast.AST) -> Optional[ast.Call]:
    """The jit(...) Call carrying donation kwargs, if this expression is
    one: `jax.jit(...)` or `functools.partial(jax.jit, ...)`."""
    if not isinstance(node, ast.Call):
        return None
    fn = _last(dotted_name(node.func))
    if fn in ("jit", "pjit"):
        return node
    if fn == "partial" and any(
        _last(dotted_name(a)) in ("jit", "pjit") for a in node.args
    ):
        return node
    return None


class DonationSafetyPass(AnalysisPass):
    rule = "donation-safety"
    description = (
        "arguments listed in donate_argnums/donate_argnames must not be "
        "referenced in the caller after the jitted call"
    )

    def run(self, modules: Sequence[SourceModule], ctx: AnalysisContext) -> List[Finding]:
        findings: List[Finding] = []
        for module in modules:
            findings.extend(self._check_module(module))
        return findings

    # ------------------------------------------------------------------ #

    def _check_module(self, module: SourceModule) -> List[Finding]:
        findings: List[Finding] = []
        donors: Dict[str, _Donor] = {}

        # registration: decorated defs (also checks arity on the spot)
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    jc = _jit_call(deco)
                    if jc is None:
                        continue
                    donor = _donation_kwargs(jc)
                    if donor is None:
                        continue
                    donors[node.name] = donor
                    findings.extend(self._check_arity(module, node, donor))
            elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                jc = _jit_call(node.value)
                if isinstance(t, ast.Name) and jc is not None:
                    donor = _donation_kwargs(jc)
                    if donor is not None:
                        donors[t.id] = donor

        if donors:
            for func in ast.walk(module.tree):
                if isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    findings.extend(self._check_callsites(module, func, donors))
        return findings

    def _check_arity(
        self, module: SourceModule, func: ast.AST, donor: _Donor
    ) -> List[Finding]:
        n_pos = len(func.args.posonlyargs) + len(func.args.args)
        bad = [i for i in donor.argnums if i >= n_pos and func.args.vararg is None]
        if not bad:
            return []
        return [
            Finding(
                rule=self.rule,
                severity=SEVERITY_ERROR,
                path=module.rel,
                line=func.lineno,
                message=(
                    f"donate_argnums {tuple(sorted(bad))} out of range for "
                    f"`{func.name}` ({n_pos} positional parameter(s)): "
                    "donation will TypeError on the first call"
                ),
            )
        ]

    def _check_callsites(
        self, module: SourceModule, func: ast.AST, donors: Dict[str, _Donor]
    ) -> List[Finding]:
        findings: List[Finding] = []
        for node in iter_child_statements(func):
            if not isinstance(node, ast.Call):
                continue
            callee = node.func.id if isinstance(node.func, ast.Name) else None
            if callee not in donors:
                continue
            donor = donors[callee]
            donated_vars: List[Tuple[str, int]] = []
            for idx in donor.argnums:
                if idx < len(node.args) and isinstance(node.args[idx], ast.Name):
                    donated_vars.append((node.args[idx].id, idx))
            for kw in node.keywords:
                if kw.arg in donor.argnames and isinstance(kw.value, ast.Name):
                    donated_vars.append((kw.value.id, kw.arg))
            for var, which in donated_vars:
                use = self._use_after(func, node, var)
                if use is not None:
                    findings.append(
                        Finding(
                            rule=self.rule,
                            severity=SEVERITY_ERROR,
                            path=module.rel,
                            line=use,
                            # no line numbers in the message: baseline
                            # keys (rule:path:message) must survive code
                            # motion
                            message=(
                                f"`{var}` used after being donated to "
                                f"`{callee}` (arg {which}): the buffer is "
                                "dead once donated — rebind or copy before "
                                "the call"
                            ),
                        )
                    )
        return findings

    def _use_after(self, func: ast.AST, call: ast.Call, var: str) -> Optional[int]:
        """First line > call where `var` is LOADed before any re-STORE.

        Line-ordered scan of the enclosing function: sound for the
        straight-line epilogue code donation bugs live in; loops where the
        next iteration rebinds are handled by the same-line/lower-line
        store rule (the canonical `state = step(state)` rebinding stores
        at the call line itself).
        """
        call_line = call.end_lineno or call.lineno
        events: List[Tuple[int, str]] = []
        for node in iter_child_statements(func):
            if isinstance(node, ast.Name) and node.id == var:
                if isinstance(node.ctx, ast.Load):
                    # the donated argument itself is a Load on the call line
                    if node.lineno > call_line:
                        events.append((node.lineno, "load"))
                elif isinstance(node.ctx, (ast.Store, ast.Del)):
                    if node.lineno >= call.lineno:
                        events.append((node.lineno, "store"))
        for line, kind in sorted(events):
            if kind == "store":
                return None
            return line
        return None
