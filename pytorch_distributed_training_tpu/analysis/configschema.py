"""config-schema: generated key/type schema + static YAML validation.

The config surface is parsed in one place per subsystem but DOCUMENTED
nowhere: ``topology.parse_*`` owns the ``training.*`` sections,
``*.from_config`` / ``resolve_config`` own checkpointing and serving, and
each uses one of two closed-set idioms to reject typos
(``unknown = set(sec) - {...}; if unknown: raise`` in topology,
dict-copy + ``pop`` + ``if sec: raise`` in serving).  This pass extracts
the accepted key/type/default surface from those sites into a generated
schema and then statically validates every shipped ``config/*.yml``
against it — so a misspelled ``bucket_mb`` fails lint instead of failing
a 30-minute run at parse time (or worse, being silently ignored in an
open section).

Extraction walks every function named ``parse_*`` / ``from_config`` /
``resolve_config`` (plus constructor bodies that copy the well-known
``scheduler`` / ``resilience`` kwargs), tracking dict aliases from the
root config down (``serve = cfg["serving"]``,
``fleet_cfg = dict(serve.get("fleet") or {})``) and recording every
``.get`` / ``.pop`` / ``[...]`` / ``in`` / ``.setdefault`` access:

  - key **types** come from literal defaults and enclosing casts
    (``int(sec.get("slots", 8))``).  A bare ``False`` default
    contributes no type — several keys (``training.zero``) accept bool
    OR int by contract; only an explicit ``bool(...)`` cast pins bool.
  - a section is **closed** when either rejection idiom is present;
    only closed sections produce unknown-key findings (open sections
    like ``model`` forward ``**kwargs`` by design).
  - a closed section's declared allow-set minus its actually-read keys
    is a **dead key** finding at the parser (accepted, never read).

YAML validation uses ``yaml.compose`` (node marks give real line
numbers; scalar tags give types without constructing) and degrades to
a no-op when PyYAML is absent — the analyzer must import anywhere the
package does.  Type checks are tag-based: bool is strict (YAML
``true`` is not an int), int satisfies float, ``null`` satisfies
anything (every key here is optional-with-default at parse level; the
hard required set lives in config_parsing and is enforced at load).
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from .core import (
    SEVERITY_ERROR,
    AnalysisContext,
    AnalysisPass,
    Finding,
    SourceModule,
    dotted_name,
)

__all__ = ["ConfigSchemaPass", "extract_schema", "schema_as_json"]

_PARSER_NAMES = ("from_config", "resolve_config")
_ROOT_PARAMS = {"cfg": (), "train_cfg": ("training",)}
# constructor kwargs that carry whole config sections past the parser
# boundary; recognized only via the dict-copy binding idiom
_SEED_PARAMS = {
    "scheduler": ("serving", "scheduler"),
    "resilience": ("serving", "resilience"),
    "quant": ("serving", "quant"),
    "lora": ("serving", "lora"),
    "speculative": ("serving", "speculative"),
    "autoscale": ("serving", "autoscale"),
    "workload": ("serving", "autoscale", "workload"),
    "disagg": ("serving", "disagg"),
}
_ACCESS_METHODS = {"get", "pop", "setdefault"}
_CASTS = {"int", "float", "bool", "str"}

_YAML_TAG_TYPES = {
    "tag:yaml.org,2002:int": "int",
    "tag:yaml.org,2002:float": "float",
    "tag:yaml.org,2002:bool": "bool",
    "tag:yaml.org,2002:str": "str",
    "tag:yaml.org,2002:null": "null",
}
# schema type -> acceptable YAML scalar types (bool-first: strict)
_COMPAT = {
    "int": {"int"},
    "float": {"int", "float"},
    "bool": {"bool"},
    "str": {"str"},
}


class _KeyInfo:
    __slots__ = ("types", "default", "required")

    def __init__(self):
        self.types: Set[str] = set()
        self.default: Optional[str] = None
        self.required = False

    @property
    def type(self) -> str:
        return next(iter(self.types)) if len(self.types) == 1 else "any"


class _Section:
    __slots__ = ("keys", "closed", "allowed", "source")

    def __init__(self):
        self.keys: Dict[str, _KeyInfo] = {}
        self.closed = False
        self.allowed: Optional[Set[str]] = None  # literal allow-set if any
        self.source: Optional[Tuple[str, int]] = None  # (rel, line)

    def effective_allowed(self) -> Set[str]:
        return set(self.allowed) if self.allowed is not None else set(self.keys)


Schema = Dict[Tuple[str, ...], _Section]


def _is_parser(fn: ast.AST) -> bool:
    name = getattr(fn, "name", "")
    return name.startswith("parse_") or name in _PARSER_NAMES


def _str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _default_type(node: Optional[ast.AST]) -> Optional[str]:
    """Type evidence from a literal default (None = no evidence)."""
    if node is None:
        return None
    if isinstance(node, ast.Constant):
        v = node.value
        if isinstance(v, bool) or v is None:
            return None  # bool-or-int keys exist; None pins nothing
        if isinstance(v, int):
            return "int"
        if isinstance(v, float):
            return "float"
        if isinstance(v, str):
            return "str"
        return None
    if isinstance(node, (ast.List, ast.Tuple)):
        return "list"
    if isinstance(node, ast.Dict):
        return "dict"
    return None


def _sectionish_default(node: Optional[ast.AST]) -> bool:
    """Could this .get default still yield a section? (absent/None/{})"""
    if node is None:
        return True
    if isinstance(node, ast.Constant) and node.value is None:
        return True
    if isinstance(node, ast.Dict) and not node.keys:
        return True
    return False


class _FunctionExtractor:
    """Extract section accesses from one parser function into `schema`."""

    def __init__(self, module: SourceModule, fn: ast.AST, schema: Schema):
        self.module = module
        self.fn = fn
        self.schema = schema
        self.env: Dict[str, Tuple[str, ...]] = {}
        self.copied: Set[str] = set()  # env names bound via dict(...) copy
        self.casts: Dict[int, str] = {}  # id(node) -> cast type

    def section(self, path: Tuple[str, ...]) -> _Section:
        sec = self.schema.setdefault(path, _Section())
        if sec.source is None:
            sec.source = (self.module.rel, self.fn.lineno)
        return sec

    # ------------------------------------------------------------- aliases

    def _resolve(self, node: ast.AST) -> Optional[Tuple[str, ...]]:
        """Resolve an expression to a config-section path, if it is one."""
        if isinstance(node, ast.Name):
            return self.env.get(node.id)
        if isinstance(node, ast.Subscript):
            key = _str_const(node.slice)
            base = self._resolve(node.value)
            if key is not None and base is not None:
                return base + (key,)
            return None
        if isinstance(node, ast.BoolOp) and isinstance(node.op, ast.Or) and node.values:
            return self._resolve(node.values[0])
        if isinstance(node, ast.Call):
            callee = dotted_name(node.func)
            if callee == "dict" and len(node.args) == 1:
                return self._resolve(node.args[0])
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in ("get", "pop")
                and node.args
            ):
                key = _str_const(node.args[0])
                default = node.args[1] if len(node.args) > 1 else None
                if key is not None and _sectionish_default(default):
                    base = self._resolve(node.func.value)
                    if base is not None:
                        return base + (key,)
        return None

    def _bind_aliases(self) -> None:
        params = {
            a.arg
            for a in list(self.fn.args.args)
            + list(self.fn.args.kwonlyargs)
            + list(self.fn.args.posonlyargs)
        }
        for name, path in _ROOT_PARAMS.items():
            if name in params:
                self.env[name] = path
        assigns = sorted(
            (n for n in ast.walk(self.fn) if isinstance(n, ast.Assign)),
            key=lambda n: n.lineno,
        )
        for node in assigns:
            if len(node.targets) != 1 or not isinstance(node.targets[0], ast.Name):
                continue
            target = node.targets[0].id
            value = node.value
            # seed kwargs enter the env only via the dict-copy idiom
            seed = None
            if isinstance(value, ast.Call) and dotted_name(value.func) == "dict":
                if len(value.args) == 1:
                    inner = value.args[0]
                    if isinstance(inner, ast.BoolOp):
                        inner = inner.values[0]
                    if isinstance(inner, ast.Name) and inner.id in _SEED_PARAMS:
                        if inner.id in params:
                            seed = _SEED_PARAMS[inner.id]
            if seed is not None:
                self.env[target] = seed
                self.copied.add(target)
                continue
            path = self._resolve(value)
            if path is not None:
                self.env[target] = path
                if isinstance(value, ast.Call) and dotted_name(value.func) == "dict":
                    self.copied.add(target)

    # ------------------------------------------------------------ accesses

    def _collect_casts(self) -> None:
        for node in ast.walk(self.fn):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in _CASTS
                and node.args
            ):
                for sub in ast.walk(node.args[0]):
                    self.casts[id(sub)] = node.func.id

    def _record(
        self,
        path: Tuple[str, ...],
        key: str,
        node: ast.AST,
        default: Optional[ast.AST],
        required: bool,
        is_section: bool,
    ) -> None:
        info = self.section(path).keys.setdefault(key, _KeyInfo())
        info.required = info.required or required
        if is_section:
            info.types.add("dict")
            return
        cast = self.casts.get(id(node))
        t = cast if cast else _default_type(default)
        if t:
            info.types.add(t)
        if default is not None and info.default is None:
            try:
                info.default = ast.unparse(default)
            except Exception:
                pass

    def _walk_accesses(self) -> None:
        for node in ast.walk(self.fn):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                if node.func.attr in _ACCESS_METHODS and node.args:
                    key = _str_const(node.args[0])
                    base = self._resolve(node.func.value)
                    if key is not None and base is not None:
                        default = node.args[1] if len(node.args) > 1 else None
                        is_section = self._resolve(node) is not None and (
                            node.func.attr != "setdefault"
                        )
                        self._record(base, key, node, default, False, is_section)
            elif isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
                key = _str_const(node.slice)
                base = self._resolve(node.value)
                if key is not None and base is not None:
                    is_section = self._resolve(node) is not None
                    self._record(base, key, node, None, True, is_section)
            elif isinstance(node, ast.Compare) and len(node.ops) == 1:
                if isinstance(node.ops[0], (ast.In, ast.NotIn)):
                    key = _str_const(node.left)
                    base = self._resolve(node.comparators[0])
                    if key is not None and base is not None:
                        self._record(base, key, node, None, False, False)

    # -------------------------------------------------------- closed sets

    def _detect_closed(self) -> None:
        # idiom 1: unknown = set(sec) - {"a", "b", ...}; if unknown: raise
        for node in ast.walk(self.fn):
            if (
                isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Sub)
                and isinstance(node.left, ast.Call)
                and dotted_name(node.left.func) == "set"
                and len(node.left.args) == 1
                and isinstance(node.right, ast.Set)
            ):
                path = self._resolve(node.left.args[0])
                allowed = {
                    s for s in (_str_const(e) for e in node.right.elts) if s
                }
                if path is not None and allowed:
                    sec = self.section(path)
                    sec.closed = True
                    sec.allowed = (sec.allowed or set()) | allowed
                    sec.source = (self.module.rel, node.lineno)
        # idiom 2: sec = dict(...); sec.pop(...)*; if sec: raise
        for node in ast.walk(self.fn):
            if (
                isinstance(node, ast.If)
                and isinstance(node.test, ast.Name)
                and node.test.id in self.copied
                and any(isinstance(s, ast.Raise) for s in node.body)
            ):
                path = self.env.get(node.test.id)
                if path is not None:
                    self.section(path).closed = True

    def extract(self) -> None:
        self._bind_aliases()
        if not self.env:
            return
        self._collect_casts()
        self._walk_accesses()
        self._detect_closed()


def _has_seed_binding(fn: ast.AST) -> bool:
    params = {a.arg for a in list(fn.args.args) + list(fn.args.kwonlyargs)}
    if not (params & set(_SEED_PARAMS)):
        return False
    for node in ast.walk(fn):
        if (
            isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Call)
            and dotted_name(node.value.func) == "dict"
        ):
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Name) and sub.id in _SEED_PARAMS:
                    return True
    return False


def _model_family_fields(cls: ast.ClassDef) -> Optional[List[ast.AnnAssign]]:
    """The annotated fields of a class that states ``is_language_model =
    True`` (a language-model family: its ``model:`` section's keys are the
    module's fields, forwarded verbatim by ``get_model``); None for any
    other class."""
    stated = any(
        isinstance(stmt, ast.Assign)
        and any(isinstance(t, ast.Name) and t.id == "is_language_model"
                for t in stmt.targets)
        and isinstance(stmt.value, ast.Constant) and stmt.value.value is True
        for stmt in cls.body
    )
    if not stated:
        return None
    return [
        stmt for stmt in cls.body
        if isinstance(stmt, ast.AnnAssign) and isinstance(stmt.target, ast.Name)
    ]


def _extract_model_families(module: SourceModule, schema: Schema) -> None:
    """The open ``model`` section learns the keys a language-model family
    takes (a field annotated plainly ``int``/``float``/``bool``/``str``
    pins its type; anything else is ``any``).  ``vocab_size`` and ``dtype``
    are the engine's to pass, not the config's."""
    for cls in ast.walk(module.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        for field in _model_family_fields(cls) or ():
            key = field.target.id
            if key in ("vocab_size", "dtype"):
                continue
            section = schema.setdefault(("model",), _Section())
            if section.source is None:
                section.source = (module.rel, cls.lineno)
            info = section.keys.setdefault(key, _KeyInfo())
            kind = field.annotation
            if isinstance(kind, ast.Name) and kind.id in _CASTS:
                info.types.add(kind.id)
            if info.default is None and field.value is not None:
                info.default = ast.unparse(field.value)


def extract_schema(modules: Sequence[SourceModule]) -> Schema:
    """Build the accepted-config schema from every parser in `modules`,
    and from the fields of the language-model families."""
    schema: Schema = {}
    for module in modules:
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if _is_parser(node) or _has_seed_binding(node):
                _FunctionExtractor(module, node, schema).extract()
        _extract_model_families(module, schema)
    return schema


def schema_as_json(schema: Schema) -> Dict[str, Any]:
    """JSON-friendly dump (the documented config reference)."""
    out: Dict[str, Any] = {}
    for path in sorted(schema):
        sec = schema[path]
        out[".".join(path) or "<root>"] = {
            "closed": sec.closed,
            "keys": {
                k: {
                    "type": info.type,
                    "default": info.default,
                    "required": info.required,
                }
                for k, info in sorted(sec.keys.items())
            },
        }
    return out


# --------------------------------------------------------------------- YAML


def _compose_yaml(text: str):
    try:
        import yaml
    except ImportError:  # analyzer must run anywhere the package imports
        return None
    return yaml.compose(text)


def _scalar_type(node) -> Optional[str]:
    tag = getattr(node, "tag", "")
    return _YAML_TAG_TYPES.get(tag)


class ConfigSchemaPass(AnalysisPass):
    rule = "config-schema"
    description = (
        "config/*.yml must match the schema generated from topology.parse_* "
        "and *.from_config: no unknown keys in closed sections, no type "
        "mismatches, no accepted-but-never-read keys"
    )

    def run(self, modules: Sequence[SourceModule], ctx: AnalysisContext) -> List[Finding]:
        schema = extract_schema(modules)
        findings: List[Finding] = []
        findings.extend(self._dead_keys(schema))
        config_dir = ctx.resolved_config_dir()
        if config_dir.is_dir():
            for path in sorted(config_dir.glob("*.yml")):
                findings.extend(self._validate_yaml(path, schema, ctx))
        return findings

    def _dead_keys(self, schema: Schema) -> List[Finding]:
        findings: List[Finding] = []
        for path in sorted(schema):
            sec = schema[path]
            if sec.allowed is None:
                continue
            for key in sorted(sec.allowed - set(sec.keys)):
                rel, line = sec.source or ("<unknown>", 1)
                findings.append(
                    Finding(
                        rule=self.rule,
                        severity=SEVERITY_ERROR,
                        path=rel,
                        line=line,
                        message=(
                            f"config key {'.'.join(path)}.{key} is accepted "
                            "by the closed-set check but never read — dead "
                            "key (drop it from the allow-set or wire it)"
                        ),
                    )
                )
        return findings

    def _validate_yaml(
        self, path: Path, schema: Schema, ctx: AnalysisContext
    ) -> List[Finding]:
        findings: List[Finding] = []
        try:
            root = _compose_yaml(path.read_text())
        except Exception as exc:
            root = None
            findings.append(
                Finding(
                    rule=self.rule,
                    severity=SEVERITY_ERROR,
                    path=self._rel(path, ctx),
                    line=1,
                    message=f"unparseable YAML: {exc}".splitlines()[0],
                )
            )
        if root is None:
            return findings
        rel = self._rel(path, ctx)
        self._walk(root, (), schema, rel, findings)
        return findings

    def _rel(self, path: Path, ctx: AnalysisContext) -> str:
        try:
            return path.relative_to(ctx.repo_root).as_posix()
        except ValueError:
            return path.name

    def _walk(self, node, path, schema, rel, findings) -> None:
        if not hasattr(node, "value") or not isinstance(node.value, list):
            return
        pairs = [
            p for p in node.value if isinstance(p, tuple) and len(p) == 2
        ]
        if not pairs:
            return
        sec = schema.get(path)
        allowed = sec.effective_allowed() if (sec and sec.closed) else None
        for key_node, val_node in pairs:
            key = getattr(key_node, "value", None)
            if not isinstance(key, str):
                continue
            line = key_node.start_mark.line + 1
            if allowed is not None and key not in allowed:
                findings.append(
                    Finding(
                        rule=self.rule,
                        severity=SEVERITY_ERROR,
                        path=rel,
                        line=line,
                        message=(
                            f"unknown key {'.'.join(path + (key,))} — the "
                            f"{'.'.join(path)} section is closed (accepted: "
                            f"{', '.join(sorted(allowed))})"
                        ),
                    )
                )
            if sec is not None and key in sec.keys:
                expected = sec.keys[key].type
                got = _scalar_type(val_node)
                if (
                    expected in _COMPAT
                    and got is not None
                    and got != "null"
                    and got not in _COMPAT[expected]
                ):
                    findings.append(
                        Finding(
                            rule=self.rule,
                            severity=SEVERITY_ERROR,
                            path=rel,
                            line=line,
                            message=(
                                f"type mismatch for "
                                f"{'.'.join(path + (key,))}: schema says "
                                f"{expected}, YAML value is {got}"
                            ),
                        )
                    )
            self._walk(val_node, path + (key,), schema, rel, findings)
