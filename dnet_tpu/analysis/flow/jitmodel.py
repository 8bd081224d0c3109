"""Resolution of jitted callables and their donation/static contracts.

The flow passes need to know, for a call like ``self._step(*args)``, that
``self._step`` is ``jax.jit(fn, donate_argnums=(3, 8))`` — possibly
wrapped in ``instrument_jit`` (the ``JIT_FNS`` seed set from
``obs.phases``).  This module builds that map per source file with the
same call-graph spirit as DL004:

- direct bindings: ``x = jax.jit(f, ...)``, ``self._step =
  instrument_jit(jax.jit(f, donate_argnums=(3, 8)), "batched_step")``,
  dict-literal bindings (``self._programs = {"head": jax.jit(...)}``)
  keyed by their constant string;
- decorator entries: ``@jax.jit`` / ``@partial(jax.jit, ...)`` defs.

A program that a factory method returns (``self._rot_fn(R)(*args)``,
parallel/pipelined.py) is not modelled: no such call site in the tree
resolves within its own file.

``donate_argnums`` / ``static_argnums`` are honoured only when literal
ints/tuples — a computed tuple (``donate_argnums=donate``) yields a spec
with unknown donation, which the passes treat as "don't know, stay
quiet" rather than guessing.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Tuple

from dnet_tpu.analysis.core import SourceFile, dotted

__all__ = ["JitSpec", "jit_bindings", "resolve_jit_call"]

_JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit"}
_WRAPPERS = {"instrument_jit", "obs.jit.instrument_jit"}
_PARTIAL = {"partial", "functools.partial"}


@dataclasses.dataclass(frozen=True)
class JitSpec:
    """One jitted callable's call contract."""

    label: str                       #: display name (binding or JIT_FNS label)
    donate: Tuple[int, ...] = ()     #: literal donate_argnums
    donate_names: Tuple[str, ...] = ()
    static: Tuple[int, ...] = ()     #: literal static_argnums
    static_names: Tuple[str, ...] = ()
    lineno: int = 0
    #: the wrapped function's name (jax.jit's first arg) when it is a
    #: plain name — lets DL022 look the callee's signature span up
    fn_name: str = ""
    #: False when donate/static kwargs were present but not literal —
    #: the passes must not reason about positions they cannot see
    exact: bool = True


def _int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out: List[int] = []
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant) and isinstance(elt.value, int)):
                return None
            out.append(elt.value)
        return tuple(out)
    return None


def _str_tuple(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out: List[str] = []
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant) and isinstance(elt.value, str)):
                return None
            out.append(elt.value)
        return tuple(out)
    return None


def _spec_from_jit_call(call: ast.Call, label: str) -> JitSpec:
    donate: Tuple[int, ...] = ()
    donate_names: Tuple[str, ...] = ()
    static: Tuple[int, ...] = ()
    static_names: Tuple[str, ...] = ()
    exact = True
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            got = _int_tuple(kw.value)
            if got is None:
                exact = False
            else:
                donate = got
        elif kw.arg == "donate_argnames":
            got_s = _str_tuple(kw.value)
            if got_s is None:
                exact = False
            else:
                donate_names = got_s
        elif kw.arg == "static_argnums":
            got = _int_tuple(kw.value)
            if got is None:
                exact = False
            else:
                static = got
        elif kw.arg == "static_argnames":
            got_s = _str_tuple(kw.value)
            if got_s is None:
                exact = False
            else:
                static_names = got_s
    fn_name = dotted(call.args[0]).split(".")[-1] if call.args else ""
    return JitSpec(
        label=label, donate=donate, donate_names=donate_names,
        static=static, static_names=static_names,
        lineno=call.lineno, fn_name=fn_name, exact=exact,
    )


def _unwrap_jit(node: ast.AST) -> Optional[Tuple[ast.Call, Optional[str]]]:
    """``(jit_call, instrument_label)`` if ``node`` is a jax.jit call,
    possibly wrapped in instrument_jit / functools.partial."""
    if not isinstance(node, ast.Call):
        return None
    d = dotted(node.func)
    if d in _JIT_NAMES:
        return node, None
    if (d in _WRAPPERS or d.split(".")[-1] == "instrument_jit") and node.args:
        inner = _unwrap_jit(node.args[0])
        if inner is not None:
            label = None
            if len(node.args) > 1 and isinstance(node.args[1], ast.Constant):
                label = str(node.args[1].value)
            return inner[0], label or inner[1]
    if d in _PARTIAL and node.args:
        return _unwrap_jit(node.args[0])
    return None


def scope_chain(src: SourceFile, node: ast.AST) -> Tuple[str, ...]:
    """Names of the function defs enclosing ``node``, outermost first."""
    names: List[str] = []
    for anc in src.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            names.append(anc.name)
    return tuple(reversed(names))


def _scoped_key(chain: Tuple[str, ...], name: str) -> str:
    return f"{'/'.join(chain)}:{name}" if chain else name


def jit_bindings(src: SourceFile) -> Dict[str, JitSpec]:
    """dotted binding -> :class:`JitSpec` for one module.

    Keys are the names call sites use: ``self._step``, ``step_fn`` and
    ``self._programs['head']`` (dict-literal bindings).  Plain-name
    bindings inside a function are scoped to it (``'outer/inner:name'``)
    so two functions' local ``jitted`` variables never collide; dotted
    (``self.*``) bindings are module-wide."""
    out: Dict[str, JitSpec] = {}
    tree = src.tree
    if tree is None:
        return out
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            hit = _unwrap_jit(node.value)
            if hit is not None:
                for t in node.targets:
                    d = dotted(t)
                    if not d:
                        continue
                    if isinstance(t, ast.Name):
                        chain = scope_chain(src, node)
                        out[_scoped_key(chain, d)] = _spec_from_jit_call(
                            hit[0], hit[1] or d
                        )
                    else:
                        out[d] = _spec_from_jit_call(hit[0], hit[1] or d)
                continue
            if isinstance(node.value, ast.Dict):
                for k, v in zip(node.value.keys, node.value.values):
                    hit = _unwrap_jit(v)
                    if hit is None or not (
                        isinstance(k, ast.Constant) and isinstance(k.value, str)
                    ):
                        continue
                    for t in node.targets:
                        d = dotted(t)
                        if d:
                            key = f"{d}[{k.value!r}]"
                            out[key] = _spec_from_jit_call(hit[0], hit[1] or key)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if dotted(dec) in _JIT_NAMES:
                    out[node.name] = JitSpec(label=node.name, lineno=node.lineno)
                elif isinstance(dec, ast.Call):
                    hit = _unwrap_jit(dec)
                    if hit is not None:
                        out[node.name] = _spec_from_jit_call(hit[0], node.name)
    return out


def resolve_jit_call(
    call: ast.Call,
    bindings: Dict[str, JitSpec],
    src: Optional[SourceFile] = None,
) -> Optional[JitSpec]:
    """The spec a call site dispatches to, or None.

    Handles ``self._step(...)`` (direct) and
    ``self._programs['head'](...)`` (dict binding).  With ``src``,
    plain-name lookups walk the call's scope chain innermost-out, matching
    the function-scoped binding keys."""
    func = call.func
    d = dotted(func)
    if d:
        if isinstance(func, ast.Name) and src is not None:
            chain = scope_chain(src, call)
            for i in range(len(chain), -1, -1):
                spec = bindings.get(_scoped_key(chain[:i], d))
                if spec is not None:
                    return spec
        spec = bindings.get(d)
        if spec is not None:
            return spec
        short = d.split(".", 1)[-1] if d.startswith("self.") else d
        return bindings.get(short)
    if isinstance(func, ast.Subscript):
        base = dotted(func.value)
        if base and isinstance(func.slice, ast.Constant) and isinstance(
            func.slice.value, str
        ):
            return bindings.get(f"{base}[{func.slice.value!r}]")
    return None
