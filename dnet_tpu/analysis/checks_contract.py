"""Contract-drift checks: conventions PRs 1-7 established, machine-checked.

DL006 — ``DNET_*`` environment reads outside ``config.py``: the settings
layer owns precedence (defaults < .env < process env < CLI) and the
settings cache; a stray ``os.environ.get("DNET_...")`` silently skips
.env files, bypasses type casting, and drifts from ``.env.example``.
``config.env_flag()`` is the sanctioned escape hatch for flags that must
observe post-cache env flips; the module allowlist below covers the
documented pre-import bootstraps.

DL007 — silent exception swallows: ``except Exception: pass`` on a
serving path turns real failures (half-closed streams, leaked channels)
into nothing.  The contract: every broad catch either logs (debug is
fine) or counts.

DL008 — typed-error and wire-header drift: (a) every ``InferenceError``
subclass must appear in the HTTP status mapping (api/http.py) — an
unmapped class falls through to 500 and breaks the 429/504 retry
contract; (b) every ``ActivationFrame`` construction must stamp
``epoch=`` and ``deadline=`` and every ``TokenPayload`` must stamp
``epoch=`` — an unstamped frame is invisible to the zombie fence and the
deadline dropper (membership PR 6, admission PR 5).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set

from dnet_tpu.analysis.core import (
    Check,
    Finding,
    Project,
    SourceFile,
    dotted,
    is_serving_path,
)

#: rel-path -> why raw DNET_* reads are sanctioned there
DL006_ALLOWLIST: Dict[str, str] = {
    "dnet_tpu/config.py": "the settings layer — THE sanctioned env reader",
}

_BROAD = {"Exception", "BaseException"}

#: wire classes (transport/protocol.py) -> keywords every constructor
#: outside the protocol module itself must stamp
_FRAME_REQUIRED = {
    "ActivationFrame": ("epoch", "deadline"),
    "TokenPayload": ("epoch",),
}

_ERROR_BASE = "InferenceError"
_STATUS_MAP_SUFFIX = "api/http.py"
#: where typed errors are defined: the api's own, and the leaf below both
#: layers that holds the base and what core/ raises
_ERROR_HOME_SUFFIXES = ("api/inference.py", "core/types.py")


def _env_read_key(node: ast.AST) -> str:
    """The literal env-var name read by this node, or ''."""
    if isinstance(node, ast.Call):
        d = dotted(node.func)
        if d in ("os.environ.get", "os.getenv") and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                return arg.value
    elif isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
        if dotted(node.value) == "os.environ" and isinstance(
            node.slice, ast.Constant
        ) and isinstance(node.slice.value, str):
            return node.slice.value
    elif isinstance(node, ast.Compare) and len(node.ops) == 1 and isinstance(
        node.ops[0], (ast.In, ast.NotIn)
    ):
        if (
            dotted(node.comparators[0]) == "os.environ"
            and isinstance(node.left, ast.Constant)
            and isinstance(node.left.value, str)
        ):
            return node.left.value
    return ""


class EnvReadOutsideConfig(Check):
    code = "DL006"
    name = "env-read-outside-config"
    description = (
        "DNET_* environment reads outside config.py bypass .env layering, "
        "type casting, and the settings cache — use a Settings field or "
        "config.env_flag()"
    )

    def run_file(self, src: SourceFile, project: Project) -> Iterable[Finding]:
        if src.rel in DL006_ALLOWLIST:
            return
        for node in ast.walk(src.tree):
            key = _env_read_key(node)
            if key.startswith("DNET_"):
                yield self.finding(
                    src.rel, node.lineno,
                    f"raw read of {key} outside config.py — route through "
                    f"a Settings field or config.env_flag()",
                    col=node.col_offset,
                )


class SilentExceptionSwallow(Check):
    code = "DL007"
    name = "silent-exception-swallow"
    description = (
        "'except Exception: pass'-style swallow on a serving path without "
        "a counter or log — failures must leave a trace"
    )

    def run_file(self, src: SourceFile, project: Project) -> Iterable[Finding]:
        if not is_serving_path(src.rel):
            return
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._broad(node.type):
                continue
            if all(self._trivial(stmt) for stmt in node.body):
                caught = dotted(node.type) if node.type is not None else "bare"
                yield self.finding(
                    src.rel, node.lineno,
                    f"broad except ({caught}) silently swallows — add a "
                    f"debug log or a counter",
                    col=node.col_offset,
                )

    @staticmethod
    def _broad(type_node) -> bool:
        if type_node is None:
            return True
        if isinstance(type_node, ast.Name):
            return type_node.id in _BROAD
        if isinstance(type_node, ast.Tuple):
            return any(
                isinstance(e, ast.Name) and e.id in _BROAD
                for e in type_node.elts
            )
        return False

    @staticmethod
    def _trivial(stmt: ast.stmt) -> bool:
        if isinstance(stmt, (ast.Pass, ast.Continue)):
            return True
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant):
            return True  # docstring / ellipsis
        return False


class ContractDrift(Check):
    code = "DL008"
    name = "error-and-header-contract"
    description = (
        "InferenceError subclasses must map to an HTTP status in "
        "api/http.py; ActivationFrame/TokenPayload constructions must "
        "stamp epoch (and deadline for frames)"
    )

    def run_project(self, project: Project) -> Iterable[Finding]:
        yield from self._typed_errors(project)
        yield from self._frame_headers(project)

    def _typed_errors(self, project: Project) -> Iterable[Finding]:
        homes = [
            h for h in map(project.find_suffix, _ERROR_HOME_SUFFIXES)
            if h is not None and h.tree is not None
        ]
        status_map = project.find_suffix(_STATUS_MAP_SUFFIX)
        if not homes or status_map is None or status_map.tree is None:
            return
        subclasses: Dict[str, tuple] = {}
        known: Set[str] = {_ERROR_BASE}
        # two passes so grandchildren (subclass-of-subclass) resolve
        for _ in range(2):
            for home in homes:
                for node in ast.walk(home.tree):
                    if isinstance(node, ast.ClassDef) and any(
                        dotted(b).split(".")[-1] in known for b in node.bases
                    ):
                        if node.name not in known:
                            known.add(node.name)
                            subclasses[node.name] = (home.rel, node.lineno)
        mapped = {
            n.id for n in ast.walk(status_map.tree) if isinstance(n, ast.Name)
        }
        for name, (rel, lineno) in sorted(subclasses.items()):
            if name not in mapped:
                yield self.finding(
                    rel, lineno,
                    f"typed error {name} has no status mapping in "
                    f"{status_map.rel} — it will fall through to a "
                    f"generic 500",
                )

    def _frame_headers(self, project: Project) -> Iterable[Finding]:
        for src in project.files:
            if src.tree is None or src.rel.endswith("transport/protocol.py"):
                continue
            for node in ast.walk(src.tree):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted(node.func)
                cls = d.split(".")[-1]
                required = _FRAME_REQUIRED.get(cls)
                # only direct constructions (Name or module.Name), not
                # classmethods like TokenPayload.from_result
                if required is None or (d != cls and "." in d and not d.endswith(
                    f".{cls}"
                )):
                    continue
                if isinstance(node.func, ast.Attribute) and node.func.attr != cls:
                    continue
                kws = {kw.arg for kw in node.keywords}
                if None in kws:  # **kwargs — assume the dict carries them
                    continue
                missing = [k for k in required if k not in kws]
                if missing:
                    yield self.finding(
                        src.rel, node.lineno,
                        f"{cls}(...) constructed without stamping "
                        f"{'/'.join(missing)} — unfenced against zombie "
                        f"epochs / deadline drops",
                        col=node.col_offset,
                    )
