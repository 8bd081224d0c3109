"""Flow-sensitive checks DL021-DL025 (dnetlint v2).

Per-node pattern matching (DL001-DL020) cannot see "used *after*" or
"inside *this* loop".  These five passes run the CFG + dataflow tier
(flow/cfg.py, flow/dataflow.py) over each function:

DL021 — donation-after-use: a name passed at a ``donate_argnums`` /
``donate_argnames`` position of a jitted callable (resolved through
``instrument_jit`` wrappers and ``*args`` tuples — see
flow/jitmodel.py) is read on some CFG path after the call without being
reassigned.  XLA frees donated buffers; on CPU the read silently works,
on TPU it is garbage.  The sanctioned quiet pattern is the
donate-and-rebind idiom: ``self.kv_store.kv = step(self.kv_store.kv,
...)`` — the rebind kills the stale name on every path.

DL022 — retrace hazards: (a) a raw Python numeric literal or a
``.shape``-derived scalar passed at a NON-static position of a jitted
callable — wrap it in ``jnp.asarray``/``jnp.int32`` (traced array) or
declare the position static; a host scalar that varies re-traces per
value, which is PR 12's mid-run width-compile stall; (b) call sites of
the same jitted callable whose keyword sets (or positional arity, when
the callee's signature cannot absorb the difference) drift — every
distinct signature is a separate compiled program.

DL023 — host sync in a hot loop: the flow refinement of DL005, scoped to
the decode/tick modules (core/batch.py, core/engine.py, sched/).  A
``.item()`` / ``np.asarray`` / ``device_get`` / ``block_until_ready``
INSIDE a per-token or per-tick loop serializes the async dispatch
pipeline once per iteration.  Straight-line packed readbacks (the one
sanctioned per-dispatch sample read) are outside any loop and stay
quiet naturally; obs-gated phase fences are exempted by the same gate
test as DL005.

DL024 — sequential independent awaits in a loop: an ``await`` inside a
``for`` whose iterations carry no data dependency (checked with a
must-assigned analysis confined to the loop body: every name the await
statement reads is either loop-invariant or definitely assigned earlier
in the SAME iteration) serializes a fan-out — N round trips instead of
one ``asyncio.gather``.  Ordered sinks (``.write``/``.drain``), pacing
(``asyncio.sleep``), executor hops (``run_in_executor`` — the compute
executor serializes by ownership contract), latency-measurement loops
(a host clock read in the body: the sequencing IS the measurement), and
loops with ``break``/``return`` early exits are exempt.

DL025 — activation-wire dtype drift: a tensor serialized onto the ring
(``tensor_to_bytes``) or reconstructed from a frame
(``bytes_to_tensor``) with a hard-coded FLOAT dtype — a literal
``np.float32`` construction or a ``"bfloat16"`` string — instead of the
configured wire dtype (``self.wire_dtype`` / model config).  When the
operator flips ``wire_dtype``, a literal site silently keeps shipping
the old width.  Integer/bool payloads (token frames are int32 by
protocol) and the sentinel frame tags (``"tokens"``/``"error"``) are
exempt.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from dnet_tpu.analysis.core import (
    Check,
    Finding,
    Project,
    SourceFile,
    dotted,
    is_serving_path,
    scoped_walk,
)
from dnet_tpu.analysis.flow.cfg import CFG, Node, build_cfg
from dnet_tpu.analysis.flow.dataflow import (
    anchor_roots,
    definitely_assigned,
    node_defs,
    node_uses,
)
from dnet_tpu.analysis.flow.jitmodel import (
    JitSpec,
    jit_bindings,
    resolve_jit_call,
)

_FN_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)


def _functions(tree: ast.AST) -> Iterable[ast.AST]:
    for node in ast.walk(tree):
        if isinstance(node, _FN_DEFS):
            yield node


def _file_bindings(src: SourceFile) -> Dict[str, JitSpec]:
    """jit_bindings memoized on the SourceFile — several flow checks need
    the same pure result for the same unchanged AST."""
    cached = getattr(src, "_flow_jit_bindings", None)
    if cached is None:
        cached = jit_bindings(src)
        src._flow_jit_bindings = cached
    return cached


def _fn_cfg(src: SourceFile, fn: ast.AST) -> CFG:
    """build_cfg memoized per (file, function def)."""
    cache = getattr(src, "_flow_cfg_cache", None)
    if cache is None:
        cache = {}
        src._flow_cfg_cache = cache
    cfg = cache.get(id(fn))
    if cfg is None:
        cfg = build_cfg(fn)
        cache[id(fn)] = cfg
    return cfg


def _anchor_calls(node: Node) -> Iterable[ast.Call]:
    """Calls evaluated by this CFG node (shallow: nested defs opaque;
    compound headers contribute only their test/iter/context exprs)."""
    stack = list(anchor_roots(node.stmt))
    while stack:
        cur = stack.pop()
        if isinstance(cur, _FN_DEFS + (ast.ClassDef, ast.Lambda)):
            continue
        if isinstance(cur, ast.Call):
            yield cur
        stack.extend(ast.iter_child_nodes(cur))


def _positional_exprs(
    call: ast.Call, fn: ast.AST
) -> Optional[List[ast.AST]]:
    """The call's effective positional expressions, resolving a single
    ``*args`` splat through the unique local ``args = (...)`` tuple
    assignment (the ``self._step(*args)`` idiom).  None when a splat
    cannot be resolved."""
    out: List[ast.AST] = []
    for arg in call.args:
        if not isinstance(arg, ast.Starred):
            out.append(arg)
            continue
        name = dotted(arg.value)
        if not name:
            return None
        tuples = [
            a.value
            for a in ast.walk(fn)
            if isinstance(a, ast.Assign)
            and isinstance(a.value, ast.Tuple)
            and any(dotted(t) == name for t in a.targets)
        ]
        if len(tuples) != 1:
            return None
        out.extend(tuples[0].elts)
    return out


# ---- DL021 ----------------------------------------------------------------


class DonationAfterUse(Check):
    code = "DL021"
    name = "donation-after-use"
    description = (
        "a name passed at a donate_argnums position of a jitted callable "
        "is read on a CFG path after the call without reassignment — XLA "
        "freed that buffer; rebind the result (self.kv = step(self.kv, ...))"
    )

    def run_file(self, src: SourceFile, project: Project) -> Iterable[Finding]:
        bindings = _file_bindings(src)
        if not bindings:
            return
        for fn in _functions(src.tree):
            yield from self._check_fn(src, fn, bindings)

    def _check_fn(self, src, fn, bindings) -> Iterable[Finding]:
        cfg = _fn_cfg(src, fn)
        emitted: Set[Tuple[int, str]] = set()
        for node in cfg.nodes:
            for call in _anchor_calls(node):
                spec = resolve_jit_call(call, bindings, src)
                if spec is None or not spec.exact:
                    continue
                if not spec.donate and not spec.donate_names:
                    continue
                for pos, name in self._donated_names(call, fn, spec):
                    yield from self._trace(
                        src, cfg, node, call, spec, pos, name, emitted
                    )

    @staticmethod
    def _donated_names(
        call: ast.Call, fn: ast.AST, spec: JitSpec
    ) -> Iterable[Tuple[str, str]]:
        """(position-label, dotted-name) pairs actually donated here."""
        exprs = _positional_exprs(call, fn)
        if exprs is not None:
            for i in spec.donate:
                if i < len(exprs):
                    d = dotted(exprs[i])
                    if d:
                        yield f"arg {i}", d
        for kw in call.keywords:
            if kw.arg is not None and kw.arg in spec.donate_names:
                d = dotted(kw.value)
                if d:
                    yield f"arg {kw.arg!r}", d

    def _trace(
        self, src, cfg: CFG, node: Node, call, spec, pos, name, emitted
    ) -> Iterable[Finding]:
        # the donate-and-rebind idiom: the calling statement itself
        # rebinds the donated name (self.kv = self._scatter(self.kv, ...))
        if name in node_defs(node):
            return
        seen: Set[int] = set()
        stack = list(node.succs)
        while stack:
            idx = stack.pop()
            if idx in seen:
                continue
            seen.add(idx)
            cur = cfg.nodes[idx]
            if name in node_uses(cur):
                key = (cur.line, name)
                if key not in emitted:
                    emitted.add(key)
                    yield self.finding(
                        src.rel, cur.line,
                        f"'{name}' was donated to {spec.label}() ({pos}, "
                        f"donate_argnums at line {spec.lineno}) and is read "
                        f"here without reassignment — XLA freed that "
                        f"buffer; rebind the call's result first",
                    )
                continue  # report the first use per path
            if name in node_defs(cur):
                continue  # rebound: this path is safe
            stack.extend(cur.succs)


# ---- DL022 ----------------------------------------------------------------


def _scalar_hazard(expr: ast.AST) -> Optional[str]:
    """'Python literal' / '.shape-derived scalar' when ``expr`` is a raw
    host scalar of that kind; None otherwise.  Anything wrapped in a call
    (jnp.asarray(...), jnp.int32(...)) is already an array — quiet."""
    if isinstance(expr, ast.Constant):
        if type(expr.value) in (int, float):
            return "Python literal"
        return None
    if isinstance(expr, ast.UnaryOp):
        return _scalar_hazard(expr.operand)
    if isinstance(expr, ast.Subscript):
        base = dotted(expr.value)
        if base.endswith(".shape") or base == "shape":
            return ".shape-derived scalar"
        return None
    if isinstance(expr, ast.Attribute):
        return None
    if isinstance(expr, ast.BinOp):
        left = _scalar_hazard(expr.left)
        right = _scalar_hazard(expr.right)
        if left is None and right is None:
            return None
        sides = []
        for side, hazard in ((expr.left, left), (expr.right, right)):
            if hazard is None and not isinstance(side, ast.Constant):
                return None  # mixed with a real array/name: not a raw scalar
            sides.append(hazard)
        return next(
            (h for h in sides if h == ".shape-derived scalar"),
            next((h for h in sides if h), None),
        )
    return None


class RetraceHazard(Check):
    code = "DL022"
    name = "retrace-hazard"
    description = (
        "a raw Python literal or .shape-derived scalar at a non-static "
        "position of a jitted callable, or call-site keyword/arity drift "
        "across sites — each distinct host signature is a fresh trace + "
        "compile (the mid-run width-compile stall)"
    )

    def run_file(self, src: SourceFile, project: Project) -> Iterable[Finding]:
        bindings = _file_bindings(src)
        if not bindings:
            return
        callee_spans = self._callee_spans(src)
        #: one jit binding (spec) -> list of (line, n_pos, kwset)
        sites: Dict[JitSpec, List[Tuple[int, int, frozenset]]] = {}
        for fn in _functions(src.tree):
            # shallow walk: a nested def's calls belong to the nested
            # scope's own visit (whose locals resolve *args tuples)
            for node in scoped_walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                spec = resolve_jit_call(node, bindings, src)
                if spec is None:
                    continue
                exprs = _positional_exprs(node, fn)
                if exprs is None:
                    continue
                if spec.exact:
                    yield from self._scalar_findings(src, node, spec, exprs)
                kws = frozenset(
                    kw.arg for kw in node.keywords if kw.arg is not None
                )
                sites.setdefault(spec, []).append(
                    (node.lineno, len(exprs), kws)
                )
        yield from self._drift_findings(src, sites, callee_spans)

    def _scalar_findings(self, src, call, spec, exprs) -> Iterable[Finding]:
        for i, expr in enumerate(exprs):
            if i in spec.static:
                continue
            hazard = _scalar_hazard(expr)
            if hazard is not None:
                yield self.finding(
                    src.rel, expr.lineno,
                    f"{hazard} passed at non-static position {i} of jitted "
                    f"{spec.label}() — a varying host scalar re-traces per "
                    f"value; pass a jnp array or declare the position "
                    f"static_argnums",
                    col=expr.col_offset,
                )
        for kw in call.keywords:
            if kw.arg is None or kw.arg in spec.static_names:
                continue
            hazard = _scalar_hazard(kw.value)
            if hazard is not None:
                yield self.finding(
                    src.rel, kw.value.lineno,
                    f"{hazard} passed at non-static keyword {kw.arg!r} of "
                    f"jitted {spec.label}() — pass a jnp array or declare "
                    f"it static_argnames",
                    col=kw.value.col_offset,
                )

    @staticmethod
    def _callee_spans(src: SourceFile) -> Dict[str, Tuple[int, int, bool]]:
        """def name -> (required positional, total positional, *args?)
        so optional-parameter differences across sites don't count as
        drift."""
        spans: Dict[str, Tuple[int, int, bool]] = {}
        for fn in _functions(src.tree):
            args = fn.args
            total = len(args.posonlyargs) + len(args.args)
            required = total - len(args.defaults)
            spans[fn.name] = (required, total, args.vararg is not None)
        return spans

    def _drift_findings(self, src, sites, callee_spans) -> Iterable[Finding]:
        for spec, calls in sorted(
            sites.items(), key=lambda kv: (kv[0].label, kv[0].lineno)
        ):
            if len(calls) < 2:
                continue
            span = callee_spans.get(spec.fn_name)

            def absorbed(n1: int, n2: int) -> bool:
                """Both arities are valid fills of the callee's signature
                (defaulted trailing params / *args) — one contract, not
                drift."""
                return span is not None and (
                    span[2]
                    or (span[0] <= n1 <= span[1] and span[0] <= n2 <= span[1])
                )

            # each differing site is judged per dimension: a kwarg-set
            # difference is always drift (jit caches kwargs separately),
            # an arity difference only when the callee cannot absorb it
            ref_line, ref_n, ref_kws = calls[0]
            for line, n, kws in calls[1:]:
                if kws != ref_kws:
                    what = f"keywords {sorted(kws)} vs {sorted(ref_kws)}"
                elif n != ref_n and not absorbed(n, ref_n):
                    what = f"arity {n} vs {ref_n}"
                else:
                    continue
                yield self.finding(
                    src.rel, line,
                    f"call-site signature of jitted {spec.label}() drifts "
                    f"across sites ({what}, first site at line "
                    f"{ref_line}) — every distinct host signature "
                    f"is a separate compiled program",
                )


# ---- DL023 ----------------------------------------------------------------

_SYNC_ATTRS = {"item", "block_until_ready"}
_SYNC_DOTTED = {
    "jax.block_until_ready",
    "jax.device_get",
    "np.asarray",
    "np.array",
    "numpy.asarray",
    "numpy.array",
}

#: the decode/tick hot-loop surface
HOT_LOOP_FILES = ("dnet_tpu/core/batch.py", "dnet_tpu/core/engine.py")
HOT_LOOP_PREFIXES = ("dnet_tpu/sched/",)


class HostSyncInHotLoop(Check):
    code = "DL023"
    name = "host-sync-in-hot-loop"
    description = (
        ".item() / np.asarray / device_get / block_until_ready inside a "
        "per-token or per-tick loop of the decode modules, outside obs "
        "gating — one forced sync per iteration serializes the dispatch "
        "pipeline (flow-refined DL005)"
    )

    def run_file(self, src: SourceFile, project: Project) -> Iterable[Finding]:
        if src.rel not in HOT_LOOP_FILES and not src.rel.startswith(
            HOT_LOOP_PREFIXES
        ):
            return
        from dnet_tpu.analysis.checks_jit import UngatedDeviceSync

        for fn in _functions(src.tree):
            cfg = _fn_cfg(src, fn)
            for node in cfg.nodes:
                if not node.loops:
                    continue
                for call in _anchor_calls(node):
                    what = self._sync_name(call)
                    if what is None:
                        continue
                    if UngatedDeviceSync._gated(src, call):
                        continue
                    yield self.finding(
                        src.rel, call.lineno,
                        f"forced host sync {what}() inside the "
                        f"{fn.name}() loop at line "
                        f"{cfg.nodes[node.loops[-1]].line} — one device "
                        f"fence per iteration; hoist it out of the loop "
                        f"or gate it on obs",
                        col=call.col_offset,
                    )

    @staticmethod
    def _sync_name(call: ast.Call) -> Optional[str]:
        d = dotted(call.func)
        if d in _SYNC_DOTTED:
            return d
        if (
            isinstance(call.func, ast.Attribute)
            and call.func.attr in _SYNC_ATTRS
            and not call.args
            and not call.keywords
        ):
            return d or call.func.attr
        return None


# ---- DL024 ----------------------------------------------------------------

_CLOCKS = {"time.perf_counter", "time.monotonic", "time.time", "loop.time"}
_AWAIT_EXEMPT_SUFFIX = (".run_in_executor", ".write", ".drain")
_AWAIT_EXEMPT_EXACT = {"asyncio.sleep"}


class SequentialAwaitFanout(Check):
    code = "DL024"
    name = "sequential-await-in-loop"
    description = (
        "await in a for loop with no loop-carried data dependency — N "
        "sequential round trips where one asyncio.gather would do; "
        "ordered sinks, sleeps, executor hops, measurement loops, and "
        "break/return loops are exempt"
    )

    def run_file(self, src: SourceFile, project: Project) -> Iterable[Finding]:
        if not is_serving_path(src.rel):
            return
        for fn in _functions(src.tree):
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            cfg = _fn_cfg(src, fn)
            for header in cfg.loop_headers():
                if not isinstance(header.stmt, ast.For):
                    continue  # async-for iterators and while loops are
                    # inherently sequential / state-driven
                finding = self._check_loop(src, fn, cfg, header)
                if finding is not None:
                    yield finding

    def _check_loop(self, src, fn, cfg: CFG, header: Node) -> Optional[Finding]:
        body = [n for n in cfg.nodes if header.idx in n.loops]
        own = [n for n in body if n.loops and n.loops[-1] == header.idx]
        # early-exit loops: sequencing is the semantics
        for n in body:
            if isinstance(n.stmt, (ast.Break, ast.Return)):
                return None
        # measurement loops: a host clock read means the await is being
        # timed — gathering would corrupt the measurement
        for n in body:
            for call in _anchor_calls(n):
                if dotted(call.func) in _CLOCKS:
                    return None
        region = {header.idx} | {n.idx for n in body}
        assigned = definitely_assigned(cfg, within=region, start=header.idx)
        written: Set[str] = set()
        for n in body:
            written |= node_defs(n)
        awaits: List[Tuple[Node, ast.Await]] = []
        for n in own:
            stack = list(anchor_roots(n.stmt))
            while stack:
                cur = stack.pop()
                if isinstance(cur, _FN_DEFS + (ast.ClassDef, ast.Lambda)):
                    continue
                if isinstance(cur, ast.Await):
                    awaits.append((n, cur))
                stack.extend(ast.iter_child_nodes(cur))
        for node, awaited in awaits:
            if self._exempt_await(awaited):
                continue
            reads = node_uses(node)
            carried = {
                name
                for name in reads & written
                if name not in assigned[node.idx]
            }
            if carried:
                continue
            return self.finding(
                src.rel, awaited.lineno,
                f"sequential await in the {fn.name}() loop at line "
                f"{header.line} with no loop-carried dependency — fan "
                f"out with asyncio.gather instead of one round trip per "
                f"iteration",
                col=awaited.col_offset,
            )
        return None

    @staticmethod
    def _exempt_await(awaited: ast.Await) -> bool:
        value = awaited.value
        if not isinstance(value, ast.Call):
            return False
        d = dotted(value.func)
        return (
            d in _AWAIT_EXEMPT_EXACT
            or d.startswith("asyncio.sleep")
            or d.endswith(_AWAIT_EXEMPT_SUFFIX)
        )


# ---- DL025 ----------------------------------------------------------------

_FLOAT_DTYPE_STRINGS = {
    "float32", "float16", "bfloat16", "float64", "f32", "f16", "bf16",
    "f64", "float8_e4m3", "float8_e5m2",
}
_FLOAT_DTYPE_DOTTED = {
    "np.float32", "np.float16", "np.float64", "numpy.float32",
    "numpy.float16", "numpy.float64", "jnp.float32", "jnp.float16",
    "jnp.bfloat16", "jax.numpy.bfloat16", "ml_dtypes.bfloat16",
    "ml_dtypes.float8_e4m3fn", "ml_dtypes.float8_e5m2",
}

#: modules that build / parse wire frames
_WIRE_PREFIXES = ("dnet_tpu/shard/", "dnet_tpu/transport/", "dnet_tpu/api/")


def _float_literal_dtype(expr: ast.AST) -> bool:
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value in _FLOAT_DTYPE_STRINGS
    return dotted(expr) in _FLOAT_DTYPE_DOTTED


def _construction_dtype_literal(expr: ast.AST) -> Optional[ast.AST]:
    """The literal FLOAT dtype node inside a tensor-construction
    expression (np.zeros(..., np.float32), x.astype('float32'), ...)."""
    for sub in ast.walk(expr):
        if not isinstance(sub, ast.Call):
            continue
        for kw in sub.keywords:
            if kw.arg == "dtype" and _float_literal_dtype(kw.value):
                return kw.value
        func = sub.func
        name = func.attr if isinstance(func, ast.Attribute) else dotted(func)
        if name.split(".")[-1] in (
            "zeros", "ones", "full", "empty", "asarray", "array", "astype"
        ):
            for arg in sub.args:
                if _float_literal_dtype(arg):
                    return arg
    return None


class WireDtypeDrift(Check):
    code = "DL025"
    name = "wire-dtype-drift"
    description = (
        "an activation serialized (tensor_to_bytes) or parsed "
        "(bytes_to_tensor) at a hard-coded float dtype instead of the "
        "configured wire dtype — flipping wire_dtype would silently skip "
        "this site; int/bool token payloads are protocol-fixed and exempt"
    )

    def run_file(self, src: SourceFile, project: Project) -> Iterable[Finding]:
        if not src.rel.startswith(_WIRE_PREFIXES):
            return
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func).split(".")[-1]
            if fname == "tensor_to_bytes":
                yield from self._check_serialize(src, node)
            elif fname in ("bytes_to_tensor", "bytes_to_device"):
                yield from self._check_parse(src, node)

    def _check_serialize(self, src, call: ast.Call) -> Iterable[Finding]:
        wire = call.args[1] if len(call.args) > 1 else None
        for kw in call.keywords:
            if kw.arg == "wire_dtype":
                wire = kw.value
        if wire is not None and _float_literal_dtype(wire):
            yield self.finding(
                src.rel, wire.lineno,
                "wire dtype hard-coded at a tensor_to_bytes call — derive "
                "it from the configured wire_dtype (config/model), not a "
                "literal",
                col=wire.col_offset,
            )
            return
        if wire is None and call.args:
            literal = _construction_dtype_literal(call.args[0])
            if literal is not None:
                yield self.finding(
                    src.rel, literal.lineno,
                    "activation built at a literal float dtype and "
                    "serialized without a wire_dtype — pass the configured "
                    "wire dtype to tensor_to_bytes or derive the "
                    "construction dtype from config",
                    col=literal.col_offset,
                )

    def _check_parse(self, src, call: ast.Call) -> Iterable[Finding]:
        dtype = call.args[1] if len(call.args) > 1 else None
        for kw in call.keywords:
            if kw.arg == "dtype":
                dtype = kw.value
        if dtype is not None and _float_literal_dtype(dtype):
            yield self.finding(
                src.rel, dtype.lineno,
                "frame payload parsed at a hard-coded float dtype — use "
                "the dtype the frame header declares",
                col=dtype.col_offset,
            )


FLOW_CHECKS = [
    DonationAfterUse(),
    RetraceHazard(),
    HostSyncInHotLoop(),
    SequentialAwaitFanout(),
    WireDtypeDrift(),
]
