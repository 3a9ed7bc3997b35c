"""The five invariant rules (DESIGN.md §11).

Each rule encodes one load-bearing contract from CHANGES.md/DESIGN.md:

  * ``r1-host-sync``      — hot-path modules make exactly the sanctioned
    host-scalar reads and no others (§8's two-phase query discipline);
  * ``r2-recompile-hazard`` — shape-bearing arguments of jitted entry
    points flow from the bucketing helpers, so live traffic can never
    conjure a shape warmup didn't compile (§5/§8 zero-recompile serving);
  * ``r3-wire-protocol``  — cluster code only names whitelisted wire
    dtypes and never imports pickle-family serializers (§10);
  * ``r4-mutation-discipline`` — mutating replica/engine calls in the
    router layer are dominated by a straggler quiesce or live inside an
    ``@under_quiesce``-marked helper (§7's hedged-straggler race);
  * ``r5-aliasing``       — no ``jnp.asarray`` zero-copy view over a
    numpy buffer that the same scope later mutates (the PR-1 delta-seal
    gotcha).

All matching is terminal-name + dotted-prefix based (see ``taint.py``):
single-module analysis cannot resolve imports, and does not need to —
the hot-path vocabulary is pinned by these very rules.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .engine import Finding, Module, Rule, qualname_of
from .taint import (FunctionTaint, TaintConfig, _dotted, call_name,
                    iter_functions, terminal_name)

__all__ = ["HostSyncRule", "RecompileHazardRule", "WireProtocolRule",
           "MutationDisciplineRule", "AliasingRule", "default_rules"]


# -- shared vocabulary ------------------------------------------------------

# calls that return device arrays (jitted entry points, pipeline stages,
# kernel wrappers); method or function position, terminal name match
DEVICE_FNS = {
    "query_compact", "warm_compact",
    "probe_index", "finish_query", "query_index",
    "build_index",
    "_query_delta", "_probe_segment", "_finish_segment",
    "_truncated_total",
    "stage_hash", "stage_probe_keys", "stage_probe_extents",
    "stage_probe_counts", "stage_fused_probe", "stage_tombstone",
    "stage_rerank", "stage_merge_pair", "stage_merge_concat",
    "rerank_candidates", "l1_distance_chunked",
    "fused_probe", "fused_rerank", "topk_merge",
}

# IndexState / Segment fields that are device arrays wherever they appear
DEVICE_ATTRS = {"sorted_keys", "sorted_ids", "occ_from", "occ_hist",
                "dataset", "gids"}

# host-side helpers whose *arguments* must already live on the host —
# passing a device array forces a transfer inside them
HOST_FNS = {"occupancy_quantile", "max_bucket_occupancy",
            "oracle_candidate_cap", "percentile"}

# helpers whose results are sanctioned static-shape sources (R2)
SHAPE_SOURCES = {"bucket_for", "shape_buckets", "buckets",
                 "candidate_ladder", "candidate_ladders", "rung_ladder",
                 "pick_rung", "candidate_bucket", "structure_signature"}


def _line_findings_key(node: ast.AST) -> Tuple[int, int]:
    return (getattr(node, "lineno", 0), getattr(node, "col_offset", 0))


# -- R1: host-sync ----------------------------------------------------------

class HostSyncRule(Rule):
    """Flag host-scalar reads and device-value branching in hot paths.

    Scope: the staged pipeline, the segmented index, the kernels, the
    serving engine, and the ``repro.obs`` hot-path helpers — the modules
    where an unplanned ``.item()`` / ``int()`` / ``np.asarray`` on a
    traced value stalls the device pipeline per batch.  ``repro/obs/`` is
    in scope because its primitives (``span``, ``record_ms``, the
    registry facade) run inside every batch: the package is stdlib-only
    by design, so a device read sneaking in there should fail the gate,
    not hide behind "it's just telemetry".  The sanctioned reads (the §8
    phase-A rung pick, seal-time cap derivation, compaction's host
    materialization, the batch-boundary result conversion, and the flight
    recorder's slow-exemplar preview — batch-boundary, post
    ``block_until_ready``, slow path only) carry inline allows with their
    justification.
    """

    id = "r1-host-sync"
    description = "host sync on a device value in a hot-path module"

    SCOPE = ("repro/core/pipeline.py", "repro/core/segments.py",
             "repro/core/index.py", "repro/serve/engine.py",
             "repro/kernels/", "repro/obs/")

    def applies(self, path: str) -> bool:
        return path.startswith(self.SCOPE)

    def _config(self) -> TaintConfig:
        return TaintConfig(
            source_calls={fn: "device" for fn in DEVICE_FNS},
            source_prefixes={"jnp": "device", "jax": "device",
                             "jax.numpy": "device"},
            source_attrs={a: "device" for a in DEVICE_ATTRS},
            clearing_calls={"int", "float", "bool", "item", "tolist",
                            "asarray", "array", "len"} | HOST_FNS,
            neutral_calls={"issubdtype", "default_backend", "iinfo",
                           "finfo", "result_type", "promote_types",
                           "can_cast", "device_count",
                           "local_device_count", "devices"},
        )

    def run(self, mod: Module) -> List[Finding]:
        out: List[Finding] = []
        for stack, fn in iter_functions(mod.tree):
            taint = FunctionTaint(fn, self._config())
            symbol = qualname_of(list(stack) + [fn])
            for node in ast.walk(fn):
                f = self._check_node(node, taint, mod, symbol)
                if f is not None:
                    out.append(f)
        out.sort(key=lambda f: (f.line, f.col))
        return out

    def _check_node(self, node: ast.AST, taint: FunctionTaint, mod: Module,
                    symbol: str) -> Optional[Finding]:
        if isinstance(node, ast.Call):
            dotted = call_name(node)
            term = terminal_name(dotted)
            if term in ("int", "float", "bool") and dotted == term:
                if any(taint.tags(a) for a in node.args):
                    return self._finding(
                        node, mod, symbol,
                        f"{term}() on a device value forces a host sync")
            if term in ("item", "tolist") and isinstance(node.func,
                                                         ast.Attribute):
                if taint.tags(node.func.value):
                    return self._finding(
                        node, mod, symbol,
                        f".{term}() on a device value forces a host sync")
            if term in ("asarray", "array", "ascontiguousarray") and (
                    dotted.startswith("np.") or dotted.startswith("numpy.")):
                if any(taint.tags(a) for a in node.args):
                    return self._finding(
                        node, mod, symbol,
                        f"np.{term}() on a device value copies it to host")
            if term in HOST_FNS:
                if any(taint.tags(a) for a in node.args) or any(
                        taint.tags(kw.value) for kw in node.keywords):
                    return self._finding(
                        node, mod, symbol,
                        f"host-side helper {term}() called with a device "
                        "value (forces a transfer per call)")
        elif isinstance(node, (ast.If, ast.While)):
            tags = taint.tainted_in_branch_test(node.test)
            if tags:
                return self._finding(
                    node.test, mod, symbol,
                    "python branch on a device value forces a host sync")
        elif isinstance(node, ast.IfExp):
            if taint.tainted_in_branch_test(node.test):
                return self._finding(
                    node.test, mod, symbol,
                    "conditional expression on a device value forces a "
                    "host sync")
        return None

    def _finding(self, node: ast.AST, mod: Module, symbol: str,
                 message: str) -> Finding:
        line, col = _line_findings_key(node)
        return Finding(rule=self.id, path=mod.path, line=line, col=col,
                       symbol=symbol, message=message)


# -- R2: recompile-hazard ---------------------------------------------------

class RecompileHazardRule(Rule):
    """Shape-bearing args of jitted entry points must flow from bucketing.

    A jitted callable specializes on its static args; if those args carry
    raw data-dependent values (``len(...)``, ``.shape``, a device-call
    result) instead of flowing through ``bucket_for``/``pick_rung``/
    ``rung_ladder``-style bucketing, live traffic compiles executables
    warmup never saw — the silent latency cliff §5/§8 exist to prevent.
    Pad-buffer shapes (``np.zeros``/``jnp.zeros``) in the engine/router
    are checked the same way.
    """

    id = "r2-recompile-hazard"
    description = "jitted-entry shape arg not derived from bucketing"

    SCOPE = ("repro/serve/engine.py", "repro/core/segments.py",
             "repro/core/index.py", "repro/cluster/router.py")
    PAD_SCOPE = ("repro/serve/engine.py", "repro/cluster/router.py")

    # terminal call name -> (positional indices, kwarg names) that are
    # static shape-bearing arguments
    CONSUMERS: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]] = {
        "_finish_segment": ((1, 2), ("cbucket", "c_cap")),
        "finish_query": ((1, 2), ("cbucket", "c_cap")),
        "stage_fused_probe": ((5,), ("cbucket", "c_cap")),
    }

    def applies(self, path: str) -> bool:
        return path.startswith(self.SCOPE)

    def _config(self) -> TaintConfig:
        cfg = TaintConfig(
            source_calls={fn: "dyn" for fn in DEVICE_FNS},
            source_prefixes={"jnp": "dyn", "jax.numpy": "dyn"},
            source_attrs={"shape": "dyn", "size": "dyn"},
            clearing_calls=set(),
        )
        cfg.source_calls["len"] = "dyn"
        # bucketing helpers override: their results are sanctioned statics
        for fn in SHAPE_SOURCES:
            cfg.source_calls[fn] = "src"
        cfg.clearing_attrs = set()      # .shape must taint here, not clear
        return cfg

    def run(self, mod: Module) -> List[Finding]:
        out: List[Finding] = []
        for stack, fn in iter_functions(mod.tree):
            taint = FunctionTaint(fn, self._config())
            symbol = qualname_of(list(stack) + [fn])
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                dotted = call_name(node)
                term = terminal_name(dotted)
                if term in self.CONSUMERS:
                    out.extend(self._check_consumer(
                        node, term, taint, mod, symbol))
                elif term == "zeros" and mod.path.startswith(
                        self.PAD_SCOPE) and (
                        dotted.startswith("np.")
                        or dotted.startswith("jnp.")):
                    out.extend(self._check_pad_shape(
                        node, taint, mod, symbol))
        out.sort(key=lambda f: (f.line, f.col))
        return out

    def _hazard(self, tags: Set[str]) -> bool:
        return "dyn" in tags and "src" not in tags

    def _check_consumer(self, node: ast.Call, term: str,
                        taint: FunctionTaint, mod: Module,
                        symbol: str) -> List[Finding]:
        pos, kws = self.CONSUMERS[term]
        out = []
        for idx in pos:
            if idx < len(node.args) and self._hazard(
                    taint.tags(node.args[idx])):
                out.append(self._finding(
                    node.args[idx], mod, symbol,
                    f"shape-bearing arg {idx} of jitted {term}() does not "
                    "flow from bucket_for/candidate_ladder/rung_ladder "
                    "(unplanned executable per distinct value)"))
        for kw in node.keywords:
            if kw.arg in kws and self._hazard(taint.tags(kw.value)):
                out.append(self._finding(
                    kw.value, mod, symbol,
                    f"shape-bearing kwarg {kw.arg}= of jitted {term}() "
                    "does not flow from bucketing helpers"))
        return out

    def _check_pad_shape(self, node: ast.Call, taint: FunctionTaint,
                         mod: Module, symbol: str) -> List[Finding]:
        if not node.args:
            return []
        shape = node.args[0]
        elts = shape.elts if isinstance(shape, ast.Tuple) else [shape]
        out = []
        for elt in elts:
            if self._hazard(taint.tags(elt)):
                out.append(self._finding(
                    elt, mod, symbol,
                    "pad-buffer dimension is data-dependent without "
                    "flowing through a shape bucket (bucket_for/"
                    "shape_buckets) — each distinct size recompiles"))
        return out

    def _finding(self, node: ast.AST, mod: Module, symbol: str,
                 message: str) -> Finding:
        line, col = _line_findings_key(node)
        return Finding(rule=self.id, path=mod.path, line=line, col=col,
                       symbol=symbol, message=message)


# -- R3: wire-protocol ------------------------------------------------------

class WireProtocolRule(Rule):
    """Cluster code: whitelisted dtypes only, and no pickle family.

    Every explicit ``np.<dtype>`` literal under ``cluster/`` must be on
    ``transport.WIRE_DTYPES`` — cluster arrays are wire-adjacent by
    construction (queries, WAL records, payload transfers all cross the
    framing), and an off-whitelist dtype would only surface as a
    ``TypeError`` at send time on some rarely-hit path.  The whitelist is
    imported from the runtime codec, so the rule cannot drift from it.

    The pickle ban covers whole modules (``pickle`` et al.) AND the
    pickle-backed corners of otherwise-legitimate packages:
    ``multiprocessing.shared_memory``/``resource_tracker`` are fine (the
    §13 slab fast path moves raw bytes + JSON descriptors), but
    ``multiprocessing.reduction``/``connection`` are pickling transports
    and banned by dotted prefix.
    """

    id = "r3-wire-protocol"
    description = "off-whitelist dtype or pickle-family import in cluster/"

    SCOPE = ("repro/cluster/",)
    FORBIDDEN_IMPORTS = {"pickle", "cPickle", "marshal", "shelve", "dill",
                         "cloudpickle"}
    # dotted-prefix bans inside packages whose other submodules are legal
    FORBIDDEN_PREFIXES = ("multiprocessing.reduction",
                          "multiprocessing.connection",
                          "multiprocessing.managers")
    DTYPE_CALLS: Dict[str, int] = {
        # terminal name -> positional index of the dtype argument
        "asarray": 1, "ascontiguousarray": 1, "array": 1, "frombuffer": 1,
        "zeros": 1, "ones": 1, "empty": 1, "full": 2,
    }

    def __init__(self):
        import numpy as np
        self._np = np
        self._whitelist = set(self._load_wire_dtypes())

    @staticmethod
    def _load_wire_dtypes():
        """Load transport.WIRE_DTYPES by file path, not package import:
        ``repro.cluster.__init__`` re-exports the router and would drag jax
        into the (otherwise stdlib+numpy) analyzer.  transport.py itself
        is jax-free at module level by design."""
        import importlib.util
        from .engine import default_root
        path = os.path.join(default_root(), "cluster", "transport.py")
        spec = importlib.util.spec_from_file_location(
            "_repro_analysis_transport", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.WIRE_DTYPES

    def applies(self, path: str) -> bool:
        return path.startswith(self.SCOPE)

    def _banned_import(self, name: str) -> bool:
        if name.split(".")[0] in self.FORBIDDEN_IMPORTS:
            return True
        return any(name == p or name.startswith(p + ".")
                   for p in self.FORBIDDEN_PREFIXES)

    def run(self, mod: Module) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if self._banned_import(alias.name):
                        out.append(self._finding(
                            node, mod, "",
                            f"import of {alias.name!r} under cluster/: the "
                            "wire protocol is pickle-free by design "
                            "(DESIGN.md §10)"))
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                # `from multiprocessing import reduction` names the banned
                # submodule in the alias, not the module field
                names = [base] + [f"{base}.{a.name}" if base else a.name
                                  for a in node.names]
                if any(self._banned_import(n) for n in names if n):
                    out.append(self._finding(
                        node, mod, "",
                        f"import from {node.module!r} under cluster/: the "
                        "wire protocol is pickle-free by design "
                        "(DESIGN.md §10)"))
            elif isinstance(node, ast.Call):
                out.extend(self._check_dtype_literal(node, mod))
        if mod.path == "repro/cluster/transport.py":
            out.extend(self._check_whitelist_definition(mod))
        out.sort(key=lambda f: (f.line, f.col))
        return out

    def _dtype_exprs(self, node: ast.Call):
        dotted = call_name(node)
        term = terminal_name(dotted)
        if term not in self.DTYPE_CALLS or not (
                dotted.startswith("np.") or dotted.startswith("numpy.")):
            return
        idx = self.DTYPE_CALLS[term]
        if idx < len(node.args):
            yield node.args[idx]
        for kw in node.keywords:
            if kw.arg == "dtype":
                yield kw.value

    def _check_dtype_literal(self, node: ast.Call,
                             mod: Module) -> List[Finding]:
        out = []
        for expr in self._dtype_exprs(node):
            if not isinstance(expr, ast.Attribute):
                continue
            root = _dotted(expr).split(".")[0]
            if root not in ("np", "numpy"):
                continue
            name = expr.attr
            try:
                dt = self._np.dtype(getattr(self._np, name))
            except (AttributeError, TypeError):
                continue
            if dt not in self._whitelist:
                out.append(self._finding(
                    expr, mod, "",
                    f"dtype np.{name} is not on the wire whitelist "
                    "(transport.WIRE_DTYPES); it cannot cross the framing"))
        return out

    def _check_whitelist_definition(self, mod: Module) -> List[Finding]:
        has_whitelist, code_from_whitelist = False, False
        for node in mod.tree.body:
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets = [node.target]
            else:
                continue
            names = {t.id for t in targets if isinstance(t, ast.Name)}
            if "WIRE_DTYPES" in names:
                has_whitelist = True
            if "_DTYPE_CODE" in names or "_DTYPES" in names:
                refs = {n.id for n in ast.walk(node.value)
                        if isinstance(n, ast.Name)}
                if "WIRE_DTYPES" in refs:
                    code_from_whitelist = True
        out = []
        if not has_whitelist:
            out.append(self._finding(
                mod.tree, mod, "",
                "transport.py must define WIRE_DTYPES (the shared codec/"
                "analyzer whitelist)"))
        elif not code_from_whitelist:
            out.append(self._finding(
                mod.tree, mod, "",
                "transport's dtype code table must derive from WIRE_DTYPES "
                "(codec and whitelist drifting apart)"))
        return out

    def _finding(self, node: ast.AST, mod: Module, symbol: str,
                 message: str) -> Finding:
        line, col = _line_findings_key(node)
        return Finding(rule=self.id, path=mod.path, line=line, col=col,
                       symbol=symbol, message=message)


# -- R4: mutation-discipline ------------------------------------------------

class MutationDisciplineRule(Rule):
    """Mutating replica/engine calls must be quiesce-dominated (§7).

    Engines are not thread-safe versus mutation: the PR-7 race was a
    hedged straggler's query future still running when a mutation landed.
    In the router layer, every call to a mutating method must either (a)
    appear after a ``_quiesce()`` call in the same function (linear
    statement-order dominance — a conservative approximation that
    matches how the router is written), (b) live in a function marked
    ``@under_quiesce`` (whose own call sites then carry the obligation,
    since the marker makes the function count as a mutator), or (c) be
    in ``__init__`` (single-threaded construction).  Mutator bound
    methods handed to a thread pool are flagged unconditionally.
    """

    id = "r4-mutation-discipline"
    description = "mutating call not dominated by a straggler quiesce"

    SCOPE = ("repro/cluster/router.py", "repro/cluster/remote.py",
             "repro/cluster/replica.py")
    MUTATORS = {"insert", "delete", "compact", "apply_records",
                "adopt_payload", "log_and_apply", "recover",
                "catch_up_from", "kill"}
    EXEMPT_FUNCTIONS = {"__init__"}

    def applies(self, path: str) -> bool:
        return path.startswith(self.SCOPE)

    def run(self, mod: Module) -> List[Finding]:
        local_mutators = self._decorated_functions(mod.tree)
        mutators = self.MUTATORS | local_mutators
        out: List[Finding] = []
        for stack, fn in iter_functions(mod.tree):
            symbol = qualname_of(list(stack) + [fn])
            decorated = self._is_marked(fn)
            exempt = decorated or fn.name in self.EXEMPT_FUNCTIONS
            quiesce_lines = [
                n.lineno for n in ast.walk(fn)
                if isinstance(n, ast.Call)
                and terminal_name(call_name(n)) in ("_quiesce", "quiesce")]
            first_quiesce = min(quiesce_lines) if quiesce_lines else None
            local_defs = {n.name: n for n in ast.walk(fn)
                          if isinstance(n, ast.FunctionDef) and n is not fn}
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                term = terminal_name(call_name(node))
                if term == "submit":
                    out.extend(self._check_submit(
                        node, mutators, local_defs, mod, symbol))
                    continue
                if term not in mutators:
                    continue
                if self._own_def(node, term, fn):
                    continue
                if exempt:
                    continue
                if first_quiesce is not None and node.lineno > first_quiesce:
                    continue
                out.append(self._finding(
                    node, mod, symbol,
                    f"mutating call {term}() is not dominated by a "
                    "_quiesce() in this function and the function is not "
                    "marked @under_quiesce — a hedged straggler's query "
                    "may still be in flight (DESIGN.md §7)"))
        out.sort(key=lambda f: (f.line, f.col))
        return out

    @staticmethod
    def _own_def(node: ast.Call, term: str, fn: ast.FunctionDef) -> bool:
        """A bare recursive self-call inside its own def is not a site."""
        return isinstance(node.func, ast.Name) and node.func.id == fn.name

    @staticmethod
    def _is_marked(fn: ast.FunctionDef) -> bool:
        for dec in fn.decorator_list:
            name = terminal_name(call_name(dec) if isinstance(dec, ast.Call)
                                 else (dec.id if isinstance(dec, ast.Name)
                                       else getattr(dec, "attr", "")))
            if name == "under_quiesce":
                return True
        return False

    def _decorated_functions(self, tree: ast.AST) -> Set[str]:
        return {fn.name for _, fn in iter_functions(tree)
                if self._is_marked(fn)}

    def _check_submit(self, node: ast.Call, mutators: Set[str],
                      local_defs: Dict[str, ast.FunctionDef], mod: Module,
                      symbol: str) -> List[Finding]:
        if not node.args:
            return []
        fn_arg = node.args[0]
        out = []
        if isinstance(fn_arg, ast.Attribute) and fn_arg.attr in mutators:
            out.append(self._finding(
                fn_arg, mod, symbol,
                f"mutator bound method .{fn_arg.attr} handed to a thread "
                "pool: engine mutations must never run on pool threads "
                "concurrent with queries (DESIGN.md §7)"))
        body: Optional[Sequence[ast.stmt]] = None
        if isinstance(fn_arg, ast.Lambda):
            body = [ast.Expr(value=fn_arg.body)]
        elif isinstance(fn_arg, ast.Name) and fn_arg.id in local_defs:
            body = local_defs[fn_arg.id].body
        if body is not None:
            for stmt in body:
                for sub in ast.walk(stmt):
                    if isinstance(sub, ast.Call) and terminal_name(
                            call_name(sub)) in mutators:
                        out.append(self._finding(
                            sub, mod, symbol,
                            f"mutating call {terminal_name(call_name(sub))}"
                            "() inside a callable handed to a thread pool "
                            "(DESIGN.md §7)"))
        return out

    def _finding(self, node: ast.AST, mod: Module, symbol: str,
                 message: str) -> Finding:
        line, col = _line_findings_key(node)
        return Finding(rule=self.id, path=mod.path, line=line, col=col,
                       symbol=symbol, message=message)


# -- R5: aliasing -----------------------------------------------------------

class AliasingRule(Rule):
    """``jnp.asarray`` zero-copy views over later-mutated numpy buffers.

    On CPU, ``jnp.asarray(np_buffer)`` may alias the buffer instead of
    copying; mutating the buffer afterwards silently corrupts the device
    array (the PR-1 delta-seal bug class).  Flagged when the asarray
    argument's root is a local name the same function later
    subscript-assigns, or a ``self.*`` buffer any method of the module
    subscript-assigns.  Any call inside the argument (``.copy()``,
    ``np.ascontiguousarray``, ``np.concatenate``) exempts it — those
    produce fresh buffers.
    """

    id = "r5-aliasing"
    description = "jnp.asarray view over a numpy buffer mutated later"

    def applies(self, path: str) -> bool:
        return path.startswith("repro/")

    def run(self, mod: Module) -> List[Finding]:
        self_stores = self._module_self_stores(mod.tree)
        out: List[Finding] = []
        for stack, fn in iter_functions(mod.tree):
            symbol = qualname_of(list(stack) + [fn])
            stores = self._local_stores(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                if not self._is_aliasing_ctor(node):
                    continue
                arg = node.args[0] if node.args else None
                if arg is None or any(isinstance(n, ast.Call)
                                      for n in ast.walk(arg)):
                    continue
                root = self._root_of(arg)
                if root is None:
                    continue
                kind, name = root
                if kind == "local" and any(ln > node.lineno
                                           for ln in stores.get(name, ())):
                    out.append(self._finding(
                        node, mod, symbol,
                        f"jnp.asarray view over local buffer {name!r} which "
                        "is mutated later in this function — zero-copy on "
                        "CPU aliases the live buffer; .copy() first"))
                elif kind == "self" and name in self_stores:
                    out.append(self._finding(
                        node, mod, symbol,
                        f"jnp.asarray view over self.{name} which this "
                        "module mutates in place — zero-copy on CPU aliases "
                        "the live buffer; .copy() first"))
        out.sort(key=lambda f: (f.line, f.col))
        return out

    @staticmethod
    def _is_aliasing_ctor(node: ast.Call) -> bool:
        dotted = call_name(node)
        if not (dotted.startswith("jnp.") or dotted.startswith("jax.numpy.")):
            return False
        term = terminal_name(dotted)
        if term == "asarray":
            return True
        if term == "array":
            for kw in node.keywords:
                if kw.arg == "copy" and isinstance(kw.value, ast.Constant) \
                        and kw.value.value is False:
                    return True
        return False

    @staticmethod
    def _root_of(arg: ast.AST) -> Optional[Tuple[str, str]]:
        node = arg
        while isinstance(node, ast.Subscript):
            node = node.value
        if isinstance(node, ast.Name):
            return ("local", node.id)
        if isinstance(node, ast.Attribute) and isinstance(
                node.value, ast.Name) and node.value.id == "self":
            return ("self", node.attr)
        return None

    @classmethod
    def _store_root(cls, target: ast.AST) -> Optional[Tuple[str, str]]:
        if isinstance(target, ast.Subscript):
            return cls._root_of(target)
        return None

    def _local_stores(self, fn: ast.FunctionDef) -> Dict[str, List[int]]:
        stores: Dict[str, List[int]] = {}
        for node in ast.walk(fn):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            for t in targets:
                root = self._store_root(t)
                if root is not None and root[0] == "local":
                    stores.setdefault(root[1], []).append(node.lineno)
        return stores

    def _module_self_stores(self, tree: ast.AST) -> Set[str]:
        stores: Set[str] = set()
        for node in ast.walk(tree):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            for t in targets:
                root = self._store_root(t)
                if root is not None and root[0] == "self":
                    stores.add(root[1])
        return stores

    def _finding(self, node: ast.AST, mod: Module, symbol: str,
                 message: str) -> Finding:
        line, col = _line_findings_key(node)
        return Finding(rule=self.id, path=mod.path, line=line, col=col,
                       symbol=symbol, message=message)


def default_rules() -> List[Rule]:
    return [HostSyncRule(), RecompileHazardRule(), WireProtocolRule(),
            MutationDisciplineRule(), AliasingRule()]
