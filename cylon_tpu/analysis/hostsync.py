"""Host-sync detector: host transfers inside traced (device) code.

`np.asarray` / `jax.device_get` / `.item()` / `float()` on a traced
value forces a device→host round trip (or a trace-time
ConcretizationTypeError on a path no test exercises). The framework's
discipline is that host syncs happen at exactly the declared points —
the count→capacity fetches between kernel phases — and NEVER inside
code that runs under `jit` / `shard_map` / `pallas_call`.

The pass is purely syntactic (nothing is imported):

1. *Trace roots.* A function is traced when it is decorated with
   ``jax.jit`` (or ``partial(jax.jit, ...)``), or its NAME is passed to
   ``jax.jit`` / ``shard_map`` / ``pl.pallas_call`` / a ``jax.lax``
   control-flow combinator — the repo's universal kernel-factory shape
   (``def kernel(...)`` then ``jax.jit(shard_map(kernel, ...))``).
2. *Closure.* Calls from a traced body to module-level functions —
   directly (``_bucket_sort(...)``) or through an intra-package module
   alias (``_join.join_plan_keys(...)``, resolved via each module's
   import table) — mark the callee traced too, transitively across the
   package. Nested ``def``s and lambdas inside a traced body are
   covered by walking the whole body.
3. *Flag.* Within traced code: ``np.asarray`` / ``np.array`` /
   ``np.ascontiguousarray``, ``jax.device_get``, ``.item()`` /
   ``.tolist()``, and ``float()/int()/bool()`` on non-static arguments
   (shape/ndim/len() expressions are static under trace and stay
   legal).

Host-side call sites — the overwhelming majority of the ~120
`np.asarray`/`device_get` sites in the package — are by construction
never flagged: they live outside any traced closure. Each finding
reports the trace chain (root → callee) so a false positive is cheap
to triage; a justified one takes a per-line ``# cylint:
disable=hostsync/...`` with a comment.

4. *The choke point.* On the host side, a fetch whose value decides
   what is dispatched next (a count, a capacity, a flag, splitters)
   goes through ``telemetry.host_fetch(site, x)``, which spans and
   counts it. In the files that hold the operators' count→capacity
   steps (``FETCH_SCOPES``) a bare ``jax.device_get`` is therefore a
   ``hostsync/bare-fetch`` finding, except in the functions declared
   as bulk movers of whole columns (``BULK_EXPORTS``: ``to_numpy``'s
   live-row read, the process-local assembly), which decide nothing
   and keep their plain ``device_get``. ``host_fetch`` itself is a
   sync call like ``device_get`` inside traced code.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import (AnalysisContext, Finding, ModuleIndex, attr_chain,
                   build_module_index, call_closure, register)

# call targets whose function-valued arguments become traced
_TRACING_CALLS = {
    ("jax", "jit"), ("jit",), ("shard_map",), ("jax", "vmap"),
    ("pl", "pallas_call"), ("pallas_call",),
    ("jax", "lax", "fori_loop"), ("jax", "lax", "while_loop"),
    ("jax", "lax", "cond"), ("jax", "lax", "scan"),
    ("jax", "lax", "switch"), ("lax", "fori_loop"), ("lax", "cond"),
    ("lax", "scan"), ("lax", "while_loop"), ("lax", "switch"),
    ("jax", "checkpoint"), ("jax", "remat"),
}

# attribute-call chains that ARE a host sync
_SYNC_CALLS = {
    ("np", "asarray"), ("np", "array"), ("np", "ascontiguousarray"),
    ("numpy", "asarray"), ("numpy", "array"),
    ("jax", "device_get"),
    # the spanned, counted choke point and the repo's aliases of it
    ("host_fetch",), ("_host_fetch",), ("telemetry", "host_fetch"),
    ("_telemetry", "host_fetch"),
}

# package-relative files in which every host-side device_get is either
# the choke point or inside a declared bulk export
# (options["fetch_scopes"] / ["bulk_exports"] override, for fixtures)
FETCH_SCOPES = ("data/table.py", "parallel/shuffle.py",
                "parallel/dist_ops.py", "parallel/shard.py")

# (file, function): whole column buffers copied to the host, to be
# exported or placed again — no value the next dispatch waits on
BULK_EXPORTS = frozenset({
    ("data/table.py", "_compact_indices"),       # to_numpy / to_pandas
    ("parallel/shard.py", "assemble_process_local"),
})

_SYNC_METHODS = {"item", "tolist"}

_CAST_BUILTINS = {"float", "int", "bool"}


# the attribute-chain resolver now lives in core (attr_chain) — one
# copy shared with the concurrency checker's call-graph pass
_attr_chain = attr_chain


def _is_jit_decorator(dec: ast.AST) -> bool:
    chain = _attr_chain(dec)
    if chain in (("jax", "jit"), ("jit",)):
        return True
    if isinstance(dec, ast.Call):
        inner = _attr_chain(dec.func)
        if inner in (("jax", "jit"), ("jit",)):
            return True
        # partial(jax.jit, static_argnames=...)
        if inner in (("partial",), ("functools", "partial")) and dec.args:
            return _attr_chain(dec.args[0]) in (("jax", "jit"), ("jit",))
    return False


def _static_params(fn: ast.AST) -> Set[str]:
    """Parameters of ``fn`` that are static under tracing: annotated as
    a scalar Python type (``max_e: int``), or named in the function's
    own ``jax.jit(static_argnames=...)`` decorator (enum/config args)."""
    out: Set[str] = set()
    args = fn.args
    all_args = list(args.posonlyargs) + list(args.args) \
        + list(args.kwonlyargs)
    for a in all_args:
        ann = a.annotation
        if isinstance(ann, ast.Name) and ann.id in ("int", "float",
                                                    "bool", "str"):
            out.add(a.arg)
    for dec in fn.decorator_list:
        if not (isinstance(dec, ast.Call) and _is_jit_decorator(dec)):
            continue
        for kw in dec.keywords:
            if kw.arg in ("static_argnames", "static_argnums"):
                names = kw.value.elts \
                    if isinstance(kw.value, (ast.Tuple, ast.List)) \
                    else [kw.value]
                for n in names:
                    if isinstance(n, ast.Constant):
                        if isinstance(n.value, str):
                            out.add(n.value)
                        elif isinstance(n.value, int) and \
                                n.value < len(all_args):
                            out.add(all_args[n.value].arg)
    return out


def _is_staticish(node: ast.AST, static_names: Set[str] = frozenset()
                  ) -> bool:
    """Expressions that stay concrete under tracing: literals, shape /
    ndim / size / itemsize introspection, len(), statically-annotated
    parameters, and arithmetic over those. Conservative: anything else
    is treated as possibly traced."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Name) and node.id in static_names:
        return True
    if isinstance(node, ast.Attribute):
        if node.attr in ("ndim", "size", "itemsize", "dtype"):
            return True
        if node.attr == "shape":
            return True
        return _is_staticish(node.value, static_names) and \
            node.attr.isidentifier()
    if isinstance(node, ast.Subscript):
        return isinstance(node.value, ast.Attribute) and \
            node.value.attr == "shape"
    if isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        if chain in (("len",), ("int",), ("float",), ("max",), ("min",)):
            return all(_is_staticish(a, static_names) for a in node.args)
        return False
    if isinstance(node, ast.BinOp):
        return _is_staticish(node.left, static_names) and \
            _is_staticish(node.right, static_names)
    if isinstance(node, ast.UnaryOp):
        return _is_staticish(node.operand, static_names)
    return False


# the per-file symbol tables now live in core (ModuleIndex) — the
# closure pass shares them with the concurrency checker
_Module = ModuleIndex


def _trace_roots(mod: _Module) -> Set[str]:
    """Names of this module's functions that enter tracing directly."""
    roots: Set[str] = set()
    for name, fn in mod.functions.items():
        if any(_is_jit_decorator(d) for d in fn.decorator_list):
            roots.add(name)
    for node in ast.walk(mod.sf.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if chain is None or chain not in _TRACING_CALLS:
            continue
        for arg in node.args:
            inner = _attr_chain(arg)
            if inner is not None and len(inner) == 1:
                roots.add(inner[0])
    return roots


def _scan_body(fn: ast.AST, mod: _Module, chain_desc: str
               ) -> List[Finding]:
    out: List[Finding] = []
    # static parameters of this function and every def nested in it
    # (kernel factories close over static config; a per-scope walk
    # would be more precise but name collisions are not a real risk)
    static_names: Set[str] = set()
    for sub in ast.walk(fn):
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            static_names |= _static_params(sub)
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        where = f" [traced via {chain_desc}]" if chain_desc else ""
        if chain in _SYNC_CALLS:
            out.append(Finding(
                rule="hostsync/transfer", path=mod.sf.rel,
                line=node.lineno,
                message=f"{'.'.join(chain)}() inside traced code forces "
                        f"a device→host transfer{where}"))
        elif isinstance(node.func, ast.Attribute) and \
                node.func.attr in _SYNC_METHODS and not node.args:
            out.append(Finding(
                rule="hostsync/transfer", path=mod.sf.rel,
                line=node.lineno,
                message=f".{node.func.attr}() inside traced code forces "
                        f"a device→host transfer{where}"))
        elif chain is not None and len(chain) == 1 and \
                chain[0] in _CAST_BUILTINS and node.args:
            if not all(_is_staticish(a, static_names) for a in node.args):
                out.append(Finding(
                    rule="hostsync/concretize", path=mod.sf.rel,
                    line=node.lineno,
                    message=f"{chain[0]}() on a possibly-traced value "
                            f"inside traced code concretizes (host "
                            f"sync or trace error){where}"))
    return out


@register("hostsync")
def check_hostsync(ctx: AnalysisContext) -> List[Finding]:
    package = ctx.package_name
    modules = build_module_index(ctx)

    # seed with direct trace roots, then close over the call graph
    # (core.call_closure — the machinery shared with the concurrency
    # checker's thread-domain reachability)
    seeds: Dict[Tuple[str, str], str] = {}
    for modname, mod in modules.items():
        for name in _trace_roots(mod):
            if name in mod.functions:
                seeds[(modname, name)] = name
    traced = call_closure(modules, seeds, package)

    findings: List[Finding] = []
    for (modname, fname), desc in sorted(traced.items()):
        mod = modules[modname]
        fn = mod.lookup(fname)
        if fn is not None:
            findings.extend(_scan_body(fn, mod, desc))

    scopes = set(ctx.options.get("fetch_scopes", FETCH_SCOPES))
    bulk = ctx.options.get("bulk_exports", BULK_EXPORTS)
    for sf in ctx.files():
        if sf.rel not in scopes:
            continue
        exempt = {id(node) for fn in ast.walk(sf.tree)
                  if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                  and (sf.rel, fn.name) in bulk
                  for node in ast.walk(fn)}
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call) and id(node) not in exempt \
                    and _attr_chain(node.func) == ("jax", "device_get"):
                findings.append(Finding(
                    rule="hostsync/bare-fetch", path=sf.rel,
                    line=node.lineno,
                    message="jax.device_get() outside the choke point: "
                            "a fetch that decides the next dispatch "
                            "goes through telemetry.host_fetch(site, x) "
                            "(spanned and counted); a bulk export of "
                            "whole columns is declared in "
                            "analysis/hostsync.BULK_EXPORTS"))

    # classification summary: every host-transfer call site in the tree
    # is either inside a traced closure (flagged above) or host-side
    # (legal — the declared count→capacity syncs between kernel phases)
    total = 0
    for sf in ctx.files():
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                if chain in _SYNC_CALLS or (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr in _SYNC_METHODS
                        and not node.args):
                    total += 1
    flagged = sum(1 for f in findings if f.rule == "hostsync/transfer")
    ctx.options.setdefault("notes", []).append(
        f"hostsync: {total} host-transfer call sites; {flagged} inside "
        f"traced closures (flagged), {total - flagged} host-side (legal); "
        f"{len(traced)} functions in the traced closure")
    return findings
