"""Jaxpr collective checker: abstract evaluation of `parallel/` kernels.

Every distributed operator in this codebase is a `jax.jit(shard_map(
kernel, mesh...))` program built by an ``@lru_cache`` factory in
`parallel/shuffle.py` / `parallel/dist_ops.py`. This checker builds
each factory on a VIRTUAL mesh (forced host devices — no accelerator
needed), traces it with abstract `ShapeDtypeStruct` inputs via
``jax.make_jaxpr``, and walks the resulting jaxpr recursively:

* ``collectives/axis-name`` — every collective primitive (`psum`,
  `all_gather`, `all_to_all`, `ppermute`, `axis_index`, `pbroadcast`)
  must name an axis of the ENCLOSING `shard_map`'s mesh. A stray name
  is a program that only works by accident of a caller's axis naming.
* ``collectives/all-to-all-axes`` — `all_to_all` must use
  ``split_axis == concat_axis``: the repo-wide exchange discipline is
  "shard-major dimension 0 in, shard-major dimension 0 out" (the
  [world, block] send stacks); mismatched axes silently transpose the
  received blocks.
* ``collectives/f64-promotion`` — no equation may INTRODUCE a float64
  value from non-float64 inputs. On TPU an implicit f64 (a stray
  ``np.float64`` scalar, a numpy-promoting op) either fails Mosaic or
  silently doubles a kernel's bandwidth; tracing with x64 enabled makes
  the promotion visible in the jaxpr.
* ``collectives/trace-error`` — the factory fails to trace at all
  (e.g. a collective over an unbound axis name raises at trace time).

Entry points are DECLARED (factory + static args + input shapes) in
``default_entry_points`` — abstract evaluation needs concrete static
configuration. Any ``_*_fn`` factory in `parallel/` the catalog does
not cover is a REAL FINDING (``collectives/uncataloged-factory``), not
a note: an uncataloged factory is a collective program no axis-name /
all-to-all / f64 check ever sees, which is exactly how catalog drift
used to rot. Helpers that merely LOOK like factories (returning plain
host callables, not jitted programs) opt out explicitly with
``# cylint: disable=collectives/uncataloged-factory`` on their def
line — exclusion is a reviewable decision, never a hidden set. The
Pallas stream factories are TPU-only (the interpreter inside jit is
prohibitive) and are skipped with a note off-TPU. Option
``collectives_coverage_only`` runs just the catalog sweep (no tracing)
— the fast form the fixture tests drive.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

from .core import AnalysisContext, Finding, register

# collective primitive name -> param key holding the axis name(s)
_COLLECTIVES = {
    "psum": "axes", "psum2": "axes", "pmax": "axes", "pmin": "axes",
    "all_gather": "axis_name", "all_to_all": "axis_name",
    "ppermute": "axis_name", "axis_index": "axis_name",
    "pbroadcast": "axes", "pcast": "axes", "pvary": "axes",
    "reduce_scatter": "axis_name",
}


@dataclass
class EntryPoint:
    """One traced program: where it lives, how to build it, what to
    feed it. ``build(mesh, mod)`` returns the jitted callable;
    ``inputs(mesh)`` returns the abstract argument tuple."""

    name: str
    path: str                       # package-relative file, for findings
    build: Callable
    inputs: Callable
    factory: str = ""               # factory function name (coverage)
    tpu_only: bool = False


def _virtual_mesh(world: int = 4):
    """A 1-D mesh over host devices. Forcing the virtual CPU device
    count only works before the jax backend initializes — harmless when
    it already has (the checker then runs on whatever width exists;
    every check below is width-independent)."""
    os.environ.setdefault("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in \
            os.environ["XLA_FLAGS"]:
        os.environ["XLA_FLAGS"] += \
            " --xla_force_host_platform_device_count=8"
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    world = min(world, len(devs))
    return Mesh(np.array(devs[:world]), ("shards",))


def _walk_jaxpr(jaxpr, allowed_axes: Tuple[str, ...], sink):
    """Recurse through all nested jaxprs; ``sink(eqn, allowed_axes)``
    sees every equation with the axis names of its enclosing
    shard_map."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        inner_allowed = allowed_axes
        if eqn.primitive.name == "shard_map":
            mesh = eqn.params.get("mesh")
            names = getattr(mesh, "axis_names", None)
            if names:
                inner_allowed = tuple(names)
        sink(eqn, allowed_axes)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(sub, ClosedJaxpr):
                    _walk_jaxpr(sub.jaxpr, inner_allowed, sink)
                elif isinstance(sub, Jaxpr):
                    _walk_jaxpr(sub, inner_allowed, sink)


def _check_jaxpr(jaxpr, entry: EntryPoint, line: int) -> List[Finding]:
    import numpy as np

    findings: List[Finding] = []

    def sink(eqn, allowed):
        prim = eqn.primitive.name
        if prim in _COLLECTIVES:
            axes = eqn.params.get(_COLLECTIVES[prim])
            axes = axes if isinstance(axes, (tuple, list)) else (axes,)
            for ax in axes:
                if isinstance(ax, str) and allowed and ax not in allowed:
                    findings.append(Finding(
                        rule="collectives/axis-name", path=entry.path,
                        line=line,
                        message=f"{entry.name}: {prim} over axis "
                                f"{ax!r}, but the enclosing shard_map "
                                f"mesh declares {allowed}"))
        if prim == "all_to_all":
            sa = eqn.params.get("split_axis")
            ca = eqn.params.get("concat_axis")
            if sa != ca:
                findings.append(Finding(
                    rule="collectives/all-to-all-axes", path=entry.path,
                    line=line,
                    message=f"{entry.name}: all_to_all split_axis="
                            f"{sa} != concat_axis={ca}: the exchange "
                            f"discipline is shard-major dim 0 both "
                            f"ways; a mismatch transposes received "
                            f"blocks"))
        # float64 introduction: an output is f64 while no input was.
        # Container primitives (pjit/shard_map/cond/...) re-surface
        # their body's dtypes — only the LEAF equation that performs
        # the promotion reports, or one finding would triple up
        if any(isinstance(v, (list, tuple)) or hasattr(v, "jaxpr")
               for v in eqn.params.values()) or \
                eqn.primitive.name in ("pjit", "shard_map", "closed_call",
                                       "core_call", "custom_jvp_call",
                                       "custom_vjp_call", "cond", "while",
                                       "scan", "remat"):
            return
        out_dts = [getattr(getattr(v, "aval", None), "dtype", None)
                   for v in eqn.outvars]
        if any(d == np.float64 for d in out_dts if d is not None):
            in_dts = [getattr(getattr(v, "aval", None), "dtype", None)
                      for v in eqn.invars]
            if not any(d == np.float64 for d in in_dts if d is not None):
                findings.append(Finding(
                    rule="collectives/f64-promotion", path=entry.path,
                    line=line,
                    message=f"{entry.name}: {prim} introduces float64 "
                            f"from non-f64 inputs (implicit promotion "
                            f"— a stray np.float64 scalar or numpy-"
                            f"promoting op entering the kernel)"))

    _walk_jaxpr(jaxpr, (), sink)
    return findings


# ---------------------------------------------------------------------------
# the declared entry-point catalog for cylon_tpu.parallel
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


_KEY_FORMS = (("plain", "int32", False, True), ("lanes", 2, False),
              ("quad", False))


def _key_program_inputs(rows):
    """Abstract operands of `_KEY_FORMS` (parallel/dist_ops._key_operands):
    data + validity; two word lanes + byte lengths; h1, h2, h3, length."""
    import jax.numpy as jnp

    i32, u32 = jnp.int32, jnp.uint32
    return (rows(i32, jnp.bool_), rows(u32, u32, i32),
            rows(u32, u32, u32, u32))


def default_entry_points() -> List[EntryPoint]:
    """Abstract-input catalog for every traceable kernel factory in
    `parallel/`. Geometry: world=4 shards, 16 rows/shard (n=64 global),
    varbytes word buffers 64 words/shard."""
    import jax.numpy as jnp

    N, W = (64,), (256,)        # global rows / words
    CI = (16,)                  # counts_in: world*world
    i32, u32, b = jnp.int32, jnp.uint32, jnp.bool_

    def rows(*dts):
        return tuple(_sds(N, d) for d in dts)

    def vb():
        # (words, starts, lengths)
        return (_sds(W, u32), _sds(N, i32), _sds(N, i32))

    def payload():
        return {"d0": _sds(N, i32), "v0": _sds(N, b)}

    sh = "parallel/shuffle.py"
    do = "parallel/dist_ops.py"

    def S(mesh):  # noqa: N802 - tiny catalog helpers
        from ..parallel import shuffle
        return shuffle

    def D(mesh):  # noqa: N802
        from ..parallel import dist_ops
        return dist_ops

    eps: List[EntryPoint] = [
        EntryPoint(
            "count", sh, lambda m: S(m)._count_fn(m),
            lambda m: rows(i32, b), factory="_count_fn"),
        EntryPoint(
            "count2", sh, lambda m: S(m)._count2_fn(m),
            lambda m: rows(i32, b, i32, b), factory="_count2_fn"),
        EntryPoint(
            "exchange_padded", sh,
            lambda m: S(m)._exchange_padded_fn(m, 16, "sort"),
            lambda m: (payload(),) + rows(i32, b),
            factory="_exchange_padded_fn"),
        EntryPoint(
            # the fused Pallas partition path, traced through the
            # interpreter so the axis/all-to-all/f64 checks cover the
            # kernel-routed program off-TPU too
            "exchange_padded_kernel", sh,
            lambda m: S(m)._exchange_padded_fn(m, 16, "interp"),
            lambda m: (payload(),) + rows(i32, b),
            factory="_exchange_padded_fn"),
        EntryPoint(
            "exchange_padded_pair", sh,
            lambda m: S(m)._exchange_padded_pair_fn(m, 16, 16,
                                                    "sort", "sort"),
            lambda m: (payload(),) + rows(i32, b)
            + (payload(),) + rows(i32, b),
            factory="_exchange_padded_pair_fn"),
        EntryPoint(
            "exchange_blockwise", sh,
            lambda m: S(m)._exchange_fn(m, 8, 2, 64),
            lambda m: (payload(),) + rows(i32, b),
            factory="_exchange_fn"),
        EntryPoint(
            "exchange_partition", sh,
            lambda m: S(m)._exchange_partition_fn(m, 16, 8, "sort"),
            lambda m: (payload(),) + rows(i32, b),
            factory="_exchange_partition_fn"),
        EntryPoint(
            "exchange_chunk_first", sh,
            lambda m: S(m)._exchange_chunk_first_fn(m, 16, 8, "sort"),
            lambda m: (payload(),) + rows(i32, b),
            factory="_exchange_chunk_first_fn"),
        EntryPoint(
            # chunked pipeline head with the Pallas partition folded in
            "exchange_chunk_first_kernel", sh,
            lambda m: S(m)._exchange_chunk_first_fn(m, 16, 8, "interp"),
            lambda m: (payload(),) + rows(i32, b),
            factory="_exchange_chunk_first_fn"),
        EntryPoint(
            # operands: chunk-padded sorted leaves (rows + world*cb),
            # per-shard start offsets, the [world*block] accumulator,
            # and the replicated chunk-index scalar
            "exchange_chunk", sh,
            lambda m: S(m)._exchange_chunk_fn(m, 16, 8),
            lambda m: ({"d0": _sds((96,), i32), "v0": _sds((96,), b)},
                       _sds(CI, i32),
                       {"d0": _sds((256,), i32), "v0": _sds((256,), b)},
                       _sds((), i32)),
            factory="_exchange_chunk_fn"),
        EntryPoint(
            # hot-key salted routing: targets+emit (+ the replicated
            # warn-factor scalar) -> salted targets + stacked count
            # matrices. salt=4 is the declared CYLON_SALT_FACTOR shape
            "salted_targets", sh,
            lambda m: S(m)._salted_targets_fn(m, 4),
            lambda m: rows(i32, b) + (_sds((), jnp.float32),),
            factory="_salted_targets_fn"),
        EntryPoint(
            "string_hash", do, lambda m: D(m)._string_hash_fn(m, 4),
            lambda m: vb(), factory="_string_hash_fn"),
        EntryPoint(
            "word_lanes", do, lambda m: D(m)._word_lanes_fn(m, 4),
            lambda m: vb(), factory="_word_lanes_fn"),
        EntryPoint(
            # one key program a stage (dist_ops "key programs"): a masked
            # int32 column, short varbytes as 2 word lanes + lengths, the
            # content-hash quad of long varbytes
            "partition_targets_program", do,
            lambda m: D(m)._partition_targets_program_fn(
                m, _KEY_FORMS, True),
            lambda m: (_key_program_inputs(rows),),
            factory="_partition_targets_program_fn"),
        EntryPoint(
            "key_bits_program", do,
            lambda m: D(m)._key_bits_program_fn(
                m, _KEY_FORMS, (True, False, False)),
            lambda m: (_key_program_inputs(rows),),
            factory="_key_bits_program_fn"),
        EntryPoint(
            "word_targets", do, lambda m: D(m)._word_targets_fn(m),
            lambda m: vb() + rows(i32, b), factory="_word_targets_fn"),
        EntryPoint(
            "starts_reconcile", do,
            lambda m: D(m)._starts_reconcile_fn(m, 16, 64),
            lambda m: (_sds(N, i32), _sds(CI, i32), _sds(CI, i32)),
            factory="_starts_reconcile_fn"),
        EntryPoint(
            "lanes_interleave", do,
            lambda m: D(m)._lanes_interleave_fn(m, 2),
            lambda m: (_sds(N, i32), _sds(N, u32), _sds(N, u32)),
            factory="_lanes_interleave_fn"),
        EntryPoint(
            "varlen_count", do, lambda m: D(m)._varlen_count_fn(m),
            lambda m: rows(i32, i32), factory="_varlen_count_fn"),
        EntryPoint(
            "varlen_count_replicated", do,
            lambda m: D(m)._varlen_count_fn(m, replicated=True),
            lambda m: (_sds((32,), i32), _sds(N, i32)),
            factory="_varlen_count_fn"),
        EntryPoint(
            "varlen_take", do, lambda m: D(m)._varlen_take_fn(m, 64),
            lambda m: vb() + (_sds(N, i32),), factory="_varlen_take_fn"),
        EntryPoint(
            "join_plan_inner", do,
            lambda m: _join_factory(D(m), m, "INNER"),
            lambda m: ((_sds(N, u32),), _sds(N, b), _sds(N, b),
                       (_sds(N, u32),), _sds(N, b), _sds(N, b)),
            factory="_join_plan_fn"),
        EntryPoint(
            "join_plan_full_outer", do,
            lambda m: _join_factory(D(m), m, "FULL_OUTER"),
            lambda m: ((_sds(N, u32),), _sds(N, b), _sds(N, b),
                       (_sds(N, u32),), _sds(N, b), _sds(N, b)),
            factory="_join_plan_fn"),
        EntryPoint(
            "join_plan_semi", do,
            lambda m: D(m)._semi_plan_fn(m, _join_type("SEMI")),
            lambda m: ((_sds(N, u32),), _sds(N, b), _sds(N, b),
                       (_sds(N, u32),), _sds(N, b), _sds(N, b)),
            factory="_semi_plan_fn"),
        EntryPoint(
            "join_materialize", do,
            lambda m: _join_mat_factory(D(m), m),
            lambda m: (_sds(N, i32), _sds(N, i32), _sds(N, i32),
                       _sds(N, b), _sds(N, b),
                       rows(i32, jnp.float32), rows(b, b),
                       rows(i32,), rows(b,)),
            factory="_join_mat_fn"),
        EntryPoint(
            # broadcast-hash join (adaptive execution): the build
            # side's key bits all_gather inside the program, probe
            # rows plan per shard against the replicated table
            "bcast_join_plan", do,
            lambda m: _bcast_join_factory(D(m), m),
            lambda m: ((_sds(N, u32),), _sds(N, b), _sds(N, b),
                       (_sds(N, u32),), _sds(N, b), _sds(N, b)),
            factory="_bcast_join_plan_fn"),
        EntryPoint(
            # ...and its materialize program: build payload lanes
            # re-gathered, match runs expanded at host-chosen capacity
            "bcast_join_mat", do,
            lambda m: _bcast_join_mat_factory(D(m), m),
            lambda m: (_sds(N, i32), _sds(N, i32), _sds(N, i32),
                       _sds(N, b), _sds(N, b),
                       rows(i32, jnp.float32), rows(b, b),
                       rows(i32,), rows(b,)),
            factory="_bcast_join_mat_fn"),
        EntryPoint(
            "setop_count", do, lambda m: D(m)._setop_count_fn(m),
            lambda m: ((_sds(N, u32),), _sds(N, b),
                       (_sds(N, u32),), _sds(N, b)),
            factory="_setop_count_fn"),
        EntryPoint(
            "setop_materialize", do,
            lambda m: _setop_mat_factory(D(m), m),
            lambda m: ((_sds(N, u32),), _sds(N, b),
                       (_sds(N, u32),), _sds(N, b),
                       rows(i32,), rows(b,), rows(i32,), rows(b,)),
            factory="_setop_mat_fn"),
        EntryPoint(
            "varlen_take_concat_count", do,
            lambda m: D(m)._varlen_take_concat_count_fn(m),
            lambda m: rows(i32, i32, i32),
            factory="_varlen_take_concat_count_fn"),
        EntryPoint(
            "varlen_take_concat", do,
            lambda m: D(m)._varlen_take_concat_fn(m, 64),
            lambda m: vb() + vb() + (_sds(N, i32),),
            factory="_varlen_take_concat_fn"),
        EntryPoint(
            "groupby", do, lambda m: _groupby_factory(D(m), m),
            lambda m: ((_sds(N, u32),), (_sds(N, i32),), (_sds(N, b),),
                       _sds(N, b), (_sds(N, jnp.float32),),
                       (_sds(N, b),), None),
            factory="_groupby_fn"),
        EntryPoint(
            "shard_sort", do,
            lambda m: D(m)._shard_sort_fn(m, 2, 2, 1),
            lambda m: ((_sds(N, u32),), _sds(N, b),
                       rows(i32, jnp.float32), rows(b, b)),
            factory="_shard_sort_fn"),
        EntryPoint(
            "join_plan_stream", do, lambda m: None, lambda m: (),
            factory="_join_plan_stream_fn", tpu_only=True),
        EntryPoint(
            "join_mat_stream", do, lambda m: None, lambda m: (),
            factory="_join_mat_stream_fn", tpu_only=True),
        EntryPoint(
            "join_plan_semi_stream", do, lambda m: None, lambda m: (),
            factory="_semi_plan_stream_fn", tpu_only=True),
    ]
    return eps


def _join_type(jt_name):
    from ..ops import join as _join
    return getattr(_join.JoinType, jt_name)


def _join_factory(dist_ops, mesh, jt_name):
    return dist_ops._join_plan_fn(mesh, _join_type(jt_name))


def _join_mat_factory(dist_ops, mesh):
    from ..ops import join as _join
    return dist_ops._join_mat_fn(mesh, _join.JoinType.INNER, 16, 0)


def _bcast_join_factory(dist_ops, mesh):
    from ..ops import join as _join
    return dist_ops._bcast_join_plan_fn(mesh, _join.JoinType.INNER)


def _bcast_join_mat_factory(dist_ops, mesh):
    from ..ops import join as _join
    return dist_ops._bcast_join_mat_fn(mesh, _join.JoinType.LEFT, 16)


def _setop_mat_factory(dist_ops, mesh):
    from ..ops import setops as _setops
    return dist_ops._setop_mat_fn(mesh, _setops.SetOp.UNION, 32)


def _groupby_factory(dist_ops, mesh):
    from ..ops import groupby as _groupby
    return dist_ops._groupby_fn(
        mesh, (_groupby.AggregationOp.SUM,), (0,), (False,))


def _load_entry_module(path: str) -> List[EntryPoint]:
    """Load ENTRY_POINTS from a fixture module file (tests)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("_cylint_entries", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return list(mod.ENTRY_POINTS)


@dataclass
class _Notes:
    items: List[str] = field(default_factory=list)


@register("collectives")
def check_collectives(ctx: AnalysisContext) -> List[Finding]:
    entry_module = ctx.options.get("collectives_entry_module")
    if entry_module is None and ctx.options.get("skip_collectives"):
        return []
    if ctx.options.get("collectives_coverage_only"):
        covered = {(e.path, e.factory)
                   for e in default_entry_points() if e.factory}
        return _coverage_findings(ctx, covered)
    import jax

    # f64-promotion detection needs x64 on: with it off, jax silently
    # downgrades the very promotions we are hunting. RESTORED after the
    # trace loop — a read-only checker must not leak global config into
    # its host process (a later eager kernel would trace under x64)
    x64_before = bool(jax.config.jax_enable_x64)
    if not x64_before:
        jax.config.update("jax_enable_x64", True)

    try:
        mesh = _virtual_mesh(int(ctx.options.get("world", 4)))
        entries = _load_entry_module(entry_module) if entry_module \
            else default_entry_points()

        findings: List[Finding] = []
        notes: List[str] = ctx.options.setdefault("notes", [])
        on_tpu = jax.default_backend() == "tpu"
        covered = set()
        for e in entries:
            if e.factory:
                covered.add((e.path, e.factory))
            if e.tpu_only and not on_tpu:
                notes.append(f"collectives: {e.name} is TPU-only "
                             f"(Pallas) — skipped on "
                             f"{jax.default_backend()}")
                continue
            line = _factory_line(ctx, e)
            try:
                fn = e.build(mesh)
                closed = jax.make_jaxpr(fn)(*e.inputs(mesh))
            except Exception as exc:  # noqa: BLE001 - reported as finding  # cylint: disable=errors/broad-swallow — trace failure becomes a Finding below
                findings.append(Finding(
                    rule="collectives/trace-error", path=e.path,
                    line=line,
                    message=f"{e.name}: abstract evaluation failed: "
                            f"{type(exc).__name__}: {exc}"))
                continue
            findings.extend(_check_jaxpr(closed.jaxpr, e, line))
        if entry_module is None:
            findings.extend(_coverage_findings(ctx, covered))
        return findings
    finally:
        if not x64_before:
            jax.config.update("jax_enable_x64", False)


def _factory_line(ctx: AnalysisContext, e: EntryPoint) -> int:
    """def-line of the factory, for clickable findings."""
    import ast

    for f in ctx.files():
        if f.rel != e.path:
            continue
        for node in f.tree.body:
            if isinstance(node, ast.FunctionDef) and \
                    node.name == e.factory:
                return node.lineno
    return 1


def _coverage_findings(ctx: AnalysisContext, covered) -> List[Finding]:
    """One ``collectives/uncataloged-factory`` finding per `_*_fn` in
    `parallel/` the entry-point catalog misses. Intentional exclusions
    (helpers returning plain host callables rather than jitted
    programs) carry a per-line ``# cylint: disable=`` — suppression
    counting keeps them visible in the run summary."""
    import ast

    findings: List[Finding] = []
    for f in ctx.files():
        if not f.rel.startswith("parallel/"):
            continue
        for node in f.tree.body:
            if isinstance(node, ast.FunctionDef) and \
                    node.name.startswith("_") and \
                    node.name.endswith("_fn") and \
                    (f.rel, node.name) not in covered:
                findings.append(Finding(
                    rule="collectives/uncataloged-factory", path=f.rel,
                    line=node.lineno,
                    message=f"{node.name} is not in the collectives "
                            f"entry-point catalog: its collective "
                            f"program is never abstractly checked — "
                            f"add an EntryPoint (or disable this rule "
                            f"on the def line if it returns a plain "
                            f"host callable)"))
    return findings
