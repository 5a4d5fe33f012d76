"""SPMD kernel launch: shard_map partitioning for registered Pallas kernels.

A ``pallas_call`` carries no SPMD partitioning rule, so before this module a
multi-device program had exactly two options: silently fall back to jnp
(what ``models.blocks.use_fused_kernels`` did) or fail to lower.  The paper
analog is Treibig/Hager/Wellein's point that per-domain *placement*, not
just per-core tiling, determines achieved bandwidth: a block shape tuned
for one core's cache is worthless if the thread's working set lands on the
wrong memory controller.  Here the placement rule is the kernel's
``Partitioning`` declaration -- which operand axes are batch-parallel (each
device owns a shard and launches the planned kernel on it), which are
replicated, and how per-shard scalar results combine across shards.

Every ``@register_kernel`` entry carries a declaration; ``api.launch``
detects an ambient multi-device ``jax.sharding.Mesh`` (``spmd_mesh``) and
routes through ``shard_map``:

  * in/out PartitionSpecs come from ``parallel.rules`` -- the same
    logical-axis tables the model's activations use -- restricted to the
    mesh's axes, with the divisibility fallback to replication (an odd
    batch never produces ragged shards, it replicates);
  * inside the body each shard re-derives its plan from its own *local*
    operand shape (``plan_for(..., local=True)``), memoized under
    ``(kernel, local_shape, dtype, mesh)`` -- the per-shard block shape is
    planned, not inherited from the global array;
  * scalar outputs declare their cross-shard combine (``reduce="mean"``
    for xent's token-mean NLL), applied with ``pmean``/``psum`` over the
    mesh axes the sharded operand axes actually mapped to.

Kernels whose access pattern couples neighboring sites across a split can
still partition -- they declare a ``spmd_body`` alongside their
``Partitioning`` and own the cross-shard communication themselves
(``ShardContext`` hands them the mesh axes each operand dim actually
mapped to):

  * xent shards the *vocab* axis (Megatron layout) and combines the
    per-shard online-softmax partials with a cross-shard log-sum-exp:
    ``pmax`` of the per-shard max, ``psum`` of the rescaled sum-exp and of
    the locally-gathered target logit -- three token-length fp32 vectors on
    the wire instead of a replicated (T, V) logits array;
  * jacobi shards its grid rows and issues its one-row halo ``ppermute``s
    *before* sweeping the interior stripe, so the wire time hides behind
    the interior Pallas sweep (docs/OVERLAP.md);
  * LBM shards its X axis the same way, with per-direction halo depth
    (only the 2x5 D3Q19 directions with c_x != 0 cross a cut).

The planner prices this traffic (``KernelPlan.predicted_comm_bytes``,
and the part the interior compute window cannot hide as
``predicted_exposed_comm_bytes``) so ``repro.measure.validate --comm``
can check the lowered program's collective census against the model and
``--exposed`` can check the program *structures* the collectives as
overlappable (``overlap_report`` below: a collective with some Pallas
compute independent of it in both dataflow directions can run
concurrently with that compute).  A declared sharding that cannot apply
(vocab % mesh != 0) falls back to replication with a logged reason
(``rules.spec_report``).  Kernels with neither a safe split nor a
``spmd_body`` stay ``replicated()``: every device computes the full
array.

The path never nests: inside an existing shard_map/pmap body (pipeline
stages) ``spmd_mesh`` returns None and ``launch`` stays single-device.
``plan_context(spmd=False)`` opts a scope out explicitly.
"""
from __future__ import annotations

import ast
import dataclasses
import inspect
import logging
import textwrap
from typing import Mapping

import jax
from jax.extend import core as jax_core
from jax.sharding import PartitionSpec as P

from repro.api import context as context_lib
from repro.obs import bus as obs_bus
from repro.obs import events as obs_events
from repro.parallel import rules as rules_lib

__all__ = ["Partitioning", "SCALAR", "replicated", "partitioning_for",
           "spmd_mesh", "spmd_launch", "ShardContext", "shard_specs",
           "consulted_operand_dims", "overlap_report", "OverlapReport",
           "CollectiveSite"]

_log = logging.getLogger(__name__)

# Sentinel out_axes: the kernel reduces to a scalar (rank-0) result.
SCALAR = "scalar"

_REDUCES = (None, "mean", "sum")


@dataclasses.dataclass(frozen=True)
class Partitioning:
    """How one registered kernel partitions over an SPMD mesh.

    in_axes:
        one template per positional operand: a tuple of *logical* axis
        names (``parallel.rules`` vocabulary: "batch", "vocab", ...) or
        ``None`` (replicate that dim), one entry per array dimension.  An
        ``...`` (Ellipsis) entry expands to ``None`` for however many
        middle dims the operand has, so one template serves the 2-D
        kernel-level call and the 3-D model call: ``("batch", ..., None)``
        is ``("batch", None)`` for (rows, d) and ``("batch", None, None)``
        for (B, S, d).
    out_axes:
        the output's template (the output is assumed shaped like operand 0,
        the convention every registered family follows), or ``SCALAR`` for
        a rank-0 reduction result.
    reduce:
        cross-shard combine for ``SCALAR`` outputs: "mean" (xent's
        token-mean -- exact because shard_map shards are equal-sized) or
        "sum".  Required for SCALAR, forbidden otherwise.
    """

    in_axes: tuple[tuple, ...]
    out_axes: tuple | str = (...,)
    reduce: str | None = None

    def __post_init__(self):
        if self.reduce not in _REDUCES:
            raise ValueError(
                f"reduce must be one of {_REDUCES}, got {self.reduce!r}"
            )
        if self.out_axes == SCALAR and self.reduce is None:
            raise ValueError(
                "a SCALAR output needs a cross-shard reduce: each shard "
                "computes only its local partial"
            )
        if self.reduce is not None and self.out_axes != SCALAR:
            raise ValueError(
                f"reduce={self.reduce!r} only applies to SCALAR outputs"
            )


def replicated(n_inputs: int) -> Partitioning:
    """Fully-replicated declaration: every device computes the whole array.
    The right call for kernels whose stencil couples neighboring sites
    across any split (jacobi halos, LBM streaming) -- and the safe default
    for kernels registered without a declaration."""
    return Partitioning(in_axes=((...,),) * n_inputs, out_axes=(...,))


def partitioning_for(entry, n_inputs: int) -> Partitioning:
    """The entry's declared partitioning, or the replicated default for its
    ``n_inputs`` positional operands."""
    part = getattr(entry, "partitioning", None)
    return part if part is not None else replicated(n_inputs)


def _expand(template, ndim: int) -> tuple:
    """Instantiate an axes template for a rank-``ndim`` operand."""
    t = tuple(template)
    if Ellipsis in t:
        i = t.index(Ellipsis)
        head, tail = t[:i], t[i + 1:]
        n_mid = ndim - len(head) - len(tail)
        if n_mid < 0:
            raise ValueError(
                f"axes template {template} needs rank >= "
                f"{len(head) + len(tail)}, operand has rank {ndim}"
            )
        return head + (None,) * n_mid + tail
    if len(t) != ndim:
        raise ValueError(
            f"axes template {template} is rank-{len(t)}, "
            f"operand has rank {ndim}"
        )
    return t


def _dim_axes(spec: P, ndim: int) -> tuple[tuple[str, ...], ...]:
    """Per-dimension mesh axis names of a PartitionSpec, padded to rank."""
    parts = tuple(spec)
    out = []
    for d in range(ndim):
        p = parts[d] if d < len(parts) else None
        if p is None:
            out.append(())
        elif isinstance(p, str):
            out.append((p,))
        else:
            out.append(tuple(p))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class ShardContext:
    """What a kernel's ``spmd_body`` needs to know about its placement.

    operand_axes:
        per operand, per dimension: the tuple of mesh axis names that
        dimension was actually sharded over (empty = whole on this shard --
        either declared replicated or a divisibility fallback).
    axis_sizes:
        ``{mesh axis: size}`` for the launch mesh.
    """

    operand_axes: tuple[tuple[tuple[str, ...], ...], ...]
    axis_sizes: Mapping[str, int]

    def axes(self, operand: int = 0, dim: int = 0) -> tuple[str, ...]:
        return self.operand_axes[operand][dim]

    def size(self, axes: tuple[str, ...]) -> int:
        """Number of shards along ``axes`` (1 when unsharded)."""
        n = 1
        for a in axes:
            n *= int(self.axis_sizes.get(a, 1))
        return n

    def index(self, axes: tuple[str, ...]):
        """This shard's linear index along ``axes`` (traced; 0 when
        unsharded), row-major over the axis tuple like the sharding is."""
        idx = 0
        for a in axes:
            idx = idx * int(self.axis_sizes.get(a, 1)) + jax.lax.axis_index(a)
        return idx


def consulted_operand_dims(fn) -> frozenset[tuple[int, int]] | None:
    """``(operand, dim)`` pairs ``fn`` reads via ``ShardContext.axes``.

    Static introspection for ``repro.analyze``'s declaration-drift rule:
    parses the ``spmd_body``'s source (no execution, no tracing) and
    collects every ``ctx.axes(operand, dim)`` call on the body's first
    positional parameter, resolving the defaults ``(0, 0)``.  Returns
    ``None`` when the source is unavailable (C extension, exec'd code) or
    when any ``axes`` call takes non-literal arguments -- callers must
    treat ``None`` as "unknowable", not "consults nothing".
    """
    try:
        tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
    except (OSError, TypeError, SyntaxError):
        return None
    fndef = next(
        (n for n in ast.walk(tree)
         if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))),
        None,
    )
    if fndef is None or not fndef.args.args:
        return None
    ctx_name = fndef.args.args[0].arg
    pairs: set[tuple[int, int]] = set()
    for node in ast.walk(fndef):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "axes"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == ctx_name):
            continue
        vals = {"operand": 0, "dim": 0}
        names = ("operand", "dim")
        if len(node.args) > len(names):
            return None
        for i, arg in enumerate(node.args):
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, int)):
                return None
            vals[names[i]] = arg.value
        for kw in node.keywords:
            if (kw.arg not in vals
                    or not isinstance(kw.value, ast.Constant)
                    or not isinstance(kw.value.value, int)):
                return None
            vals[kw.arg] = kw.value.value
        pairs.add((vals["operand"], vals["dim"]))
    return frozenset(pairs)


def shard_specs(mesh, templates, arrays):
    """Build ``(in_specs, operand_axes, axis_sizes, fallbacks)`` for axis
    ``templates`` over ``arrays`` under the ambient (or default) rules,
    restricted to ``mesh``.  Shared by ``spmd_launch`` and kernel-owned
    shard_maps (xent's vocab-parallel backward)."""
    table = rules_lib.restrict_to_mesh(
        rules_lib.current_rules() or rules_lib.DEFAULT_RULES, mesh
    )
    sizes = dict(zip(tuple(mesh.axis_names), tuple(mesh.devices.shape)))
    in_specs = []
    fallbacks: list[str] = []
    for t, a in zip(templates, arrays):
        s, fb = rules_lib.spec_report(
            *_expand(t, a.ndim), rules=table,
            shape=tuple(int(x) for x in a.shape), axis_sizes=sizes)
        in_specs.append(s)
        fallbacks.extend(fb)
    in_specs = tuple(in_specs)
    operand_axes = tuple(
        _dim_axes(s, a.ndim) for s, a in zip(in_specs, arrays)
    )
    return in_specs, operand_axes, sizes, fallbacks


def _spec_mesh_axes(spec: P) -> tuple[str, ...]:
    """Every mesh axis name appearing in a PartitionSpec, in order."""
    names: list[str] = []
    for part in spec:
        if part is None:
            continue
        for n in (part,) if isinstance(part, str) else tuple(part):
            if n not in names:
                names.append(n)
    return tuple(names)


def spmd_mesh(ctx: "context_lib.PlanContext | None" = None):
    """The mesh ``launch`` would shard_map over right now, or ``None``.

    Routing requires a *real* multi-device ``jax.sharding.Mesh`` (a
    ``{axis: size}`` mapping plans shard-aligned padding but cannot place
    computation), an SPMD-enabled context, and no enclosing mapped trace
    (nesting a shard_map inside a pipeline stage's shard_map would rebind
    its axis names).  ``models.blocks.use_fused_kernels`` gates the model
    hot paths on exactly this predicate."""
    ctx = ctx if ctx is not None else context_lib.current_context()
    if not ctx.spmd:
        return None
    mesh = ctx.mesh
    if mesh is None:
        mesh = rules_lib.current_mesh()
    if not isinstance(mesh, jax.sharding.Mesh):
        return None
    if mesh.size <= 1:
        return None
    if inside_shard_map():
        return None
    return mesh


def inside_shard_map() -> bool:
    """True under an active mapped trace: a shard_map or pmap body binds
    its mesh axis names into the axis environment, plain jit does not."""
    return bool(jax.core.nonempty_axis_env_DO_NOT_USE())


_FALLBACK_LOGGED: set[tuple] = set()


def _log_fallbacks(entry, mesh, arrays, fallbacks) -> None:
    """Record (once per kernel/shapes/mesh) every declared sharding that
    fell back to replication -- the vocab-parallel rule silently degrading
    to full-vocab shards is a real perf cliff, not an implementation
    detail.  See docs/SPMD.md ('Communication-minimal partitionings')."""
    if not fallbacks:
        return
    if obs_bus.enabled():
        # Every degraded launch emits (the obs report counts occurrences);
        # only the human-facing log line below dedups per site.
        obs_bus.emit(obs_events.SpmdFallbackEvent(
            kernel=entry.name,
            mesh=tuple(zip(tuple(mesh.axis_names),
                           tuple(mesh.devices.shape))),
            reasons=tuple(fallbacks)))
    key = (entry.name,
           tuple(tuple(int(s) for s in a.shape) for a in arrays),
           tuple(mesh.axis_names), tuple(mesh.devices.shape))
    if key in _FALLBACK_LOGGED:
        return
    _FALLBACK_LOGGED.add(key)
    _log.info(
        "SPMD launch of %r over mesh %s: declared partitioning partially "
        "replicated (%s) -- see docs/SPMD.md",
        entry.name,
        dict(zip(tuple(mesh.axis_names), tuple(mesh.devices.shape))),
        "; ".join(fallbacks),
    )


def spmd_launch(entry, mesh, arrays, scalars):
    """Launch ``entry`` on ``arrays`` partitioned over ``mesh``.

    Builds in/out specs from the kernel's declaration under the ambient
    (or default) sharding rules, then shard_maps a body over them.  A
    kernel that registered an ``spmd_body`` owns its shard body -- it
    receives a ``ShardContext`` (which mesh axes each operand dim actually
    mapped to) and performs its own halo exchange / cross-shard combine.
    Otherwise the generic body plans each shard's *local* block shape, runs
    the registered Pallas body on it, and applies the declared scalar
    reduce.  Scalar kwargs (eps, omega, ...) close over the body;
    array-valued options ride along replicated.
    """
    part = partitioning_for(entry, len(arrays))
    if len(part.in_axes) != len(arrays):
        raise ValueError(
            f"{entry.name}: partitioning declares {len(part.in_axes)} "
            f"operand(s), launch got {len(arrays)}"
        )
    in_specs, operand_axes, sizes, fallbacks = shard_specs(
        mesh, part.in_axes, arrays
    )
    _log_fallbacks(entry, mesh, arrays, fallbacks)
    if part.out_axes == SCALAR:
        out_spec = P()
        # The local partial must be combined over every mesh axis the
        # (sharded) data operand was split across; if divisibility forced
        # full replication this is empty and the local result is global.
        reduce_axes = _spec_mesh_axes(in_specs[0])
    else:
        # The output is shaped like operand 0, so its spec derives the
        # same way the inputs' did (same rules table, same divisibility).
        (out_spec,), _, _, _ = shard_specs(
            mesh, (part.out_axes,), (arrays[0],))
        reduce_axes = ()

    if entry.spmd_body is not None:
        ctx = ShardContext(operand_axes=operand_axes, axis_sizes=sizes)

        def _shard_body(*local):
            return entry.spmd_body(ctx, *local, **scalars)
    else:
        def _shard_body(*local):
            from repro.api import dispatch  # lazy: dispatch imports this module

            shape, dtype = entry.plan_args(*local, **scalars)
            plan = dispatch.plan_for(entry.name, shape, dtype, local=True)
            out = entry.body(plan, *local, **scalars)
            if reduce_axes:
                if part.reduce == "mean":
                    out = jax.lax.pmean(out, reduce_axes)
                elif part.reduce == "sum":
                    out = jax.lax.psum(out, reduce_axes)
            return out

    fn = jax.shard_map(_shard_body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_spec, check_vma=False)
    return fn(*arrays)


# ---------------------------------------------------------------------------
# Overlap structure analysis (validate --comm --exposed).
#
# Whether a collective's wire time *can* hide behind compute is a property
# of the program's dataflow, not of the runtime: a collective that no
# Pallas call depends on (and that depends on no Pallas call) is free to
# run concurrently with that call -- XLA's async pairs (the
# collective-permute-start/done ``lowering.collective_census`` parses in
# HLO) are exactly the latitude the scheduler takes when the dependence
# graph allows it.  The jaxpr is the right level to check this: dataflow
# is explicit, and the shard-body structure the kernels author (halo
# ppermute issued before the interior sweep, boundary stitch after) is
# still visible rather than fused away.

_COLLECTIVE_PRIMS = frozenset({
    "ppermute", "pbroadcast", "psum", "psum_invariant", "pmax", "pmin",
    "all_gather", "all_to_all", "reduce_scatter",
})
_COMPUTE_PRIMS = frozenset({"pallas_call"})


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One collective equation in the flattened program.

    axes:
        mesh axis names the collective communicates over (its group size
        is the product of their mesh sizes).
    result_bytes:
        per-device result size -- the same number the HLO census reads off
        the lowered op, here from the jaxpr output avals (local shapes,
        because the eqn sits inside the shard_map body).
    overlappable:
        True iff some Pallas call is independent of this collective in
        both dataflow directions, i.e. the schedule may run them
        concurrently and the wire time can hide behind that compute.
    """

    primitive: str
    axes: tuple[str, ...]
    result_bytes: int
    overlappable: bool


@dataclasses.dataclass(frozen=True)
class OverlapReport:
    collectives: tuple[CollectiveSite, ...]
    n_pallas_calls: int

    @property
    def n_overlappable(self) -> int:
        return sum(1 for c in self.collectives if c.overlappable)

    @property
    def all_overlappable(self) -> bool:
        """Every collective can hide (vacuously true with none)."""
        return all(c.overlappable for c in self.collectives)


def _sub_jaxprs(params):
    """Every Jaxpr nested in an eqn's params (unwrapping ClosedJaxpr),
    including tuples of them (cond branches)."""
    subs = []
    for v in params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns") and hasattr(inner, "invars"):
                subs.append(inner)
    return subs


def _flatten_rows(jaxpr, var_ids, rows, counter):
    """Inline sub-jaxprs into flat ``(prim, in_ids, out_ids, avals,
    params)`` rows.

    ``var_ids`` maps jaxpr Vars to dataflow node ids; inlining binds an
    inner jaxpr's invars/outvars to the enclosing eqn's, so dependence
    chains survive the pjit/shard_map nesting ``launch`` produces.  When
    the operand lists don't align one-to-one (while, mismatched-arity
    custom calls) the eqn is bridged through a junction node that makes
    everything inside depend on everything in -- conservative: it can only
    under-report overlappability, never invent it.
    """

    def fresh():
        counter[0] += 1
        return counter[0]

    def vid(v, make=False):
        if isinstance(v, jax_core.Literal):
            return None
        if v not in var_ids:
            if not make:
                return None
            var_ids[v] = fresh()
        return var_ids[v]

    for eqn in jaxpr.eqns:
        in_ids = [i for v in eqn.invars if (i := vid(v)) is not None]
        # A pallas_call's params carry the *kernel* jaxpr -- that is the
        # compute unit itself, not program nesting to inline through.
        subs = ([] if eqn.primitive.name in _COMPUTE_PRIMS
                else _sub_jaxprs(eqn.params))
        if not subs:
            out_ids = [vid(v, make=True) for v in eqn.outvars]
            rows.append((eqn.primitive.name, in_ids, out_ids,
                         tuple(v.aval for v in eqn.outvars), eqn.params))
            continue
        aligned = all(
            len(s.invars) <= len(eqn.invars)
            and len(s.outvars) == len(eqn.outvars)
            for s in subs
        )
        if aligned:
            # pjit/shard_map/custom_* (1:1), cond (branches take the
            # operands after the predicate): tail-align invars, merge each
            # branch's outvars into the eqn's.
            branch_outs = []
            for s in subs:
                for iv, ov in zip(s.invars, eqn.invars[-len(s.invars):]):
                    oid = vid(ov)
                    if oid is not None:
                        var_ids[iv] = oid
                for cv in s.constvars:
                    var_ids.setdefault(cv, fresh())
                _flatten_rows(s, var_ids, rows, counter)
                branch_outs.append([vid(v, make=True) for v in s.outvars])
            for k, ov in enumerate(eqn.outvars):
                srcs = [bo[k] for bo in branch_outs]
                if len(subs) == 1:
                    var_ids[ov] = srcs[0]
                else:
                    rows.append((f"{eqn.primitive.name}:merge",
                                 srcs + in_ids, [vid(ov, make=True)],
                                 (ov.aval,), {}))
        else:
            # No positional alignment: junction in, junction out.
            hub = fresh()
            rows.append((f"{eqn.primitive.name}:in", in_ids, [hub], (), {}))
            inner_outs = []
            for s in subs:
                for iv in list(s.invars) + list(s.constvars):
                    var_ids[iv] = hub
                _flatten_rows(s, var_ids, rows, counter)
                inner_outs.extend(vid(v, make=True) for v in s.outvars)
            rows.append((f"{eqn.primitive.name}:out", inner_outs + [hub],
                         [vid(v, make=True) for v in eqn.outvars],
                         tuple(v.aval for v in eqn.outvars), {}))


def _aval_bytes(avals) -> int:
    total = 0
    for a in avals:
        size = getattr(a, "size", None)
        dt = getattr(a, "dtype", None)
        if size is not None and dt is not None:
            total += int(size) * dt.itemsize
    return total


def _site_axes(params) -> tuple[str, ...]:
    for key in ("axes", "axis_name"):
        v = params.get(key)
        if v is None:
            continue
        return tuple(v) if isinstance(v, (tuple, list)) else (str(v),)
    return ()


def overlap_report(fn, *args, **kwargs) -> OverlapReport:
    """Classify every collective in ``fn(*args, **kwargs)`` as
    overlappable or blocking.

    Traces ``fn`` to a jaxpr (or takes a ready-made ClosedJaxpr as
    ``fn``), inlines the pjit/shard_map nesting, and marks a collective
    overlappable iff some ``pallas_call`` is neither upstream nor
    downstream of it.  The overlapped jacobi/LBM shard bodies pass (halo
    ppermute independent of the interior sweep); the PR-5
    exchange-then-compute shape fails (every Pallas call reads the
    arrived halo).  ``validate --comm --exposed`` prices the blocking
    sites as fully exposed wire bytes.
    """
    if hasattr(fn, "jaxpr") and hasattr(fn, "consts"):
        closed = fn
    else:
        closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    var_ids: dict = {}
    rows: list = []
    counter = [0]
    jx = closed.jaxpr
    for v in list(jx.invars) + list(jx.constvars):
        counter[0] += 1
        var_ids[v] = counter[0]
    _flatten_rows(jx, var_ids, rows, counter)

    # Ancestor bitsets in one topological pass (jaxpr eqns are ordered).
    n = len(rows)
    anc = [0] * n
    producer: dict[int, int] = {}
    for i, (_, in_ids, out_ids, _avals, _params) in enumerate(rows):
        a = 0
        for v in in_ids:
            p = producer.get(v)
            if p is not None:
                a |= anc[p] | (1 << p)
        anc[i] = a
        for v in out_ids:
            producer[v] = i

    pallas = [i for i, r in enumerate(rows) if r[0] in _COMPUTE_PRIMS]
    sites = []
    for i, (name, _in, _out, avals, params) in enumerate(rows):
        if name not in _COLLECTIVE_PRIMS:
            continue
        free = any(
            not (anc[i] >> p) & 1 and not (anc[p] >> i) & 1 for p in pallas
        )
        sites.append(CollectiveSite(
            primitive=name,
            axes=_site_axes(params),
            result_bytes=_aval_bytes(avals),
            overlappable=free,
        ))
    return OverlapReport(collectives=tuple(sites),
                         n_pallas_calls=len(pallas))
