"""Trace Pallas kernels to jaxprs and extract symbolic scatter sites.

`jax.make_jaxpr` traces a launcher *without executing the kernel*; the
resulting jaxpr contains a ``pallas_call`` equation whose params carry
the inner kernel jaxpr and the grid mapping.  This module walks that
inner jaxpr with an abstract interpreter over the expression language in
:mod:`repro.lint.symbolic`, recognizing the idioms the repo's kernels
(and Pallas scatter/histogram kernels generally) are built from:

* the one-hot scatter idiom — ``eq(stream[:, None], iota(dim=1))``
  reduced with ``reduce_sum`` (popcount/histogram) or contracted with
  ``dot_general`` (row scatter-add) and accumulated into an output ref;
* ``pl.when(pl.program_id(a) == 0)`` init guards around zero stores;
* read-modify-write accumulation (``get`` → combine → ``swap`` on the
  same ref) and retry loops (``while`` bodies containing ``swap``).

The output is a :class:`KernelModel` per ``pallas_call``: scatter sites
with *symbolic index streams*, per-ref init-guard axes, and the grid
axes each ref's block index depends on.  Everything downstream —
classifying a stream as affine/static vs data-dependent, deriving exact
degree counters, rule evaluation — lives in :mod:`repro.lint.analysis`
and :mod:`repro.lint.rules`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import numpy as np

from repro.lint import symbolic as sym


# -- one-hot idiom tags ------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class OneHotTag:
    """``eq(stream, iota(dim=bin_axis))`` — a one-hot scatter mask."""

    stream: sym.Expr            # token-indexed bin id, bin axis squeezed out
    bin_axis: int
    num_bins: int
    stream_len: int


@dataclasses.dataclass(frozen=True, eq=False)
class AccumTag:
    """A one-hot mask reduced over tokens — a scatter-shaped update."""

    onehot: OneHotTag
    kind: str                   # "one_hot_popcount" | "one_hot_matmul"
    row_elems: int              # elements updated per bin row


@dataclasses.dataclass
class ScatterSite:
    """One accumulate-into-ref site found in a kernel jaxpr."""

    ref: int
    ref_name: str
    stream: sym.Expr
    stream_len: int
    num_bins: int
    kind: str
    row_elems: int
    rmw: bool                   # value reads the ref's previous contents
    guard_axes: frozenset       # init-guard program_id axes at this site


@dataclasses.dataclass
class WriteRecord:
    ref: int
    rmw: bool
    is_zero_init: bool
    guard_axes: frozenset


@dataclasses.dataclass
class KernelModel:
    name: str
    grid: tuple
    num_inputs: int
    num_outputs: int
    block_shapes: list
    block_dep_axes: list        # per ref: frozenset of grid axes, or None
    sites: list
    writes: list
    init_guards: dict           # ref -> set of guarded program_id axes
    has_while: bool = False
    while_has_swap: bool = False
    num_eqns: int = 0
    source_file: str = ""
    source_line: int = 0

    def dep_axes(self, ref: int):
        if 0 <= ref < len(self.block_dep_axes):
            return self.block_dep_axes[ref]
        return None


@dataclasses.dataclass
class PallasRecord:
    """Raw pieces of one ``pallas_call`` equation."""

    name: str
    grid: tuple
    jaxpr: Any                  # inner kernel jaxpr (jax.core.Jaxpr)
    consts: list
    block_mappings: list
    num_inputs: int
    num_outputs: int
    num_index_operands: int

    def block_shape(self, ref: int):
        bm = self.block_mappings[ref]
        return tuple(int(b.block_size) for b in bm.block_shape)

    def block_for(self, ref: int, operand, step: tuple) -> np.ndarray:
        """Fetch the block an operand ref sees at one grid step."""
        from jax.extend import core as jcore

        bm = self.block_mappings[ref]
        coords = jcore.jaxpr_as_fun(bm.index_map_jaxpr)(*step)
        shape = self.block_shape(ref)
        arr = np.asarray(operand)
        slices = tuple(
            slice(int(c) * int(b), (int(c) + 1) * int(b))
            for c, b in zip(coords, shape))
        return arr[slices]


# -- pallas_call discovery ---------------------------------------------------


def _subjaxprs(value):
    from jax.extend import core as jcore

    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _index_map_dep_axes(bm) -> Optional[frozenset]:
    """Grid axes a block index map depends on; None if not a plain map."""
    from jax.extend import core as jcore

    jx = bm.index_map_jaxpr.jaxpr
    if jx.eqns:
        return None
    pos = {id(v): i for i, v in enumerate(jx.invars)}
    deps = set()
    for ov in jx.outvars:
        if isinstance(ov, jcore.Literal):
            continue
        i = pos.get(id(ov))
        if i is None:
            return None
        deps.add(i)
    return frozenset(deps)


def find_pallas_calls(fn: Callable, *args, **kwargs) -> list[PallasRecord]:
    """Trace ``fn`` (no kernel execution) and collect pallas_call records."""
    import jax
    from jax.extend import core as jcore

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    records: list[PallasRecord] = []

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                inner = eqn.params["jaxpr"]
                if isinstance(inner, jcore.ClosedJaxpr):
                    inner_jaxpr, consts = inner.jaxpr, list(inner.consts)
                else:
                    inner_jaxpr, consts = inner, []
                name = str(eqn.params.get("name_and_src_info", "pallas_call"))
                records.append(PallasRecord(
                    name=name.split(" ")[0],
                    grid=tuple(int(g) for g in gm.grid),
                    jaxpr=inner_jaxpr,
                    consts=consts,
                    block_mappings=list(gm.block_mappings),
                    num_inputs=int(getattr(gm, "num_inputs",
                                           len(gm.block_mappings) - 1)),
                    num_outputs=int(getattr(gm, "num_outputs", 1)),
                    num_index_operands=int(
                        getattr(gm, "num_index_operands", 0)),
                ))
            for sub in _subjaxprs(list(eqn.params.values())):
                visit(sub)

    visit(closed.jaxpr)
    return records


# -- the abstract interpreter ------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class SymVal:
    expr: sym.Expr
    tags: frozenset = frozenset()


@dataclasses.dataclass(frozen=True, eq=False)
class RefVal:
    ref: int
    name: str


_ELEMENTWISE = {
    "add", "sub", "mul", "max", "min", "div", "rem",
    "eq", "ne", "lt", "le", "gt", "ge", "and", "or", "xor", "not", "neg",
    "select_n", "integer_pow", "sign", "floor", "ceil", "round",
}


def _avals(var):
    return tuple(var.aval.shape), var.aval.dtype


def _strip_reindex(expr: sym.Expr) -> sym.Expr:
    while isinstance(expr, sym.Reindex):
        expr = expr.src
    return expr


def _strip_convert(expr: sym.Expr) -> sym.Expr:
    while isinstance(expr, sym.Elem) and expr.op == "convert":
        expr = expr.args[0]
    return expr


def _guard_axis(pred: sym.Expr) -> Optional[int]:
    """Axis ``a`` if ``pred`` is (a convert of) ``program_id(a) == 0``."""
    pred = _strip_convert(_strip_reindex(pred))
    if isinstance(pred, sym.Elem) and pred.op == "eq":
        a, b = (_strip_convert(_strip_reindex(x)) for x in pred.args[:2])
        for pid, zero in ((a, b), (b, a)):
            if isinstance(pid, sym.ProgramId) and sym.is_zero(zero):
                return pid.axis
    return None


def _resolve_iota_axis(expr: sym.Expr) -> Optional[int]:
    """Output axis an iota counts along, tracked through broadcasts."""
    if isinstance(expr, sym.Elem) and expr.op == "convert":
        return _resolve_iota_axis(expr.args[0])
    if isinstance(expr, sym.Iota):
        return expr.dim
    if isinstance(expr, sym.Reindex) and expr.kind == "broadcast":
        inner = _resolve_iota_axis(expr.src)
        if inner is None or inner >= len(expr.info):
            return None
        return int(expr.info[inner])
    return None


def _drop_axis(expr: sym.Expr, axis: int) -> Optional[sym.Expr]:
    """Expr without ``axis``, valid iff provably constant along it.

    jnp's broadcasting lowers ``flat[:, None] == iota(...)`` with the
    stream side broadcast up to the full (tokens, bins) shape; this
    peels those broadcasts back off the bin axis.  Returns None when
    constancy along the axis cannot be shown structurally (then the eq
    is not a one-hot against that iota).
    """
    if expr.shape[axis] == 1:
        return sym.squeeze_axis(expr, axis)
    if isinstance(expr, sym.Elem) and expr.op == "convert":
        inner = _drop_axis(expr.args[0], axis)
        if inner is None:
            return None
        return sym.Elem(shape=inner.shape, dtype=expr.dtype, op="convert",
                        args=(inner,))
    if isinstance(expr, sym.Reindex) and expr.kind == "broadcast":
        new_shape = tuple(s for i, s in enumerate(expr.shape) if i != axis)
        if axis not in expr.info:
            info = tuple(d - (d > axis) for d in expr.info)
            return sym.Reindex(shape=new_shape, dtype=expr.dtype,
                               kind="broadcast", src=expr.src, info=info)
        i = expr.info.index(axis)
        if expr.src.shape[i] == 1:
            inner = sym.squeeze_axis(expr.src, i)
            info = tuple(d - (d > axis)
                         for j, d in enumerate(expr.info) if j != i)
            return sym.Reindex(shape=new_shape, dtype=expr.dtype,
                               kind="broadcast", src=inner, info=info)
    return None


def _onehot_from_eq(lhs: SymVal, rhs: SymVal, out_shape) -> Optional[OneHotTag]:
    """Detect ``stream == iota(dim=d)`` where stream is flat along d."""
    for iota_side, stream_side in ((lhs, rhs), (rhs, lhs)):
        d = _resolve_iota_axis(iota_side.expr)
        if d is None or d >= len(out_shape):
            continue
        stream = stream_side.expr
        if len(stream.shape) == len(out_shape):
            flat = _drop_axis(stream, d)
        elif len(stream.shape) == len(out_shape) - 1:
            flat = stream
        else:
            flat = None
        if flat is None:
            continue
        stream_len = int(np.prod(flat.shape)) if flat.shape else 1
        return OneHotTag(stream=flat, bin_axis=d,
                         num_bins=int(out_shape[d]), stream_len=stream_len)
    return None


def _contains_ref_read(expr: sym.Expr, ref: int) -> bool:
    return ref in sym.data_refs(expr)


def _jaxpr_has_swap(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("swap", "addupdate"):
            return True
        for sub in _subjaxprs(list(eqn.params.values())):
            if _jaxpr_has_swap(sub):
                return True
    return False


class _Interpreter:
    def __init__(self, record: PallasRecord, model: KernelModel):
        self.record = record
        self.model = model
        self.guard_stack: list[tuple] = []      # (pred_expr, branch_index)

    # env helpers ------------------------------------------------------

    def _read(self, env, var):
        from jax.extend import core as jcore

        if isinstance(var, jcore.Literal):
            val = np.asarray(var.val)
            return SymVal(sym.Const(shape=tuple(val.shape), dtype=val.dtype,
                                    value=val))
        got = env.get(var)
        if got is None:                 # DropVar / unbound: never crash
            shape, dtype = _avals(var)
            return SymVal(sym.Opaque(shape=shape, dtype=dtype,
                                     reason="unbound var"))
        return got

    def _guard_axes(self) -> frozenset:
        axes = set()
        for pred, _branch in self.guard_stack:
            ax = _guard_axis(pred)
            if ax is not None:
                axes.add(ax)
        return frozenset(axes)

    # write handling ---------------------------------------------------

    def _record_write(self, ref_val: RefVal, value: SymVal):
        rmw = _contains_ref_read(value.expr, ref_val.ref)
        zero_init = sym.is_zero(value.expr)
        guard_axes = self._guard_axes()
        self.model.writes.append(WriteRecord(
            ref=ref_val.ref, rmw=rmw, is_zero_init=zero_init,
            guard_axes=guard_axes))
        if zero_init and guard_axes:
            self.model.init_guards.setdefault(
                ref_val.ref, set()).update(guard_axes)
        for tag in value.tags:
            if isinstance(tag, AccumTag):
                self.model.sites.append(ScatterSite(
                    ref=ref_val.ref, ref_name=ref_val.name,
                    stream=tag.onehot.stream,
                    stream_len=tag.onehot.stream_len,
                    num_bins=tag.onehot.num_bins,
                    kind=tag.kind, row_elems=tag.row_elems,
                    rmw=rmw, guard_axes=guard_axes))
                break

    # main loop --------------------------------------------------------

    def run(self, jaxpr, consts, in_vals):
        env: dict = {}
        for var, c in zip(jaxpr.constvars, consts):
            arr = np.asarray(c) if not hasattr(c, "aval") else None
            if arr is not None:
                env[var] = SymVal(sym.Const(
                    shape=tuple(arr.shape), dtype=arr.dtype, value=arr))
            else:
                shape, dtype = _avals(var)
                env[var] = SymVal(sym.Opaque(
                    shape=shape, dtype=dtype, reason="traced const"))
        for var, v in zip(jaxpr.invars, in_vals):
            env[var] = v
        for eqn in jaxpr.eqns:
            self.model.num_eqns += 1
            self._eqn(env, eqn)
        outs = []
        for var in jaxpr.outvars:
            outs.append(self._read(env, var))
        return outs

    def _opaque_outs(self, env, eqn, reason, tags=frozenset()):
        for ov in eqn.outvars:
            shape, dtype = _avals(ov)
            env[ov] = SymVal(sym.Opaque(shape=shape, dtype=dtype,
                                        reason=reason), tags)

    def _eqn(self, env, eqn):
        name = eqn.primitive.name
        handler = getattr(self, "_prim_" + name.replace("-", "_"), None)
        if handler is not None:
            handler(env, eqn)
            return
        if name in _ELEMENTWISE:
            self._elementwise(env, eqn, name)
            return
        # unknown primitive: opaque, but tags still flow through so a
        # one-hot mask passing an unmodeled op can still reach its swap
        tags = frozenset()
        for iv in eqn.invars:
            v = self._read(env, iv)
            if isinstance(v, SymVal):
                tags |= v.tags
        self._opaque_outs(env, eqn, reason=name, tags=tags)

    # primitive handlers -----------------------------------------------

    def _elementwise(self, env, eqn, op):
        args = tuple(self._read(env, iv) for iv in eqn.invars)
        shape, dtype = _avals(eqn.outvars[0])
        tags = frozenset().union(*(a.tags for a in args))
        expr = sym.Elem(shape=shape, dtype=dtype, op=op,
                        args=tuple(a.expr for a in args))
        if op == "eq" and len(args) == 2:
            tag = _onehot_from_eq(args[0], args[1], shape)
            if tag is not None:
                tags = tags | {tag}
        env[eqn.outvars[0]] = SymVal(expr, tags)

    def _prim_program_id(self, env, eqn):
        shape, dtype = _avals(eqn.outvars[0])
        env[eqn.outvars[0]] = SymVal(sym.ProgramId(
            shape=shape, dtype=dtype, axis=int(eqn.params["axis"])))

    def _prim_iota(self, env, eqn):
        shape, dtype = _avals(eqn.outvars[0])
        env[eqn.outvars[0]] = SymVal(sym.Iota(
            shape=shape, dtype=dtype, dim=int(eqn.params["dimension"])))

    def _prim_convert_element_type(self, env, eqn):
        arg = self._read(env, eqn.invars[0])
        shape, dtype = _avals(eqn.outvars[0])
        env[eqn.outvars[0]] = SymVal(
            sym.Elem(shape=shape, dtype=dtype, op="convert",
                     args=(arg.expr,)), arg.tags)

    def _reindex(self, env, eqn, kind, info):
        arg = self._read(env, eqn.invars[0])
        shape, dtype = _avals(eqn.outvars[0])
        env[eqn.outvars[0]] = SymVal(
            sym.Reindex(shape=shape, dtype=dtype, kind=kind, src=arg.expr,
                        info=info), arg.tags)

    def _prim_broadcast_in_dim(self, env, eqn):
        dims = tuple(int(d) for d in eqn.params["broadcast_dimensions"])
        self._reindex(env, eqn, "broadcast", dims)

    def _prim_reshape(self, env, eqn):
        if eqn.params.get("dimensions") is not None:
            self._opaque_outs(env, eqn, reason="permuting reshape")
            return
        self._reindex(env, eqn, "reshape", ())

    def _prim_squeeze(self, env, eqn):
        self._reindex(env, eqn, "reshape", ())

    def _prim_expand_dims(self, env, eqn):
        self._reindex(env, eqn, "reshape", ())

    def _prim_transpose(self, env, eqn):
        perm = tuple(int(p) for p in eqn.params["permutation"])
        self._reindex(env, eqn, "transpose", perm)

    def _prim_slice(self, env, eqn):
        starts = tuple(int(s) for s in eqn.params["start_indices"])
        limits = tuple(int(s) for s in eqn.params["limit_indices"])
        strides = eqn.params.get("strides") or (1,) * len(starts)
        strides = tuple(int(s) for s in strides)
        self._reindex(env, eqn, "slice", (starts, limits, strides))

    def _prim_get(self, env, eqn):
        ref = env.get(eqn.invars[0])
        shape, dtype = _avals(eqn.outvars[0])
        if isinstance(ref, RefVal):
            env[eqn.outvars[0]] = SymVal(sym.Data(
                shape=shape, dtype=dtype, ref=ref.ref, name=ref.name))
        else:
            self._opaque_outs(env, eqn, reason="get on unknown ref")

    def _prim_swap(self, env, eqn):
        ref = env.get(eqn.invars[0])
        if isinstance(ref, RefVal) and len(eqn.invars) >= 2:
            value = self._read(env, eqn.invars[1])
            self._record_write(ref, value)
            shape, dtype = _avals(eqn.outvars[0])
            env[eqn.outvars[0]] = SymVal(sym.Data(
                shape=shape, dtype=dtype, ref=ref.ref, name=ref.name))
        else:
            self._opaque_outs(env, eqn, reason="swap on unknown ref")

    def _prim_addupdate(self, env, eqn):
        ref = env.get(eqn.invars[0])
        if isinstance(ref, RefVal) and len(eqn.invars) >= 2:
            value = self._read(env, eqn.invars[1])
            shape, dtype = value.expr.shape, value.expr.dtype
            # addupdate is inherently read-modify-write: model it as
            # ref <- ref + value so rmw detection sees the self-read
            prev = sym.Data(shape=shape, dtype=dtype, ref=ref.ref,
                            name=ref.name)
            summed = SymVal(sym.Elem(shape=shape, dtype=dtype, op="add",
                                     args=(prev, value.expr)), value.tags)
            self._record_write(ref, summed)
        for ov in eqn.outvars:
            shape, dtype = _avals(ov)
            env[ov] = SymVal(sym.Opaque(shape=shape, dtype=dtype,
                                        reason="addupdate token"))

    def _prim_cond(self, env, eqn):
        pred = self._read(env, eqn.invars[0])
        branches = eqn.params["branches"]
        operands = [self._read(env, iv) for iv in eqn.invars[1:]]
        outs_per_branch = []
        for k, br in enumerate(branches):
            self.guard_stack.append((pred.expr, k))
            try:
                outs_per_branch.append(
                    self.run(br.jaxpr, list(br.consts), operands))
            finally:
                self.guard_stack.pop()
        for i, ov in enumerate(eqn.outvars):
            shape, dtype = _avals(ov)
            tags = frozenset()
            for outs in outs_per_branch:
                if i < len(outs):
                    tags |= outs[i].tags
            env[ov] = SymVal(sym.Opaque(shape=shape, dtype=dtype,
                                        reason="cond join"), tags)

    def _prim_while(self, env, eqn):
        self.model.has_while = True
        body = eqn.params.get("body_jaxpr")
        if body is not None and _jaxpr_has_swap(body.jaxpr):
            self.model.while_has_swap = True
        self._opaque_outs(env, eqn, reason="while loop")

    def _prim_scan(self, env, eqn):
        inner = eqn.params.get("jaxpr")
        if inner is not None and _jaxpr_has_swap(inner.jaxpr):
            self.model.has_while = True
            self.model.while_has_swap = True
        self._opaque_outs(env, eqn, reason="scan loop")

    def _inline_call(self, env, eqn, closed):
        operands = [self._read(env, iv) for iv in eqn.invars]
        outs = self.run(closed.jaxpr, list(closed.consts), operands)
        for ov, val in zip(eqn.outvars, outs):
            env[ov] = val

    def _prim_jit(self, env, eqn):
        # nested jit (jnp's ``%`` and friends trace to one): inline it
        self._inline_call(env, eqn, eqn.params["jaxpr"])

    def _prim_closed_call(self, env, eqn):
        self._inline_call(env, eqn, eqn.params["call_jaxpr"])

    def _prim_custom_jvp_call(self, env, eqn):
        self._inline_call(env, eqn, eqn.params["call_jaxpr"])

    def _prim_custom_vjp_call_jaxpr(self, env, eqn):
        self._inline_call(env, eqn, eqn.params["fun_jaxpr"])

    def _prim_reduce_sum(self, env, eqn):
        arg = self._read(env, eqn.invars[0])
        axes = tuple(int(a) for a in eqn.params["axes"])
        tags = set()
        for tag in arg.tags:
            if isinstance(tag, OneHotTag) and tag.bin_axis not in axes:
                tags.add(AccumTag(onehot=tag, kind="one_hot_popcount",
                                  row_elems=1))
            elif isinstance(tag, AccumTag):
                tags.add(tag)
        self._opaque_outs(env, eqn, reason="reduce_sum",
                          tags=frozenset(tags))

    def _prim_dot_general(self, env, eqn):
        lhs = self._read(env, eqn.invars[0])
        rhs = self._read(env, eqn.invars[1])
        out_shape, _ = _avals(eqn.outvars[0])
        tags = set()
        for tag in lhs.tags | rhs.tags:
            if isinstance(tag, OneHotTag):
                row = int(out_shape[-1]) if out_shape else 1
                tags.add(AccumTag(onehot=tag, kind="one_hot_matmul",
                                  row_elems=row))
            elif isinstance(tag, AccumTag):
                tags.add(tag)
        self._opaque_outs(env, eqn, reason="dot_general",
                          tags=frozenset(tags))


# -- entry point -------------------------------------------------------------


def analyze_callable(fn: Callable, *args, name: str = "",
                     **kwargs) -> list[KernelModel]:
    """Trace ``fn`` and build a KernelModel per pallas_call (no exec)."""
    import inspect

    records = find_pallas_calls(fn, *args, **kwargs)
    models = []
    src_file, src_line = "", 0
    target = inspect.unwrap(fn)
    try:
        src_file = inspect.getsourcefile(target) or ""
        _, src_line = inspect.getsourcelines(target)
    except (OSError, TypeError):
        pass
    for record in records:
        model = KernelModel(
            name=name or record.name, grid=record.grid,
            num_inputs=record.num_inputs, num_outputs=record.num_outputs,
            block_shapes=[record.block_shape(i)
                          for i in range(len(record.block_mappings))],
            block_dep_axes=[_index_map_dep_axes(bm)
                            for bm in record.block_mappings],
            sites=[], writes=[], init_guards={},
            source_file=src_file, source_line=src_line)
        interp = _Interpreter(record, model)
        nio = record.num_index_operands
        refs = record.jaxpr.invars[nio:]
        in_vals: list = []
        for var in record.jaxpr.invars[:nio]:
            shape, dtype = _avals(var)
            in_vals.append(SymVal(sym.Opaque(shape=shape, dtype=dtype,
                                             reason="index operand")))
        for i, var in enumerate(refs):
            in_vals.append(RefVal(ref=i, name=str(var)))
        interp.run(record.jaxpr, record.consts, in_vals)
        model.record = record    # analysis needs block fetch + grid
        models.append(model)
    return models
