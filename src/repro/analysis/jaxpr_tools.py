"""Jaxpr walking utilities shared by the lint passes.

Everything here works on `jax.make_jaxpr` output — pure abstract
traces, nothing is compiled or executed.  Two structural facts the
walkers rely on (pinned by tests/test_analysis.py so a jax upgrade
that changes them fails loudly):

  * higher-order eqns (pjit, scan, while, cond, pallas_call) carry
    their body as a Jaxpr/ClosedJaxpr somewhere in `eqn.params` —
    possibly nested inside tuples/lists — so generic recursion over
    params values finds every sub-jaxpr without a per-primitive table;
  * `pallas_call` body invars have MemRef avals whose `.inner_aval`
    holds the real ShapedArray; their shapes are the BLOCK shapes the
    grid spec carved out, which is exactly what a VMEM audit needs.
"""
from __future__ import annotations

from typing import Any, Iterator, Optional

import jax
import numpy as np
from jax._src import core as jax_core


# --------------------------------------------------------------------------
# Avals
# --------------------------------------------------------------------------
def unwrap_aval(aval: Any) -> Any:
    """MemRef avals (pallas kernel refs) wrap the payload ShapedArray
    in `.inner_aval`; everything else passes through."""
    return getattr(aval, "inner_aval", aval)


def aval_bytes(aval: Any) -> int:
    """Buffer size in bytes, 0 for avals without shape/dtype (tokens)."""
    aval = unwrap_aval(aval)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def vmem_aval_bytes(aval: Any) -> int:
    """VMEM bytes of one buffer: `aval_bytes` padded to whole
    (sublane, lane) tiles, the way Mosaic allocates it (see
    `kernels.tuning.tile_bytes`)."""
    from repro.kernels import tuning
    aval = unwrap_aval(aval)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return tuning.tile_bytes(shape, np.dtype(dtype).itemsize)


def aval_short(aval: Any) -> str:
    aval = unwrap_aval(aval)
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    return f"{np.dtype(dtype).name if dtype is not None else '?'}" \
           f"[{','.join(str(d) for d in shape)}]"


# --------------------------------------------------------------------------
# Sub-jaxpr recursion
# --------------------------------------------------------------------------
def _params_jaxprs(value: Any) -> Iterator[jax_core.Jaxpr]:
    """Yield every Jaxpr reachable from one params value."""
    if isinstance(value, jax_core.Jaxpr):
        yield value
    elif isinstance(value, jax_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _params_jaxprs(item)


def eqn_subjaxprs(eqn: jax_core.JaxprEqn) -> list[jax_core.Jaxpr]:
    """Sub-jaxprs carried by one equation (pjit/scan/while/cond bodies,
    pallas_call kernel bodies, ...)."""
    out: list[jax_core.Jaxpr] = []
    for value in eqn.params.values():
        out.extend(_params_jaxprs(value))
    return out


def iter_jaxprs(jaxpr: jax_core.Jaxpr) -> Iterator[jax_core.Jaxpr]:
    """The jaxpr and every nested sub-jaxpr, outermost first."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in eqn_subjaxprs(eqn):
            yield from iter_jaxprs(sub)


def find_pallas_calls(
        jaxpr: jax_core.Jaxpr) -> list[jax_core.JaxprEqn]:
    """Every pallas_call equation anywhere in the trace."""
    return [eqn for j in iter_jaxprs(jaxpr) for eqn in j.eqns
            if eqn.primitive.name == "pallas_call"]


def pallas_kernel_jaxpr(eqn: jax_core.JaxprEqn) -> jax_core.Jaxpr:
    """The kernel-body jaxpr of a pallas_call eqn (invars are refs with
    BLOCK-shaped inner avals)."""
    body = eqn.params.get("jaxpr")
    if isinstance(body, jax_core.ClosedJaxpr):
        body = body.jaxpr
    if not isinstance(body, jax_core.Jaxpr):
        raise TypeError("pallas_call eqn carries no kernel jaxpr "
                        f"(params keys: {sorted(eqn.params)})")
    return body


def pallas_ref_avals(eqn: jax_core.JaxprEqn) -> list[Any]:
    """Unwrapped (ShapedArray) avals of the kernel body's refs, in
    invar order — inputs, then outputs, then scratch."""
    return [unwrap_aval(v.aval) for v in pallas_kernel_jaxpr(eqn).invars]


# --------------------------------------------------------------------------
# Dataflow within one (sub)jaxpr scope
# --------------------------------------------------------------------------
def consumers_map(
        jaxpr: jax_core.Jaxpr
) -> dict[jax_core.Var, list[jax_core.JaxprEqn]]:
    """var -> equations (in this scope only) that read it."""
    out: dict[jax_core.Var, list[jax_core.JaxprEqn]] = {}
    for eqn in jaxpr.eqns:
        for invar in eqn.invars:
            if isinstance(invar, jax_core.Var):
                out.setdefault(invar, []).append(eqn)
    return out


# Ops that merely move/reshape data: a value flowing through them keeps
# its identity for the terminal-consumer walk.  A further dtype
# conversion keeps it too: a widened panel converted again (uint8 ->
# int32 -> bfloat16, the route to the MXU where no direct convert
# exists) is still that panel, and what consumes it decides the verdict.
LAYOUT_PRESERVING = frozenset({
    "transpose", "reshape", "broadcast_in_dim", "squeeze", "slice",
    "rev", "copy", "dynamic_slice", "convert_element_type",
})

# Call-like primitives whose body invars map 1:1 onto the eqn invars,
# so the walk can descend (jnp helpers like `take`/`einsum` wrap their
# gather/dot in a named pjit — a widened panel must be followed inside
# or the lint would stop at the wrapper).  Loop/branch primitives
# (scan, while, cond) interleave carries/consts and stay boundaries.
_CALL_PRIMS = frozenset({"jit", "pjit", "closed_call", "core_call",
                         "custom_jvp_call", "custom_vjp_call"})

# The jit call primitive: named "jit" since JAX 0.7, "pjit" before.
JIT_PRIMS = frozenset({"jit", "pjit"})


def _call_body(eqn: jax_core.JaxprEqn) -> Optional[jax_core.Jaxpr]:
    for key in ("jaxpr", "call_jaxpr"):
        j = eqn.params.get(key)
        if isinstance(j, jax_core.ClosedJaxpr):
            return j.jaxpr
        if isinstance(j, jax_core.Jaxpr):
            return j
    return None


def terminal_consumers(
        jaxpr: jax_core.Jaxpr,
        start: jax_core.Var,
        consumers: Optional[dict] = None,
) -> list[tuple[jax_core.JaxprEqn, jax_core.Var]]:
    """(eqn, var) pairs that *use* (not merely move) the value in
    `start` — `var` is the alias of `start` the eqn actually reads, so
    callers can check which operand position it feeds.

    Follows outputs of LAYOUT_PRESERVING eqns transitively, and
    descends into call-like sub-jaxprs (pjit etc.) by operand
    position.  Loop/branch eqns (scan, while, pallas_call) are
    boundaries: returned as terminals for the caller to classify.  A
    value that escapes via a scope's outvars is simply not reported
    (the enclosing scope sees the producing eqn)."""
    cmaps: dict[int, dict] = {
        id(jaxpr): consumers if consumers is not None
        else consumers_map(jaxpr)}

    def cmap(scope):
        m = cmaps.get(id(scope))
        if m is None:
            m = consumers_map(scope)
            cmaps[id(scope)] = m
        return m

    out: list[tuple[jax_core.JaxprEqn, jax_core.Var]] = []
    seen: set[tuple[int, int]] = set()
    stack = [(jaxpr, start)]
    while stack:
        scope, var = stack.pop()
        for eqn in cmap(scope).get(var, ()):
            if (id(eqn), id(var)) in seen:
                continue
            seen.add((id(eqn), id(var)))
            subs = eqn_subjaxprs(eqn)
            name = eqn.primitive.name
            if not subs and name in LAYOUT_PRESERVING:
                stack.extend((scope, v) for v in eqn.outvars
                             if isinstance(v, jax_core.Var))
            elif subs and name in _CALL_PRIMS:
                body = _call_body(eqn)
                if body is not None \
                        and len(body.invars) == len(eqn.invars):
                    stack.extend((body, body.invars[pos])
                                 for pos, iv in enumerate(eqn.invars)
                                 if iv is var)
                else:  # unexpected arity: keep it visible as terminal
                    out.append((eqn, var))
            else:
                out.append((eqn, var))
    return out


# --------------------------------------------------------------------------
# Liveness / peak-working-set estimate
# --------------------------------------------------------------------------
# Primitives whose output is never a fresh buffer: a pallas `get`
# reads a VMEM-resident ref block (the ref itself is counted by the
# audit), and XLA fuses/rematerializes iota and broadcasts into their
# consumers rather than materializing them.
_UNCHARGED = frozenset({"get", "iota", "broadcast_in_dim"})


def peak_live_bytes(jaxpr: jax_core.Jaxpr,
                    include_invars: bool = True, *,
                    size=aval_bytes) -> int:
    """Upper-bound estimate of the scope's peak live buffer bytes.

    Walks eqns in order; an eqn's outputs are allocated when it runs,
    its inputs are released after their last use — for a leaf eqn the
    dying inputs release *before* the output allocates (XLA donates
    elementwise operands in place), for an eqn carrying sub-jaxprs
    (scan/pjit bodies stay live while the body runs) they release
    after.  Sub-jaxpr scopes contribute their own peak as a transient
    on top of the enclosing live set.  Pessimistic for XLA fusion
    (which may never materialize intermediates) but honest as a "what
    could be resident at once" bound, which is what the VMEM audit
    compares against the tuning footprint models.  Ref loads (`get`),
    iota/broadcast values and dead outputs (`swap`'s discarded old
    value) are not charged.  `size` prices one aval (`vmem_aval_bytes`
    for the tile-padded VMEM view)."""
    last_use: dict[jax_core.Var, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, jax_core.Var):
                last_use[v] = i
    n_eqns = len(jaxpr.eqns)
    for v in jaxpr.outvars:
        if isinstance(v, jax_core.Var):
            last_use[v] = n_eqns  # escapes: live to the end

    # A value read only as a gather's index operand is an index
    # encoding (jnp.take_along_axis appends a unit dim that the kernel
    # lowering shape-casts away), never a resident buffer of its own.
    index_only: set = set()
    readers: dict[jax_core.Var, list] = {}
    for eqn in jaxpr.eqns:
        for pos, v in enumerate(eqn.invars):
            if isinstance(v, jax_core.Var):
                readers.setdefault(v, []).append((eqn.primitive.name, pos))
    for v, uses in readers.items():
        if all(name == "gather" and pos > 0 for name, pos in uses):
            index_only.add(v)

    def out_bytes(v, eqn) -> int:
        if eqn.primitive.name in _UNCHARGED or v not in last_use \
                or v in index_only:
            return 0
        return size(v.aval)

    alloc_by: dict[jax_core.Var, jax_core.JaxprEqn] = {}

    def release(v) -> int:
        src = alloc_by.get(v)
        if src is not None:
            return out_bytes(v, src)
        return size(v.aval) if include_invars else 0

    live = 0
    if include_invars:
        roots = list(jaxpr.invars) + list(jaxpr.constvars)
        live += sum(size(v.aval) for v in roots)
    peak = live
    for i, eqn in enumerate(jaxpr.eqns):
        subs = eqn_subjaxprs(eqn)
        dying = [v for v in eqn.invars
                 if isinstance(v, jax_core.Var) and last_use.get(v) == i]
        if not subs:
            live -= sum(release(v) for v in dying)
        for v in eqn.outvars:
            if isinstance(v, jax_core.Var):
                alloc_by[v] = eqn
                live += out_bytes(v, eqn)
        transient = 0
        for sub in subs:
            # Sub-scope invars alias buffers already counted live here,
            # so only its *interior* growth is a transient.
            transient = max(transient,
                            peak_live_bytes(sub, include_invars=False,
                                            size=size))
        peak = max(peak, live + transient)
        if subs:
            live -= sum(release(v) for v in dying)
    return peak


# --------------------------------------------------------------------------
# Tracing
# --------------------------------------------------------------------------
def trace_abstract(fn: Any, *avals: Any, **kwargs: Any):
    """`jax.make_jaxpr` over ShapeDtypeStructs: trace without running.

    Returns the ClosedJaxpr.  kwargs are static (baked into the trace),
    matching how the registry impls take n_bins/n_leaves etc."""
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*avals)
