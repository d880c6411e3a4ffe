"""Pluggable kernel registry: named implementations per op.

The paper's evaluator picks one vectorized implementation per hotspot at
startup (scalar / SSE / RVV dispatch in CatBoost's `EvaluatorImpl`);
the ROADMAP's multi-backend north star needs the same architecture
here instead of `backend="auto"|"ref"|"pallas"` string kwargs threaded
through every call site.  Each op registers named implementations with
capability metadata:

  op            one of: binarize, leaf_index, leaf_gather, l2sq,
                fused_predict, histogram
  impl name     "ref" (pure jnp oracle), "pallas" (TPU kernel,
                interpret mode off-TPU), and dtype-specialized variants
                such as "pallas_u8" / "ref_u8" (uint8 bin stream — the
                paper's actual representation)
  dtypes        bin-stream dtypes the implementation produces/consumes
  platforms     where the implementation is production-fit (everything
                runs everywhere; interpret-mode Pallas off-TPU is a
                correctness tool, not a fast path)
  constraints   human-readable shape/dtype constraints for docs

`kernels.ops` registers every implementation at import time and its
public wrappers dispatch through `resolve()`/`dispatch()`; the old
`backend=` kwargs are thin shims over the same lookup.  `table()` makes
the whole dispatch surface introspectable for benchmarks and docs.

Call accounting: `dispatch` ticks a per-op counter.  Like
`ops.pad_stats`, the counter ticks when the dispatch code *runs* — once
per XLA trace for jitted callers, once per call for eager ones — so
"zero binarize dispatches while scoring a quantized pool" is a
checkable invariant (tests/test_quantized.py pins it).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

# The six kernel ops every backend family must cover (histogram is the
# training-side op; the other five serve prediction).
CORE_OPS = ("binarize", "leaf_index", "leaf_gather", "l2sq",
            "fused_predict", "histogram")


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """One registered implementation of one op."""
    op: str
    name: str                          # registry key, e.g. "pallas_u8"
    fn: Callable[..., Any]
    family: str                        # legacy backend family: ref | pallas
    dtypes: tuple[str, ...]            # bin-stream dtypes it handles
    platforms: tuple[str, ...]         # production-fit platforms
    layouts: tuple[str, ...]           # physical model layouts it consumes
    constraints: str                   # human-readable constraint note
    # Declared contract-checker exceptions, "<rule>: <reason>" each (see
    # repro.analysis / docs/analysis.md).  An intentional deviation from
    # a lint rule is suppressed HERE, next to the capability claims it
    # qualifies — never silently inside the checker.  The checker flags
    # suppressions that no longer match any finding, so stale entries
    # cannot linger.
    suppressions: tuple[str, ...] = ()


_REGISTRY: dict[str, dict[str, KernelImpl]] = {}
_CALL_STATS: dict[str, int] = {}
_IMPLS_RUN: dict[str, set[str]] = {}


@functools.cache
def _platform() -> str:
    """Process platform, resolved once (mirrors `ops.default_platform`;
    duplicated here so the registry stays importable without ops)."""
    import jax
    return jax.default_backend()


def register(op: str, name: str, *, family: Optional[str] = None,
             dtypes: tuple[str, ...] = ("int32",),
             platforms: tuple[str, ...] = ("cpu", "tpu"),
             layouts: tuple[str, ...] = ("soa",),
             constraints: str = "",
             suppressions: tuple[str, ...] = ()) -> Callable:
    """Decorator: register `fn` as implementation `name` of `op`.

    `layouts` names the physical model layouts (see `repro.core.layout`)
    whose arrays the implementation consumes: "soa" kernels take the
    (T, D) split arrays, "depth_major" kernels take the lowered
    (T, D, F) one-hot / (D, T) bit-plane arrays.  The "depth_grouped"
    layout evaluates group-by-group through soa kernels, so soa impls
    typically claim it too.  Ops whose inputs carry no model structure
    (binarize, l2sq) are layout-independent and claim every layout.

    Returns the function unchanged, so module-level names keep working.
    Registering the same (op, name) twice is an error — it would
    silently shadow a live implementation.
    """
    def deco(fn: Callable) -> Callable:
        impls = _REGISTRY.setdefault(op, {})
        if name in impls:
            raise ValueError(f"kernel impl {op}:{name} already registered")
        impls[name] = KernelImpl(
            op=op, name=name, fn=fn,
            family=family or ("pallas" if name.startswith("pallas")
                              else "ref"),
            dtypes=tuple(dtypes), platforms=tuple(platforms),
            layouts=tuple(layouts), constraints=constraints,
            suppressions=tuple(suppressions))
        return fn
    return deco


def unregister(op: str, name: str) -> None:
    """Remove a registered implementation.

    For test fixtures only: lets a deliberately-broken toy impl be
    registered against the contract checker and cleaned up without
    leaking into later tests.  Unknown (op, name) raises KeyError."""
    impls = _REGISTRY.get(op)
    if impls is None or name not in impls:
        raise KeyError(f"kernel impl {op}:{name} not registered")
    del impls[name]
    if not impls:
        del _REGISTRY[op]


def ops() -> list[str]:
    """Registered op names, sorted."""
    return sorted(_REGISTRY)


def implementations(op: str) -> dict[str, KernelImpl]:
    if op not in _REGISTRY:
        raise KeyError(f"unknown kernel op {op!r}; registered: {ops()}")
    return dict(_REGISTRY[op])


def get(op: str, name: str) -> KernelImpl:
    impls = implementations(op)
    if name not in impls:
        raise KeyError(f"op {op!r} has no implementation {name!r}; "
                       f"available: {sorted(impls)}")
    return impls[name]


def has(op: str, name: str) -> bool:
    return name in _REGISTRY.get(op, {})


def default_backend() -> str:
    """The `auto` resolution: pallas on TPU, the jnp reference
    elsewhere (interpret-mode Pallas is a correctness tool, far too
    slow for CPU production use)."""
    return "pallas" if _platform() == "tpu" else "ref"


def known_backends() -> tuple[str, ...]:
    """Backend names valid as a `PredictConfig.backend` / legacy
    `backend=` value: implementation names registered for *every* core
    op (dtype-specialized variants like `pallas_u8` exist only where
    the dtype matters, so they are per-op names, not backends)."""
    names: Optional[set] = None
    for op in CORE_OPS:
        impls = set(_REGISTRY.get(op, {}))
        names = impls if names is None else names & impls
    return tuple(sorted(names or ()))


# Layout -> implementation-name suffix tried when the backend's base
# implementation does not consume that layout's arrays.
_LAYOUT_SUFFIX = {"depth_major": "dm", "bitpacked": "bp"}


def resolve(op: str, backend: str = "auto", *,
            dtype: Optional[str] = None,
            layout: Optional[str] = None) -> str:
    """Map a legacy `backend=` value (or an exact impl name) to the
    implementation name to run.

    `auto` resolves via `default_backend()`.  When `layout` is given
    and the resolved implementation does not consume that layout's
    arrays, the layout-suffixed sibling (`<name>_dm` for depth_major)
    is tried first — layout determines the *signature*, so it routes
    before dtype.  When `dtype` is given and the implementation does
    not handle it, the dtype-suffixed sibling (`<name>_u8` for uint8)
    is tried before raising.
    """
    name = default_backend() if backend == "auto" else backend
    impls = implementations(op)
    if name not in impls:
        raise KeyError(f"op {op!r} has no implementation {name!r}; "
                       f"available: {sorted(impls)} (legacy backends: "
                       f"{known_backends()} or 'auto')")
    if layout is not None and layout not in impls[name].layouts:
        suffix = _LAYOUT_SUFFIX.get(layout)
        alt = f"{name}_{suffix}" if suffix else None
        if alt is not None and alt in impls \
                and layout in impls[alt].layouts:
            name = alt
        else:
            raise ValueError(
                f"op {op!r} implementation {name!r} does not consume "
                f"layout {layout!r} (consumes {impls[name].layouts}); "
                f"no {layout}-capable variant registered")
    if dtype is not None and dtype not in impls[name].dtypes:
        alt = f"{name}_u8" if dtype == "uint8" else None
        if alt is not None and alt in impls:
            return alt
        raise ValueError(
            f"op {op!r} implementation {name!r} does not handle "
            f"dtype {dtype!r} (handles {impls[name].dtypes}); no "
            f"{dtype}-capable variant registered")
    return name


def dispatch(op: str, backend: str, *args: Any,
             dtype: Optional[str] = None,
             layout: Optional[str] = None, **kw: Any) -> Any:
    """Resolve and call: the single entry every `kernels.ops` public
    wrapper (and its legacy `backend=` shim) funnels through.

    It runs while a plan entry is being traced, not per call, so it
    records no span: `compile/<entry>` instants mark compiles, and each
    kernel's device time is its named op in a profiler trace."""
    impl = get(op, resolve(op, backend, dtype=dtype, layout=layout))
    _CALL_STATS[op] = _CALL_STATS.get(op, 0) + 1
    _IMPLS_RUN.setdefault(op, set()).add(impl.name)
    return impl.fn(*args, **kw)


def impls_for_layout(op: str, layout: str) -> list[str]:
    """Implementation names of `op` that consume `layout`'s arrays
    (the CI capability smoke asserts this is non-empty for every op a
    layout claims)."""
    return sorted(name for name, impl in implementations(op).items()
                  if layout in impl.layouts)


# --------------------------------------------------------------------------
# Accounting + introspection
# --------------------------------------------------------------------------
def call_stats() -> dict[str, int]:
    """Per-op dispatch counts (ticks once per trace under jit — see the
    module docstring)."""
    return dict(_CALL_STATS)


def dispatched_impls() -> dict[str, list[str]]:
    """Per op, the implementation names dispatch has resolved to since
    the last reset — which kernel actually ran for each op."""
    return {op: sorted(names) for op, names in sorted(_IMPLS_RUN.items())}


def reset_call_stats() -> None:
    _CALL_STATS.clear()
    _IMPLS_RUN.clear()


def table() -> list[dict[str, str]]:
    """One row per (op, implementation): the introspection surface for
    docs and benchmarks.  Rows are plain dicts, sorted by (op, name)."""
    rows = []
    for op in ops():
        for name in sorted(_REGISTRY[op]):
            impl = _REGISTRY[op][name]
            rows.append({
                "op": op,
                "impl": name,
                "family": impl.family,
                "dtypes": "/".join(impl.dtypes),
                "platforms": "/".join(impl.platforms),
                "layouts": "/".join(impl.layouts),
                "constraints": impl.constraints,
                "suppressions": " ; ".join(impl.suppressions),
            })
    return rows


def load_verified() -> dict[str, str]:
    """Per-implementation verdicts ("op:impl" -> "ok"/"FAIL"/...) from
    the contract checker's last committed report
    (results/analysis/contract-report.json).  Missing or unreadable
    report -> {} (the verified column renders "-")."""
    import json
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[3]
            / "results" / "analysis" / "contract-report.json")
    try:
        with open(path, encoding="utf-8") as f:
            verified = json.load(f).get("verified", {})
    except (OSError, ValueError):
        return {}
    return {str(k): str(v) for k, v in verified.items()}


def format_table(verified: Optional[dict[str, str]] = None) -> str:
    """`table()` rendered as a markdown table (docs/api.md embeds the
    output of this function; `launch.serve --show-kernels` prints it).

    The `verified` column carries the contract checker's per-impl
    verdict (`repro.launch.analyze`); by default it is sourced from the
    checker's last committed report via `load_verified()`.  Pass a dict
    to override, or `{}` to render the column blank.

    The `dispatch_count` column is this process's `call_stats()` total
    for the row's op (counts are per-op — the registry ticks before
    impl resolution is observable per-call)."""
    if verified is None:
        verified = load_verified()
    stats = call_stats()
    rows = table()
    for r in rows:
        r["verified"] = verified.get(f"{r['op']}:{r['impl']}", "-")
        r["dispatch_count"] = str(stats.get(r["op"], 0))
    cols = ("op", "impl", "family", "dtypes", "platforms", "layouts",
            "verified", "dispatch_count", "constraints")
    widths = {c: max(len(c), *(len(r[c]) for r in rows)) for c in cols}
    def line(vals):
        return "| " + " | ".join(v.ljust(widths[c])
                                 for c, v in zip(cols, vals)) + " |"
    out = [line(cols),
           "|" + "|".join("-" * (widths[c] + 2) for c in cols) + "|"]
    out += [line([r[c] for c in cols]) for r in rows]
    return "\n".join(out)
