"""Readings taken from the system under test: its resolved plan, the
kernels its dispatch ran, and its compile counters."""
from __future__ import annotations


def plan_record(plan) -> dict:
    c = plan.config
    return {"strategy": c.strategy, "backend": c.backend,
            "layout": c.layout, "block_n": c.block_n, "block_t": c.block_t,
            "total_traces": plan.stats["total_traces"]}


def impls() -> dict:
    from repro.kernels import registry

    return registry.dispatched_impls()

