"""Per-layer numbers from the daemon's own ``/metrics`` and the load
generator's counts: ``scale * sum(num) / sum(den)`` over the window.

A term is ``{"prom": sample}`` (the sample's value after the window
minus before; a name without labels sums every label set of it),
``{"client": name}`` (a count or clock of the load generator) or
``{"const": x}``. A sample that /metrics does not carry, or a
denominator of 0, means there is nothing to read: None, never 0.
"""

from __future__ import annotations


def _delta(name: str, before: dict, after: dict):
    keys = [k for k in after if k == name or k.startswith(name + "{")]
    if not keys:
        return None
    return sum(after[k] - before.get(k, 0.0) for k in keys)


def _total(terms, ctx):
    total = 0.0
    for t in terms:
        if "prom" in t:
            v = _delta(t["prom"], ctx["before"], ctx["after"])
        elif "client" in t:
            v = ctx["client"].get(t["client"])
        else:
            v = t["const"]
        if v is None:
            return None
        total += v
    return total


def read(spec: dict, ctx: dict):
    num = _total(spec["num"], ctx)
    den = _total(spec["den"], ctx) if "den" in spec else 1.0
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den
