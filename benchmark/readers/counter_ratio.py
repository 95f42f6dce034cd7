"""A ratio of two role counters' growth over the window, times `scale`.
`role` is "resolver" (the core's RESOLVER_METRICS) or "storage" (summed over
the storage processes' STORAGE_METRICS); `per_second` divides by the window
instead of by a counter."""


def _delta(ctx: dict, role: str, names: list[str]) -> float | None:
    pairs = [ctx["resolver"]] if role == "resolver" else ctx["storage"]
    total = 0.0
    for before, after in pairs:
        for name in names:
            if name not in before or name not in after:
                return None
            total += after[name] - before[name]
    return total


def read(ctx: dict, role: str, numerator: list[str],
         denominator: list[str] | None = None, scale: float = 1.0,
         per_second: bool = False) -> float | None:
    num = _delta(ctx, role, numerator)
    if num is None:
        return None
    den = ctx["seconds"] if per_second else _delta(ctx, role, denominator)
    if not den:
        return None
    return scale * num / den
