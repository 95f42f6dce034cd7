"""A percentile of the latencies, in ms, of all transactions of one kind
(`commit`: those that write; `read`: read-only ones) that finished inside the
window: taken once over the merged raw samples of every worker, from the start
of a transaction's first attempt to its acknowledgement, retries included. A
transaction that failed has the latency infinity: it misses every limit."""

import math

NEVER_MS = 1e9  # what is printed where the percentile falls on a failure


def percentile(samples: list[float], q: float) -> float:
    """Nearest rank over the raw samples of all workers together."""
    if not samples:
        raise ValueError("no samples")
    ordered = sorted(samples)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def read(ctx: dict, kind: str, q: float) -> float | None:
    samples = ctx["samples"][kind]
    if not samples:
        return None
    value = percentile(samples, q)
    return value if math.isfinite(value) else NEVER_MS
