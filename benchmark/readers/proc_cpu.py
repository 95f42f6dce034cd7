"""CPU seconds the client worker processes used inside the window
(`os.times` at its open and close, taken by each worker itself), as a share
of window x workers: 100% is every worker holding a core throughout."""


def read(ctx: dict) -> float | None:
    used = wall = 0.0
    for w in ctx["workers"]:
        cpu = w.get("cpu") or {}
        if "open" not in cpu or "close" not in cpu:
            return None
        used += cpu["close"][1] - cpu["open"][1]
        wall += cpu["close"][0] - cpu["open"][0]
    return 100.0 * used / wall if wall > 0 else None
