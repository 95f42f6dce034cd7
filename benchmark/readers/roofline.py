"""A program's share of its roofline: the least time the chip could take for
the bytes `kernel_cost.py` counts from the configuration's shapes, at the HBM
peak `peaks.json` gives for the device kind, over the program's measured
device time. An unknown device kind is an error, never a default."""

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def peak_bytes_per_s(device_kind: str) -> float:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return float(peaks[device_kind]["hbm_bytes_per_s"])


def read(ctx: dict, programs: list[str], cost: str) -> float | None:
    import kernel_cost  # run.py puts the benchmark's directory on the path
    from readers import xplane
    step_ms = xplane.read(ctx, programs=programs)
    if step_ms is None:
        return None
    shapes_of, bytes_of = kernel_cost.COSTS[cost]
    least_s = bytes_of(**shapes_of(ctx["config"])) / peak_bytes_per_s(
        ctx["device"]["kind"])
    return 100.0 * least_s / (step_ms / 1e3)
