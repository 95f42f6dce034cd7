"""The key-partitioned step program's device time under the scopes it has
beside `conflict_step`'s six: `clip` (the batch's ranges cut to the shard)
and `combine` (the min of the verdicts and the other collectives over the
resolver axis), as `parallel/sharded_conflict.py` names them and writes them
into its scope map. `step_phases.phases` does the work; its own list of
scopes is fixed, so this reader hands it the longer one.

A program without these scopes (the one-chip step, a parent of the PR that
added them) reads None: nothing ran under them.
"""

from readers import step_phases

SCOPES = step_phases.SCOPES + ("clip", "combine")


def read(ctx: dict, programs: list[str], scopes: list[str]) -> float | None:
    """ms an execution under `scopes` (summed), on the first device."""
    got = step_phases.phases(ctx, programs, scopes=SCOPES)
    if got is None:
        return None
    seconds = sum(got[s] for s in scopes)
    return 1e3 * seconds / got["count"] if seconds > 0 else None
