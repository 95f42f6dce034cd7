"""A core server whose resolver's verdicts are altered where they are
produced: every `not_committed` becomes `committed`. Isolation is broken
underneath an otherwise whole run; run.py has to say `correct: false`.

    python core_commits_everything.py '<spec json>' [--spans]
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, BENCH)


def main(argv: list[str]) -> None:
    from foundationdb_tpu.ops.batch import COMMITTED, CONFLICT
    from foundationdb_tpu.server.resolver import Resolver

    import core_main
    finish = Resolver._finish_batch

    def finish_all_committed(self, req, reply, statuses):
        finish(self, req, reply, [COMMITTED if s == CONFLICT else s
                                  for s in statuses])

    Resolver._finish_batch = finish_all_committed
    core_main.main(argv)


if __name__ == "__main__":
    main(sys.argv)
