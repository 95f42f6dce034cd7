"""A storage server with one guarantee broken underneath, by the word in
BENCH_TEST_FAULT: `drop_write` leaves one acknowledged set in 97 out of the
store (durability); `alter_read` answers one point read in 50 with altered
bytes (an answer altered where it is produced). run.py has to say
`correct: false` for either.

    python storage_faulty.py '<spec json>'
"""

import os
import sys


class Faulty:
    """The versioned store, with the fault in front of it."""

    def __init__(self, store, fault: str):
        self._store, self._fault, self._n = store, fault, 0

    def __getattr__(self, name):
        if name == "get_batch_encoded":  # the C store would answer itself
            raise AttributeError(name)
        return getattr(self._store, name)

    def get_batch(self, reads):
        return [(code, self._answer(value) if code == 0 else value)
                for code, value in self._store.get_batch(reads)]

    def apply(self, version, m):
        from foundationdb_tpu.utils.types import MutationType
        if self._fault == "drop_write" and m.type == MutationType.SET_VALUE:
            self._n += 1
            if self._n % 97 == 0:
                return None
        return self._store.apply(version, m)

    def get(self, key, version):
        return self._answer(self._store.get(key, version))

    def _answer(self, value):
        if self._fault == "alter_read" and value:
            self._n += 1
            if self._n % 50 == 0:
                return bytes([value[0] ^ 1]) + value[1:]
        return value


def main(argv: list[str]) -> None:
    from foundationdb_tpu.net import server_main
    from foundationdb_tpu.server import storage
    fault = os.environ["BENCH_TEST_FAULT"]
    if fault not in ("drop_write", "alter_read"):
        raise SystemExit(f"unknown fault {fault!r}")
    make = storage.make_versioned_map
    storage.make_versioned_map = lambda *a, **k: Faulty(make(*a, **k), fault)
    server_main.main(argv[1])


if __name__ == "__main__":
    main(sys.argv)
