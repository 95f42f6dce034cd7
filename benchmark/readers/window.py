"""What the window as a whole gives: `rate` is the operations of every
transaction acknowledged inside it over its seconds (all the work over all the
time); `setup` is the seconds from the process's start to the window's open."""


def read(ctx: dict, what: str) -> float | None:
    if what == "rate":
        return ctx["acknowledged_ops"] / ctx["seconds"]
    if what == "setup":
        return ctx["setup_s"]
    raise ValueError(f"unknown reading {what!r}")
