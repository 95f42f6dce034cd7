"""A share over the transactions that finished in the window, from the
workers' raw rows: sum of one column over sum of another, among rows whose
`where` column is 1."""


def read(ctx: dict, numerator: str, denominator: str, where: str | None = None,
         scale: float = 100.0) -> float | None:
    rows = ctx["rows"]
    if where:
        rows = rows[rows[where] == 1]
    den = float(rows[denominator].sum())
    if den <= 0:
        return None
    return scale * float(rows[numerator].sum()) / den
