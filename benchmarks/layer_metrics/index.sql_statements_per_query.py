"""SQL statements run per index query of the window that the store's
answer cache did not answer (`/debug` cache.mas_sql: statements over
queries; the read of the generation counts as one).  2 where every
query reads the generation and selects its candidates by a statement, 0
where a store in memory answers from its generation's arrays.  None from
a program whose `/debug` has no `mas_sql`, and from a window without
such a query."""

from benchmarks.ctx import dig


def read(ctx):
    if dig(ctx.debug1, "cache.mas_sql", None) is None:
        return None
    return ctx.ratio(["cache.mas_sql.statements"], ["cache.mas_sql.queries"])
