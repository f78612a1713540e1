"""recall@k of the answers the timed path gave to the compared queries,
against the plain reference's exact neighbours."""


def read(ctx):
    return ctx["recall"] if ctx["n_answers"] else None
