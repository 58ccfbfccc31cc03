"""Mean recall@10 of every answer of the window against the exact
filtered top-10 of the plain reference (computed by the check)."""


def read(run, trace):
    return run.checks["recall_at_10"]["value"]
