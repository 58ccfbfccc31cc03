"""SearchStats.filter_checks per answered query: the bitmap probes the
paper counts."""
from fvsbench import readers


def read(run, trace):
    return readers.counter_per_query(run, "filter_checks")
