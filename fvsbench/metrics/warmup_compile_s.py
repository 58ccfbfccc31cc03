"""Seconds of set-up in JAX tracing, lowering, compiling or loading from
the compilation cache (the program recorder's compile events before the
window, their intervals' union)."""
from fvsbench import spans


def read(run, trace):
    return spans.setup_compile_seconds(run)
