"""Seconds of backend compilation or persistent-cache loading inside the
window (jax.monitoring's backend-compile duration events), over the
window.  A steady serving loop compiles nothing: this should read 0."""
from bench.readers import compile_share


def read(r):
    return compile_share(r)
