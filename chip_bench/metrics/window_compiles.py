"""XLA executables compiled or loaded from the persistent cache inside
the window (a ``jax.monitoring`` listener); steady state reads 0."""


def read(m):
    return m.compiles
