"""XLA programs compiled inside the measured window, as the program's
``jax.monitoring`` listener counted them (``xla_compiles``; a load from the
persistent cache is ``xla_cache_loads`` and does not count). Warm-up is
there so that this reads 0."""


def read(window, counters, trace):
    n = counters.get("xla_compiles")
    return None if n is None else float(n)
