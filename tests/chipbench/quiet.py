"""How tier-1 starts a rehearsed run: few threads, low priority."""

import os


def quiet_env(**extra):
    """The environment of a rehearsed run inside tier-1: one thread a pool,
    so that a run does not starve the timing-sensitive tests that other
    workers run beside it (``quietly`` lowers its priority as well)."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("CHIPBENCH_REHEARSE", None)
    env.update(JAX_PLATFORMS="cpu", OMP_NUM_THREADS="1",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false "
                         "intra_op_parallelism_threads=1")
    env.update(extra)
    return env


def quietly():
    os.nice(19)
