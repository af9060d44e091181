"""Shared bootstrap for multi-process collective test workers.

The reference's collective tests spawn real subprocesses per rank
(test/legacy_test/test_dist_base.py:952); these workers are the same
pattern on the CPU debug backend. Workers pin the platform to the CPU
BEFORE touching any backend, then init the distributed runtime through
the normal paddle_tpu entry point.
"""
import os


def bootstrap():
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from paddle_tpu.distributed import env

    env.init_parallel_env()
    return int(os.environ["PADDLE_TRAINER_ID"]), \
        int(os.environ["PADDLE_TRAINERS_NUM"])
