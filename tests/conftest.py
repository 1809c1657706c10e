"""Test configuration: force an 8-device CPU platform so multi-chip sharding
paths are exercised without TPU hardware (the strategy SURVEY.md §4 calls for:
in-process fakes, like the reference's embedded-Hazelcast / Spark local[8]
harnesses).

Both settings go through jax.config before first backend use, so the suite
runs on the CPU whatever ``JAX_PLATFORMS`` says. The chip is reached only
through ``chip_smoke.py`` (README "Running").
"""

import os

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# The CLI mains place a persistent compile cache under the checkout
# (utils/compile_cache.py) and tests call them in-process and as child
# processes: tier-1 neither writes that cache nor depends on one.
jax.config.update("jax_enable_compilation_cache", False)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


@pytest.fixture
def lockwatch():
    """The utils.lockwatch runtime lock-order watchdog, armed for the
    test: every lock created through the seam (DecodeEngine scheduler,
    AsyncCheckpointer error lock, tracker client/state, registry, tracer,
    profile store/sampler) becomes a watched primitive — acquisition
    order feeds the cycle detector (raise armed: an order inversion fails
    the test at the acquire, not as a hang), wait/hold land in
    ``lockwatch_*`` registry metrics, and an acquire blocked past the
    watchdog threshold dumps all thread stacks through the flight
    recorder. Yields the module; ``lockwatch.summary()`` for assertions."""
    from deeplearning4j_tpu.utils import lockwatch as lw

    lw.reset()
    lw.enable(raise_on_cycle=True, watchdog_s=20.0)
    try:
        yield lw
    finally:
        lw.disable()
        lw.reset()


@pytest.fixture
def retrace_budget():
    """The utils.retrace_guard context manager as a fixture: pin a region's
    XLA compile budget with ``with retrace_budget(0, label="..."): ...`` —
    any retrace beyond the budget fails the test (shape/weak-type drift
    can never silently recompile a warmed step per call again)."""
    from deeplearning4j_tpu.utils.retrace_guard import retrace_guard

    return retrace_guard
