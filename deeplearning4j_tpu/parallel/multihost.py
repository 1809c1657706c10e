"""Multi-host distributed initialization (DCN scale-out).

The reference scales across machines with Akka remoting / Spark / YARN
(SURVEY.md §2.5): host-side serialization of param vectors between JVMs.
The TPU-native equivalent is JAX multi-controller SPMD: every host runs the
same program, `jax.distributed.initialize` wires the PJRT coordination
service, and the SAME jitted train step spans all hosts' devices — XLA
routes intra-slice collectives over ICI and cross-slice traffic over DCN.
No parameter serialization crosses the control plane at all.

Usage on each host (the reference's DeepLearning4jDistributed.setup analogue):

    from deeplearning4j_tpu.parallel import multihost
    multihost.initialize(coordinator="host0:9901",
                         num_processes=4, process_id=rank)
    mesh = multihost.global_mesh(("data",))
    # parallel/trainer.py and ring_attention work unchanged over this mesh
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

_initialized = False


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Wire this process into the multi-host cluster.

    All-None arguments read DL4J_COORDINATOR / DL4J_NUM_PROCESSES /
    DL4J_PROCESS_ID (JAX itself only honors JAX_COORDINATOR_ADDRESS, not a
    process-count env var, so this module parses its own). Safe no-op when
    no coordinator is configured (single-process session).
    """
    global _initialized
    if _initialized:
        return
    if coordinator is None:
        coordinator = os.environ.get(
            "DL4J_COORDINATOR", os.environ.get("JAX_COORDINATOR_ADDRESS")
        )
        if num_processes is None and "DL4J_NUM_PROCESSES" in os.environ:
            num_processes = int(os.environ["DL4J_NUM_PROCESSES"])
        if process_id is None and "DL4J_PROCESS_ID" in os.environ:
            process_id = int(os.environ["DL4J_PROCESS_ID"])
    if coordinator is None:
        # single-process session — nothing to coordinate
        _initialized = True
        return
    if num_processes is None or process_id is None:
        raise ValueError(
            "a coordinator address requires num_processes and process_id "
            "(or DL4J_NUM_PROCESSES / DL4J_PROCESS_ID in the environment)"
        )
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True


def process_info() -> Tuple[int, int]:
    """(process_index, process_count)."""
    return jax.process_index(), jax.process_count()


def global_mesh(axis_names: Sequence[str] = ("data",),
                axis_sizes: Optional[Sequence[int]] = None) -> Mesh:
    """Mesh over ALL devices across every host.

    Default: one data axis spanning everything. With axis_sizes, reshape
    global devices into the named axes (product must equal the global device
    count); put the DCN-crossing axis FIRST so XLA keeps the fast-changing
    axes on ICI.
    """
    devs = np.array(jax.devices())
    if axis_sizes is None:
        if len(axis_names) != 1:
            raise ValueError("axis_sizes required for a multi-axis mesh")
        return Mesh(devs, tuple(axis_names))
    sizes = tuple(axis_sizes)
    if int(np.prod(sizes)) != devs.size:
        raise ValueError(
            f"axis sizes {sizes} do not cover {devs.size} devices"
        )
    return Mesh(devs.reshape(sizes), tuple(axis_names))


def is_coordinator() -> bool:
    """True on exactly one process — gate host-side side effects
    (checkpoint writes, UI server, logging) the way the reference gated
    master-only work on the MasterActor role."""
    return jax.process_index() == 0
