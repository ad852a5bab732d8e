"""ParallelEnv + process bootstrap.

Reference parity: `python/paddle/distributed/parallel.py` (`ParallelEnv`,
`init_parallel_env` :915) and the TCPStore rendezvous (:1077).

TPU-native: rank/world come from the reference's env-var contract
(PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_MASTER, set by our launch CLI);
multi-host bring-up delegates to `jax.distributed.initialize`, whose coordination
service replaces TCPStore/gen_comm_id.  Collectives then ride ICI/DCN via XLA.
"""
from __future__ import annotations

import os

import jax


class ParallelEnv:
    def __init__(self):
        self._rank = int(os.getenv("PADDLE_TRAINER_ID", "0"))
        self._world_size = int(os.getenv("PADDLE_TRAINERS_NUM", "1"))
        endpoints = os.getenv("PADDLE_TRAINER_ENDPOINTS", "")
        self._trainer_endpoints = endpoints.split(",") if endpoints else []
        self._current_endpoint = os.getenv("PADDLE_CURRENT_ENDPOINT", "")
        self._device_id = int(os.getenv("FLAGS_selected_tpus",
                                        os.getenv("FLAGS_selected_gpus", "0")))
        self._nrings = int(os.getenv("FLAGS_nccl_nrings", "1"))

    @property
    def rank(self):
        return self._rank

    @property
    def world_size(self):
        return self._world_size

    @property
    def device_id(self):
        return self._device_id

    @property
    def current_endpoint(self):
        return self._current_endpoint

    @property
    def trainer_endpoints(self):
        return self._trainer_endpoints

    @property
    def nrings(self):
        return self._nrings

    # legacy aliases
    local_rank = rank
    nranks = world_size
    dev_id = device_id


_initialized = False


def _is_initialized():
    return _initialized


def init_parallel_env():
    """Bring up the distributed runtime (reference `init_parallel_env` :915).

    Multi-host: jax.distributed.initialize against PADDLE_MASTER (the coordination
    service is the TCPStore analog).  Single-process: no-op — collectives degrade to
    identity, exactly like the reference with nranks==1.
    """
    global _initialized
    if _initialized:
        return ParallelEnv()
    env = ParallelEnv()
    # local-cluster simulation (the reference's TestDistBase pattern,
    # test/legacy_test/test_dist_base.py:962): trainer processes pin the CPU
    # backend BEFORE jax initializes so the single real TPU isn't fought over
    if os.getenv("PADDLE_DIST_DEVICE", "").lower() == "cpu":
        jax.config.update("jax_platforms", "cpu")
    if env.world_size > 1 and os.getenv("PADDLE_DIST_BACKEND", "xla") == "xla":
        master = os.getenv("PADDLE_MASTER")
        if master is None and env.trainer_endpoints:
            master = env.trainer_endpoints[0]
        if master:
            host, _, port = master.partition(":")
            coord = f"{host}:{int(port) + 7}"
            # a rank that cannot join raises: carrying on "in local mode"
            # would train world_size disconnected copies under one job name
            if not jax.distributed.is_initialized():
                jax.distributed.initialize(coordinator_address=coord,
                                           num_processes=env.world_size,
                                           process_id=env.rank)
    _initialized = True
    from .communication.group import _init_default_group
    _init_default_group(env)
    return env


def get_rank(group=None):
    if group is not None:
        return group.get_group_rank(ParallelEnv().rank)
    return ParallelEnv().rank


def get_world_size(group=None):
    if group is not None:
        return group.nranks
    return ParallelEnv().world_size
