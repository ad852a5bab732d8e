"""Launch CLI (reference: `python/paddle/distributed/launch/main.py:18`,
`controllers/collective.py` — node/device discovery, rendezvous, Pod of Containers,
watch loop with elastic relaunch).

TPU-native: one trainer process per host drives all local chips (XLA model), so
`--nproc_per_node` defaults to 1 on TPU hosts (the reference's per-GPU process model
is preserved for CPU simulation with N>1).  Rendezvous uses the reference's env-var
contract (PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM/PADDLE_MASTER/...); the coordination
service behind it is jax.distributed (see parallel_env).
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _cpu_only_platform(env, nproc):
    """JAX_PLATFORMS for the children of a multi-process local pod.

    A chip belongs to one process, and nothing here binds a child to its
    own chip (FLAGS_selected_tpus is only an id), so N > 1 trainers on one
    host are the CPU simulation: they run only when the environment already
    says cpu, and are then pinned to it from their first import."""
    if env.get("PADDLE_DIST_DEVICE", "").lower() == "cpu" or \
            env.get("JAX_PLATFORMS", "").lower() == "cpu":
        return "cpu"
    raise RuntimeError(
        f"{nproc} local trainer processes would all claim this host's "
        "accelerator; one process drives every local chip.  Start one "
        "process, or set PADDLE_DIST_DEVICE=cpu for the multi-process CPU "
        "simulation.")


class Container:
    """One trainer process (reference `launch/job/container.py`)."""

    def __init__(self, rank, cmd, env, log_dir):
        self.rank = rank
        self.cmd = cmd
        self.env = env
        self.log_dir = log_dir
        self.proc = None
        self.log_file = None

    def start(self):
        os.makedirs(self.log_dir, exist_ok=True)
        path = os.path.join(self.log_dir, f"workerlog.{self.rank}")
        self.log_file = open(path, "ab")
        self.proc = subprocess.Popen(self.cmd, env=self.env, stdout=self.log_file,
                                     stderr=subprocess.STDOUT)

    def alive(self):
        return self.proc is not None and self.proc.poll() is None

    @property
    def returncode(self):
        return self.proc.poll() if self.proc else None

    def terminate(self):
        if self.alive():
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        if self.log_file:
            self.log_file.close()


class CollectiveController:
    """(reference `controllers/collective.py:22`)."""

    def __init__(self, args, training_args):
        self.args = args
        self.training_args = training_args
        self.containers = []

    def build_pod(self):
        n = self.args.nproc_per_node
        master = self.args.master or f"127.0.0.1:{_free_port()}"
        endpoints = []
        host, _, mport = master.partition(":")
        for i in range(n):
            endpoints.append(f"{host}:{int(mport) + i}")
        base_env = dict(os.environ)
        if n > 1:
            base_env["JAX_PLATFORMS"] = _cpu_only_platform(base_env, n)
        for rank in range(n):
            env = dict(base_env)
            env.update({
                "PADDLE_TRAINER_ID": str(rank + self.args.rank * n),
                "PADDLE_TRAINERS_NUM": str(n * self.args.nnodes),
                "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
                "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
                "PADDLE_MASTER": master,
                "PADDLE_LOCAL_RANK": str(rank),
                "PADDLE_LOCAL_SIZE": str(n),
                "FLAGS_selected_tpus": str(rank),
            })
            if self.args.devices:
                env["CUDA_VISIBLE_DEVICES"] = self.args.devices
            cmd = [sys.executable] + ([self.args.training_script]
                                      if not self.args.module
                                      else ["-m", self.args.training_script]) \
                + self.training_args
            self.containers.append(Container(rank, cmd, env, self.args.log_dir))

    def run(self):
        from ..fleet.elastic import ElasticManager, ElasticStatus
        n0 = self.args.nproc_per_node
        mgr = ElasticManager(self.args.np or str(n0), timeout=10.0,
                             max_restart=self.args.max_restart)

        self.build_pod()
        for c in self.containers:
            c.start()
            mgr.register(c.rank)
        print(f"[launch] started {len(self.containers)} trainer(s); "
              f"logs in {self.args.log_dir}")

        def handler(sig, frame):
            for c in self.containers:
                c.terminate()
            sys.exit(1)

        signal.signal(signal.SIGINT, handler)
        signal.signal(signal.SIGTERM, handler)

        while True:
            time.sleep(1)
            # process liveness IS the heartbeat (ref: etcd heartbeats)
            for c in self.containers:
                if c.alive():
                    mgr.heartbeat(c.rank)
            dead = [c for c in self.containers if not c.alive()]
            failed = [c for c in dead if c.returncode != 0]
            if not failed and len(dead) == len(self.containers):
                print("[launch] all trainers finished")
                return 0
            if not failed:
                continue
            for c in failed:
                mgr.report_failure(c.rank)
            status = mgr.decide()
            if status == ElasticStatus.RESTART and self.args.elastic_level > 0 \
                    and mgr.restarts < self.args.max_restart:
                new_n = mgr.scaled_np() if self.args.np else n0
                mgr.on_restart()
                print(f"[launch] trainer failed (rc={failed[0].returncode}); "
                      f"elastic relaunch {mgr.restarts}/{self.args.max_restart} "
                      f"with np={new_n}")
                for c in self.containers:
                    c.terminate()
                self.containers = []
                self.args.nproc_per_node = new_n
                self.build_pod()
                for c in self.containers:
                    c.start()
                    mgr.register(c.rank)
            else:
                print(f"[launch] trainer {failed[0].rank} failed with "
                      f"rc={failed[0].returncode}; terminating pod")
                for c in self.containers:
                    c.terminate()
                return failed[0].returncode or 1


def launch():
    parser = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    parser.add_argument("--master", default=None,
                        help="rendezvous endpoint host:port")
    parser.add_argument("--nnodes", type=int,
                        default=int(os.getenv("PADDLE_NNODES", "1")))
    parser.add_argument("--rank", type=int, default=int(os.getenv("PADDLE_RANK", "0")),
                        help="node rank")
    parser.add_argument("--nproc_per_node", type=int,
                        default=int(os.getenv("PADDLE_NPROC_PER_NODE", "1")))
    parser.add_argument("--devices", "--gpus", "--tpus", default=None)
    parser.add_argument("--log_dir", default="log")
    parser.add_argument("--run_mode", default="collective")
    parser.add_argument("--job_id", default="default")
    parser.add_argument("--elastic_level", type=int,
                        default=int(os.getenv("PADDLE_ELASTIC_LEVEL", "0")))
    parser.add_argument("--max_restart", type=int, default=3)
    parser.add_argument("--np", default=os.getenv("PADDLE_ELASTIC_NP"),
                        help="elastic world-size range 'min:max' (ref elastic "
                             "np): on member loss the pod relaunches scaled "
                             "down to the live count within the range")
    parser.add_argument("--module", "-m", action="store_true",
                        help="run training script as a module")
    parser.add_argument("training_script")
    parser.add_argument("training_args", nargs=argparse.REMAINDER)
    args = parser.parse_args()

    ctl = CollectiveController(args, args.training_args)
    sys.exit(ctl.run())


if __name__ == "__main__":
    launch()
