"""WorkerGroup: the N train-worker actors.

Reference: ``python/ray/train/_internal/worker_group.py:102`` (actor group)
+ ``backend_executor.py:358`` (rank/world-size env). A ray_tpu train worker
is a *host*: one JAX process driving all local chips, so ranks here are host
ranks (jax process indices), not device ranks.
"""

from __future__ import annotations

import os
import socket
from typing import Any, Callable, Optional

import ray_tpu
from ray_tpu.train._session import TrainContext, _TrainSession


class RayTrainWorker:
    """Actor body. Holds the running train session for this worker."""

    def __init__(self):
        self.session: Optional[_TrainSession] = None

    def node_info(self) -> dict:
        import ray_tpu as rt

        ctx = rt.get_runtime_context()
        try:
            ip = socket.gethostbyname(socket.gethostname())
        except OSError:
            ip = "127.0.0.1"
        return {
            "node_id": ctx.get_node_id(),
            "pid": os.getpid(),
            "hostname": socket.gethostname(),
            "ip": ip,
        }

    def set_env(self, env: dict[str, str]) -> None:
        os.environ.update(env)

    def execute(self, fn: Callable, *args, **kwargs) -> Any:
        """Run an arbitrary function in the worker process (reference:
        WorkerGroup.execute)."""
        return fn(*args, **kwargs)

    def start_training(
        self,
        train_fn: Callable,
        config: Optional[dict],
        context: TrainContext,
        checkpoint,
        dataset_shards: Optional[dict],
    ) -> float:
        """Returns the instant the loop was entered in this worker (the
        trainer's ``train.worker_start`` event carries it)."""
        assert self.session is None or self.session.finished, "training already running"
        self.session = _TrainSession(train_fn, config, context, checkpoint, dataset_shards)
        return self.session.start()

    def next_result(self, timeout: float = 1.0):
        """One session event or None: ('result', metrics, ckpt) |
        ('done', ret, None) | ('error', exc, tb)."""
        if self.session is None:
            return ("error", RuntimeError("no session"), None)
        ev = self.session.next(timeout=timeout)
        if ev is not None and ev[0] in ("done", "error"):
            self.session.finished = True
        return ev

    def ack_result(self) -> bool:
        """Driver committed the last reported result; unblock report()."""
        if self.session is not None:
            self.session.ack_event.set()
        return True

    def shutdown(self) -> bool:
        return True


class WorkerGroup:
    """Spawns and addresses the worker actors."""

    def __init__(
        self,
        num_workers: int,
        resources_per_worker: dict[str, float],
        placement_strategy: str = "PACK",
        max_restarts: int = 0,
    ):
        from ray_tpu.util.placement_group import placement_group
        from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy

        self.num_workers = num_workers
        self.pg = placement_group([dict(resources_per_worker)] * num_workers, strategy=placement_strategy)
        self.pg.wait(timeout_seconds=60.0)
        cls = ray_tpu.remote(
            num_cpus=0,
            max_restarts=0,
        )(RayTrainWorker)
        self.workers = [
            cls.options(
                resources={k: v for k, v in resources_per_worker.items()},
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    self.pg, placement_group_bundle_index=i
                ),
            ).remote()
            for i in range(num_workers)
        ]
        infos = ray_tpu.get([w.node_info.remote() for w in self.workers])
        # Host ranks: stable sort by (node, pid); local ranks count within node.
        order = sorted(range(num_workers), key=lambda i: (infos[i]["node_id"], infos[i]["pid"]))
        self.ranks = [0] * num_workers
        for rank, idx in enumerate(order):
            self.ranks[idx] = rank
        self.infos = infos
        self.local_ranks = [0] * num_workers
        self.node_ranks = [0] * num_workers
        per_node: dict[str, int] = {}
        node_idx: dict[str, int] = {}
        for rank, idx in enumerate(order):
            nid = infos[idx]["node_id"]
            if nid not in node_idx:
                node_idx[nid] = len(node_idx)
            self.local_ranks[idx] = per_node.get(nid, 0)
            per_node[nid] = per_node.get(nid, 0) + 1
            self.node_ranks[idx] = node_idx[nid]
        self.local_world_sizes = [per_node[infos[i]["node_id"]] for i in range(num_workers)]

    def context_for(self, i: int, experiment: str = "train", trial: str = "trial") -> TrainContext:
        return TrainContext(
            world_size=self.num_workers,
            world_rank=self.ranks[i],
            local_rank=self.local_ranks[i],
            local_world_size=self.local_world_sizes[i],
            node_rank=self.node_ranks[i],
            experiment_name=experiment,
            trial_name=trial,
        )

    def execute(self, fn: Callable, *args, **kwargs) -> list:
        return ray_tpu.get([w.execute.remote(fn, *args, **kwargs) for w in self.workers])

    def execute_single(self, i: int, fn: Callable, *args, **kwargs):
        return ray_tpu.get(self.workers[i].execute.remote(fn, *args, **kwargs))

    def set_env(self, envs: "list[dict[str, str]]") -> None:
        ray_tpu.get([w.set_env.remote(e) for w, e in zip(self.workers, envs)])

    def shutdown(self):
        from ray_tpu._private.log_util import warn_throttled

        try:
            ray_tpu.get([w.shutdown.remote() for w in self.workers], timeout=5.0)
        except Exception:
            pass
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception as e:
                # best-effort teardown, but not silent: a kill that fails for
                # any reason other than "already dead" means leaked workers
                warn_throttled("train worker group teardown", e)
        from ray_tpu.util.placement_group import remove_placement_group

        try:
            remove_placement_group(self.pg)
        except Exception:
            pass
