"""BackendExecutor: drives a worker group through one training run.

Reference: ``python/ray/train/_internal/backend_executor.py`` — ``start``
:124 (spawn group, backend.on_start), ``start_training`` :438,
``get_with_failure_handling`` :640. The JAX backend's ``on_start`` is the
TPU counterpart of ``_setup_torch_process_group`` (``train/torch/config.py:
47-91``): instead of ``dist.init_process_group(nccl)``, hosts learn the
rank-0 coordinator address so ``jax.distributed.initialize`` can join them
into one global device mesh; collectives then compile onto ICI/DCN.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

import ray_tpu
from ray_tpu.train._config import JaxConfig, ScalingConfig
from ray_tpu.train._session import TrainContext
from ray_tpu.train._worker_group import WorkerGroup


class TrainingWorkerError(RuntimeError):
    def __init__(self, rank: int, cause: BaseException, tb: Optional[str]):
        super().__init__(f"worker rank={rank} failed: {cause}")
        self.rank = rank
        self.cause = cause
        self.tb = tb


class JaxBackend:
    """Mesh bring-up across hosts."""

    def __init__(self, config: Optional[JaxConfig] = None):
        self.config = config or JaxConfig()

    def on_start(self, wg: WorkerGroup) -> None:
        # rank-0 host is the jax.distributed coordinator (the reference
        # broadcasts rank-0's addr for init_process_group the same way)
        rank0 = wg.ranks.index(0)
        coord = f"{wg.infos[rank0]['ip']}:{self.config.coordinator_port}"
        envs = []
        for i in range(wg.num_workers):
            env = {
                "RAY_TRAIN_COORDINATOR_ADDRESS": coord,
                "RAY_TRAIN_NUM_PROCESSES": str(wg.num_workers),
                "RAY_TRAIN_PROCESS_ID": str(wg.ranks[i]),
            }
            envs.append(env)
        wg.set_env(envs)
        if self.config.init_distributed and wg.num_workers > 1:
            wg.execute(_jax_distributed_init)

    def on_shutdown(self, wg: WorkerGroup) -> None:
        if self.config.init_distributed and wg.num_workers > 1:
            try:
                wg.execute(_jax_distributed_shutdown)
            except Exception:
                pass


def _jax_distributed_init():
    import jax

    jax.distributed.initialize(
        coordinator_address=os.environ["RAY_TRAIN_COORDINATOR_ADDRESS"],
        num_processes=int(os.environ["RAY_TRAIN_NUM_PROCESSES"]),
        process_id=int(os.environ["RAY_TRAIN_PROCESS_ID"]),
    )


def _jax_distributed_shutdown():
    import jax

    jax.distributed.shutdown()


class BackendExecutor:
    def __init__(
        self,
        scaling: ScalingConfig,
        backend: Optional[JaxBackend] = None,
        experiment_name: str = "train",
        trial_name: str = "trial",
    ):
        self.scaling = scaling
        self.backend = backend or JaxBackend()
        self.experiment_name = experiment_name
        self.trial_name = trial_name
        self.wg: Optional[WorkerGroup] = None

    def start(self) -> None:
        self.wg = WorkerGroup(
            self.scaling.num_workers,
            self.scaling.worker_resources(),
            self.scaling.placement_strategy,
        )
        self.backend.on_start(self.wg)

    def start_training(
        self,
        train_fn: Callable,
        config: Optional[dict],
        checkpoint,
        dataset_splitter: Optional[Callable[[int, int], dict]] = None,
    ) -> list:
        assert self.wg is not None
        calls = []
        for i, w in enumerate(self.wg.workers):
            ctx: TrainContext = self.wg.context_for(i, self.experiment_name, self.trial_name)
            shards = dataset_splitter(ctx.world_rank, ctx.world_size) if dataset_splitter else None
            calls.append(w.start_training.remote(train_fn, config, ctx, checkpoint, shards))
        try:
            return ray_tpu.get(calls)  # the instant each worker's loop was entered
        except Exception as e:
            # a worker can die before even acking start (instant user crash)
            raise TrainingWorkerError(-1, e, None) from e

    def next_results(self, done_mask=None, timeout_per_wait: float = 10.0, deadline_s: float = 3600.0):
        """One event from every not-yet-done worker (lockstep; reference
        ``get_with_failure_handling``). Long-lived ``next_result`` futures
        are consumed in completion order via ``ray_tpu.wait`` — one in-flight
        call per worker instead of a 1 Hz poll per worker (the reference uses
        futures the same way; a polling loop is a control-plane storm at
        64-host scale). Returns list of events (None for workers already
        done); raises TrainingWorkerError on worker failure, TimeoutError
        past ``deadline_s`` (guards against unequal report counts across
        workers deadlocking the loop)."""
        import time as _time

        assert self.wg is not None
        events: list = [None] * len(self.wg.workers)
        pending = {
            i for i in range(len(self.wg.workers)) if not (done_mask and done_mask[i])
        }
        futures: dict = {}  # future -> worker index
        deadline = _time.monotonic() + deadline_s
        while pending:
            if _time.monotonic() > deadline:
                raise TimeoutError(
                    f"train workers {sorted(pending)} produced no result for "
                    f"{deadline_s}s — check that every worker calls "
                    f"ray_tpu.train.report() the same number of times"
                )
            inflight = set(futures.values())
            for i in sorted(pending - inflight):
                futures[self.wg.workers[i].next_result.remote(timeout_per_wait)] = i
            ready, _ = ray_tpu.wait(list(futures), num_returns=1, timeout=5.0)
            if not ready:
                continue
            fut = ready[0]
            i = futures.pop(fut)
            try:
                ev = ray_tpu.get(fut)
            except Exception as e:  # actor died
                raise TrainingWorkerError(self.wg.ranks[i], e, None) from e
            if ev is None:
                continue  # worker had nothing within timeout_per_wait; re-arm
            if ev[0] == "error":
                raise TrainingWorkerError(self.wg.ranks[i], ev[1], ev[2])
            events[i] = ev
            pending.discard(i)
        return events

    def shutdown(self):
        if self.wg is not None:
            try:
                self.backend.on_shutdown(self.wg)
            finally:
                self.wg.shutdown()
                self.wg = None
