"""Elastic runtime: transparent resize of a live job (§5).

To the job, the world size W never changes.  The runtime maps W logical
ranks onto P physical devices; resizing swaps the splice factor s = W/P in
the compiled step — the training state is untouched (work-conserving), the
data pipeline cursor is untouched, and the trajectory is invariant (tested).

ZeRO partial sharding (§5.4): a job whose optimizer state is sharded
``zero_shard_factor``-way can only be spliced up to W / shard_factor — the
runtime enforces the paper's placement rule (only replicas of the same
shard are spliced together).

Spans on the runtime's ``profiler`` (``repro.utils.profiler``):

- ``step.batch`` builds a step's batch on the host; ``step.dispatch``
  calls the step program until it returns (enqueued, not finished);
  ``step.wait`` reads back what the host needs of the step (barrier
  flags, step counter, loss), so it ends when the step has run.
- ``step.build.<cause>`` takes the place of ``step.dispatch`` on the
  first call of each step program the runtime builds: trace, lowering,
  compile or cache load, and enqueue.  ``<cause>`` says why the runtime
  needed the program: ``admit`` (a fresh runtime), ``restore``
  (``from_snapshot``) or ``resize``.
- counters ``moe.rows``, ``moe.rows_peak``, ``moe.block_steps``, of a
  model with MoE blocks, read in ``step.wait`` once the step has run: the
  rows its held experts computed; per block and step the busiest held
  expert's rows, summed; and the blocks times the steps.
- ``ckpt.d2h`` copies the state to the host for a snapshot;
  ``restore.h2d`` copies a snapshot's state to the device, and waits for
  the copy only when the profiler is enabled.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, TrainConfig
from repro.core.barrier_jax import BarrierDriver
from repro.data.pipeline import DataPipeline
from repro.models.frontend import synth_extra_inputs
from repro.optim.zero import validate_partial_sharding
from repro.training.state import TrainState, init_train_state
from repro.training.step import build_train_step
from repro.utils.profiler import Profiler


class ElasticRuntime:
    """Host-side elastic training driver (CPU-scale; the production path
    lowers the same spliced step onto the pod mesh via launch/)."""

    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, world_size: int,
                 physical_devices: int, global_batch: int, seq_len: int,
                 seed: int = 0, state: Optional[TrainState] = None,
                 pipeline_state: Optional[Dict] = None,
                 profiler: Optional[Profiler] = None):
        assert world_size % physical_devices == 0
        self.cfg = cfg
        self.tcfg = tcfg
        self.world_size = world_size
        self.physical = physical_devices
        validate_partial_sharding(world_size, tcfg.zero_shard_factor,
                                  world_size // physical_devices)
        self.pipeline = DataPipeline(cfg.vocab_size, seq_len, global_batch,
                                     world_size, seed=tcfg.seed)
        if pipeline_state:
            self.pipeline.restore(pipeline_state)
        key = jax.random.PRNGKey(tcfg.seed)
        self.state = state if state is not None else init_train_state(
            cfg, tcfg, key)
        self.barrier = BarrierDriver(n_shards=1)
        self._extra_key = jax.random.PRNGKey(tcfg.seed + 1)
        self._steps: Dict[int, any] = {}
        # why the next program built is needed, and per splice the cause
        # of a program built and not yet called
        self._cause = "admit"
        self._unbuilt: Dict[int, str] = {}
        self.history: List[Dict] = []
        self.prof = profiler if profiler is not None else Profiler()

    # ------------------------------------------------------------------ step
    @property
    def splice(self) -> int:
        return self.world_size // self.physical

    def _step_fn(self):
        s = self.splice
        if s not in self._steps:
            self._steps[s] = jax.jit(build_train_step(
                self.cfg, self.tcfg, splice=s, with_barrier=True))
            self._unbuilt[s] = self._cause
        return self._steps[s]

    # ----------------------------------------------------- preemption flow
    def request_preemption(self) -> None:
        """Scheduler command: quiesce at the next safe boundary (§4).  The
        (need, ack) payload rides the job's own compiled step — the
        in-graph tandem meta-allreduce."""
        self.barrier.request()

    @property
    def quiesced(self) -> bool:
        return self.barrier.acquired

    def _batch(self) -> Dict:
        with self.prof.span("step.batch"):
            tokens, labels = self.pipeline.next_batch()
            batch = {"tokens": jnp.asarray(tokens),
                     "labels": jnp.asarray(labels)}
            batch.update(synth_extra_inputs(self.cfg, tokens.shape[0],
                                            self._extra_key))
        return batch

    def run_steps(self, n: int, stop_on_barrier: bool = False) -> List[Dict]:
        out = []
        fn = self._step_fn()
        for _ in range(n):
            batch = self._batch()
            cause = self._unbuilt.pop(self.splice, None)
            name = "step.dispatch" if cause is None else "step.build." + cause
            with self.prof.span(name):
                self.state, metrics = fn(self.state, batch,
                                         self.barrier.flags())
            with self.prof.span("step.wait"):
                acquired = self.barrier.observe(metrics["barrier"])
                step, loss = int(self.state["step"]), float(metrics["loss"])
                if "moe_rows" in metrics:
                    self._count_rows(np.asarray(metrics["moe_rows"]))
            rec = {"step": step,
                   "loss": loss,
                   "splice": self.splice,
                   "physical": self.physical,
                   "barrier_acquired": acquired}
            out.append(rec)
            self.history.append(rec)
            if acquired and stop_on_barrier:
                break
        return out

    def _count_rows(self, rows: np.ndarray) -> None:
        """rows: (MoE blocks, held experts) of one step."""
        self.prof.add("moe.rows", float(rows.sum()))
        self.prof.add("moe.rows_peak", float(rows.max(axis=-1).sum()))
        self.prof.add("moe.block_steps", float(rows.shape[0]))

    # ---------------------------------------------------------------- resize
    def resize(self, new_physical: int) -> Dict:
        """Transparent resize: same logical world, new physical mapping.

        Work-conserving by construction: state and data cursor unchanged.
        """
        assert self.world_size % new_physical == 0, \
            f"world {self.world_size} not divisible by {new_physical}"
        validate_partial_sharding(self.world_size, self.tcfg.zero_shard_factor,
                                  self.world_size // new_physical)
        old = self.physical
        self.physical = new_physical
        if self._steps:     # a runtime yet to run its first step is still
            self._cause = "resize"      # being admitted or restored
        self._step_fn()     # the new splice's step, built on its first call
        return {"from": old, "to": new_physical,
                "splice": self.splice,
                "at_step": int(self.state["step"])}

    # ------------------------------------------------------------- snapshots
    def snapshot(self) -> Dict:
        """The complete program state (work-conserving checkpoint payload)."""
        with self.prof.span("ckpt.d2h"):
            state = jax.tree_util.tree_map(np.asarray, self.state)
        return {
            "state": state,
            "pipeline": self.pipeline.snapshot(),
            "world_size": self.world_size,
        }

    @classmethod
    def from_snapshot(cls, cfg: ModelConfig, tcfg: TrainConfig, snap: Dict,
                      physical_devices: int, global_batch: int, seq_len: int,
                      profiler: Optional[Profiler] = None
                      ) -> "ElasticRuntime":
        prof = profiler if profiler is not None else Profiler()
        with prof.span("restore.h2d"):
            state = jax.tree_util.tree_map(jnp.asarray, snap["state"])
            if prof.enabled:    # the copy's time, not its enqueue
                jax.block_until_ready(state)
        rt = cls(cfg, tcfg, snap["world_size"], physical_devices,
                 global_batch, seq_len, state=state,
                 pipeline_state=snap["pipeline"], profiler=prof)
        rt._cause = "restore"
        return rt
