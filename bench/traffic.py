"""The one general traffic generator: a traffic file drives the executor.

A traffic file (``bench/traffic/<name>.json``) holds:

- ``slots``: device slots of the fleet (``FleetExecutor(total_slots=...)``);
- ``steps_per_tick``: steps each running job takes per ``tick``;
- ``jobs``: jobs submitted at the start, each ``{id, tier, world,
  total_steps}``;
- ``loop``: actions taken in order between ticks and repeated from the
  first once the last is done: ``{"wait_steps": n, "job": id}`` waits until
  that job has run ``n`` more steps, ``{"submit": job}`` submits a job (its
  id gets the loop count), ``{"wait_done": id}`` waits until the job of
  that id submitted in this loop is done.  A closed loop: the next arrival
  waits for the system, so faster mechanisms fit more loops into a window;
- ``warmup_loops``: loops that set-up runs before the window opens;
- ``warmup_loop`` (optional): the actions of those set-up loops, where they
  differ from ``loop`` (a shorter wait runs the same programs sooner);
- ``follow``: the job whose first steps the plain reference follows, and
  the splice each of those steps must run at.

Every job of a cell runs the cell's configuration at its batch.  The run's
``--seed`` fixes each job's weights and tokens (``job_seed``); sizes and
arrivals are the same for every seed.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List

import numpy as np

from repro.scheduler.executor import FleetExecutor, ManagedJob


def job_seed(run_seed: int, job_id: str) -> int:
    """A 31-bit seed of one job, from the run's seed (any size) and the
    job's id.  The program's PRNG keys keep only 32 bits of a seed."""
    words = [run_seed & 0xFFFFFFFF, (run_seed >> 32) & 0xFFFFFFFF,
             run_seed >> 64, zlib.crc32(job_id.encode())]
    ss = np.random.SeedSequence(words)
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


@dataclasses.dataclass
class SeededJob(ManagedJob):
    """A ``ManagedJob`` whose weights and tokens come from ``seed``."""
    seed: int = 0

    def train_config(self):
        return dataclasses.replace(super().train_config(), seed=self.seed)


class Driver:
    """Submits a traffic file's jobs into a ``FleetExecutor`` and ticks it."""

    def __init__(self, ex: FleetExecutor, traffic: Dict, run_seed: int,
                 model_cfg, global_batch: int, seq_len: int):
        self.ex = ex
        self.traffic = traffic
        self.run_seed = run_seed
        self.cfg = model_cfg
        self.gb, self.sl = global_batch, seq_len
        self.warmup_loops = int(traffic.get("warmup_loops", 0))
        self.steps_per_tick = int(traffic["steps_per_tick"])
        self.pos = 0          # index of the current loop action
        self.loops = 0        # loops completed
        self.mark = 0         # steps_done when the current wait began
        self.current: Dict[str, str] = {}   # loop job id -> submitted id
        self.submitted: List[SeededJob] = []

    def actions(self, n: int) -> List[Dict]:
        """The actions of loop ``n`` (0-based)."""
        if n < self.warmup_loops:
            return self.traffic.get("warmup_loop", self.traffic["loop"])
        return self.traffic["loop"]

    @property
    def loop(self) -> List[Dict]:
        return self.actions(self.loops)

    def job(self, jid: str) -> SeededJob:
        return self.ex.jobs[self.current.get(jid, jid)]

    def _submit(self, spec: Dict, jid: str) -> SeededJob:
        job = SeededJob(id=jid, tier=spec["tier"], arch=self.cfg.name,
                        world_size=int(spec["world"]),
                        total_steps=int(spec["total_steps"]),
                        seed=job_seed(self.run_seed, jid))
        self.ex.submit(job, self.gb, self.sl, cfg=self.cfg)
        self.submitted.append(job)
        return job

    def start(self) -> None:
        for spec in self.traffic["jobs"]:
            self._submit(spec, spec["id"])
        self._enter()

    def _enter(self) -> None:
        if self.loop and "wait_steps" in self.loop[self.pos]:
            self.mark = self.job(self.loop[self.pos]["job"]).steps_done

    def _advance(self) -> None:
        self.pos += 1
        if self.pos == len(self.loop):
            self.pos = 0
            self.loops += 1
        self._enter()

    def arrivals(self) -> None:
        """Take every loop action that is due now."""
        for _ in range(len(self.loop)):
            a = self.loop[self.pos]
            if "wait_steps" in a:
                if self.job(a["job"]).steps_done - self.mark < a["wait_steps"]:
                    return
            elif "wait_done" in a:
                if not self.job(a["wait_done"]).done:
                    return
            else:
                spec = a["submit"]
                jid = f"{spec['id']}-{self.loops}"
                self._submit(spec, jid)
                self.current[spec["id"]] = jid
            self._advance()

    def tick(self) -> None:
        self.arrivals()
        self.ex.tick(self.steps_per_tick)

    def schedule(self, loops: int) -> List[Dict]:
        """The jobs this file submits in its first ``loops`` loops, in order,
        with their seeds: what the run's seed fixes."""
        out = [dict(spec, seed=job_seed(self.run_seed, spec["id"]))
               for spec in self.traffic["jobs"]]
        for n in range(loops):
            for a in self.actions(n):
                if "submit" in a:
                    jid = f"{a['submit']['id']}-{n}"
                    out.append(dict(a["submit"], id=jid,
                                    seed=job_seed(self.run_seed, jid)))
        return out
