"""Fault tolerance: restart policy, straggler mitigation, failure simulation.

On a 1000+-node fleet the failure model is: chips/hosts fail mid-step
(XLA raises, the coordinator loses a heartbeat), stragglers stretch step
time, and capacity changes (preemption / repair) resize the usable mesh.
The control plane here implements the standard production responses:

  * ``RestartPolicy``     — bounded restarts with exponential backoff;
    restore from the newest committed checkpoint; deterministic data
    skip-ahead (the pipeline is a pure function of step, so no replay log).
  * ``StragglerMonitor``  — EWMA step-time tracker; flags steps beyond
    k·σ and counts per-host incidents so the launcher can cordon a host
    (on TPU pods a straggler is usually a host, not a chip).
  * ``ElasticPlan``       (runtime/elastic.py) — recompute the mesh and
    shardings for a changed device count; checkpoint restore absorbs the
    re-shard (checkpoint/checkpoint.py saves unsharded values).
  * ``simulate_failures`` — deterministic failure injector used by the
    integration tests to prove train-loop recovery end-to-end.
"""

from __future__ import annotations

import dataclasses
import math
import random
import time
from typing import Callable, Dict, List, Optional, Tuple


@dataclasses.dataclass
class RestartPolicy:
    """Bounded restarts with exponential backoff.

    ``sleep`` is injectable so tests (and the serving chaos lane) can run
    the policy with a no-op while production keeps the FULL computed
    delay — the backoff math and what actually gets slept are the same
    code path either way.
    """
    max_restarts: int = 10
    backoff_s: float = 1.0
    backoff_factor: float = 2.0
    max_backoff_s: float = 300.0
    sleep: Callable[[float], None] = time.sleep

    def backoff(self, restart_index: int) -> float:
        """Delay before restart #``restart_index`` (1-based), capped at
        ``max_backoff_s``."""
        return min(
            self.backoff_s * self.backoff_factor ** (restart_index - 1),
            self.max_backoff_s,
        )

    def run(self, make_loop: Callable[[int], int], log=print) -> int:
        """make_loop(start_step) -> last_step, raising on simulated/real
        failure.  Returns the final step reached."""
        restarts = 0
        last_step = 0
        while True:
            try:
                return make_loop(last_step)
            except TrainingFailure as e:
                restarts += 1
                last_step = e.resume_step
                if restarts > self.max_restarts:
                    raise RuntimeError(
                        f"exceeded {self.max_restarts} restarts"
                    ) from e
                delay = self.backoff(restarts)
                log(f"[ft] failure at step {e.step} ({e.reason}); "
                    f"restart #{restarts} from step {e.resume_step} "
                    f"after {delay:.1f}s backoff")
                self.sleep(delay)


class TrainingFailure(Exception):
    def __init__(self, step: int, resume_step: int, reason: str):
        super().__init__(f"step {step}: {reason}")
        self.step = step
        self.resume_step = resume_step
        self.reason = reason


@dataclasses.dataclass
class StragglerMonitor:
    """EWMA + variance tracker over step times; straggler = step beyond
    ``sigma_k`` standard deviations (and above an absolute floor)."""
    alpha: float = 0.1
    sigma_k: float = 3.0
    min_steps: int = 8
    floor_ratio: float = 1.5

    mean: float = 0.0
    var: float = 0.0
    n: int = 0
    incidents: Dict[str, int] = dataclasses.field(default_factory=dict)

    def observe(self, step_time_s: float, host: str = "host0") -> bool:
        """Returns True if this step is flagged as a straggler."""
        self.n += 1
        if self.n == 1:
            self.mean = step_time_s
            self.var = 0.0
            return False
        d = step_time_s - self.mean
        flagged = False
        if self.n > self.min_steps:
            sigma = math.sqrt(max(self.var, 1e-12))
            if (step_time_s > self.mean + self.sigma_k * sigma
                    and step_time_s > self.floor_ratio * self.mean):
                flagged = True
                self.incidents[host] = self.incidents.get(host, 0) + 1
        self.mean += self.alpha * d
        self.var = (1 - self.alpha) * (self.var + self.alpha * d * d)
        return flagged

    def cordon_candidates(self, threshold: int = 3) -> List[str]:
        return [h for h, c in self.incidents.items() if c >= threshold]


class InjectedFault(RuntimeError):
    """Raised by a ``FaultPlan`` crash hook inside a replica worker — the
    deterministic stand-in for an XLA/driver failure killing the thread."""


@dataclasses.dataclass
class FaultPlan:
    """Seeded, deterministic fault-injection plan for the serving plane.

    The training loop has ``simulate_failures``; this is the serving
    equivalent, consumed by ``launch/router.py`` (per-replica ``fault_hook``
    called with the replica's worked-chunk counter) and by
    ``benchmarks/chaos_serve.py``:

      * ``crash_at``  — replica index → chunk index at which that replica's
        worker raises ``InjectedFault`` (fires once; a restarted replica
        does not re-crash);
      * ``stall_at``  — replica index → ``(chunk index, seconds)``: the
        worker sleeps before that chunk — a slow-chunk straggler that trips
        the router's watchdog (``SUSPECT``) and then recovers;
      * ``poison``    — request trace indices served with NaN logits (the
        chaos lane plants the magic poison token in those prompts);
      * ``corrupt_checkpoint`` — whether the checkpoint-integrity leg
        rewrites a committed shard with wrong bytes.

    Same seed ⇒ same plan ⇒ same injection points: the chaos lane is
    reproducible run to run.
    """
    crash_at: Dict[int, int] = dataclasses.field(default_factory=dict)
    stall_at: Dict[int, Tuple[int, float]] = dataclasses.field(
        default_factory=dict)
    poison: Tuple[int, ...] = ()
    corrupt_checkpoint: bool = False
    sleep: Callable[[float], None] = time.sleep

    def __post_init__(self):
        self._fired: set = set()

    @classmethod
    def seeded(cls, seed: int, replicas: int, requests: int,
               crashes: int = 1, stalls: int = 1, poisons: int = 1,
               stall_s: float = 1.0, span: int = 6) -> "FaultPlan":
        """Draw a plan from ``seed``: ``crashes`` replicas die and
        ``stalls`` (different) replicas straggle at chunk indices in
        ``[1, span)``; ``poisons`` of the ``requests`` trace entries are
        NaN-poisoned."""
        rng = random.Random(seed)
        reps = list(range(replicas))
        rng.shuffle(reps)
        crashing = reps[:min(crashes, replicas)]
        stalling = reps[len(crashing):] or reps
        plan = cls(
            crash_at={r: rng.randrange(1, span) for r in crashing},
            stall_at={r: (rng.randrange(1, span), stall_s)
                      for r in stalling[:min(stalls, len(stalling))]},
            poison=tuple(sorted(rng.sample(range(requests),
                                           min(poisons, requests)))),
            corrupt_checkpoint=True,
        )
        return plan

    def hook_for(self, replica: int) -> Callable[[int], None]:
        """The per-replica hook the router calls with its worked-chunk
        counter.  Each injection fires exactly once per plan instance."""
        def hook(chunk: int) -> None:
            stall = self.stall_at.get(replica)
            if (stall is not None and chunk >= stall[0]
                    and ("stall", replica) not in self._fired):
                self._fired.add(("stall", replica))
                self.sleep(stall[1])
            if (replica in self.crash_at
                    and chunk >= self.crash_at[replica]
                    and ("crash", replica) not in self._fired):
                self._fired.add(("crash", replica))
                raise InjectedFault(
                    f"fault plan: replica {replica} crash at chunk {chunk}")
        return hook

    def counts(self) -> Dict[str, int]:
        """Planned injection counts (what the chaos lane reports)."""
        return {
            "crashes": len(self.crash_at),
            "stalls": len(self.stall_at),
            "poisoned_requests": len(self.poison),
            "corrupt_checkpoints": int(self.corrupt_checkpoint),
        }

    def fired(self) -> Dict[str, int]:
        """How many planned injections actually fired."""
        return {
            "crashes": sum(1 for k in self._fired if k[0] == "crash"),
            "stalls": sum(1 for k in self._fired if k[0] == "stall"),
        }


def simulate_failures(fail_steps: Dict[int, str]):
    """Decorator-ish injector: raise TrainingFailure when step hits a key.
    Used by tests/integration to drive RestartPolicy."""
    fired = set()

    def check(step: int, resume_step: int):
        if step in fail_steps and step not in fired:
            fired.add(step)
            raise TrainingFailure(step, resume_step, fail_steps[step])

    return check
