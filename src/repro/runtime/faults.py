"""Deterministic fault injection for executor backends.

The chaos harness's single source of truth: a :class:`FaultPlan` wraps any
registered executor in a :class:`FaultyExecutor` that injects faults at the
``run`` / ``run_batch`` boundary — exactly where a real device call would
fail — while delegating everything else (capabilities, arena geometry,
compile counters) to the wrapped backend.  Injection is deterministic: a
seeded RNG drives per-call probabilities, and a ``schedule`` of call indices
scripts exact storms, so a chaos run replays bit-identically.

Fault taxonomy (``FaultSpec.kind``):

    ``error``          — the call raises :class:`InjectedFaultError` (a
                         crashed device submission).
    ``hang``           — the call blocks indefinitely (a wedged accelerator);
                         only the scheduler's watchdog, or an explicit
                         ``release_hangs()``, unblocks it.  A released hang
                         still raises — a call that hung never produced data.
    ``slow``           — the call completes correctly but late, by
                         ``latency_mult`` x its own duration (or an absolute
                         ``delay_s``) — host/accelerator contention.
    ``corrupt_output`` — the call returns, with flipped output bytes.  This
                         is the one *silent* fault: nothing downstream can
                         detect it without a reference — chaos soaks script
                         it only where a reference is available.
    ``corrupt_arena``  — weight-region bytes are scribbled over and the call
                         raises: a crashed DMA poisoning the resident arena.
                         The supervisor's checksum (``arena_ok``) catches it
                         and ``reset_arena()`` heals before the retry.

    Session.load(art, fault_plan=FaultPlan(specs=(
        FaultSpec("error", probability=0.01),
        FaultSpec("hang", schedule=(7,)),
    ), seed=42))
"""

from __future__ import annotations

import dataclasses
import threading
import time
import random
from typing import Optional, Tuple

import numpy as np

from repro.core.executor import ExecResult

FAULT_KINDS = ("error", "hang", "slow", "corrupt_output", "corrupt_arena")


class InjectedFaultError(RuntimeError):
    """Raised by ``FaultyExecutor`` in place of a real backend failure."""

    def __init__(self, kind: str, call_index: int):
        super().__init__(f"injected fault {kind!r} at call {call_index}")
        self.kind, self.call_index = kind, call_index


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One fault source: what to inject and when.

    A call triggers the spec when its index is in ``schedule`` OR the seeded
    coin with ``probability`` comes up; ``max_faults`` caps total injections
    (None = unbounded) so a scripted outage can end and let recovery happen.
    """
    kind: str
    probability: float = 0.0
    schedule: Tuple[int, ...] = ()
    latency_mult: float = 10.0           # "slow": multiplier on the call's
                                         # own duration
    delay_s: Optional[float] = None      # "slow": absolute delay instead
    max_faults: Optional[int] = None

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"known: {FAULT_KINDS}")
        if not 0.0 <= self.probability <= 1.0:
            raise ValueError(f"probability must be in [0, 1], "
                             f"got {self.probability}")
        object.__setattr__(self, "schedule",
                           tuple(int(i) for i in self.schedule))


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A seeded set of fault sources, injectable into any executor."""
    specs: Tuple[FaultSpec, ...]
    seed: int = 0

    def __post_init__(self):
        object.__setattr__(self, "specs", tuple(self.specs))


class FaultyExecutor:
    """Executor-protocol wrapper injecting a :class:`FaultPlan`.

    Satisfies ``ExecutorBackend`` by delegation: every attribute the
    scheduler or Session consults (``capabilities``, ``input_dims``,
    ``compile_count``, ``arena_ok``/``reset_arena``, ...) resolves on the
    wrapped executor; only ``run`` / ``run_batch`` pass through the
    injection point.  ``faults_injected`` counts injections (mirrored into
    ``NetStats`` by the dispatcher); ``release_hangs()`` unblocks any call
    stuck in a ``hang`` fault (tests/benchmarks call it at teardown so
    abandoned watchdog workers don't linger).
    """

    def __init__(self, inner, plan: FaultPlan):
        self.inner = inner
        self.plan = plan
        self._rng = random.Random(plan.seed)
        self._lock = threading.Lock()
        self._hang = threading.Event()
        self._spec_counts = [0] * len(plan.specs)
        self.call_index = 0              # calls seen (run and run_batch alike)
        self.faults_injected = 0
        self.faults_by_kind = {k: 0 for k in FAULT_KINDS}

    # -- delegation ----------------------------------------------------------
    def __getattr__(self, name):
        return getattr(self.inner, name)

    @property
    def batch_sharding(self):
        return getattr(self.inner, "batch_sharding", None)

    @batch_sharding.setter
    def batch_sharding(self, value):     # the dispatcher assigns this
        setattr(self.inner, "batch_sharding", value)

    def capabilities(self):
        # the split halves would reach the inner executor past the
        # injection point: the wrapper launches whole
        return dataclasses.replace(self.inner.capabilities(),
                                   split_launch=False)

    def release_hangs(self) -> None:
        """Unblock every call stuck in a ``hang`` fault (they then raise)."""
        self._hang.set()

    # -- injection -----------------------------------------------------------
    def _pick(self) -> Tuple[Optional[FaultSpec], int]:
        with self._lock:
            idx = self.call_index
            self.call_index += 1
            for i, spec in enumerate(self.plan.specs):
                if spec.max_faults is not None \
                        and self._spec_counts[i] >= spec.max_faults:
                    continue
                hit = idx in spec.schedule
                if not hit and spec.probability:
                    hit = self._rng.random() < spec.probability
                if hit:
                    self._spec_counts[i] += 1
                    self.faults_injected += 1
                    self.faults_by_kind[spec.kind] += 1
                    return spec, idx
            return None, idx

    def _corrupt_arena(self, idx: int) -> None:
        """Scribble over a weight-region byte range OUTSIDE the input surface
        (the input is rewritten per call — corrupting it would self-heal),
        then drop device copies so the poison is what the next launch sees."""
        inner = self.inner
        eb = inner.cfg.elem_bytes
        in_lo = inner.input_off
        in_hi = in_lo + int(np.prod(inner.input_dims[1:])) * eb
        for off, b in inner._preload:
            lo, hi = off, off + b.size
            if hi <= in_lo or lo >= in_hi:       # disjoint from the input
                span = min(64, b.size)
                inner.arena0[lo:lo + span] ^= 0xA5
                inner._drop_device_state()
                return
        raise RuntimeError("no weight region outside the input surface "
                           "to corrupt")

    def _corrupt_output(self, res: ExecResult) -> ExecResult:
        bad = np.array(res.output_int8, copy=True)
        bad.reshape(-1).view(np.uint8)[...] ^= 0x55
        out = np.array(res.output, copy=True)
        out.reshape(-1)[...] += 1e3
        return ExecResult(output_int8=bad, output=out,
                          degraded=getattr(res, "degraded", False))

    def _call(self, fn):
        spec, idx = self._pick()
        if spec is None:
            return fn()
        if spec.kind == "error":
            raise InjectedFaultError("error", idx)
        if spec.kind == "hang":
            self._hang.wait()                    # until release_hangs()
            raise InjectedFaultError("hang", idx)
        if spec.kind == "slow":
            t0 = time.perf_counter()
            res = fn()
            dt = time.perf_counter() - t0
            time.sleep(spec.delay_s if spec.delay_s is not None
                       else dt * max(spec.latency_mult - 1.0, 0.0))
            return res
        if spec.kind == "corrupt_output":
            return self._corrupt_output(fn())
        self._corrupt_arena(idx)                 # "corrupt_arena"
        raise InjectedFaultError("corrupt_arena", idx)

    # -- executor protocol ---------------------------------------------------
    def run(self, x: np.ndarray) -> ExecResult:
        return self._call(lambda: self.inner.run(x))

    def run_batch(self, X: np.ndarray,
                  lanes: Optional[int] = None) -> ExecResult:
        return self._call(lambda: self.inner.run_batch(X, lanes=lanes))
