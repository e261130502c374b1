"""What a measured window records, shared by the system drivers."""
from __future__ import annotations

import sys
import traceback
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple


@dataclass
class Invocation:
    """One invocation or request: arrival and first result on the harness's
    clock (``first`` is ``None`` when no result came)."""
    name: str
    arrival: float
    first: Optional[float] = None
    failed: bool = False

    @property
    def latency(self) -> Optional[float]:
        return None if self.first is None else self.first - self.arrival


@dataclass
class Window:
    start: float
    seconds: float
    invocations: List[Invocation] = field(default_factory=list)
    # (t0, t1, tokens): output tokens that work from t0 to t1 completed
    token_events: List[Tuple[float, float, int]] = field(default_factory=list)
    # per-engine-step work: admitted prompt lengths, decode contexts
    steps: List[Dict[str, Any]] = field(default_factory=list)
    end: float = 0.0

    @property
    def deadline(self) -> float:
        return self.start + self.seconds

    @property
    def failed(self) -> int:
        return sum(1 for i in self.invocations if i.failed)


@dataclass
class Check:
    """One number compared with its limit; ``exact`` numbers must equal 0."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


class FailureLog:
    """Counts failures of the system under test; prints the first traceback
    to standard error so a failing run says why."""

    def __init__(self) -> None:
        self.count = 0

    def record(self, what: str) -> None:
        self.count += 1
        if self.count == 1:
            print(f"[bench] {what} failed:", file=sys.stderr)
            traceback.print_exc(file=sys.stderr)


def leaves_bytes_differing(a: Any, b: Any) -> int:
    """Bytes that differ between two pytrees of arrays of the same layout; a
    leaf whose shape or dtype differs counts whole."""
    import jax
    import numpy as np

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return sum(np.asarray(x).nbytes for x in la + lb)
    wrong = 0
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            wrong += max(x.nbytes, y.nbytes)
            continue
        wrong += int(np.count_nonzero(x.reshape(-1).view(np.uint8)
                                      != y.reshape(-1).view(np.uint8)))
    return wrong
